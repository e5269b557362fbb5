"""The host's own time per engine tick, in ms: the summed ``repro.tick``
spans less the summed ``repro.sync`` spans (the blocking reads of device
results) over the number of ticks, as the program's process-wide registry
tallied them while the profile ran (the window and its drain). Nothing
where the program keeps no such tally or profiled no tick."""


def read(ctx):
    if ctx["mix"]["driver"] != "serve":
        return None
    try:
        from repro.obs.registry import PROCESS
    except ImportError:
        return None
    n = PROCESS.get("repro.tick.n")
    if n <= 0:
        return None
    return 1e3 * (PROCESS.get("repro.tick.s")
                  - PROCESS.get("repro.sync.s")) / n
