"""Share of the chip's bf16 peak that the engine's ticks fill: model FLOPs
of the prefill and decode tokens processed in the window (``bench/flops.py``)
over the summed wall time of ``engine.tick()`` and the peak, in %."""


def read(ctx):
    out, peak = ctx["outcome"], ctx["peak"]
    if ctx["mix"]["driver"] != "serve" or peak is None:
        return None
    c = out["counters"]
    if c["tick_s"] <= 0:
        return None
    return 100.0 * c["flops"] / (c["tick_s"] * ctx["chips"]
                                 * peak["bf16_flops_per_s"])
