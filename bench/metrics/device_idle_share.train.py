"""Share of the traced training window in which no operation ran on the
chips (1 - busy / window, averaged over the chips), in %."""


def read(ctx):
    s = ctx["summary"]
    if ctx["mix"]["driver"] != "train" or s is None:
        return None
    return 100.0 * s.idle_share
