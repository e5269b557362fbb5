"""Share of the traced serving window in which no operation ran on the
chip (1 - busy / window), in %."""


def read(ctx):
    s = ctx["summary"]
    if ctx["mix"]["driver"] != "serve" or s is None:
        return None
    return 100.0 * s.idle_share
