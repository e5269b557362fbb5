"""Share of the chips' bf16 peak that the window's training FLOPs fill:
model FLOPs of every step (``bench/flops.py``, recompute not counted)
over window x chips x peak, in %."""


def read(ctx):
    out, peak = ctx["outcome"], ctx["peak"]
    if ctx["mix"]["driver"] != "train" or peak is None:
        return None
    c = out["counters"]
    return 100.0 * c["flops"] / (out["window_s"] * ctx["chips"]
                                 * peak["bf16_flops_per_s"])
