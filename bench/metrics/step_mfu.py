"""Model FLOPs of the traced window (every step and evaluation, no
discount for sampling, work.py) over window seconds x chips x peak, in
percent."""
from work import window_model_flops


def read(ctx):
    if ctx.counts["epochs"] == 0 or ctx.peak is None:
        return None
    flops = window_model_flops(ctx.shape, ctx.counts)
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peak["flops_per_s"])
