"""Share of the SpMM kernel's roofline: the least time of every SpMM the
traced window ran (from nonzeros and widths, work.py) over the measured
kernel time, in percent."""
from tracereduce import is_pallas, op_seconds
from work import spmm_least_seconds


def read(ctx):
    s = op_seconds(ctx.trace, is_pallas) / ctx.chips
    if s <= 0 or ctx.peak is None:
        return None
    least = spmm_least_seconds(ctx.shape, ctx.counts, ctx.budget, ctx.peak)
    return 100.0 * least / s
