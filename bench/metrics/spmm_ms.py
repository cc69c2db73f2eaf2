"""Device milliseconds per epoch in the SpMM kernel: the durations of the
Pallas custom calls in the traced window, summed and averaged over chips
(the GNN step runs no other kernel)."""
from tracereduce import is_pallas, op_seconds


def read(ctx):
    s = op_seconds(ctx.trace, is_pallas) / ctx.chips
    if s <= 0 or ctx.counts["epochs"] == 0:
        return None
    return 1e3 * s / ctx.counts["epochs"]
