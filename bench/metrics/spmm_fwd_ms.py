"""Device milliseconds per training step in the forward SpMM: the Pallas
calls scoped ``spmm_fwd`` in the two train-step programs (the
evaluation's forward left out), averaged over chips."""
import tracescope


def read(ctx):
    n = ctx.counts["rsc_steps"] + ctx.counts["exact_steps"]
    trace = tracescope.of(ctx)
    if n == 0 or trace is None:
        return None
    s = tracescope.kernel_seconds(trace, "spmm_fwd") / ctx.chips
    if s <= 0:
        return None
    return 1e3 * s / n
