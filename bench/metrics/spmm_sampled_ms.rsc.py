"""Device milliseconds per RSC step in the sampled backward SpMM: the
Pallas calls scoped ``spmm_bwd_sampled`` (every layer the plan covers,
kept whole or sampled), averaged over chips."""
import tracescope


def read(ctx):
    n = ctx.counts["rsc_steps"]
    trace = tracescope.of(ctx)
    if n == 0 or trace is None:
        return None
    s = tracescope.kernel_seconds(trace, "spmm_bwd_sampled",
                                  ("rsc_step",)) / ctx.chips
    if s <= 0:
        return None
    return 1e3 * s / n
