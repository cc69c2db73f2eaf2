"""Host milliseconds per training step in the engine's ``dispatch`` span:
the call into the jitted step until it returns, before the wait for its
result (argument handling, the launch, and any retrace or compile)."""


def read(ctx):
    n = ctx.counts["rsc_steps"] + ctx.counts["exact_steps"]
    spans = [e - s for name, s, e in ctx.spans if name == "dispatch"]
    if n == 0 or not spans:
        return None
    return sum(spans) / 1e6 / n
