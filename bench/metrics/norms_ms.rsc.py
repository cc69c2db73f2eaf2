"""Host milliseconds per RSC step in the engine's ``norms`` span: the
planner's copy of every layer's gradient row norms to the host."""


def read(ctx):
    n = ctx.counts["rsc_steps"]
    spans = [e - s for name, s, e in ctx.spans if name == "norms"]
    if n == 0 or not spans:
        return None
    return sum(spans) / 1e6 / n
