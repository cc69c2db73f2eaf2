"""Host milliseconds per RSC step in the engine's ``plan`` span (plan
cache lookup and, every refresh, the allocator and plan rebuild)."""


def read(ctx):
    n = ctx.counts["rsc_steps"]
    spans = [e - s for name, s, e in ctx.spans if name == "plan"]
    if n == 0 or not spans:
        return None
    return sum(spans) / 1e6 / n
