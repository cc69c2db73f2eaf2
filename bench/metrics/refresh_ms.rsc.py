"""Host milliseconds per plan refresh in the plan cache's
``plan.refresh`` span: block scores, the allocator, and every layer's
plan built and uploaded."""


def read(ctx):
    spans = [e - s for name, s, e in ctx.spans if name == "plan.refresh"]
    if not spans:
        return None
    return sum(spans) / 1e6 / len(spans)
