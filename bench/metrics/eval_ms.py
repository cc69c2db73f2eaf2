"""Host milliseconds of the engine's ``eval`` span, spread over the
traced window's epochs (one evaluation every ``eval_every`` epochs)."""


def read(ctx):
    spans = [e - s for name, s, e in ctx.spans if name == "eval"]
    if ctx.counts["epochs"] == 0 or not spans:
        return None
    return sum(spans) / 1e6 / ctx.counts["epochs"]
