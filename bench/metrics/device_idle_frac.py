"""Share of the traced window in which no operation ran on the chips,
averaged over the chips the cell uses (1 - busy union / window)."""


def read(ctx):
    if not ctx.trace.ops:
        return None
    return 1.0 - ctx.busy_s / ctx.window_s
