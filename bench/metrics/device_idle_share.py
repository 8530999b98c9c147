"""Share of the traced device window in which no operation ran, in %:
1 − busy / window, averaged over the chips."""


def read(ctx):
    if ctx.trace is None:
        return None
    window = ctx.trace.window_s()
    if window <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / window)
