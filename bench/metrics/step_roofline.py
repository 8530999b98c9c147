"""The whole step's least time (the sum over its calls, compiler and
glue, of max(FLOPs / peak, bytes / HBM peak), shared by the cell's chips)
times the traced steps, over the device trace's window (from the first
program's start to the last one's end), in %."""


def read(ctx):
    if ctx.peaks is None or ctx.trace is None or ctx.traced_steps == 0:
        return None
    window = ctx.trace.window_s()
    if window <= 0:
        return None
    least = sum(w.least_s(ctx.peaks) for _, w in ctx.work) / ctx.cell.chips
    return 100.0 * ctx.traced_steps * least / window
