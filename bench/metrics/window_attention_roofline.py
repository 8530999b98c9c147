"""Share of the windowed attention calls' roofline: the least time of the
(query, key) pairs the window keeps (the stage's ``work()``, peaks from
`bench/peaks.py`) over the device time of the programs those calls
launched, in %.  A kernel that computes tiles outside the window pays for
them in time, not in work."""


def read(ctx):
    return ctx.roofline("ops.attention")
