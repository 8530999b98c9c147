"""Share of the gemv calls' roofline: the least time their algorithmic
work needs on this chip (`bench/work.py`, peaks from `bench/peaks.py`)
over the device time of the programs those calls launched, in %."""


def read(ctx):
    return ctx.roofline("ops.gemv")
