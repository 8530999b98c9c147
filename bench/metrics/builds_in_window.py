"""Pipelines the compiler built or traced during the window (its
``DISPATCH_STATS`` ``builds`` and ``traces``, lowering and frontend): a
warm cell reads 0."""


def read(ctx):
    keys = ("builds", "traces")
    return float(sum(ctx.counters_after[k] - ctx.counters_before[k]
                     for k in ctx.counters_after
                     if k.split(".", 1)[1] in keys))
