"""Host milliseconds per step spent inside compiler calls until they
return: the ``ops.*`` spans, on the host clock, over the traced steps."""


def read(ctx):
    if ctx.traced_steps == 0:
        return None
    host = sum(v for k, v in ctx.dispatch_s.items() if k.startswith("ops."))
    return 1e3 * host / ctx.traced_steps
