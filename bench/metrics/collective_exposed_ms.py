"""Milliseconds per step of collective operations during which no other
operation ran on that chip, averaged over the chips."""


def read(ctx):
    if ctx.trace is None or ctx.traced_steps == 0:
        return None
    exposed = ctx.trace.collective_exposed_s()
    return None if exposed is None else 1e3 * exposed / ctx.traced_steps
