"""Device idle milliseconds per step whose host interval lies inside a
compiler call (``ssr.call``), the device's clock mapped onto the host's by
the offset ``bench/program_trace.py`` estimates, averaged over the chips."""

from bench import program_trace


def read(ctx):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    return program_trace.per_step_ms(pt.idle_in("ssr.call"), ctx)
