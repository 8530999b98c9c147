"""Host milliseconds per step inside compiler calls but outside their
launch or build: the self time of the program's ``ssr.call`` spans (gate,
registry entry, key build, epoch, cache probe), over the traced steps."""

from bench import program_trace


def read(ctx):
    pt = program_trace.of(ctx)
    calls = pt.named("ssr.call") if pt is not None else []
    if not calls:
        return None
    return program_trace.per_step_ms(
        sum(pt.self_ns(c, ("ssr.launch", "ssr.build")) for c in calls), ctx)
