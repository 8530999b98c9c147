"""Host milliseconds per step in the program's ``ssr.launch`` spans: each
call into a cached jitted pipeline until JAX returns (under ``jax.vmap``,
the batching rule too), over the traced steps."""

from bench import program_trace


def read(ctx):
    pt = program_trace.of(ctx)
    launches = pt.named("ssr.launch") if pt is not None else []
    if not launches:
        return None
    return program_trace.per_step_ms(sum(s.dur for s in launches), ctx)
