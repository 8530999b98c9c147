"""Seconds the compiler spent building pipelines during set-up: its
``frontend.build_ns`` counter when the window starts (the harness never
resets it)."""


def read(ctx):
    ns = ctx.counters_before.get("frontend.build_ns")
    return None if ns is None else ns / 1e9
