"""Warm kernel calls under ``jax.vmap`` (kernels/frontend.py).

Called eagerly under ``jax.vmap``, a ``NestKernel`` peels the batch
tracers off its arguments, one level per enclosing vmap, and calls a
cached ``jax.jit(jax.vmap(pipeline))`` on the values under them, instead
of letting JAX batch the per-example pipeline again on every call.  These
tests pin that the batched pipeline computes exactly what the per-example
path computes, eagerly and under ``jax.jit`` (the cluster path), and that
its cache and counters behave as the unbatched path's do.
"""

import math
import os
import subprocess
import sys
import textwrap
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import autotune, compiler, lowering, resilience
from repro.core.lowering import Schedule
from repro.core.resilience import inject_faults
from repro.kernels import frontend, ops, ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RNG = np.random.default_rng(18)


def arr(*shape):
    return jnp.asarray(RNG.standard_normal(shape), jnp.float32)


def decode(scale):
    """One vmap over KV heads, as a decode step calls attention."""
    return jax.vmap(partial(ops.flash_attention, scale=scale, ssr=True))


def prefill(scale, window=None):
    """The group's q heads inside, the KV heads outside, as prefill."""
    return jax.vmap(jax.vmap(
        partial(ops.flash_attention, causal=True, window=window,
                scale=scale, ssr=True),
        in_axes=(0, None, None)))


# name -> (make the vmapped call, its arguments, the plain reference)
CASES = {
    "decode": (
        lambda: decode(1 / math.sqrt(128)),
        lambda: (arr(2, 8, 128), arr(2, 128, 128), arr(2, 128, 128)),
        lambda q, k, v: jax.vmap(partial(
            ref.attention_ref, scale=1 / math.sqrt(128)))(q, k, v)),
    "prefill-causal": (
        lambda: prefill(1 / math.sqrt(128)),
        lambda: (arr(2, 2, 128, 128), arr(2, 128, 128), arr(2, 128, 128)),
        lambda q, k, v: jax.vmap(jax.vmap(
            partial(ref.attention_ref, causal=True,
                    scale=1 / math.sqrt(128)),
            in_axes=(0, None, None)))(q, k, v)),
    "prefill-window-d80": (
        lambda: prefill(1 / math.sqrt(80), window=48),
        lambda: (arr(2, 2, 128, 80), arr(2, 128, 80), arr(2, 128, 80)),
        lambda q, k, v: jax.vmap(jax.vmap(
            partial(ref.attention_ref, causal=True, window=48,
                    scale=1 / math.sqrt(80)),
            in_axes=(0, None, None)))(q, k, v)),
}


def unpeel(monkeypatch):
    """The per-example path from here on: no call is peeled, and JAX
    batches the per-example pipeline on every call."""
    monkeypatch.setattr(frontend._Peeled, "of",
                        classmethod(lambda cls, args, params: None))


@pytest.fixture(autouse=True)
def _clean_resilience():
    resilience.reset()
    yield
    resilience.reset()


@pytest.mark.parametrize("case", sorted(CASES))
def test_batched_call_is_bit_identical(case, monkeypatch):
    make, args, want = CASES[case]
    q, k, v = args()
    eager = np.asarray(make()(q, k, v))          # builds
    eager_warm = np.asarray(make()(q, k, v))     # the cached batched call
    jitted = np.asarray(jax.jit(make())(q, k, v))
    unpeel(monkeypatch)
    today = np.asarray(make()(q, k, v))
    np.testing.assert_array_equal(eager, jitted)
    np.testing.assert_array_equal(eager_warm, eager)
    np.testing.assert_array_equal(eager, today)
    np.testing.assert_allclose(eager, np.asarray(want(q, k, v)),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("case", ["decode", "prefill-causal"])
def test_warm_repeat_is_one_vmap_launch(case):
    make, args, _ = CASES[case]
    f, (q, k, v) = make(), args()
    f(q, k, v)                                   # build
    stats = dict(frontend.DISPATCH_STATS)
    low = dict(lowering.DISPATCH_STATS)
    f(q, k, v)
    moved = {key: frontend.DISPATCH_STATS[key] - stats[key]
             for key in stats if frontend.DISPATCH_STATS[key] != stats[key]}
    assert moved == {"calls": 1, "vmap_launches": 1}
    assert dict(lowering.DISPATCH_STATS) == low


def test_new_batch_size_is_a_new_build():
    f = decode(1 / math.sqrt(128))
    f(arr(2, 8, 128), arr(2, 128, 128), arr(2, 128, 128))
    stats = dict(frontend.DISPATCH_STATS)
    out = f(arr(3, 8, 128), arr(3, 128, 128), arr(3, 128, 128))
    assert out.shape == (3, 8, 128)
    assert frontend.DISPATCH_STATS["builds"] == stats["builds"] + 1
    assert frontend.DISPATCH_STATS["vmap_launches"] == stats["vmap_launches"]


def test_concrete_gemv_call_is_not_peeled():
    w, x = arr(40, 256), arr(256)
    ops.gemv(w, x, ssr=True)
    before = dict(frontend.DISPATCH_STATS)
    ops.gemv(w, x, ssr=True)
    assert frontend.DISPATCH_STATS["vmap_launches"] == \
        before["vmap_launches"]
    assert frontend.DISPATCH_STATS["calls"] == before["calls"] + 1


@pytest.mark.parametrize("axis", [0, 1])
def test_vmapped_gemv_is_peeled_and_exact(axis):
    w, xs = arr(40, 256), arr(3, 256)
    f = jax.vmap(partial(ops.gemv, ssr=True), in_axes=(None, axis))
    xs_in = xs if axis == 0 else xs.T
    got = f(w, xs_in)
    before = frontend.DISPATCH_STATS["vmap_launches"]
    np.testing.assert_array_equal(np.asarray(f(w, xs_in)), np.asarray(got))
    assert frontend.DISPATCH_STATS["vmap_launches"] == before + 1
    rows = [np.asarray(ops.gemv(w, x, ssr=True)) for x in xs]
    np.testing.assert_allclose(np.asarray(got), np.stack(rows),
                               rtol=1e-5, atol=1e-5)


def test_named_vmap_takes_the_per_example_path():
    q, k, v = arr(2, 8, 128), arr(2, 128, 128), arr(2, 128, 128)
    f = jax.vmap(partial(ops.flash_attention, ssr=True), axis_name="h")
    want = np.asarray(decode(None)(q, k, v))
    f(q, k, v)
    before = frontend.DISPATCH_STATS["vmap_launches"]
    np.testing.assert_array_equal(np.asarray(f(q, k, v)), want)
    assert frontend.DISPATCH_STATS["vmap_launches"] == before


def test_without_jax_internals_the_call_is_not_peeled(monkeypatch):
    q, k, v = arr(2, 8, 128), arr(2, 128, 128), arr(2, 128, 128)
    want = np.asarray(decode(None)(q, k, v))
    monkeypatch.setattr(frontend, "_BatchTracer", None)   # as if missing
    before = frontend.DISPATCH_STATS["vmap_launches"]
    for _ in range(2):
        np.testing.assert_array_equal(
            np.asarray(decode(None)(q, k, v)), want)
    assert frontend.DISPATCH_STATS["vmap_launches"] == before


def test_epoch_bump_rebuilds_the_batched_pipeline_once():
    f = decode(1 / math.sqrt(128))
    q, k, v = arr(2, 8, 128), arr(2, 128, 128), arr(2, 128, 128)
    want = np.asarray(f(q, k, v))
    autotune._bump_epoch()
    stats = dict(frontend.DISPATCH_STATS)
    np.testing.assert_array_equal(np.asarray(f(q, k, v)), want)
    assert frontend.DISPATCH_STATS["builds"] == stats["builds"] + 1
    assert frontend.DISPATCH_STATS["traces"] == stats["traces"] + 1
    assert frontend.DISPATCH_STATS["vmap_launches"] == stats["vmap_launches"]
    f(q, k, v)
    assert frontend.DISPATCH_STATS["builds"] == stats["builds"] + 1
    assert frontend.DISPATCH_STATS["vmap_launches"] == \
        stats["vmap_launches"] + 1


@pytest.fixture
def tuned_env(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_SCHEDULE_CACHE", str(tmp_path / "sch"))
    lowering.clear_caches()
    yield autotune.global_cache()


@pytest.mark.parametrize("batched", [False, True],
                         ids=["unbatched", "vmap"])
def test_compile_fault_degrades_as_unbatched(tuned_env, batched):
    q, k, v = arr(2, 8, 128), arr(2, 128, 128), arr(2, 128, 128)
    f = decode(None) if batched else \
        partial(ops.flash_attention, ssr=True)
    args = (q, k, v) if batched else (q[0], k[0], v[0])
    healthy = np.asarray(f(*args))
    nest = compiler.attention_nest(8, 128, 128)
    key = autotune.cache_key(nest, {"Q": q[0], "K": k[0], "V": v[0]},
                             mode="reduce", out_dtype="float32")
    tuned_env.put(key, Schedule(rows=16))
    frontend.reset_dispatch_stats()
    resilience.reset_fallback_log()
    with inject_faults("compile") as specs:
        got = np.asarray(f(*args))
    assert specs[0].fired == 1
    assert frontend.DISPATCH_STATS["degraded"] == 1
    assert tuned_env.get(key) is None                # quarantined
    sites = [e.site for e in resilience.fallback_events()]
    assert sites == ["nest_kernel:attention"]
    np.testing.assert_array_equal(got, healthy)


def test_lowered_program_under_jit_is_unchanged(monkeypatch):
    """``jax.jit`` of the vmapped call stages the same program whether or
    not the call is peeled."""
    q, k, v = arr(2, 2, 128, 128), arr(2, 128, 128), arr(2, 128, 128)
    peeled = jax.jit(prefill(0.125)).lower(q, k, v).as_text()
    unpeel(monkeypatch)
    assert jax.jit(prefill(0.125)).lower(q, k, v).as_text() == peeled


def test_cluster_kernel_values_are_unchanged():
    """``cluster_kernel`` over the vmapped attention under ``jax.jit``, on
    four host devices, gives the per-example path's values."""
    code = f"""
    import math, sys
    from functools import partial
    sys.path.insert(0, {os.path.join(REPO, 'src')!r})
    import numpy as np, jax, jax.numpy as jnp
    from repro.kernels import frontend, ops
    from repro.parallel.cluster import cluster_kernel

    def stage():
        attn = jax.vmap(jax.vmap(
            partial(ops.flash_attention, causal=True,
                    scale=1 / math.sqrt(128), ssr=True),
            in_axes=(0, None, None)))
        return jax.jit(lambda q, k, v: cluster_kernel(
            attn, (q, k, v), cores=4, in_dims=(0, 0, 0), out_dim=0))

    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal(s), jnp.float32)
               for s in ((4, 2, 128, 128), (4, 128, 128), (4, 128, 128)))
    got = np.asarray(stage()(q, k, v))
    built = frontend.DISPATCH_STATS["builds"]
    frontend._Peeled.of = classmethod(lambda cls, args, params: None)
    today = np.asarray(stage()(q, k, v))
    assert built == 1, built
    assert np.array_equal(got, today), float(np.abs(got - today).max())
    print("OK", got.shape)
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "OK (4, 2, 128, 128)" in out.stdout
