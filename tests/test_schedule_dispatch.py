"""Schedule threading + zero-overhead dispatch (lowering/frontend/cluster).

The tentpole contracts of the autotuner PR:

* every lowering entry point accepts a non-default :class:`Schedule` and
  produces identical numerics (the schedule changes *how*, never *what*);
* a repeated identical call is a cache hit on the jitted prepare→engine→
  finish pipeline — no re-trace, no eager pad/trim dispatch (asserted via
  trace counters that only move while tracing);
* ``NestKernel`` resolves tuned schedules from the persistent cache
  transparently, and the cluster layer picks the per-core tile's schedule.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import autotune, compiler, lowering
from repro.core.lowering import (DEFAULT_SCHEDULE, LoweringError, Schedule,
                                 lower_nest, plan_stats, ssr_call,
                                 ssr_chain_call)
from repro.kernels import frontend

RNG = np.random.default_rng(3)


def arr(shape):
    return jnp.asarray(RNG.standard_normal(shape), jnp.float32)


class TestPowerOfTwoRegression:
    def test_zero_rejected(self):
        with pytest.raises(ValueError, match="power-of-two"):
            frontend.require_power_of_two(0, "fft input")

    def test_negative_rejected(self):
        with pytest.raises(ValueError, match="power-of-two"):
            frontend.require_power_of_two(-4, "fft input")

    @pytest.mark.parametrize("n", [1, 2, 1024])
    def test_powers_accepted(self, n):
        frontend.require_power_of_two(n, "ok")

    @pytest.mark.parametrize("n", [3, 12, 1000])
    def test_non_powers_rejected(self, n):
        with pytest.raises(ValueError, match="power-of-two"):
            frontend.require_power_of_two(n, "bad")


class TestScheduleEquivalence:
    """Non-default schedules must be bit-for-bit (or fp-tolerance) neutral."""

    def test_reduce_across_block_geometries(self):
        n = 5000
        nest = compiler.dot_product_nest(n)
        x, y = arr(n), arr(n)
        body = lambda a, b: a * b  # noqa: E731
        want = ssr_call(nest, body, {"A": x, "B": y})
        for sched in (Schedule(rows=4), Schedule(rows=16),
                      Schedule(rows=16, lanes=256), Schedule(lanes=256)):
            got = ssr_call(nest, body, {"A": x, "B": y}, schedule=sched)
            np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    def test_map_trim_with_odd_size(self):
        n = 1025   # exercises _trim_output under a non-default policy
        nest = compiler.elementwise_nest(n)
        x = arr(n)
        body = lambda a: jnp.maximum(a, 0.0)  # noqa: E731
        want = ssr_call(nest, body, {"X": x}, mode="map")
        got = ssr_call(nest, body, {"X": x}, mode="map",
                       schedule=Schedule(rows=16, lanes=256))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_chain_with_schedule(self):
        from repro.kernels.chained import _chain_nests

        n = 4096
        x, y = arr(n), arr(n)
        nests = _chain_nests(n, consumer_reads_w=False)
        bodies = (lambda a, b: (a - b) * (a - b), lambda t: t)
        want = ssr_chain_call(nests, bodies, {"X": x, "Y": y}, mode="reduce")
        got = ssr_chain_call(nests, bodies, {"X": x, "Y": y}, mode="reduce",
                             schedule=Schedule(rows=16))
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    def test_gemm_tile_factors_and_axis_order(self):
        m, n, k = 32, 32, 256
        a, b = arr((m, k)), arr((k, n))
        want = jnp.dot(a, b)

        def run(sched):
            from repro.kernels.gemm import ssr_matmul

            return ssr_matmul(a, b, out_dtype=jnp.float32, schedule=sched)

        # small tile targets force real multi-tile grids (m: 4, k: 2)
        base = Schedule(rows_tile_factor=1, lanes_tile_factor=1)
        np.testing.assert_allclose(np.asarray(run(base)), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        # permuting the parallel axes re-walks the same tiles: identical
        permuted = Schedule(rows_tile_factor=1, lanes_tile_factor=1,
                            axis_order=(1, 0, 2))
        np.testing.assert_allclose(np.asarray(run(permuted)),
                                   np.asarray(run(base)), rtol=1e-6)

    def test_gemm_axis_order_changes_grid_order(self):
        nest = compiler.gemm_nest(32, 32, 256)
        plan = lowering._plan_for(nest, 3)
        base = lower_nest(plan, schedule=Schedule(rows_tile_factor=1,
                                                  lanes_tile_factor=1))
        perm = lower_nest(plan, schedule=Schedule(rows_tile_factor=1,
                                                  lanes_tile_factor=1,
                                                  axis_order=(1, 0, 2)))
        assert base.grid == (4, 1, 2)
        assert perm.grid == (1, 4, 2)
        assert base.semantics == ("parallel", "parallel", "arbitrary")
        assert perm.semantics == ("parallel", "parallel", "arbitrary")

    def test_axis_order_illegal_cases(self):
        nest = compiler.gemm_nest(32, 32, 256)
        plan = lowering._plan_for(nest, 3)
        with pytest.raises(LoweringError, match="not a permutation"):
            lower_nest(plan, schedule=Schedule(axis_order=(0, 1)))
        with pytest.raises(LoweringError, match="trailing"):
            lower_nest(plan, schedule=Schedule(axis_order=(2, 0, 1)))

    def test_flat_path_rejects_axis_order(self):
        nest = compiler.dot_product_nest(2048)
        with pytest.raises(LoweringError, match="level-mapped"):
            ssr_call(nest, lambda a, b: a * b,
                     {"A": arr(2048), "B": arr(2048)},
                     schedule=Schedule(axis_order=(0,)))

    def test_stencil_widths_identical(self):
        from repro.kernels.stencil import TAPS, ssr_stencil1d

        x, w = arr(1024 + TAPS - 1), arr(TAPS) * 0.3
        want = ssr_stencil1d(x, w)
        for lanes in (256, 512, 1024):
            got = ssr_stencil1d(x, w, schedule=Schedule(lanes=lanes))
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_plan_stats_untouched_by_schedule(self):
        # the Eq. (1)-(3) verdict is schedule-independent: geometry moves
        # blocks, not instructions
        nest = compiler.dot_product_nest(4096)
        s = plan_stats(nest)
        assert s.n_base > s.n_ssr


class TestZeroOverheadDispatch:
    """Second identical call = dict hit; trace counters must not move."""

    def test_ssr_call_traces_once(self):
        lowering.clear_caches()
        lowering.reset_dispatch_stats()
        n = 2048
        nest = compiler.dot_product_nest(n)
        x, y = arr(n), arr(n)
        body = lambda a, b: a * b  # noqa: E731
        first = ssr_call(nest, body, {"A": x, "B": y})
        after_first = dict(lowering.DISPATCH_STATS)
        assert after_first["builds"] == 1
        assert after_first["traces"] >= 1
        second = ssr_call(nest, body, {"A": x, "B": y})
        assert lowering.DISPATCH_STATS["builds"] == 1
        assert lowering.DISPATCH_STATS["traces"] == after_first["traces"]
        assert lowering.DISPATCH_STATS["calls"] == 2
        np.testing.assert_allclose(float(first), float(second))

    def test_ssr_chain_call_traces_once(self):
        from repro.kernels.chained import _chain_nests

        lowering.clear_caches()
        lowering.reset_dispatch_stats()
        n = 2048
        nests = _chain_nests(n, consumer_reads_w=False)
        bodies = (lambda a, b: (a - b) * (a - b), lambda t: t)
        ops = {"X": arr(n), "Y": arr(n)}
        ssr_chain_call(nests, bodies, ops, mode="reduce")
        t1 = lowering.DISPATCH_STATS["traces"]
        ssr_chain_call(nests, bodies, ops, mode="reduce")
        assert lowering.DISPATCH_STATS["traces"] == t1
        assert lowering.DISPATCH_STATS["builds"] == 1

    def test_nest_kernel_pipeline_traces_once(self):
        from repro.kernels.reduction import ssr_dot

        x, y = arr(3000), arr(3000)
        frontend.reset_dispatch_stats()
        ssr_dot(x, y)
        t1 = frontend.DISPATCH_STATS["traces"]
        b1 = frontend.DISPATCH_STATS["builds"]
        ssr_dot(x, y)
        assert frontend.DISPATCH_STATS["traces"] == t1
        assert frontend.DISPATCH_STATS["builds"] == b1
        assert frontend.DISPATCH_STATS["calls"] >= 2

    def test_stream_kernel_pipeline_traces_once(self):
        from repro.kernels.stencil import TAPS, ssr_stencil1d

        x, w = arr(777 + TAPS - 1), arr(TAPS) * 0.3
        frontend.reset_dispatch_stats()
        ssr_stencil1d(x, w)
        t1 = frontend.DISPATCH_STATS["traces"]
        ssr_stencil1d(x, w)
        assert frontend.DISPATCH_STATS["traces"] == t1

    def test_monolithic_kernel_pipeline_traces_once(self):
        from repro.kernels.relu import baseline_relu

        x = arr(999)
        frontend.reset_dispatch_stats()
        want = baseline_relu(x)
        t1 = frontend.DISPATCH_STATS["traces"]
        got = baseline_relu(x)
        assert frontend.DISPATCH_STATS["traces"] == t1
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_new_shape_rebuilds(self):
        from repro.kernels.reduction import ssr_dot

        frontend.reset_dispatch_stats()
        ssr_dot(arr(1111), arr(1111))
        b1 = frontend.DISPATCH_STATS["builds"]
        ssr_dot(arr(2222), arr(2222))
        assert frontend.DISPATCH_STATS["builds"] == b1 + 1


class _Spans:
    """Stands in for ``jax.profiler.TraceAnnotation``: records every span
    opened as ``(name, enclosing span's name)``."""

    def __init__(self):
        self.opened = []
        self._stack = []

    def __call__(self, name, **_kw):
        rec = self

        class _Span:
            def __enter__(self):
                rec.opened.append((name, rec._stack[-1] if rec._stack
                                   else None))
                rec._stack.append(name)

            def __exit__(self, *exc):
                rec._stack.pop()

        return _Span()


class TestDispatchSpans:
    """The profiler spans of the dispatch path and the ``build_ns``
    counter: a warm call opens ``ssr.call`` ⊃ ``ssr.launch`` and moves
    ``calls`` only; a cold one opens ``ssr.call`` ⊃ ``ssr.build`` ⊃
    ``ssr.lookup``/``ssr.lower`` and moves ``build_ns``."""

    @pytest.fixture
    def spans(self, monkeypatch, tmp_path):
        from repro.kernels import registry

        monkeypatch.setenv("REPRO_SCHEDULE_CACHE", str(tmp_path))
        rec = _Spans()
        for mod in (registry, frontend, lowering):
            monkeypatch.setattr(mod, "TraceAnnotation", rec)
        return rec

    # data of their own: drawing from the module's RNG would change every
    # later test's inputs
    rng = np.random.default_rng(14)

    @classmethod
    def arr(cls, shape):
        return jnp.asarray(cls.rng.standard_normal(shape), jnp.float32)

    CALLS = {
        "matmul": lambda t, ops, n: ops.matmul(t.arr((n, 96)),
                                               t.arr((96, 160)), ssr=True),
        "gemv": lambda t, ops, n: ops.gemv(t.arr((n, 256)), t.arr(256),
                                           ssr=True),
    }

    @pytest.mark.parametrize("op", sorted(CALLS))
    def test_warm_call_opens_call_and_launch_only(self, spans, op):
        from repro.kernels import ops

        self.CALLS[op](self, ops, 40)            # warm the pipeline
        stats = dict(frontend.DISPATCH_STATS)
        low = dict(lowering.DISPATCH_STATS)
        spans.opened.clear()
        self.CALLS[op](self, ops, 40)
        assert spans.opened == [("ssr.call", None),
                                ("ssr.launch", "ssr.call")]
        moved = {k for k, v in frontend.DISPATCH_STATS.items()
                 if v != stats[k]}
        assert moved == {"calls"}
        assert frontend.DISPATCH_STATS["calls"] == stats["calls"] + 1
        assert dict(lowering.DISPATCH_STATS) == low
        assert frontend.DISPATCH_STATS["build_ns"] == stats["build_ns"]

    @pytest.mark.parametrize("op", sorted(CALLS))
    def test_cold_call_opens_build_lookup_and_lower(self, spans, op):
        from repro.kernels import ops

        before = frontend.DISPATCH_STATS["build_ns"]
        self.CALLS[op](self, ops, 56)            # a shape no test used
        assert spans.opened[:2] == [("ssr.call", None),
                                    ("ssr.build", "ssr.call")]
        inner = set(spans.opened[2:])
        assert ("ssr.lookup", "ssr.build") in inner
        assert ("ssr.lower", "ssr.build") in inner
        assert {n for n, _ in inner} == {"ssr.lookup", "ssr.lower"}
        assert frontend.DISPATCH_STATS["build_ns"] > before

    def test_call_under_vmap_opens_one_call_span(self, spans):
        import jax
        from repro.kernels import ops

        attn = jax.vmap(lambda q, k, v: ops.flash_attention(
            q, k, v, causal=True, ssr=True))
        q, k, v = (self.arr((2, 64, 64)) for _ in range(3))
        attn(q, k, v)
        assert [n for n, _ in spans.opened].count("ssr.call") == 1
        spans.opened.clear()
        attn(q, k, v)
        assert spans.opened == [("ssr.call", None),
                                ("ssr.launch", "ssr.call")]

    def test_unstreamed_call_opens_no_span(self, spans):
        from repro.kernels import ops

        ops.gemv(self.arr((40, 256)), self.arr(256), ssr=False)
        assert spans.opened == []


class TestTransparentTuning:
    """NestKernel + cluster pick up committed winners without code changes."""

    @pytest.fixture
    def tuned_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_SCHEDULE_CACHE", str(tmp_path / "sch"))
        # the global cache re-resolves its path lazily
        yield autotune.global_cache()

    def test_nest_kernel_resolves_committed_schedule(self, tuned_env):
        from repro.kernels import reduction

        n = 2048
        x, y = arr(n), arr(n)
        nest = compiler.dot_product_nest(n)
        committed = Schedule(rows=16, lanes=128)
        key = autotune.cache_key(nest, {"A": x, "B": y}, mode="reduce",
                                 out_dtype="float32")
        tuned_env.put(key, committed)
        assert reduction._ssr.schedule_for(x, y) == committed
        # and the call still matches the oracle under the tuned schedule
        got = reduction.ssr_dot(x, y)
        np.testing.assert_allclose(float(got), float(jnp.dot(x, y)),
                                   rtol=1e-4)

    def test_commit_takes_effect_without_restart(self, tuned_env):
        from repro.kernels import reduction

        n = 4096
        x, y = arr(n), arr(n)
        assert reduction._ssr.schedule_for(x, y) == DEFAULT_SCHEDULE
        reduction.ssr_dot(x, y)          # builds the default pipeline
        nest = compiler.dot_product_nest(n)
        key = autotune.cache_key(nest, {"A": x, "B": y}, mode="reduce",
                                 out_dtype="float32")
        tuned_env.put(key, Schedule(rows=32))   # epoch bump
        assert reduction._ssr.schedule_for(x, y) == Schedule(rows=32)
        got = reduction.ssr_dot(x, y)    # rebuilt under the new schedule
        np.testing.assert_allclose(float(got), float(jnp.dot(x, y)),
                                   rtol=1e-4)

    def test_cluster_cores1_accepts_schedule(self):
        from repro.parallel.cluster import LAST_DISPATCH, cluster_call

        n = 2048
        nest = compiler.dot_product_nest(n)
        x, y = arr(n), arr(n)
        body = lambda a, b: a * b  # noqa: E731
        want = cluster_call(nest, body, {"A": x, "B": y}, cores=1)
        LAST_DISPATCH.clear()
        got = cluster_call(nest, body, {"A": x, "B": y}, cores=1,
                           schedule=Schedule(rows=16))
        assert LAST_DISPATCH["schedule"] == Schedule(rows=16)
        assert LAST_DISPATCH["cores"] == 1
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    def test_stencil_resolves_committed_width(self, tuned_env):
        # the migrated stencil rides transparent tuning: a schedule
        # committed under the halo nest's key must reach plain
        # ssr_stencil1d (and be exactly as wide as the default — per-tap
        # fmadd order is width-independent)
        from repro.kernels.stencil import TAPS, _ssr_1d, ssr_stencil1d

        n = 1024
        x, w = arr(n + TAPS - 1), arr(TAPS) * 0.3
        want = ssr_stencil1d(x, w)      # default schedule (cache miss)
        key = autotune.cache_key(compiler.stencil_nest(n, TAPS),
                                 {"x": x, "w": w}, mode="reduce",
                                 out_dtype="float32")
        committed = Schedule(lanes=512)
        tuned_env.put(key, committed)
        assert _ssr_1d.schedule_for(x, w) == committed
        got = ssr_stencil1d(x, w)       # resolves the committed schedule
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_cluster_cores1_stays_bit_identical_after_commit(self, tuned_env):
        # regression: entry.ssr resolves tuned schedules via NestKernel,
        # and the cores=1 cluster bypass must resolve the SAME schedule —
        # otherwise a committed winner silently breaks the bit-equality
        # between the single-core registry path and cores=1
        from repro.kernels import reduction

        n = 2048
        x, y = arr(n), arr(n)
        nest = compiler.dot_product_nest(n)
        key = autotune.cache_key(nest, {"A": x, "B": y}, mode="reduce",
                                 out_dtype="float32")
        tuned_env.put(key, Schedule(rows=4, lanes=512))
        got = reduction.cluster_dot(x, y, cores=1)
        want = reduction.ssr_dot(x, y)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        from repro.parallel.cluster import LAST_DISPATCH

        assert LAST_DISPATCH["schedule"] == Schedule(rows=4, lanes=512)
        assert LAST_DISPATCH["tuned"]

    def test_explicit_policy_is_never_overridden_by_commit(self, tuned_env):
        # regression: a caller pinning a legacy policy= must get exactly
        # that geometry even when the autotuner has committed a different
        # winner for the same problem — the lookup only fires for the
        # fully-default call
        from repro.core.lowering import BlockPolicy
        from repro.parallel.cluster import LAST_DISPATCH, cluster_call

        n = 2048
        x, y = arr(n), arr(n)
        nest = compiler.dot_product_nest(n)
        key = autotune.cache_key(nest, {"A": x, "B": y}, mode="reduce",
                                 out_dtype="float32")
        tuned_env.put(key, Schedule(rows=16, lanes=256))
        body = lambda a, b: a * b  # noqa: E731
        LAST_DISPATCH.clear()
        pinned = cluster_call(nest, body, {"A": x, "B": y}, cores=1,
                              policy=BlockPolicy(rows=4))
        assert LAST_DISPATCH["schedule"] == Schedule(rows=4)
        assert not LAST_DISPATCH["tuned"]
        want = ssr_call(nest, body, {"A": x, "B": y},
                        policy=BlockPolicy(rows=4))
        np.testing.assert_array_equal(np.asarray(pinned), np.asarray(want))

    def test_cluster_per_core_lookup_uses_shard_shapes(self, tuned_env):
        # commit a winner for the PER-CORE tile (n/2) and check the
        # cluster layer's lookup helper resolves it for cores=2
        from repro.parallel import cluster as pc

        n = 4096
        sub = compiler.dot_product_nest(n // 2)
        x, y = arr(n), arr(n)
        shard_ops = {"A": ((n // 2,), "float32"),
                     "B": ((n // 2,), "float32")}
        key = autotune.cache_key(sub, shard_ops, mode="reduce",
                                 out_dtype="float32")
        committed = Schedule(rows=16)
        tuned_env.put(key, committed)
        got = pc._core_schedule([sub], {"A": x, "B": y},
                                mode="reduce", out_dtype=jnp.float32)
        assert got == committed
