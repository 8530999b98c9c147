"""Traffic-chosen GEMM tiles and the declared VMEM limit.

On a schedule-cache miss a plain contraction nest (an output accumulator
revisited across trailing contraction levels) gets the tiles that least
modelled HBM traffic favours (``autotune.traffic_schedule``); every other
nest keeps ``DEFAULT_SCHEDULE``.  A kernel whose working set outgrows
Mosaic's default scoped VMEM declares its limit (``ssr.vmem_limit_bytes``).
The shapes are the decoder-layer GEMMs of qwen3-14b and yi-6b at 2048
tokens, and one four-chip shard of qwen3-14b at 8192.
"""

import math

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import autotune, compiler, resilience, ssr
from repro.core.autotune import (_lower_candidate, _stream_block_bytes,
                                 schedule_is_legal, traffic_bytes,
                                 traffic_schedule)
from repro.core.lowering import DEFAULT_SCHEDULE, Schedule
from repro.core.resilience import inject_faults
from repro.kernels import frontend, ops, ref

F32 = 4


def _operands(m, k, n, dtype="float32"):
    return {"A": ((m, k), dtype), "B": ((k, n), dtype)}


def _gemm(m, k, n):
    return compiler.gemm_nest(m, n, k)


@pytest.fixture
def empty_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_SCHEDULE_CACHE", str(tmp_path / "sched"))
    return autotune.global_cache()


#: (M, K, N) and the tiles the default schedule gives them, (tm, tn, tk).
DEFAULT_TILES = [
    ((2048, 5120, 17408), (256, 512, 512)),   # qwen3 gate/up
    ((2048, 17408, 5120), (256, 512, 512)),   # qwen3 down
    ((2048, 5120, 7168), (256, 512, 512)),    # qwen3 QKV
    ((2048, 4096, 11008), (256, 256, 512)),   # yi gate/up
    ((2048, 11008, 4096), (256, 512, 256)),   # yi down
    ((4096, 5120, 8704), (256, 512, 512)),    # qwen3 x4 gate/up shard
]


@pytest.mark.parametrize("mkn,tiles", DEFAULT_TILES,
                         ids=[f"{m}x{k}x{n}" for (m, k, n), _ in DEFAULT_TILES])
def test_traffic_bytes_match_closed_form(mkn, tiles):
    m, k, n = mkn
    lowered = _lower_candidate(_gemm(m, k, n), DEFAULT_SCHEDULE)
    tm, tn, tk = lowered.tiles
    assert (tm, tn, tk) == tiles
    a, b, c = m * k * F32, k * n * F32, m * n * F32
    want = a * math.ceil(n / tn) + b * math.ceil(m / tm) + c
    assert traffic_bytes(lowered, {}) == want


def test_traffic_bytes_whole_contraction_reads_a_once():
    # tk = K: A's block index stays put across the n walk, so A is read
    # once per row block and B once per row block
    m, k, n = 2048, 4096, 11008
    lowered = _lower_candidate(
        _gemm(m, k, n), Schedule(rows_tile_factor=128, lanes_tile_factor=32))
    assert lowered.tiles == (1024, 256, 4096)
    want = m * k * F32 + k * n * F32 * (m // 1024) + m * n * F32
    assert traffic_bytes(lowered, {}) == want


#: (M, K, N) → the tiles the traffic model picks, (tm, tn, tk).
PICKS = [
    ((2048, 5120, 17408), (1024, 2176, 1280)),   # qwen3 gate/up
    ((2048, 17408, 5120), (2048, 1280, 1024)),   # qwen3 down
    ((2048, 5120, 7168), (2048, 1024, 1024)),    # qwen3 QKV
    ((2048, 5120, 5120), (2048, 1280, 1280)),    # qwen3 O
    ((2048, 4096, 11008), (1024, 256, 4096)),    # yi gate/up
    ((2048, 11008, 4096), (2048, 2048, 256)),    # yi down
    ((2048, 4096, 5120), (2048, 1280, 1024)),    # yi QKV
    ((2048, 4096, 4096), (2048, 1024, 1024)),    # yi O
]


@pytest.mark.parametrize("mkn,tiles", PICKS,
                         ids=[f"{m}x{k}x{n}" for (m, k, n), _ in PICKS])
def test_chosen_tiles(mkn, tiles):
    m, k, n = mkn
    nest = _gemm(m, k, n)
    sched = traffic_schedule(nest, _operands(m, k, n))
    assert sched is not None
    lowered = _lower_candidate(nest, sched)
    assert lowered.tiles == tiles
    # the pick keeps the default block geometry, so operands pad as before
    assert (sched.rows, sched.lanes) == (DEFAULT_SCHEDULE.rows,
                                         DEFAULT_SCHEDULE.lanes)
    default = _lower_candidate(nest, DEFAULT_SCHEDULE)
    assert traffic_bytes(lowered, {}) < 0.5 * traffic_bytes(default, {})


@pytest.mark.parametrize("mkn",
                         [mkn for mkn, _ in PICKS] + [(4096, 5120, 8704)],
                         ids=lambda s: "x".join(map(str, s)))
def test_chosen_schedule_legal_and_under_declared_limit(mkn):
    m, k, n = mkn
    nest = _gemm(m, k, n)
    sched = traffic_schedule(nest, _operands(m, k, n))
    ok, why = schedule_is_legal(nest, sched)
    assert ok, why
    ws = _stream_block_bytes(_lower_candidate(nest, sched), F32)
    assert ws <= ssr.VMEM_BUDGET_BYTES
    limit = ssr.vmem_limit_bytes(ws)
    # these working sets outgrow Mosaic's default, so each declares a limit
    assert limit is not None and ws < limit <= ssr.VMEM_LIMIT_CAP_BYTES


def test_least_bytes_then_smaller_working_set():
    # no legal candidate moves fewer bytes; among those that tie on bytes,
    # the pick has the smallest working set
    m, k, n = 2048, 5120, 7168
    nest = _gemm(m, k, n)
    pick = _lower_candidate(nest, traffic_schedule(nest, _operands(m, k, n)))
    best = traffic_bytes(pick, {})
    for rf in (32, 64, 128, 256):
        for lf in (4, 5, 7, 8, 10, 14, 20, 28, 40, 56):
            lowered = _lower_candidate(
                nest, Schedule(rows_tile_factor=rf, lanes_tile_factor=lf))
            ws = _stream_block_bytes(lowered, F32)
            if ws > ssr.VMEM_BUDGET_BYTES:
                continue
            got = traffic_bytes(lowered, {})
            assert got >= best
            if got == best:
                assert ws >= _stream_block_bytes(pick, F32)


@pytest.mark.parametrize("m,n,tiles", [(22016, 4096, (512, 512)),
                                       (4096, 11008, (512, 256))])
def test_gemv_tiles_unchanged(empty_cache, m, n, tiles):
    nest = compiler.gemv_nest(m, n)
    operands = {"A": ((m, n), "float32"), "x": ((n,), "float32")}
    assert traffic_schedule(nest, operands) is None
    sched, source = autotune.resolve(nest, operands)
    assert (sched, source) == (DEFAULT_SCHEDULE, "default")
    assert _lower_candidate(nest, sched).tiles == tiles


def test_attention_tiles_unchanged(empty_cache):
    # the online-rescaled accumulator is not a plain contraction
    nest = compiler.attention_nest(2048, 2048, 128)
    operands = {"Q": ((2048, 128), "float32"), "K": ((2048, 128), "float32"),
                "V": ((2048, 128), "float32")}
    assert traffic_schedule(nest, operands) is None
    assert autotune.lookup(nest, operands) == DEFAULT_SCHEDULE
    assert _lower_candidate(nest, DEFAULT_SCHEDULE).tiles == (256, 128, 256)


def test_small_gemm_keeps_default(empty_cache):
    # the default tiles already cover the problem: nothing to save
    nest = _gemm(64, 384, 256)
    assert traffic_schedule(nest, _operands(64, 384, 256)) is None


def test_tuned_winner_overrides_the_model(empty_cache):
    m, k, n = 2048, 5120, 17408
    nest = _gemm(m, k, n)
    ops_ = _operands(m, k, n)
    assert autotune.resolve(nest, ops_)[1] == "traffic"
    tuned = Schedule(rows_tile_factor=64, lanes_tile_factor=8)
    empty_cache.put(autotune.cache_key(nest, ops_, mode="reduce"), tuned)
    assert autotune.resolve(nest, ops_) == (tuned, "tuned")
    assert autotune.lookup(nest, ops_) == tuned


def test_default_working_set_declares_no_limit():
    # a kernel that fits Mosaic's default scoped VMEM compiles as before
    for nest in (_gemm(2048, 5120, 17408), compiler.gemv_nest(22016, 4096),
                 compiler.attention_nest(2048, 2048, 128)):
        ws = _stream_block_bytes(_lower_candidate(nest, DEFAULT_SCHEDULE), F32)
        assert ws <= ssr.MOSAIC_DEFAULT_VMEM_BYTES
        assert ssr.vmem_limit_bytes(ws) is None
    assert ssr.vmem_limit_bytes(ssr.MOSAIC_DEFAULT_VMEM_BYTES + 1) is not None
    assert ssr.vmem_limit_bytes(1 << 40) == ssr.VMEM_LIMIT_CAP_BYTES


# --------------------------------------------------------------------------
# Interpret-mode runs through ops.matmul
# --------------------------------------------------------------------------

#: Small enough to interpret, large enough that the default tiles
#: (256, 512, 512) re-read both operands.
M, K, N = 512, 1024, 1536


def _ab(seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((M, K)), jnp.float32),
            jnp.asarray(rng.standard_normal((K, N)), jnp.float32))


def test_matmul_on_traffic_tiles_matches_reference(empty_cache):
    a, b = _ab()
    nest = _gemm(M, K, N)
    sched = traffic_schedule(nest, _operands(M, K, N))
    assert sched is not None
    tiles = _lower_candidate(nest, sched).tiles
    default = _lower_candidate(nest, DEFAULT_SCHEDULE).tiles
    assert all(t >= d for t, d in zip(tiles, default)) and tiles != default
    before = frontend.DISPATCH_STATS["traffic_tiles"]
    got = ops.matmul(a, b, ssr=True, out_dtype=jnp.float32)
    assert frontend.DISPATCH_STATS["traffic_tiles"] == before + 1
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.matmul_ref(a, b)),
                               rtol=2e-4, atol=2e-4)


def test_refused_traffic_schedule_degrades_without_quarantine(empty_cache):
    from repro.kernels.gemm import ssr_matmul

    a, b = _ab(1)
    healthy = ssr_matmul(a, b, schedule=DEFAULT_SCHEDULE)
    frontend.reset_dispatch_stats()
    resilience.reset_fallback_log()
    with inject_faults("compile") as specs:       # the compiler refuses once
        got = ssr_matmul(a, b)
    assert specs[0].fired == 1
    assert frontend.DISPATCH_STATS["degraded"] == 1
    events = resilience.fallback_events()
    assert [(e.from_schedule, e.to_schedule, e.key) for e in events] == \
        [("traffic", "default", None)]
    assert empty_cache.keys() == []               # nothing was quarantined
    np.testing.assert_allclose(np.asarray(got), np.asarray(healthy),
                               rtol=1e-6, atol=1e-6)
