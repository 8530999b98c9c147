"""Banded attention nests: a causal or windowed ``attention_nest`` declares
its keys as a ``Band`` on the kv level, and ``lower_nest`` gives the kv
grid axis the band's tiles, not the rectangle's.

Checked in interpret mode against ``ref.attention_ref`` at sizes where the
tokens, the window and the tiles do not divide one another, with the tiles
each lowering visits counted against a brute-force count of the (q tile,
kv tile) pairs that hold a kept (query, key) pair.
"""


import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import autotune, compiler, lowering, nest_analysis
from repro.core.compiler import Band, LoopNest, MemRef
from repro.core.lowering import LoweringError, Schedule
from repro.core.stream import Direction
from repro.kernels import ref
from repro.kernels.attention import _ssr, ssr_flash_attention

#: Small tiles (32 rows), so a few hundred tokens span many kv tiles.
SMALL = Schedule(rows_tile_factor=4)

#: (sq, sk, d, causal, window, schedule)
CASES = [
    (300, 300, 80, True, None, SMALL),      # causal, head dim 80
    (300, 300, 80, True, 50, SMALL),        # window over many tiles
    (300, 300, 64, True, 10, SMALL),        # window < bk
    (200, 200, 64, True, 500, SMALL),       # window >= T
    (300, 300, 32, False, 50, SMALL),       # window without causal
    (100, 260, 32, True, 64, SMALL),        # queries at the keys' end
    (520, 520, 80, True, 100, None),        # default tiles (104)
]
IDS = ["causal_d80", "window50", "window_lt_bk", "window_ge_T",
       "window_noncausal", "sq_lt_sk", "default_tiles"]


def _qkv(sq, sk, d, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(shape), jnp.float32)
                 for shape in ((sq, d), (sk, d), (sk, d)))


def _lowered(sq, sk, d, causal, window, sched):
    nest = compiler.attention_nest(sq, sk, d, causal=causal, window=window)
    return autotune._lower_candidate(
        nest, sched or lowering.DEFAULT_SCHEDULE)


def _brute_tiles(sq, sk, causal, window, tq, tk):
    """(q tile, kv tile) pairs holding at least one kept (query, key)."""
    i = np.arange(sq)[:, None] + (sk - sq)
    j = np.arange(sk)[None, :]
    keep = np.ones((sq, sk), bool)
    if causal:
        keep &= j <= i
    if window is not None:
        keep &= j > i - window
    nq, nk = -(-sq // tq), -(-sk // tk)
    return sum(bool(keep[a * tq:(a + 1) * tq, b * tk:(b + 1) * tk].any())
               for a in range(nq) for b in range(nk))


@pytest.mark.parametrize("sq,sk,d,causal,window,sched", CASES, ids=IDS)
def test_banded_attention_matches_reference(sq, sk, d, causal, window,
                                            sched):
    q, k, v = _qkv(sq, sk, d)
    got = ssr_flash_attention(q, k, v, causal=causal, window=window,
                              schedule=sched)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sq,sk,d,causal,window,sched", CASES, ids=IDS)
def test_band_visits_only_the_tiles_it_meets(sq, sk, d, causal, window,
                                             sched):
    lw = _lowered(sq, sk, d, causal, window, sched)
    tq, tk = lw.tiles[0], lw.tiles[2]
    nq, nk = lw.padded_bounds[0] // tq, lw.padded_bounds[2] // tk
    assert lw.band is not None
    assert lw.visited_steps == _brute_tiles(sq, sk, causal, window, tq, tk)
    assert lw.rect_steps == nq * nk
    # the kv axis is as long as the widest q block's band, no longer
    assert lw.grid == (nq, 1, max(lw.band.counts))
    assert lw.semantics == ("parallel", "parallel", "arbitrary")


def test_counters_move_once_per_built_kernel():
    sq = sk = 300
    q, k, v = _qkv(sq, sk, 80, seed=1)
    lowering.clear_caches()
    _ssr._cache.clear()
    lowering.reset_dispatch_stats()
    ssr_flash_attention(q, k, v, causal=True, window=50, schedule=SMALL)
    ssr_flash_attention(q, k, v, causal=True, window=50, schedule=SMALL)
    lw = _lowered(sq, sk, 80, True, 50, SMALL)
    assert lowering.DISPATCH_STATS["band_tiles"] == lw.visited_steps
    assert lowering.DISPATCH_STATS["rect_tiles"] == lw.rect_steps
    assert lw.visited_steps < lw.rect_steps


def test_a_clamped_tile_is_not_accumulated_twice():
    """All scores equal, V's first column the key's index: each query's
    output is the mean index of the keys its band keeps, which a clamped
    step folded in again would pull toward its block's keys."""
    sq = sk = 300
    window = 50
    lw = _lowered(sq, sk, 64, True, window, SMALL)
    # some q block visits fewer tiles than the kv axis walks: clamped steps
    assert min(lw.band.counts) < lw.grid[2]
    q = jnp.zeros((sq, 64), jnp.float32)
    k = jnp.asarray(np.random.default_rng(2).standard_normal((sk, 64)),
                    jnp.float32)
    v = jnp.zeros((sk, 64), jnp.float32).at[:, 0].set(jnp.arange(sk))
    got = ssr_flash_attention(q, k, v, causal=True, window=window,
                              schedule=SMALL)
    i = np.arange(sq)
    lo = np.maximum(i - window + 1, 0)
    np.testing.assert_allclose(np.asarray(got[:, 0]), (lo + i) / 2.0,
                               rtol=1e-5, atol=1e-4)


def test_plain_attention_keeps_its_rectangle():
    # non-causal attention without a window: decode's kernel, unchanged
    for sq, sk in ((8, 4096), (2048, 2048)):
        nest = compiler.attention_nest(sq, sk, 128)
        assert nest.band is None
        lw = autotune._lower_candidate(nest, lowering.DEFAULT_SCHEDULE)
        assert lw.band is None
        assert lw.visited_steps == lw.rect_steps == lw.steps
    assert lw.tiles == (256, 128, 256) and lw.grid == (8, 1, 8)


def test_causal_2k_visits_about_half():
    # the benchmark's causal prefill at the default tiles: 36 of 64
    lw = _lowered(2048, 2048, 128, True, None, None)
    assert (lw.visited_steps, lw.rect_steps) == (36, 64)


def test_head_dim_80_streams_unpadded():
    lw = _lowered(16384, 16384, 80, True, 4096, None)
    assert lw.tiles[1] == 80 and lw.padded_bounds[1] == 80
    assert all(s.stream.block_shape[-1] == 80
               for s in (*lw.in_streams, lw.out_stream))
    # window 4096 over 16384 tokens in 256-row tiles: 952 of 4096 steps
    assert (lw.visited_steps, lw.rect_steps) == (952, 4096)


def test_nest_compute_counts_band_pairs():
    t, d, w = 100, 16, 30
    causal = compiler.attention_nest(t, t, d, causal=True)
    window = compiler.attention_nest(t, t, d, causal=True, window=w)
    full = compiler.attention_nest(t, t, d)
    assert nest_analysis.nest_compute(full) == 2 * d * t * t
    assert nest_analysis.nest_compute(causal) == 2 * d * t * (t + 1) // 2
    pairs = sum(min(i + 1, w) for i in range(t))
    assert nest_analysis.nest_compute(window) == 2 * d * pairs
    sigs = {autotune.nest_signature(n) for n in (causal, window, full)}
    assert len(sigs) == 3


@pytest.mark.parametrize("band,match", [
    (Band(outer=2, inner=0, hi=0), "outer < inner"),
    (Band(outer=0, inner=2), "lo, hi or both"),
    (Band(outer=0, inner=2, lo=3, hi=1), "lo > hi"),
])
def test_malformed_band_is_refused(band, match):
    refs = compiler.attention_nest(8, 8, 4).refs
    with pytest.raises(ValueError, match=match):
        LoopNest(bounds=(8, 4, 8), refs=refs, compute_per_level=(0, 0, 2),
                 band=band)


def test_band_on_a_plain_accumulator_is_refused():
    # a sum accumulator's body sees no offsets, so it cannot mask a band
    nest = LoopNest(
        bounds=(64, 128, 64),
        refs=(MemRef("A", Direction.READ, (64, 0, 1)),
              MemRef("B", Direction.READ, (0, 1, 128)),
              MemRef("C", Direction.WRITE, (128, 1, 0))),
        compute_per_level=(0, 0, 1), band=Band(outer=0, inner=2, hi=0))
    with pytest.raises(LoweringError, match="online_softmax"):
        autotune._lower_candidate(nest, lowering.DEFAULT_SCHEDULE)
