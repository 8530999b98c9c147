"""Property-based differential tests for the §13 lowering patterns.

The halo-read lowering (windowed ``MemRef`` → ``2**k`` +1-shifted twin
streams, stitched in-kernel) and the online-rescaled accumulator
(``acc_kind="online_softmax"`` → flash m/l/acc VMEM recurrence) replaced
the hand-scheduled Launch paths of the whole stencil/attention family.
These tests sweep stencil widths and sizes (hand-built nests through
``ssr_call``, so the tap count is a free variable, not the kernels'
fixed diameter) and attention (seq, head) shapes — ragged and tiny
included — against plain-numpy oracles to ≤ 1e-5, and pin the loud
``LoweringError`` for a halo window wider than the block tile verbatim
(the message is API surface: it is the migration guide for the next
windowed kernel).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from hypothesis import example, given, settings
import hypothesis.strategies as st

from repro.core import LoweringError, compiler, ssr_call
from repro.core.lowering import Schedule
from repro.kernels import ref
from repro.kernels.attention import ssr_flash_attention
from repro.kernels.chained import fused_stencil1d_relu
from repro.kernels.stencil import TAPS, ssr_stencil1d, ssr_stencil2d

#: Differential-agreement bound (ISSUE acceptance): streamed halo /
#: rescaled paths vs plain-numpy oracles, both f32.
TOL = 1e-5


def _assert_close(got, want, tol=TOL):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert float(np.max(np.abs(got - want))) <= tol


def _tap_body(taps):
    """Generic fully-unrolled 1-D tap loop over a widened halo block."""

    def body(x_wide, w_blk):
        t = x_wide.shape[-1] - (taps - 1)
        acc = w_blk[0, 0] * x_wide[:, 0:t]
        for j in range(1, taps):
            acc = acc + w_blk[0, j] * x_wide[:, j:j + t]
        return acc

    return body


# --------------------------------------------------------------------------
# Halo reads — 1-D width sweep (hand-built nests: taps is free)
# --------------------------------------------------------------------------


class TestHaloStencil1D:
    @given(taps=st.integers(min_value=2, max_value=13),
           n=st.integers(min_value=1, max_value=400),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    @settings(max_examples=25, deadline=None)
    def test_width_sweep_matches_oracle(self, taps, n, seed):
        rng = np.random.default_rng(seed)
        x = rng.standard_normal(n + taps - 1).astype(np.float32)
        w = (rng.standard_normal(taps) * 0.3).astype(np.float32)
        nest = compiler.stencil_nest(n, taps)
        got = ssr_call(nest, _tap_body(taps),
                       {"x": jnp.asarray(x), "w": jnp.asarray(w)})
        want = sum(w[j] * x[j:j + n] for j in range(taps))
        _assert_close(got, want)

    @given(n=st.integers(min_value=1, max_value=3000),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    @settings(max_examples=15, deadline=None)
    def test_public_kernel_ragged_sizes(self, n, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal(n + TAPS - 1), jnp.float32)
        w = jnp.asarray(rng.standard_normal(TAPS) * 0.3, jnp.float32)
        _assert_close(ssr_stencil1d(x, w), ref.stencil1d_ref(x, w))

    @given(n=st.integers(min_value=1, max_value=1500),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_fused_relu_consumer_rides_halo(self, n, seed):
        # the chained consumer reuses the producer's halo nest: same
        # shifted streams, relu applied in-VMEM before the write drains
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal(n + TAPS - 1), jnp.float32)
        w = jnp.asarray(rng.standard_normal(TAPS) * 0.3, jnp.float32)
        _assert_close(fused_stencil1d_relu(x, w),
                      np.maximum(np.asarray(ref.stencil1d_ref(x, w)), 0.0))


# --------------------------------------------------------------------------
# Halo reads — 2-D (2 halo'd levels → 4 shifted streams); H ≥ 9 so the
# sublane tile can cover the TAPS − 1 = 10 overlap columns
# --------------------------------------------------------------------------


class TestHaloStencil2D:
    @given(h=st.integers(min_value=9, max_value=80),
           wd=st.integers(min_value=1, max_value=80),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    @settings(max_examples=15, deadline=None)
    def test_shape_sweep_matches_oracle(self, h, wd, seed):
        r = TAPS // 2
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((h + 2 * r, wd + 2 * r)),
                        jnp.float32)
        wx = jnp.asarray(rng.standard_normal(TAPS) * 0.3, jnp.float32)
        wy = jnp.asarray(rng.standard_normal(TAPS) * 0.3, jnp.float32)
        _assert_close(ssr_stencil2d(x, wx, wy), ref.stencil2d_ref(x, wx, wy))


class TestHaloWindowTooWide:
    """Satellite 2: the halo legality error is loud and pinned verbatim."""

    #: the exact message for an 11-point window over an 8-row grid (the
    #: sublane tile caps at the padded 8-row extent < 10 overlap columns)
    PINNED = ("stream 'x': halo window (11, 11) needs 10 overlap columns "
              "on level 0, but the block tile is only 8 wide; widen the "
              "tile so one block plus its +1-shifted neighbour covers the "
              "window")

    def _grid(self, h):
        r = TAPS // 2
        rng = np.random.default_rng(0)
        return (jnp.asarray(rng.standard_normal((h + 2 * r, 64 + 2 * r)),
                            jnp.float32),
                jnp.asarray(rng.standard_normal(TAPS) * 0.3, jnp.float32),
                jnp.asarray(rng.standard_normal(TAPS) * 0.3, jnp.float32))

    def test_window_exceeding_tile_is_loud_and_verbatim(self):
        x, wx, wy = self._grid(8)      # padded rows extent 8 < TAPS - 1
        with pytest.raises(LoweringError) as exc:
            ssr_stencil2d(x, wx, wy)
        assert str(exc.value) == self.PINNED

    def test_boundary_height_lowers(self):
        x, wx, wy = self._grid(9)      # rounds up to a 16-row tile: legal
        _assert_close(ssr_stencil2d(x, wx, wy), ref.stencil2d_ref(x, wx, wy))


# --------------------------------------------------------------------------
# Online-rescaled accumulator — attention (seq, head) sweep
# --------------------------------------------------------------------------


@st.composite
def attention_shapes(draw):
    """(sq, sk, d, causal, window) with sk ≥ sq (causal rows stay
    non-empty under the decode-style query/key end alignment)."""
    sq = draw(st.integers(min_value=1, max_value=200))
    sk = sq + draw(st.integers(min_value=0, max_value=200))
    d = draw(st.sampled_from([4, 32, 64]))
    causal = draw(st.booleans())
    window = draw(st.sampled_from([None, 7, 64]))
    return sq, sk, d, causal, window


class TestOnlineRescaledAttention:
    @given(shape=attention_shapes(),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    # one key: the KV level has bound 1 and still carries the m/l/acc walk
    @example(shape=(1, 1, 4, False, None), seed=0)
    @example(shape=(1, 1, 4, True, 7), seed=1)
    @settings(max_examples=20, deadline=None)
    def test_shape_sweep_matches_oracle(self, shape, seed):
        sq, sk, d, causal, window = shape
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((sq, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((sk, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((sk, d)), jnp.float32)
        _assert_close(
            ssr_flash_attention(q, k, v, causal=causal, window=window),
            ref.attention_ref(q, k, v, causal=causal, window=window))

    def test_schedule_invariance(self):
        # the m/l/acc recurrence must not depend on the kv tiling: the
        # rescale factor exp(m − m') re-normalises whatever the block
        # boundary was, so any legal schedule agrees to float error
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((192, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((256, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((256, 64)), jnp.float32)
        base = ssr_flash_attention(q, k, v, causal=True)
        for sched in (Schedule(buffer_depth=3), Schedule(rows=16),
                      Schedule(lanes_tile_factor=2)):
            got = ssr_flash_attention(q, k, v, causal=True, schedule=sched)
            _assert_close(got, base, tol=1e-6)
