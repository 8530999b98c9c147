"""Streaming flash attention — the SSR technique applied to the LM hot spot.

Attention *is* the paper's reduction (§4.1/Fig. 4) writ large, and since
the online-rescaled accumulator landed in ``lower_nest`` it is fully
*nest-lowered*: the module declares only
:func:`repro.core.compiler.attention_nest` — K/V read streams over the kv
contraction level, Q a repeat stream, and an output WRITE ref with
``acc_kind="online_softmax"`` — plus the score body.  The lowering owns
the flash recurrence (DESIGN.md §13):

* the m/l/acc online-softmax state lives in VMEM scratch across the kv
  walk, *rescaled* by ``exp(m − m')`` every step — the generalised
  accumulator register;
* the kv grid axis is ``arbitrary`` (sequential), the rest ``parallel``;
  the pipeline prefetches the next K/V tile during this tile's two
  matmuls — the data-mover run-ahead that gives the paper its 3×;
* causal and sliding-window masks live in the nest: ``attention_nest``
  declares them as a band on the kv level, so the grid visits only the kv
  tiles the band meets.  The body's element mask (iota against the grid
  offsets — static index arithmetic, data-oblivious as SSR requires) runs
  only on the tiles at the band's edges and the padded last kv tile.

Supports MHA/GQA (q heads grouped over kv heads via an outer vmap), causal
and sliding-window (h2o-danube) masking.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import compiler
from repro.core.lowering import Schedule

from .frontend import NestKernel, promote
from .registry import KernelEntry, register_kernel

_NEG_INF = -1e30


def _prepare(q, k, v, causal=False, window=None, scale=None,
             bq=128, bk=128):
    # bq/bk are retained for call-site compatibility with the old
    # hand-tiled launch; tiling now comes from the lowering schedule.
    sq, d = q.shape
    sk = k.shape[0]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    static = (sq, sk, d, bool(causal), window, float(scale), str(q.dtype))
    return {"Q": q, "K": k, "V": v}, static, None


def _nest(static):
    sq, sk, d, causal, window = static[:5]
    return compiler.attention_nest(sq, sk, d, causal=causal, window=window)


def _body(static):
    sq, sk, d, causal, window, scale, _dt = static
    offs_rc = sk - sq  # query/key end alignment (decode-friendly)

    def body(k_blk, v_blk, q_blk, offs):
        # Raw scores for one (q-tile, kv-tile) step; the lowering's
        # online-softmax kernel owns the m/l/acc rescaling recurrence.
        # ``offs`` are the per-level global offsets (q, d, kv).
        s = jax.lax.dot_general(
            promote(q_blk), promote(k_blk), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        bq, bk = s.shape
        r0, c0 = offs[0] + offs_rc, offs[2]

        def masked(s):
            rows = r0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = c0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = cols < sk                     # padded kv columns
            if causal:
                mask = jnp.logical_and(mask, cols <= rows)
            if window is not None:
                mask = jnp.logical_and(mask, cols > rows - window)
            return jnp.where(mask, s, _NEG_INF)

        # a tile inside the band, short of the padding, keeps every pair
        edge = c0 + bk > sk
        if causal:
            edge = jnp.logical_or(edge, c0 + bk - 1 > r0)
        if window is not None:
            edge = jnp.logical_or(edge, c0 <= r0 + bq - 1 - window)
        return jax.lax.cond(edge, masked, lambda s: s, s), v_blk

    return body


_ssr = NestKernel("attention", prepare=_prepare, nest=_nest, body=_body,
                  out_dtype=lambda static: static[6])


def ssr_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = False, window: int | None = None,
                        scale: float | None = None, bq: int = 128,
                        bk: int = 128, interpret=None,
                        schedule: Schedule | None = None) -> jax.Array:
    """Single-head streaming attention; q (Sq,D), k/v (Sk,D).

    Multi-head / batch: ``jax.vmap`` this (tested); GQA: vmap over kv heads
    with q reshaped (kv_heads, group, Sq, D).  ``schedule=None`` resolves
    a tuned schedule from the autotuner's cache; ``bq``/``bk`` are
    accepted for call-site compatibility (tiles come from the schedule).
    """
    return _ssr(q, k, v, causal=causal, window=window, scale=scale,
                bq=bq, bk=bk, interpret=interpret, schedule=schedule)


@register_kernel("attention")
def _entry() -> KernelEntry:
    from . import ref

    def _ref(q, k, v, **kw):
        return ref.attention_ref(q, k, v, **kw).astype(q.dtype)

    def example(rng, odd: bool = False):
        # the odd size is windowed, over a head dim short of a lane tile
        sq, sk, d = (128, 256, 80) if odd else (256, 256, 64)
        kw = {"causal": True, "window": 100} if odd else {"causal": True}
        return ((jnp.asarray(rng.standard_normal((sq, d)), jnp.float32),
                 jnp.asarray(rng.standard_normal((sk, d)), jnp.float32),
                 jnp.asarray(rng.standard_normal((sk, d)), jnp.float32)),
                kw)

    return KernelEntry(name="attention", ssr=ssr_flash_attention, ref=_ref,
                       example=example, tol={"rtol": 2e-4, "atol": 2e-4},
                       problem="flash attention, S=256 D=64")
