"""Bitonic sort network — paper §4.2 (1024 values).

A sort network is data-oblivious: the compare-exchange pattern is fixed, so
every stage's partner access is an affine (power-of-two strided) stream —
the reason the paper can SSR-ify a *sort*.  All log²(n)/2-ish stages unroll
statically.  The vector sits in VMEM as (rows, 128) tiles; element
``i = row·128 + lane`` meets its partner ``i ^ j`` through two rotations
(lanes for ``j < 128``, rows otherwise) and a static iota mask picks the
rotation and the min/max side — no data-dependent addressing and no shape
casts, which is what Mosaic can lower.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core import BlockStream, Direction

from .frontend import LANES, Launch, StreamKernel, require_power_of_two
from .registry import KernelEntry, register_kernel


# Every layout is at least one whole (8, 128) 32-bit tile; shorter inputs
# are padded with the dtype's largest value, which sorts to the tail that
# ``finish`` trims.
_MIN_ELEMS = 8 * LANES


def _prepare(x):
    n = x.shape[0]
    require_power_of_two(n, "bitonic network")
    pad = max(0, _MIN_ELEMS - n)
    if pad:
        top = (jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
               else jnp.iinfo(x.dtype).max)
        x = jnp.pad(x, (0, pad), constant_values=top)
    return (x.reshape(-1, LANES),), None, n


def _partner(x, i, j: int):
    """``x[i ^ j]`` for every flat index ``i`` of a (rows, LANES) block."""
    axis, d = (1, j) if j < LANES else (0, j // LANES)
    size = x.shape[axis]
    up = pltpu.roll(x, size - d, axis)       # x[i + j]
    down = pltpu.roll(x, d, axis)            # x[i - j]
    return jnp.where((i & j) == 0, up, down)


def _body(static):
    def body(x_ref, o_ref):
        x = x_ref[...]
        rows = x.shape[0]
        n = rows * LANES
        i = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) * LANES
             + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1))
        for ks in range(1, int(math.log2(n)) + 1):   # k = 2**ks
            k = 1 << ks
            for js in range(ks - 1, -1, -1):          # j = 2**js
                j = 1 << js
                p = _partner(x, i, j)
                # the lower index of an ascending pair (or the upper of a
                # descending one) keeps the min
                keep_min = ((i & j) == 0) == ((i & k) == 0)
                x = jnp.where(keep_min, jnp.minimum(x, p), jnp.maximum(x, p))
        o_ref[...] = x

    return body


def _launch(static, x2d):
    return Launch(
        grid=(1,),
        in_streams=(BlockStream(x2d.shape, lambda i: (0, 0), name="x"),),
        out_streams=(BlockStream(x2d.shape, lambda i: (0, 0),
                                 Direction.WRITE, name="y"),),
        out_shapes=(jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),),
    )


_ssr = StreamKernel(
    "bitonic", prepare=_prepare, launch=_launch, body=_body,
    finish=lambda out, n: out.reshape(-1)[:n],
    lowering_waiver=(
        "compare-exchange network: each stage pairs elements at a "
        "different power-of-two distance — data-oblivious and affine per "
        "stage, but not one dense block walk over a single LoopNest"))


def ssr_sort(x: jax.Array, *, interpret=None) -> jax.Array:
    """Ascending sort of a power-of-two length vector."""
    return _ssr(x, interpret=interpret)


@register_kernel("bitonic")
def _entry() -> KernelEntry:
    from . import ref

    def example(rng, odd: bool = False):
        n = 64 if odd else 1024    # no odd sizes: the network requires 2^k
        return ((jnp.asarray(rng.standard_normal(n), jnp.float32),), {})

    return KernelEntry(name="bitonic", ssr=ssr_sort, ref=ref.sort_ref,
                       example=example, tol={"rtol": 0.0, "atol": 0.0},
                       problem="sort network, n=1024")
