"""Scan (all prefix sums) over a long vector — paper §4.2, 4096 values.

SSR structure: one read stream and one write stream walk the vector in
lockstep; the carry lives in a VMEM scratch register across grid steps (the
sequential dependence the paper handles with the accumulator register).  The
grid dimension is ``arbitrary`` (sequential) — blocks must retire in order,
but the *fetch* of block i+1 still overlaps the compute of block i.

The in-block prefix is two matmuls against triangular ones matrices (Mosaic
has no ``cumsum`` lowering): lane prefixes ``x @ U`` within each row, then
each row's offset as ``L @ row_totals`` — both land on the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import BlockStream, Direction

from .frontend import (LANES, ROWS, Launch, MonolithicKernel, StreamKernel,
                       pad_vector, promote, trim_vector)
from .registry import KernelEntry, register_kernel


def _prepare(x):
    return (pad_vector(x),), None, x.shape[0]


def _tri(n: int, strict: bool) -> jax.Array:
    """``n×n`` ones on and above (or strictly below) the diagonal."""
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.where(c < r if strict else r <= c, 1.0, 0.0).astype(jnp.float32)


def block_prefix(x: jax.Array) -> jax.Array:
    """Inclusive row-major prefix sum of a (rows, lanes) f32 block."""
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    rows = dot(x, _tri(x.shape[1], strict=False))        # lane prefixes
    offs = dot(_tri(x.shape[0], strict=True), rows[:, -1:])  # earlier rows
    return rows + offs


def _ssr_body(static):
    def body(x_ref, o_ref, carry_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            carry_ref[...] = jnp.zeros_like(carry_ref)

        out = block_prefix(promote(x_ref[...])) + carry_ref[...]
        o_ref[...] = out
        carry_ref[...] = out[-1:, -1:]

    return body


def _launch(static, x2d):
    return Launch(
        grid=(x2d.shape[0] // ROWS,),
        in_streams=(BlockStream((ROWS, LANES), lambda i: (i, 0), name="x"),),
        out_streams=(BlockStream((ROWS, LANES), lambda i: (i, 0),
                                 Direction.WRITE, name="y"),),
        out_shapes=(jax.ShapeDtypeStruct(x2d.shape, jnp.float32),),
        scratch_shapes=(pltpu.VMEM((1, 1), jnp.float32),),
        dimension_semantics=("arbitrary",),
    )


_ssr = StreamKernel(
    "scan", prepare=_prepare, launch=_launch, body=_ssr_body,
    finish=trim_vector,
    lowering_waiver=(
        "loop-carried dependence: block i+1's prefix needs block i's total "
        "— a sequenced VMEM carry register, not an affine stream walk a "
        "LoopNest can express"))


def _baseline_body(static):
    def body(x_ref, o_ref):
        # Monolithic: single grid step, in-body block walk, explicit loads.
        nblk = x_ref.shape[0] // ROWS

        def step(i, carry):
            x = promote(x_ref[pl.dslice(i * ROWS, ROWS), :])
            out = block_prefix(x) + carry
            o_ref[pl.dslice(i * ROWS, ROWS), :] = out
            return out[-1:, -1:]

        jax.lax.fori_loop(0, nblk, step, jnp.zeros((1, 1), jnp.float32))

    return body


_base = MonolithicKernel(
    "scan", prepare=_prepare, body=_baseline_body,
    out_shape=lambda static, x2d: jax.ShapeDtypeStruct(x2d.shape,
                                                       jnp.float32),
    finish=trim_vector)


def ssr_scan(x: jax.Array, *, interpret=None) -> jax.Array:
    """Inclusive prefix sum; input padded to whole blocks, result trimmed."""
    return _ssr(x, interpret=interpret)


def baseline_scan(x: jax.Array, *, interpret=None) -> jax.Array:
    return _base(x, interpret=interpret)


@register_kernel("scan")
def _entry() -> KernelEntry:
    from . import ref

    def example(rng, odd: bool = False):
        n = 3000 if odd else 4096
        return ((jnp.asarray(rng.standard_normal(n), jnp.float32),), {})

    return KernelEntry(name="scan", ssr=ssr_scan, baseline=baseline_scan,
                       ref=ref.scan_ref, example=example,
                       tol={"rtol": 1e-3, "atol": 1e-3},
                       problem="prefix sums, n=4096")
