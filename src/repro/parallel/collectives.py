"""Collective-overlap matmuls: the SSR data-mover idea at cluster scale.

The paper's data mover prefetches the *next* operand while the FPU consumes
the current one.  At the ICI level the same structure is a **ring collective
matmul**: weights live row-sharded across the axis (FSDP); instead of
``all-gather(W)`` followed by one big matmul (fetch-then-compute, the
"explicit load" shape), each device multiplies the shard it currently holds
while ``ppermute`` streams the next shard around the ring — compute hides
the transfer exactly as the SSR FIFO hides memory latency.  Total bytes
equal the all-gather's; the win is overlap (plus never materialising the
full W per device: peak weight memory drops from |W| to 2·|W|/n).

Used by the §Perf hillclimb on the FSDP-gather-bound dense cells; validated
numerically against the plain matmul on fake devices.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _ring_body(x: jax.Array, w_shard: jax.Array, axis: str) -> jax.Array:
    """Inside shard_map: y_local = x_local @ concat_j(w_j).

    x (b_loc, D) — full contraction dim locally; w_shard (D/n, F) — this
    device's row block.  n ring steps, each: partial matmul with the block
    currently held, then rotate the block to the neighbour.
    """
    n = jax.lax.psum(1, axis)
    me = jax.lax.axis_index(axis)
    d_blk = w_shard.shape[0]
    f = w_shard.shape[1]
    b = x.shape[0]
    perm = [(i, (i - 1) % n) for i in range(n)]

    def step(t, carry):
        acc, w_cur = carry
        j = (me + t) % n                       # block id currently held
        xb = jax.lax.dynamic_slice_in_dim(x, j * d_blk, d_blk, axis=1)
        acc = acc + jnp.dot(xb, w_cur, preferred_element_type=jnp.float32)
        # stream the next operand block around the ring (the data mover)
        w_cur = jax.lax.ppermute(w_cur, axis, perm)
        return acc, w_cur

    acc0 = jnp.zeros((b, f), jnp.float32)
    acc, _ = jax.lax.fori_loop(0, n, step, (acc0, w_shard))
    return acc.astype(x.dtype)


def ring_matmul(x: jax.Array, w: jax.Array, mesh: Mesh, *,
                axis: str = "data",
                batch_axes: Optional[tuple] = None) -> jax.Array:
    """y = x @ w with w row-sharded over ``axis`` and streamed via ring.

    ``x`` (B, D) may be batch-sharded over ``batch_axes``; ``w`` (D, F) is
    stored P(axis, None).  Output follows x's batch sharding.
    """
    n = mesh.shape[axis]
    if w.shape[0] % n:
        raise ValueError(f"contraction dim {w.shape[0]} not divisible by "
                         f"ring size {n}")
    bspec = batch_axes if batch_axes else None
    fn = shard_map(
        functools.partial(_ring_body, axis=axis), mesh=mesh,
        in_specs=(P(bspec, None), P(axis, None)),
        out_specs=P(bspec, None),
        check_vma=False,
    )
    return fn(x, w)


def reduce_scatter_matmul(x: jax.Array, w: jax.Array, mesh: Mesh, *,
                          axis: str = "model",
                          batch_axes: Optional[tuple] = None) -> jax.Array:
    """y = x @ w with x col-sharded / w row-sharded over ``axis`` (TP down-
    projection): local partial matmul + psum — the contraction-parallel
    partner of :func:`ring_matmul` used by down-projections.
    """
    bspec = batch_axes if batch_axes else None

    def body(x_l, w_l):
        part = jnp.dot(x_l, w_l, preferred_element_type=jnp.float32)
        return jax.lax.psum(part, axis).astype(x.dtype)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(bspec, axis), P(axis, None)),
                   out_specs=P(bspec, None), check_vma=False)
    return fn(x, w)
