"""CSR sparse kernels (SpMV / SpMM) on the indirection-stream path.

The SSR paper streams *affine* walks; its follow-ups — Indirection-SSR
(arXiv 2011.08070) and Sparse SSR (arXiv 2305.05559) — extend the AGU with
an index stream feeding the address stage, which is exactly what CSR
sparse-dense products need: the column-index stream drives gathers from the
dense operand.  This module is that extension end to end through the
*existing* pipeline:

* the host side validates CSR (loud ``ValueError`` per malformed invariant)
  and packs it to ELL — ``(m, k)`` value/column-index planes, ``k`` the max
  row population — because Pallas block schedules need static shapes; the
  pad entries are ``(0.0, 0)`` so they gather ``x[0]`` times zero;
* :func:`repro.core.compiler.spmv_nest` / :func:`~repro.core.compiler.
  spmm_nest` declare the loop nests with an **indirect** :class:`~repro.
  core.nest_analysis.MemRef` (``index_of="cidx"``), and ``ssrify`` /
  ``lower_nest`` / ``ssr_call`` do the rest — the gather table rides whole
  in VMEM, the body sees gathered blocks, the contraction accumulates;
* the baselines are monolithic single-step kernels with the *explicit*
  index handling (in-body ``jnp.take`` per element) the indirection papers
  charge against scalar cores; the refs densify and ``jnp.dot``.

Because ELL's row capacity ``k`` is a *data* fact (max nnz per row), the
public entry points take concrete CSR arrays, derive ``k`` on the host, and
only then enter the shape-static ``NestKernel`` — sparse formats are not
jit-transparent, by construction.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import compiler

from .frontend import MonolithicKernel, NestKernel, promote
from .registry import KernelEntry, register_kernel

# Mosaic's gather lowering takes only a 2-D ``take_along_axis`` whose index
# block has the operand's shape; the indirection stream gathers arbitrary
# addresses from a flattened VMEM-resident table, so both kernels stay off
# the chip until the gather is re-lowered (a segment reduce over indptr).
MOSAIC_REFUSAL = ("Pallas TPU lowering rejects the in-kernel jnp.take on the "
                  "flattened gather table: 'Only 2D gather is supported'")

# Padded row pitch of the SpMM gather table: the indirect ref's
# ``index_scale`` must be a static layout fact, so the dense operand is
# padded to a lane-aligned pitch independent of the (searched) schedule.
_TABLE_PITCH = 128


# --------------------------------------------------------------------------
# Host-side CSR validation + ELL packing
# --------------------------------------------------------------------------


def validate_csr(data, indices, indptr, num_cols: int
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Check the CSR invariants; return host arrays + row count.

    Every violation raises a ``ValueError`` whose message is pinned by
    ``tests/test_sparse.py`` — these are API surface, not prose.
    """
    data = np.asarray(data)
    indices = np.asarray(indices)
    indptr = np.asarray(indptr)
    if indptr.ndim != 1 or indptr.size < 2:
        raise ValueError(
            "CSR indptr must be 1-D with at least two entries (m+1)")
    if data.ndim != 1 or indices.ndim != 1 or data.shape != indices.shape:
        raise ValueError("CSR data and indices must be 1-D of equal length")
    if np.any(np.diff(indptr) < 0):
        raise ValueError("CSR indptr must be non-decreasing")
    if indptr[0] != 0 or indptr[-1] != data.size:
        raise ValueError("CSR indptr must start at 0 and end at nnz")
    if indices.size and (indices.min() < 0 or indices.max() >= num_cols):
        raise ValueError(
            f"CSR column index out of range [0, {num_cols})")
    if indices.size > 1:
        jumps = np.diff(indices)
        same_row = np.ones(indices.size - 1, dtype=bool)
        starts = indptr[1:-1]
        starts = starts[(starts > 0) & (starts < indices.size)]
        same_row[starts - 1] = False
        if np.any(jumps[same_row] <= 0):
            raise ValueError(
                "CSR column indices must be strictly increasing within "
                "each row")
    return data, indices, indptr, indptr.size - 1


def csr_to_ell(data, indices, indptr, num_cols: int
               ) -> Tuple[np.ndarray, np.ndarray, int, int]:
    """Validate + pack CSR to ELL ``(vals, cidx, m, k)`` host arrays.

    ``k`` is the max row population (≥ 1 so the nest never degenerates);
    pad slots hold ``(0.0, 0)`` — a zero-weighted gather of element 0.
    """
    data, indices, indptr, m = validate_csr(data, indices, indptr, num_cols)
    counts = np.diff(indptr)
    k = int(max(1, counts.max(initial=0)))
    vals = np.zeros((m, k), np.float32)
    cidx = np.zeros((m, k), np.int32)
    if data.size:
        rows = np.repeat(np.arange(m), counts)
        pos = np.arange(data.size) - np.repeat(indptr[:-1], counts)
        vals[rows, pos] = data
        cidx[rows, pos] = indices
    return vals, cidx, m, k


def csr_to_dense(data, indices, indptr, num_cols: int) -> np.ndarray:
    """Densify (validating) — the differential-testing oracle's input."""
    data, indices, indptr, m = validate_csr(data, indices, indptr, num_cols)
    dense = np.zeros((m, num_cols), np.float32)
    if data.size:
        counts = np.diff(indptr)
        rows = np.repeat(np.arange(m), counts)
        dense[rows, indices] = data
    return dense


# --------------------------------------------------------------------------
# SpMV: y[m] = A_csr[m, n] · x[n]
# --------------------------------------------------------------------------


def _prepare_spmv(vals, cidx, x, m=None, k=None):
    return ({"vals": vals, "cidx": cidx, "x": x}, (m, k), None)


def _nest_spmv(static):
    m, k = static
    return compiler.spmv_nest(m, k)


def _body_spmv(static):
    def body(vals_blk, cidx_blk, x_gathered):
        # cidx's block rides to the kernel anyway (it feeds the gather's
        # addresses); the body consumes only vals × gathered-x.
        del cidx_blk
        return jnp.sum(promote(vals_blk) * promote(x_gathered), axis=1)

    return body


_ssr_spmv = NestKernel("spmv", prepare=_prepare_spmv, nest=_nest_spmv,
                       body=_body_spmv, mosaic_refusal=MOSAIC_REFUSAL)


def ssr_spmv(data, indices, indptr, x, *, interpret=None,
             schedule=None) -> jax.Array:
    """y = A·x for CSR ``A`` through the compiled indirection-stream path."""
    x = jnp.asarray(x, jnp.float32)
    vals, cidx, m, k = csr_to_ell(data, indices, indptr, int(x.shape[0]))
    return _ssr_spmv(jnp.asarray(vals), jnp.asarray(cidx), x, m=m, k=k,
                     interpret=interpret, schedule=schedule)


# The sparse-row generalisation of gemv: identical entry point, named for
# call sites that think in dense-kernel terms (cidx = iota recovers gemv).
ssr_sparse_gemv = ssr_spmv


def _prepare_spmv_base(vals, cidx, x):
    return ((vals, cidx, x.reshape(1, -1)), int(vals.shape[0]), None)


def _base_body_spmv(static):
    def body(v_ref, c_ref, x_ref, o_ref):
        # Explicit index handling, the scalar-core baseline the papers
        # count: load the index block, compute each address, gather one
        # element at a time (batched here as one take), then multiply.
        x = x_ref[...].reshape(-1)
        c = c_ref[...]
        g = jnp.take(x, c.reshape(-1), mode="clip").reshape(c.shape)
        o_ref[...] = jnp.sum(v_ref[...] * g, axis=1, keepdims=True)

    return body


_base_spmv = MonolithicKernel(
    "spmv", prepare=_prepare_spmv_base, body=_base_body_spmv,
    out_shape=lambda m, v, c, x: jax.ShapeDtypeStruct((m, 1), jnp.float32),
    finish=lambda out, _final: out[:, 0])


def baseline_spmv(data, indices, indptr, x, *, interpret=None) -> jax.Array:
    x = jnp.asarray(x, jnp.float32)
    vals, cidx, _m, _k = csr_to_ell(data, indices, indptr, int(x.shape[0]))
    return _base_spmv(jnp.asarray(vals), jnp.asarray(cidx), x,
                      interpret=interpret)


def ref_spmv(data, indices, indptr, x) -> jax.Array:
    """Densified ``jnp.dot`` oracle (also the ``ssrcfg``-off path)."""
    x = jnp.asarray(x, jnp.float32)
    dense = csr_to_dense(data, indices, indptr, int(x.shape[0]))
    return jnp.dot(jnp.asarray(dense), x)


# --------------------------------------------------------------------------
# SpMM: Y[m, c] = A_csr[m, n] · X[n, c]
# --------------------------------------------------------------------------


def _prepare_spmm(vals, cidx, x, m=None, k=None, pitch=None):
    c = int(x.shape[1])
    xp = jnp.pad(x, ((0, 0), (0, pitch - c)))
    return ({"vals": vals, "cidx": cidx, "X": xp}, (m, c, k, pitch), None)


def _nest_spmm(static):
    m, c, k, pitch = static
    return compiler.spmm_nest(m, c, k, pitch)


def _body_spmm(static):
    def body(vals_blk, cidx_blk, x_gathered):
        del cidx_blk
        # gathered block is (tile_c, tile_m, tile_k): the affine column
        # level prepends one dimension to the index block's (m, k) walk.
        return jnp.einsum("mk,cmk->mc", promote(vals_blk),
                          promote(x_gathered))

    return body


_ssr_spmm = NestKernel("spmm", prepare=_prepare_spmm, nest=_nest_spmm,
                       body=_body_spmm, mosaic_refusal=MOSAIC_REFUSAL)


def ssr_spmm(data, indices, indptr, x, *, interpret=None,
             schedule=None) -> jax.Array:
    """Y = A·X for CSR ``A``, dense ``X[n, c]`` — the compiled gather path."""
    x = jnp.asarray(x, jnp.float32)
    if x.ndim != 2:
        raise ValueError(f"spmm needs a dense (n, c) operand, got {x.shape}")
    vals, cidx, m, k = csr_to_ell(data, indices, indptr, int(x.shape[0]))
    pitch = -(-int(x.shape[1]) // _TABLE_PITCH) * _TABLE_PITCH
    return _ssr_spmm(jnp.asarray(vals), jnp.asarray(cidx), x,
                     m=m, k=k, pitch=pitch,
                     interpret=interpret, schedule=schedule)


def _prepare_spmm_base(vals, cidx, x):
    return ((vals, cidx, x),
            (int(vals.shape[0]), int(x.shape[1])), None)


def _base_body_spmm(static):
    def body(v_ref, c_ref, x_ref, o_ref):
        c = c_ref[...]
        g = jnp.take(x_ref[...], c.reshape(-1), axis=0, mode="clip")
        g = g.reshape(c.shape + (x_ref.shape[1],))
        o_ref[...] = jnp.einsum("mk,mkc->mc", v_ref[...], g)

    return body


_base_spmm = MonolithicKernel(
    "spmm", prepare=_prepare_spmm_base, body=_base_body_spmm,
    out_shape=lambda st, v, c, x: jax.ShapeDtypeStruct(st, jnp.float32))


def baseline_spmm(data, indices, indptr, x, *, interpret=None) -> jax.Array:
    x = jnp.asarray(x, jnp.float32)
    vals, cidx, _m, _k = csr_to_ell(data, indices, indptr, int(x.shape[0]))
    return _base_spmm(jnp.asarray(vals), jnp.asarray(cidx), x,
                      interpret=interpret)


def ref_spmm(data, indices, indptr, x) -> jax.Array:
    x = jnp.asarray(x, jnp.float32)
    dense = csr_to_dense(data, indices, indptr, int(x.shape[0]))
    return jnp.dot(jnp.asarray(dense), x)


# --------------------------------------------------------------------------
# Registry entries
# --------------------------------------------------------------------------


def random_csr(rng, m: int, n: int, density: float
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """A random CSR ``(data, indices, indptr)`` triple at ``density``."""
    mask = rng.random((m, n)) < density
    dense = np.where(mask, rng.standard_normal((m, n)), 0.0)
    indptr = np.zeros(m + 1, np.int64)
    cols, vals = [], []
    for i in range(m):
        nz = np.nonzero(dense[i])[0]
        cols.append(nz)
        vals.append(dense[i, nz])
        indptr[i + 1] = indptr[i] + nz.size
    indices = np.concatenate(cols) if cols else np.zeros(0, np.int64)
    data = np.concatenate(vals) if vals else np.zeros(0, np.float64)
    return (data.astype(np.float32), indices.astype(np.int64), indptr)


@register_kernel("spmv")
def _entry_spmv() -> KernelEntry:
    def example(rng, odd: bool = False):
        m, n, density = (37, 53, 0.1) if odd else (32, 32, 0.25)
        data, indices, indptr = random_csr(rng, m, n, density)
        x = rng.standard_normal(n).astype(np.float32)
        return ((data, indices, indptr, x), {})

    return KernelEntry(name="spmv", ssr=ssr_spmv, baseline=baseline_spmv,
                       ref=ref_spmv, example=example,
                       mosaic_refusal=MOSAIC_REFUSAL,
                       tol={"rtol": 1e-5, "atol": 1e-5},
                       problem="CSR 32×32 @ 25% density")


@register_kernel("spmm")
def _entry_spmm() -> KernelEntry:
    def example(rng, odd: bool = False):
        m, n, c, density = (29, 41, 17, 0.1) if odd else (32, 32, 16, 0.25)
        data, indices, indptr = random_csr(rng, m, n, density)
        x = rng.standard_normal((n, c)).astype(np.float32)
        return ((data, indices, indptr, x), {})

    return KernelEntry(name="spmm", ssr=ssr_spmm, baseline=baseline_spmm,
                       ref=ref_spmm, example=example,
                       mosaic_refusal=MOSAIC_REFUSAL,
                       tol={"rtol": 1e-5, "atol": 1e-5},
                       problem="CSR 32×32 · 32×16 @ 25% density")
