"""Algorithmic work of each call, from its shapes alone.

FLOPs and bytes here are what the algorithm needs, never what a kernel
happens to do: a kernel that re-reads an operand, pads, or computes masked
tiles pays for it in time, not in work.  Each operand and each output is
moved through HBM once.  The least time of a call on a chip is the larger
of its FLOPs over the FLOP peak and its bytes over the HBM peak.
"""

from __future__ import annotations

from typing import NamedTuple

from .peaks import Peaks


class Work(NamedTuple):
    family: str
    flops: float
    bytes: float

    def least_s(self, peaks: Peaks) -> float:
        return max(self.flops / peaks.flops, self.bytes / peaks.hbm_bytes)


def gemm(m: int, k: int, n: int, itemsize: int = 4) -> Work:
    """(m, k) · (k, n): 2mkn FLOPs; A, B and C moved once."""
    return Work("gemm", 2.0 * m * k * n, float(itemsize) * (m * k + k * n + m * n))


def gemv(rows: int, cols: int, itemsize: int = 4) -> Work:
    """(rows, cols) · (cols,): 2·rows·cols FLOPs; matrix, vector and output
    moved once."""
    return Work("gemv", 2.0 * rows * cols,
                float(itemsize) * (rows * cols + cols + rows))


def attention(q_heads: int, kv_heads: int, sq: int, sk: int, d: int, *,
              causal: bool, itemsize: int = 4) -> Work:
    """Softmax attention of ``q_heads`` query heads over ``kv_heads`` K/V
    heads: QKᵀ and PV, 2·d FLOPs per (query, key) pair each.

    Causal counts only the pairs the mask keeps, key j ≤ query i with the
    query block aligned to the end of the keys: T(T+1)/2 pairs for a square
    (T, T) block.  Q and O are moved once per query head; K and V once per
    KV head.
    """
    if causal:
        off = sk - sq
        pairs = sum(min(sk, off + i + 1) for i in range(sq)) \
            if off else sq * (sq + 1) / 2
    else:
        pairs = sq * sk
    flops = 4.0 * d * pairs * q_heads
    moved = itemsize * d * (2 * sq * q_heads + 2 * sk * kv_heads)
    return Work("attention", flops, float(moved))


def glue(name: str, read_elems: int, write_elems: int,
         itemsize: int = 4) -> Work:
    """Element-wise glue (norms, RoPE, SwiGLU, residuals): bytes only."""
    return Work(f"glue.{name}", 0.0,
                float(itemsize) * (read_elems + write_elems))
