"""A sliding-window GQA decoder stage driven through the stream compiler.

``dense_gqa``'s prefill, call for call — QKV, attention, O, gate, up and
down through ``repro.kernels.ops`` with ``ssr=True``, the benchmark's own
jitted glue in between — with the configuration's ``sliding_window`` given
to ``ops.flash_attention``: query i attends to keys (i − window, i].
"""

from __future__ import annotations

import math
from functools import partial
from typing import List, Tuple

import jax

from repro.kernels import ops

from .. import work
from ..configs.dense_gqa import sizes
from .dense_gqa import Stage as _DenseStage
from .dense_gqa import step_work as _dense_step_work
from .dense_gqa import weight_shardings  # noqa: F401  (the harness reads it)


class Stage(_DenseStage):
    """``step(i)`` issues one prefill of every layer on prompt ``i``."""

    def __init__(self, cfg: dict, traffic: dict, *args, **kw):
        if traffic["phase"] != "prefill":
            raise ValueError("the window_gqa stage is built for prefill only")
        super().__init__(cfg, traffic, *args, **kw)

    def _glue(self):
        super()._glue()
        self.attn_prefill = jax.vmap(jax.vmap(
            partial(ops.flash_attention, causal=True,
                    window=self.cfg["sliding_window"],
                    scale=1.0 / math.sqrt(self.s["hd"]), ssr=True),
            in_axes=(0, None, None)))

    def work(self) -> List[Tuple[str, work.Work]]:
        return step_work(self.cfg, self.traffic)


def window_pairs(t: int, window: int) -> int:
    """(query, key) pairs a window keeps over ``t`` tokens: query i sees
    min(i + 1, window) keys."""
    w = min(window, t)
    return w * (w + 1) // 2 + (t - w) * w


def step_work(cfg: dict, traffic: dict) -> List[Tuple[str, work.Work]]:
    """``dense_gqa``'s prefill work, each attention call counting the pairs
    the window keeps: 4·hd FLOPs a pair a query head (QKᵀ and PV), Q and O
    moved once per query head, K and V once per KV head."""
    s = sizes(cfg)
    t = traffic["tokens"]
    pairs = window_pairs(t, cfg["sliding_window"])
    attn = work.Work(
        "attention", 4.0 * s["hd"] * pairs * s["heads"],
        4.0 * s["hd"] * (2 * t * s["heads"] + 2 * t * s["kv"]))
    return [(span, attn if span == "ops.attention" else w)
            for span, w in _dense_step_work(cfg, traffic)]
