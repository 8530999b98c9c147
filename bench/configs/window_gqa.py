"""Plain float32 reference of a sliding-window GQA decoder stage (prefill).

The architecture of ``h2o-danube-1.8b.json`` (arXiv:2401.16818): the
Llama block of ``dense_gqa`` — RMSNorm, one fused QKV projection, rotary
embeddings, grouped-query attention, output projection and residual, then
RMSNorm, a SwiGLU MLP and residual — with Mistral's sliding window on
every layer: query i attends to keys j with i − window < j ≤ i.

The norms, RoPE, weights and the controls are ``dense_gqa``'s.  The
attention is its own: it runs in query blocks, each over the keys its
band keeps, so a 16384-token prompt needs (heads, block, block + window − 1)
scores at a time, not (heads, T, T).  Straight ``jax.numpy``; it imports
nothing of the system under test.  The caller runs it under
``jax.default_matmul_precision("highest")``; every contraction goes through
``dot`` and every weight and stored activation through ``store``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import List

import jax
import jax.numpy as jnp

from .dense_gqa import (  # noqa: F401  (the controls calibrate.py reads)
    Dot, Store, _rms, _rope, bf16_store, exact_dot, fp8_dot, init_weights,
    keep, sizes, weight_shapes)

#: Queries a block of the reference's attention holds.
BLOCK = 512


def _attend(dot: Dot, q, k, v, window: int):
    """q (T, H, hd), k/v (T, KV, hd); query i sees keys (i − window, i].

    Query block b holds queries [b·B, (b+1)·B) and reads the keys
    [b·B − window + 1, (b+1)·B): its band, from K/V padded in front by
    window − 1 rows, so every block slices the same width.
    """
    t, h, hd = q.shape
    group = h // k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    bq = min(BLOCK, t)
    nb = -(-t // bq)
    width = bq + window - 1
    q = jnp.pad(q, ((0, nb * bq - t), (0, 0), (0, 0)))
    k = jnp.pad(k, ((window - 1, nb * bq - t), (0, 0), (0, 0)))
    v = jnp.pad(v, ((window - 1, nb * bq - t), (0, 0), (0, 0)))

    def block(b):
        a = b * bq
        qb = jnp.transpose(jax.lax.dynamic_slice_in_dim(q, a, bq), (1, 0, 2))
        kb = jax.lax.dynamic_slice_in_dim(k, a, width)
        vb = jax.lax.dynamic_slice_in_dim(v, a, width)
        kb = jnp.repeat(jnp.transpose(kb, (1, 2, 0)), group, 0)  # (H,hd,W)
        vb = jnp.repeat(jnp.transpose(vb, (1, 0, 2)), group, 0)  # (H,W,hd)
        s = dot(qb, kb) * scale                                   # (H,B,W)
        i = a + jnp.arange(bq)[:, None]
        j = a - window + 1 + jnp.arange(width)[None, :]
        keep_ = (j >= 0) & (j <= i) & (j > i - window)
        p = jax.nn.softmax(jnp.where(keep_, s, -jnp.inf), axis=-1)
        return dot(p, vb)                                         # (H,B,hd)

    out = jax.lax.map(block, jnp.arange(nb))            # (nb, H, B, hd)
    out = jnp.transpose(out, (0, 2, 1, 3)).reshape(nb * bq, h * hd)
    return out[:t]


@partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(cfg_items, dot: Dot, store: Store, w, x):
    cfg = dict(cfg_items)
    s = sizes(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    w = {name: store(m) for name, m in w.items()}
    t = x.shape[0]
    pos = jnp.arange(t)
    h = store(_rms(x, w["ln1"], eps))
    qkv = store(dot(h, w["wqkv"]))
    nq, nk = s["heads"] * s["hd"], s["kv"] * s["hd"]
    q = qkv[:, :nq].reshape(t, s["heads"], s["hd"])
    k = qkv[:, nq:nq + nk].reshape(t, s["kv"], s["hd"])
    v = qkv[:, nq + nk:].reshape(t, s["kv"], s["hd"])
    q, k = store(_rope(q, pos, theta)), store(_rope(k, pos, theta))
    a = store(_attend(dot, q, k, v, cfg["sliding_window"]))
    x = store(x + store(dot(a, w["wo"])))
    h2 = store(_rms(x, w["ln2"], eps))
    gate, up = store(dot(h2, w["wgate"])), store(dot(h2, w["wup"]))
    return store(x + store(dot(store(jax.nn.silu(gate) * up), w["wdown"])))


def _key(cfg: dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def forward(cfg: dict, weights: List[dict], inputs: dict, i: int,
            dot: Dot = exact_dot, store: Store = keep) -> jax.Array:
    """The stage's output for prompt ``i`` of the generated inputs:
    (tokens, d) after prefill.  One jitted layer, called once per layer,
    so the compile does not grow with the depth."""
    if "k_cache" in inputs:
        raise ValueError("window_gqa is a prefill reference")
    key = _key(cfg)
    x = store(inputs["x"][i])
    for w in weights:
        x = _layer(key, dot, store, w, x)
    return x
