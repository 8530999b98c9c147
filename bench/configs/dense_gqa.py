"""Plain float32 reference of a dense GQA decoder stage, and its weights.

The architecture of ``qwen3-14b.json`` and ``yi-6b.json``: pre-norm
decoder layers with RMSNorm, one fused QKV projection, optional per-head
RMSNorm on q and k (Qwen3), rotary embeddings in the ``rotate_half`` form
of the Hugging Face modelling code, causal grouped-query attention (query
head h reads KV head h // group), an output projection and residual, then
RMSNorm, a SwiGLU MLP (down(silu(gate(x)) * up(x))) and residual.

Straight ``jax.numpy``; it imports nothing of the system under test.  The
caller runs it under ``jax.default_matmul_precision("highest")``.  Every
contraction goes through ``dot``, and every weight and stored activation
through ``store``, so that the correctness controls can run the same forward
at a lower precision.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

Dot = Callable[[jax.Array, jax.Array], jax.Array]
Store = Callable[[jax.Array], jax.Array]


def sizes(cfg: dict) -> Dict[str, int]:
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return {"d": cfg["hidden_size"], "ff": cfg["intermediate_size"],
            "heads": h, "kv": kv, "hd": hd, "group": h // kv,
            "qkv": (h + 2 * kv) * hd, "layers": cfg["num_hidden_layers"]}


def weight_shapes(cfg: dict, phase: str) -> Dict[str, tuple]:
    """Shapes of one layer's weights.  Prefill multiplies activations by
    (in, out) matrices; decode multiplies (out, in) matrices by a vector,
    with gate and up stacked into one (2·ff, d) matrix."""
    s = sizes(cfg)
    d, ff, hd = s["d"], s["ff"], s["hd"]
    if phase == "prefill":
        mats = {"wqkv": (d, s["qkv"]), "wo": (s["heads"] * hd, d),
                "wgate": (d, ff), "wup": (d, ff), "wdown": (ff, d)}
    else:
        mats = {"wqkv": (s["qkv"], d), "wo": (d, s["heads"] * hd),
                "wgu": (2 * ff, d), "wdown": (d, ff)}
    vecs = {"ln1": (d,), "ln2": (d,)}
    if cfg.get("qk_norm"):
        vecs.update(q_norm=(hd,), k_norm=(hd,))
    return {**mats, **vecs}


def fan_in(name: str, shape: tuple, phase: str) -> int:
    return shape[0] if phase == "prefill" else shape[1]


def init_weights(cfg: dict, key: jax.Array, phase: str,
                 shardings: Optional[Dict[str, object]] = None) -> List[dict]:
    """Every layer's weights from ``key``, made on the device in one jitted
    call: N(0, 1/fan_in) matrices, so activations stay O(1) through the
    stage, and norm gains 1 + 0.1·N(0, 1).  ``shardings`` places each
    weight (by name) where it is made, so no chip holds more than its
    share."""
    shapes = weight_shapes(cfg, phase)
    layers = sizes(cfg)["layers"]

    def make(key):
        out = []
        for lk in jax.random.split(key, layers):
            w = {}
            for (name, shape), k in zip(sorted(shapes.items()),
                                        jax.random.split(lk, len(shapes))):
                z = jax.random.normal(k, shape, jnp.float32)
                w[name] = (1.0 + 0.1 * z if len(shape) == 1 else
                           z * (1.0 / math.sqrt(fan_in(name, shape, phase))))
            out.append(w)
        return out

    if shardings is None:
        return jax.jit(make)(key)
    out = [{name: shardings[name] for name in shapes}] * layers
    return jax.jit(make, out_shardings=out)(key)


# ---------------------------------------------------------------------------
# The forward, written once for both phases
# ---------------------------------------------------------------------------


def exact_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` in float32 (at the precision the caller sets)."""
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x (..., T, heads, hd) rotated at positions pos (T,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def _attend(dot: Dot, q, k, v, causal_offset):
    """q (Tq, H, hd), k/v (Tk, KV, hd); query i sees key j ≤ i + offset
    (all keys when ``causal_offset`` is None).  One head at a time."""
    tq, h, hd = q.shape
    group = h // k.shape[1]
    scale = 1.0 / math.sqrt(hd)

    def head(i):
        qh = q[:, i, :]
        kh = k[:, i // group, :]
        vh = v[:, i // group, :]
        s = dot(qh, kh.T) * scale
        if causal_offset is not None:
            rows = jnp.arange(tq)[:, None] + causal_offset
            s = jnp.where(jnp.arange(k.shape[0])[None, :] <= rows, s,
                          -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return dot(p, vh)

    out = jax.lax.map(head, jnp.arange(h))          # (H, Tq, hd)
    return jnp.transpose(out, (1, 0, 2)).reshape(tq, h * hd)


def keep(a: jax.Array) -> jax.Array:
    """Storage in float32: the value as computed."""
    return a


def _layer(cfg, dot: Dot, store: Store, w, x, pos, k_past, v_past, phase):
    s = sizes(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    w = {name: store(m) for name, m in w.items()}

    def proj(a, name):
        m = w[name]
        return store(dot(a, m if phase == "prefill" else m.T))

    t = x.shape[0]
    h = store(_rms(x, w["ln1"], eps))
    qkv = proj(h, "wqkv")
    nq, nk = s["heads"] * s["hd"], s["kv"] * s["hd"]
    q = qkv[:, :nq].reshape(t, s["heads"], s["hd"])
    k = qkv[:, nq:nq + nk].reshape(t, s["kv"], s["hd"])
    v = qkv[:, nq + nk:].reshape(t, s["kv"], s["hd"])
    if cfg.get("qk_norm"):
        q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
    q, k = store(_rope(q, pos, theta)), store(_rope(k, pos, theta))
    if k_past is not None:       # decode: the cache holds the earlier keys
        k = jnp.concatenate([store(k_past), k], 0)
        v = jnp.concatenate([store(v_past), v], 0)
    a = store(_attend(dot, q, k, v, k.shape[0] - t))
    x = store(x + proj(a, "wo"))
    h2 = store(_rms(x, w["ln2"], eps))
    if phase == "prefill":
        gate, up = proj(h2, "wgate"), proj(h2, "wup")
    else:
        gu = proj(h2, "wgu")
        gate, up = gu[:, :s["ff"]], gu[:, s["ff"]:]
    return store(x + proj(store(jax.nn.silu(gate) * up), "wdown"))


@partial(jax.jit, static_argnums=(0, 1, 2))
def _prefill(cfg_items, dot, store, weights, x):
    cfg = dict(cfg_items)
    pos = jnp.arange(x.shape[0])
    x = store(x)
    for w in weights:
        x = _layer(cfg, dot, store, w, x, pos, None, None, "prefill")
    return x


@partial(jax.jit, static_argnums=(0, 1, 2))
def _decode(cfg_items, dot, store, weights, x, k_cache, v_cache):
    cfg = dict(cfg_items)
    ctx = k_cache.shape[2]
    pos = jnp.array([ctx - 1])
    x = store(x[None, :])
    for l, w in enumerate(weights):
        # the cache's last position is the new token's own slot
        k_past = jnp.transpose(k_cache[l, :, :ctx - 1], (1, 0, 2))
        v_past = jnp.transpose(v_cache[l, :, :ctx - 1], (1, 0, 2))
        x = _layer(cfg, dot, store, w, x, pos, k_past, v_past, "decode")
    return x[0]


def _key(cfg: dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def forward(cfg: dict, weights: List[dict], inputs: dict, i: int,
            dot: Dot = exact_dot, store: Store = keep) -> jax.Array:
    """The stage's output for pool entry ``i`` of the generated inputs:
    (tokens, d) after prefill, (d,) after one decode step."""
    if "k_cache" in inputs:
        return _decode(_key(cfg), dot, store, weights, inputs["x"][i],
                       inputs["k_cache"], inputs["v_cache"])
    return _prefill(_key(cfg), dot, store, weights, inputs["x"][i])


# ---------------------------------------------------------------------------
# The controls: the same forward with fp8 contractions, or stored in bf16
# ---------------------------------------------------------------------------


def bf16_store(a: jax.Array) -> jax.Array:
    """Round to bfloat16 (8 exponent bits, 7 mantissa bits), kept in
    float32: weights, activations and the KV cache as a program that stores
    them in bfloat16 would hold them.  Its contractions then take bf16
    operands with f32 accumulation, and attention keeps its scores and
    softmax in f32, so it is the lenient form of such a program."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _fp8(a: jax.Array) -> jax.Array:
    """Round to an fp8 e4m3 grid (4 exponent bits, 3 mantissa bits) under
    a per-tensor amax scale, as an fp8 path would store it, in float32.

    ``reduce_precision`` and not a cast to ``float8_e4m3fn`` and back: XLA
    may drop such a round trip where it fuses the contraction into an
    element-wise reduce (as it does for a matrix-vector product on the
    TPU), which would leave the control at full precision.  The scale maps
    the largest magnitude to 240, the format's largest finite value.
    """
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 240.0
    return jax.lax.reduce_precision(a / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def fp8_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """The contraction one precision step below the program's bf16 pass:
    fp8 (e4m3) operands, f32 accumulation."""
    return jnp.matmul(_fp8(a), _fp8(b), preferred_element_type=jnp.float32)
