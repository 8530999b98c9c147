"""A dense GQA decoder stage driven through the stream compiler.

This is the system under test as its users drive it: every projection and
the attention go through ``repro.kernels.ops`` with ``ssr=True`` (so through
``registry.dispatch`` → ``NestKernel`` → ``ssr_call`` → Mosaic), one call at
a time from Python, and the norms, RoPE, SwiGLU and residuals are the
benchmark's own jitted ``jax.numpy`` in between.  Attention is
``ops.flash_attention`` under ``jax.vmap``: over the query heads of a group
and the KV heads at prefill, over the KV heads at decode with the group's
query heads stacked as the rows of one block.

Per layer, prefill makes 6 compiler calls (QKV, attention, O, gate, up,
down GEMMs) and decode 5 (QKV, attention, O, gate·up, down GEMVs).

A configuration with ``"split": "cluster_2x2"`` runs every layer over four
chips through the paper's cluster layer (``repro.parallel.cluster``): each
GEMM split 2×2 over token rows × output columns by ``cluster_kernel2d``,
attention split over KV heads by ``cluster_kernel``.  Those calls build a
new ``shard_map`` on every call, which compiles every time when called
eagerly, so here each one is wrapped in ``jax.jit`` as its caller would:
the compiler's Python then runs once, when the call is traced, and a step
dispatches the compiled programs.  Weights are made in place, each GEMM's
matrix split by output columns and held by both chips of its row pair.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import ops
from repro.parallel.cluster import cluster_kernel, cluster_kernel2d, \
    factor_cores

from .. import work
from ..configs.dense_gqa import sizes, weight_shapes

Call = Callable[..., jax.Array]


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope_tables(pos, hd, theta):
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([ang, ang], -1)
    return jnp.cos(emb), jnp.sin(emb)


def _rotate(x, cos, sin):
    """x (T, heads, hd) with tables (T, hd)."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


def cluster_mesh(chips: int) -> Mesh:
    """The (rows × cols) mesh ``cluster_kernel2d`` builds for ``chips``."""
    cr, cc = factor_cores(chips)
    return Mesh(np.asarray(jax.devices()[:chips]).reshape(cr, cc),
                ("rows", "cols"))


def weight_shardings(cfg: dict, traffic: dict,
                     chips: int) -> Optional[Dict[str, NamedSharding]]:
    """Where each weight is made: ``None`` (the default device) on one
    chip; on the cluster each GEMM matrix split by output columns, as
    ``cluster_kernel2d`` reads it, and the norm gains on every chip."""
    if not cfg.get("split"):
        return None
    mesh = cluster_mesh(chips)
    cols, rep = NamedSharding(mesh, P(None, "cols")), NamedSharding(mesh, P())
    return {name: cols if len(shape) == 2 else rep
            for name, shape in weight_shapes(cfg, traffic["phase"]).items()}


class Stage:
    """``step(i)`` issues one forward of every layer on prompt ``i`` and
    returns its (not yet ready) output."""

    def __init__(self, cfg: dict, traffic: dict, weights: List[dict],
                 inputs: dict, call: Call, chips: int = 1):
        split = cfg.get("split")
        if (split, chips) not in ((None, 1), ("cluster_2x2", 4)):
            raise ValueError(f"no stage for split {split!r} on {chips} chips")
        if split and traffic["phase"] != "prefill":
            raise ValueError("the cluster split is built for prefill only")
        self.cfg, self.traffic, self.call = cfg, traffic, call
        self.cores = chips
        #: whether every ``ops.*`` span calls the compiler's frontend (eager),
        #: rather than a program the compiler's calls were traced into
        self.eager = split is None
        self.s = s = sizes(cfg)
        self.w = weights
        self.x = inputs["x"]
        if split:
            mesh = cluster_mesh(chips)
            self.x = jax.device_put(self.x, NamedSharding(
                mesh, P(None, "rows", None)))
        self.eps = cfg["rms_norm_eps"]
        self.decode = traffic["phase"] == "decode"
        self.prompts = [self.x[i] for i in range(self.x.shape[0])]
        if self.decode:
            ctx = traffic["context"]
            self.k = [inputs["k_cache"][l] for l in range(s["layers"])]
            self.v = [inputs["v_cache"][l] for l in range(s["layers"])]
            pos = jnp.array([ctx - 1])
        else:
            pos = jnp.arange(traffic["tokens"])
        self.cos, self.sin = _rope_tables(pos, s["hd"], cfg["rope_theta"])
        self._glue()

    # -- the benchmark's own glue ---------------------------------------

    def _glue(self):
        s, eps, qk_norm = self.s, self.eps, bool(self.cfg.get("qk_norm"))
        nq, nk = s["heads"] * s["hd"], s["kv"] * s["hd"]

        def heads(qkv, w, cos, sin):
            t = qkv.shape[0]
            q = qkv[:, :nq].reshape(t, s["heads"], s["hd"])
            k = qkv[:, nq:nq + nk].reshape(t, s["kv"], s["hd"])
            v = qkv[:, nq + nk:].reshape(t, s["kv"], s["hd"])
            if qk_norm:
                q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
            return _rotate(q, cos, sin), _rotate(k, cos, sin), v

        def qkv_prefill(qkv, w, cos, sin):
            q, k, v = heads(qkv, w, cos, sin)
            t = q.shape[0]
            q = q.reshape(t, s["kv"], s["group"], s["hd"])
            return (jnp.transpose(q, (1, 2, 0, 3)),
                    jnp.transpose(k, (1, 0, 2)), jnp.transpose(v, (1, 0, 2)))

        def qkv_decode(qkv, w, cos, sin, kc, vc):
            q, k, v = heads(qkv[None, :], w, cos, sin)
            slot = kc.shape[1] - 1
            kc = kc.at[:, slot].set(k[0])
            vc = vc.at[:, slot].set(v[0])
            return q[0].reshape(s["kv"], s["group"], s["hd"]), kc, vc

        def merge(a):
            if a.ndim == 3:          # decode: (kv, group, hd)
                return a.reshape(-1)
            kv, g, t, hd = a.shape
            return jnp.transpose(a, (2, 0, 1, 3)).reshape(t, kv * g * hd)

        def res_norm(x, delta, g):
            x = x + delta
            return x, _rms(x, g, eps)

        def swiglu(gate, up):
            return jax.nn.silu(gate) * up

        def swiglu_stacked(gu):
            return jax.nn.silu(gu[:s["ff"]]) * gu[s["ff"]:]

        self.g_norm = jax.jit(lambda x, g: _rms(x, g, eps))
        self.g_qkv = jax.jit(qkv_prefill)
        self.g_qkv_decode = jax.jit(qkv_decode, donate_argnums=(4, 5))
        self.g_merge = jax.jit(merge)
        self.g_res_norm = jax.jit(res_norm)
        self.g_res = jax.jit(jnp.add)
        self.g_swiglu = jax.jit(swiglu)
        self.g_swiglu_stacked = jax.jit(swiglu_stacked)
        self.matmul = partial(ops.matmul, ssr=True)
        self.gemv = partial(ops.gemv, ssr=True)
        scale = 1.0 / math.sqrt(s["hd"])
        self.attn_prefill = jax.vmap(jax.vmap(
            partial(ops.flash_attention, causal=True, scale=scale, ssr=True),
            in_axes=(0, None, None)))
        self.attn_decode = jax.vmap(
            partial(ops.flash_attention, scale=scale, ssr=True))
        if not self.eager:
            mm, attn, cores = self.matmul, self.attn_prefill, self.cores
            self.matmul = jax.jit(lambda a, b: cluster_kernel2d(
                mm, (a, b), cores=cores, in_dims=((0, None), (None, 1)),
                out_dims=(0, 1)))
            self.attn_prefill = jax.jit(lambda q, k, v: cluster_kernel(
                attn, (q, k, v), cores=cores, in_dims=(0, 0, 0), out_dim=0))

    # -- one step --------------------------------------------------------

    def step(self, i: int) -> jax.Array:
        return self._decode(i) if self.decode else self._prefill(i)

    def _prefill(self, i: int) -> jax.Array:
        c, L = self.call, len(self.w)
        x = self.prompts[i]
        h = c("glue.norm", self.g_norm, x, self.w[0]["ln1"])
        for l, w in enumerate(self.w):
            qkv = c("ops.gemm", self.matmul, h, w["wqkv"])
            q, k, v = c("glue.qkv", self.g_qkv, qkv, w, self.cos, self.sin)
            a = c("ops.attention", self.attn_prefill, q, k, v)
            a = c("glue.merge", self.g_merge, a)
            o = c("ops.gemm", self.matmul, a, w["wo"])
            x, h2 = c("glue.res_norm", self.g_res_norm, x, o, w["ln2"])
            gate = c("ops.gemm", self.matmul, h2, w["wgate"])
            up = c("ops.gemm", self.matmul, h2, w["wup"])
            act = c("glue.swiglu", self.g_swiglu, gate, up)
            down = c("ops.gemm", self.matmul, act, w["wdown"])
            if l + 1 < L:
                x, h = c("glue.res_norm", self.g_res_norm, x, down,
                         self.w[l + 1]["ln1"])
            else:
                x = c("glue.res", self.g_res, x, down)
        return x

    def _decode(self, i: int) -> jax.Array:
        c, L = self.call, len(self.w)
        x = self.prompts[i]
        h = c("glue.norm", self.g_norm, x, self.w[0]["ln1"])
        for l, w in enumerate(self.w):
            qkv = c("ops.gemv", self.gemv, w["wqkv"], h)
            q, self.k[l], self.v[l] = c(
                "glue.qkv", self.g_qkv_decode, qkv, w, self.cos, self.sin,
                self.k[l], self.v[l])
            a = c("ops.attention", self.attn_decode, q, self.k[l], self.v[l])
            a = c("glue.merge", self.g_merge, a)
            o = c("ops.gemv", self.gemv, w["wo"], a)
            x, h2 = c("glue.res_norm", self.g_res_norm, x, o, w["ln2"])
            gu = c("ops.gemv", self.gemv, w["wgu"], h2)
            act = c("glue.swiglu", self.g_swiglu_stacked, gu)
            down = c("ops.gemv", self.gemv, w["wdown"], act)
            if l + 1 < L:
                x, h = c("glue.res_norm", self.g_res_norm, x, down,
                         self.w[l + 1]["ln1"])
            else:
                x = c("glue.res", self.g_res, x, down)
        return x

    # -- the work of one step, from shapes ------------------------------

    def work(self) -> List[Tuple[str, work.Work]]:
        return step_work(self.cfg, self.traffic)


def step_work(cfg: dict, traffic: dict) -> List[Tuple[str, work.Work]]:
    """(span, work) of every call in one step, from the shapes alone."""
    s = sizes(cfg)
    L = s["layers"]
    d, ff, hd, H, KV = s["d"], s["ff"], s["hd"], s["heads"], s["kv"]
    if traffic["phase"] == "decode":
        ctx = traffic["context"]
        first = ("glue.norm", work.glue("norm", 2 * d, d))
        per = [("ops.gemv", work.gemv(s["qkv"], d)),
               ("glue.qkv", work.glue("qkv", s["qkv"],
                                      H * hd + 2 * KV * hd)),
               ("ops.attention", work.attention(H, KV, 1, ctx, hd,
                                                causal=False)),
               ("glue.merge", work.glue("merge", H * hd, H * hd)),
               ("ops.gemv", work.gemv(d, H * hd)),
               ("glue.res_norm", work.glue("res_norm", 3 * d, 2 * d)),
               ("ops.gemv", work.gemv(2 * ff, d)),
               ("glue.swiglu", work.glue("swiglu", 2 * ff, ff)),
               ("ops.gemv", work.gemv(d, ff))]
        rows = 1
    else:
        t = rows = traffic["tokens"]
        first = ("glue.norm", work.glue("norm", t * d + d, t * d))
        per = [("ops.gemm", work.gemm(t, d, s["qkv"])),
               ("glue.qkv", work.glue("qkv", t * s["qkv"], t * s["qkv"])),
               ("ops.attention", work.attention(H, KV, t, t, hd,
                                                causal=True)),
               ("glue.merge", work.glue("merge", t * H * hd, t * H * hd)),
               ("ops.gemm", work.gemm(t, H * hd, d)),
               ("glue.res_norm", work.glue("res_norm", 2 * t * d + d,
                                           2 * t * d)),
               ("ops.gemm", work.gemm(t, d, ff)),
               ("ops.gemm", work.gemm(t, d, ff)),
               ("glue.swiglu", work.glue("swiglu", 2 * t * ff, t * ff)),
               ("ops.gemm", work.gemm(t, ff, d))]
    out = [first]
    for l in range(L):
        out += per
        out.append(("glue.res_norm",
                    work.glue("res_norm", 2 * rows * d + d, 2 * rows * d))
                   if l + 1 < L else
                   ("glue.res", work.glue("res", 2 * rows * d, rows * d)))
    return out
