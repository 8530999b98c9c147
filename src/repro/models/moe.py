"""Mixture-of-Experts FFN with sort-based scatter dispatch.

Top-k routing with static per-expert capacity (tokens over capacity are
dropped — GShard semantics).  The dispatch avoids the (N·K, E) one-hot
blow-up: positions-within-expert come from an argsort + offset subtraction,
so peak intermediates are O(N·K) + the (E, C, D) expert buffers, both of
which shard cleanly (tokens over the data axes, experts over the model axis
= expert parallelism).

Supports DeepSeek-style shared experts (always-on dense experts added to the
routed output) and fine-grained experts (d_expert ≪ d_ff).  The router aux
load-balance loss (Switch-style) is returned as a metric.

SSR tie-in: the per-expert grouped GEMM ``einsum('ecd,edf->ecf')`` is the
paper's GEMM kernel with the expert axis as an outer AGU loop; under the ssr
region on TPU it lowers to the streamed ``kernels/gemm.py`` tiles per expert.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.activations import BATCH, MODEL, constrain

from .config import ModelConfig, MoEConfig
from .layers import init_dense


def init_moe(key, cfg: ModelConfig):
    m: MoEConfig = cfg.moe
    d, f, e = cfg.d_model, m.d_expert, m.num_experts
    ks = jax.random.split(key, 7)
    dt = jnp.dtype(cfg.param_dtype)
    std = 1.0 / math.sqrt(d)
    params = {
        "router": init_dense(ks[0], d, e, jnp.float32),
        "experts": {
            "w_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32)
                       * std).astype(dt),
            "w_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32)
                     * std).astype(dt),
            "w_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32)
                       / math.sqrt(f)).astype(dt),
        },
    }
    if m.num_shared:
        fs = f * m.num_shared
        params["shared"] = {
            "w_gate": init_dense(ks[4], d, fs, dt),
            "w_up": init_dense(ks[5], d, fs, dt),
            "w_down": init_dense(ks[6], fs, d, dt),
        }
    return params


def capacity(n_tokens: int, m: MoEConfig) -> int:
    c = math.ceil(n_tokens * m.top_k * m.capacity_factor / m.num_experts)
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def _route(xf, router, m: MoEConfig):
    """Shared routing math: (gate_vals, expert_ids, aux_loss)."""
    n = xf.shape[0]
    e, k = m.num_experts, m.top_k
    logits = jnp.dot(xf.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)           # (N, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    occupancy = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(
        1.0 / (n * k))
    mean_probs = jnp.mean(probs, axis=0)
    return gate_vals, expert_ids, occupancy, mean_probs


def _positions_in_expert(ids, e):
    """Sort-based rank of each dispatch slot within its expert."""
    nk = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    counts = jnp.zeros((e,), jnp.int32).at[ids].add(1)
    starts = jnp.cumsum(counts) - counts
    ranks_sorted = jnp.arange(nk, dtype=jnp.int32) - starts[ids[order]]
    return jnp.zeros((nk,), jnp.int32).at[order].set(ranks_sorted)


def moe_apply(params, x: jax.Array, cfg: ModelConfig
              ) -> Tuple[jax.Array, jax.Array]:
    """Dispatcher: expert-parallel shard_map when a mesh is ambient."""
    from repro.parallel.activations import get_activation_mesh  # noqa: PLC0415

    m: MoEConfig = cfg.moe
    mesh = get_activation_mesh()
    if (m.impl in ("auto", "ep") and mesh is not None
            and "model" in mesh.axis_names and mesh.shape["model"] > 1
            and m.num_experts % mesh.shape["model"] == 0):
        # decode-sized token sets: weights-stationary variants (weights
        # never move; tokens/activations are tiny)
        if x.shape[0] * x.shape[1] <= 4096:
            axes, world = ep2d_axes(mesh, m.num_experts)
            if len(axes) > 1 and world > mesh.shape["model"]:
                return _moe_apply_ep2d(params, x, cfg, mesh)
            if "data" in mesh.axis_names and mesh.shape["data"] > 1:
                return _moe_apply_ep_dstat(params, x, cfg, mesh)
        return _moe_apply_ep(params, x, cfg, mesh)
    return _moe_apply_xla(params, x, cfg)


def _moe_apply_xla(params, x: jax.Array, cfg: ModelConfig
                   ) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, D) → (out (B, S, D), aux_loss scalar)."""
    m: MoEConfig = cfg.moe
    b, s, d = x.shape
    n = b * s
    e, k = m.num_experts, m.top_k
    xf = constrain(x.reshape(n, d), BATCH, None)

    logits = constrain(
        jnp.dot(xf.astype(jnp.float32), params["router"]), BATCH, None)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)           # (N, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Switch-style load-balance loss: E · Σ_e f_e · p_e
    occupancy = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(
        1.0 / (n * k))
    aux = e * jnp.sum(occupancy * jnp.mean(probs, axis=0))

    # --- dispatch: position-within-expert via sort ------------------------
    nk = n * k
    c = capacity(n, m)
    ids = expert_ids.reshape(nk)
    order = jnp.argsort(ids, stable=True)
    counts = jnp.zeros((e,), jnp.int32).at[ids].add(1)
    starts = jnp.cumsum(counts) - counts                      # (E,)
    ranks_sorted = jnp.arange(nk, dtype=jnp.int32) - starts[ids[order]]
    pos = jnp.zeros((nk,), jnp.int32).at[order].set(ranks_sorted)
    keep = pos < c
    dst = jnp.where(keep, ids * c + pos, e * c)               # drop → OOB

    token_idx = jnp.arange(nk, dtype=jnp.int32) // k
    slot_x = xf[token_idx]                                    # (NK, D)
    buf = jnp.zeros((e * c, d), x.dtype).at[dst].set(
        slot_x, mode="drop")

    # --- per-expert grouped SwiGLU (EP: experts sharded over 'model') -----
    bufe = constrain(buf.reshape(e, c, d), MODEL, None, None)
    ew = params["experts"]
    g = jnp.einsum("ecd,edf->ecf", bufe, ew["w_gate"],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", bufe, ew["w_up"],
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    y_e = constrain(jnp.einsum("ecf,efd->ecd", h, ew["w_down"],
                     preferred_element_type=jnp.float32), MODEL, None, None)

    # --- combine -----------------------------------------------------------
    y_slots = y_e.reshape(e * c, d)[jnp.minimum(dst, e * c - 1)]
    y_slots = jnp.where(keep[:, None], y_slots, 0.0)
    y = jnp.sum(
        (y_slots * gate_vals.reshape(nk, 1)).reshape(n, k, d), axis=1)

    if m.num_shared:
        sh = params["shared"]
        gs = jnp.dot(xf, sh["w_gate"], preferred_element_type=jnp.float32)
        us = jnp.dot(xf, sh["w_up"], preferred_element_type=jnp.float32)
        hs = (jax.nn.silu(gs) * us).astype(x.dtype)
        y = y + jnp.dot(hs, sh["w_down"],
                        preferred_element_type=jnp.float32)

    return y.reshape(b, s, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (shard_map): the SSR idea at the cluster level.
#
# XLA's SPMD partitioner cannot shard the scatter/gather dispatch of the
# plain-jit path — it falls back to *replicating* the (N·K, D) slot tensors
# and (E·C, D) buffers per device (observed: 315 GiB/device and 23.8 TB of
# collective traffic on deepseek-v3 train_4k).  The shard_map form pins the
# algorithm instead of hoping propagation finds it:
#
#   * routing is computed redundantly on every model shard (tokens are
#     replicated over 'model'; the router matmul is negligible),
#   * each shard runs ONLY its E/tp local experts on the locally-built
#     capacity buffer — no token exchange at all on dispatch,
#   * the combine is one psum of the (n_local, D) output over 'model' —
#     the only collective in the layer.
#
# This mirrors the paper's data-mover economics: keep operands local, let a
# cheap deterministic "address pattern" (the router) decide what each
# compute unit consumes, and pay one bounded stream of results.
# ---------------------------------------------------------------------------


def _moe_apply_ep(params, x: jax.Array, cfg: ModelConfig, mesh
                  ) -> Tuple[jax.Array, jax.Array]:
    from jax.sharding import PartitionSpec as P  # noqa: PLC0415
    from jax import shard_map  # noqa: PLC0415
    from repro.parallel.sharding import dp_axes  # noqa: PLC0415

    m: MoEConfig = cfg.moe
    e, k = m.num_experts, m.top_k
    tp = mesh.shape["model"]
    e_loc = e // tp
    dp = dp_axes(mesh)
    b, s, d = x.shape
    f = m.d_expert
    batch_sharded = dp and all(
        b % int(np.prod([mesh.shape[a] for a in dp[:i + 1]])) == 0
        for i in range(len(dp)))
    bspec = tuple(dp) if batch_sharded else None

    def local(x_l, router, wg, wu, wd):
        bl, sl, _ = x_l.shape
        nl = bl * sl
        xf = x_l.reshape(nl, d)
        gate_vals, expert_ids, occ, mp = _route(xf, router, m)
        if dp:
            occ = jax.lax.pmean(occ, dp)
            mp = jax.lax.pmean(mp, dp)
        aux = e * jnp.sum(occ * mp)

        nk = nl * k
        c = capacity(nl, m)
        ids = expert_ids.reshape(nk)
        pos = _positions_in_expert(ids, e)
        keep = pos < c
        dst = jnp.where(keep, ids * c + pos, e * c)
        token_idx = jnp.arange(nk, dtype=jnp.int32) // k
        slot_x = xf[token_idx]

        # scatter straight into the LOCAL experts' buffer — building the
        # full (E·C, D) buffer and slicing costs tp× the memory (observed
        # +9.4 GiB/device/layer on deepseek prefill_32k)
        my = jax.lax.axis_index("model")
        local_dst = dst - my * (e_loc * c)
        mine_in = keep & (local_dst >= 0) & (local_dst < e_loc * c)
        bufe = jnp.zeros((e_loc * c, d), x.dtype).at[
            jnp.where(mine_in, local_dst, e_loc * c)].set(
            slot_x, mode="drop").reshape(e_loc, c, d)
        g = jnp.einsum("ecd,edf->ecf", bufe, wg,
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("ecd,edf->ecf", bufe, wu,
                       preferred_element_type=jnp.float32)
        hh = (jax.nn.silu(g) * u).astype(x.dtype)
        y_e = jnp.einsum("ecf,efd->ecd", hh, wd,
                         preferred_element_type=jnp.float32)  # (e_loc, C, D)

        # combine: only slots owned by the local experts contribute
        local_dst = dst - my * e_loc * c
        mine = keep & (local_dst >= 0) & (local_dst < e_loc * c)
        safe = jnp.where(mine, local_dst, 0)
        y_slots = y_e.reshape(e_loc * c, d)[safe]
        y_slots = jnp.where(mine[:, None], y_slots, 0.0)
        y_l = jnp.sum((y_slots * gate_vals.reshape(nk, 1)).reshape(nl, k, d),
                      axis=1)
        y_l = jax.lax.psum(y_l, "model")                  # THE collective
        return y_l.reshape(bl, sl, d).astype(x.dtype), aux

    in_specs = (P(bspec, None, None), P(), P("model", None, None),
                P("model", None, None), P("model", None, None))
    out_specs = (P(bspec, None, None), P())
    fn = shard_map(local, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    ew = params["experts"]
    y, aux = fn(x, params["router"], ew["w_gate"], ew["w_up"], ew["w_down"])

    if m.num_shared:
        xf = x.reshape(b * s, d)
        sh = params["shared"]
        gs = jnp.dot(xf, sh["w_gate"], preferred_element_type=jnp.float32)
        us = jnp.dot(xf, sh["w_up"], preferred_element_type=jnp.float32)
        hs = (jax.nn.silu(gs) * us).astype(x.dtype)
        y = y + jnp.dot(hs, sh["w_down"],
                        preferred_element_type=jnp.float32
                        ).reshape(b, s, d).astype(x.dtype)
    return y, aux


# ---------------------------------------------------------------------------
# Weights-stationary 2-D expert parallelism — the decode path.
#
# At decode, tokens are tiny (≤ a few thousand × D) while expert weights are
# enormous; the 1-D EP path still all-gathers each layer's data-sharded
# expert weights (~1.4 GiB/layer on deepseek-v3).  Here experts are sharded
# over ('model' × 'data') jointly (one expert per device at E=256 on the
# 256-chip pod), the token batch is all-gathered over 'data' (a few MiB),
# every device runs only the experts it OWNS in place, and one psum over
# both axes returns the combined output — weights never move.  This is the
# paper's economics inverted for the serving regime: stream the (small)
# operand set to the (huge) stationary weights.
# ---------------------------------------------------------------------------


def ep2d_axes(mesh, num_experts: int):
    """Largest ('model', 'data'[, 'pod']) prefix whose size divides E."""
    axes = []
    size = 1
    for a in ("model", "data", "pod"):
        if a in mesh.axis_names and num_experts % (size * mesh.shape[a]) == 0:
            axes.append(a)
            size *= mesh.shape[a]
    return tuple(axes), size


def _moe_apply_ep2d(params, x: jax.Array, cfg: ModelConfig, mesh
                    ) -> Tuple[jax.Array, jax.Array]:
    from jax.sharding import PartitionSpec as P  # noqa: PLC0415
    from jax import shard_map  # noqa: PLC0415
    from repro.parallel.sharding import dp_axes  # noqa: PLC0415

    m: MoEConfig = cfg.moe
    e, k = m.num_experts, m.top_k
    ep_axes, world = ep2d_axes(mesh, e)
    e_loc = e // world
    b, s, d = x.shape
    dp = dp_axes(mesh)
    gather_axes = tuple(a for a in ep_axes if a != "model")
    dp_rest = tuple(a for a in dp if a not in ep_axes)
    bspec = None
    if dp and b % int(np.prod([mesh.shape[a] for a in dp])) == 0:
        bspec = tuple(dp)

    def local(x_l, router, wg, wu, wd):
        bl, sl, _ = x_l.shape
        xf = x_l.reshape(bl * sl, d)
        if gather_axes:
            xf = jax.lax.all_gather(xf, gather_axes, axis=0, tiled=True)
        nl = xf.shape[0]
        gate_vals, expert_ids, occ, mp = _route(xf, router, m)
        if dp_rest:
            occ = jax.lax.pmean(occ, dp_rest)
            mp = jax.lax.pmean(mp, dp_rest)
        aux = e * jnp.sum(occ * mp)

        nk = nl * k
        c = capacity(nl, m)
        ids = expert_ids.reshape(nk)
        pos = _positions_in_expert(ids, e)
        keep = pos < c
        dst = jnp.where(keep, ids * c + pos, e * c)
        token_idx = jnp.arange(nk, dtype=jnp.int32) // k
        slot_x = xf[token_idx]

        # flat device rank along ep_axes (major-to-minor = axes order)
        my = jnp.int32(0)
        for a in ep_axes:
            my = my * mesh.shape[a] + jax.lax.axis_index(a)
        local_dst = dst - my * (e_loc * c)
        mine_in = keep & (local_dst >= 0) & (local_dst < e_loc * c)
        bufe = jnp.zeros((e_loc * c, d), x.dtype).at[
            jnp.where(mine_in, local_dst, e_loc * c)].set(
            slot_x, mode="drop").reshape(e_loc, c, d)
        g = jnp.einsum("ecd,edf->ecf", bufe, wg,
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("ecd,edf->ecf", bufe, wu,
                       preferred_element_type=jnp.float32)
        hh = (jax.nn.silu(g) * u).astype(x.dtype)
        y_e = jnp.einsum("ecf,efd->ecd", hh, wd,
                         preferred_element_type=jnp.float32)

        safe = jnp.where(mine_in, local_dst, 0)
        y_slots = jnp.where(mine_in[:, None],
                            y_e.reshape(e_loc * c, d)[safe], 0.0)
        y_all = jnp.sum(
            (y_slots * gate_vals.reshape(nk, 1)).reshape(nl, k, d), axis=1)
        y_all = jax.lax.psum(y_all, ep_axes)          # everyone gets all toks
        if gather_axes:
            # slice back this shard's tokens
            gsz = int(np.prod([mesh.shape[a] for a in gather_axes]))
            gidx = jnp.int32(0)
            for a in gather_axes:
                gidx = gidx * mesh.shape[a] + jax.lax.axis_index(a)
            y_l = jax.lax.dynamic_slice_in_dim(
                y_all, gidx * (nl // gsz), nl // gsz, 0)
        else:
            y_l = y_all
        return y_l.reshape(bl, sl, d).astype(x.dtype), aux

    espec = P(tuple(ep_axes) if len(ep_axes) > 1 else ep_axes[0],
              None, None)
    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(bspec, None, None), P(), espec, espec, espec),
                   out_specs=(P(bspec, None, None), P()),
                   check_vma=False)
    ew = params["experts"]
    y, aux = fn(x, params["router"], ew["w_gate"], ew["w_up"], ew["w_down"])

    if m.num_shared:
        xf = x.reshape(b * s, d)
        sh = params["shared"]
        gs = jnp.dot(xf, sh["w_gate"], preferred_element_type=jnp.float32)
        us = jnp.dot(xf, sh["w_up"], preferred_element_type=jnp.float32)
        hs = (jax.nn.silu(gs) * us).astype(x.dtype)
        y = y + jnp.dot(hs, sh["w_down"],
                        preferred_element_type=jnp.float32
                        ).reshape(b, s, d).astype(x.dtype)
    return y, aux


def _moe_apply_ep_dstat(params, x: jax.Array, cfg: ModelConfig, mesh
                        ) -> Tuple[jax.Array, jax.Array]:
    """Weights-stationary decode MoE for small expert counts (E ∤ world).

    Experts shard over 'model' (EP) and the hidden dims over 'data': each
    device holds (E/tp, D/dd, F) of w_gate/w_up and (E/tp, F/dd, D) of
    w_down.  Tokens are gathered over 'data' (tiny at decode); the two
    contractions are partial over the data-sharded dim and pay one small
    psum each — expert weights never move (vs ~30 GB/token of per-layer
    weight all-gathers on dbrx decode).
    """
    from jax.sharding import PartitionSpec as P  # noqa: PLC0415
    from jax import shard_map  # noqa: PLC0415
    from repro.parallel.sharding import dp_axes  # noqa: PLC0415

    m: MoEConfig = cfg.moe
    e, k = m.num_experts, m.top_k
    tp = mesh.shape["model"]
    dd = mesh.shape["data"]
    e_loc = e // tp
    d_model = cfg.d_model
    f = m.d_expert
    if d_model % dd or f % dd:
        return _moe_apply_ep(params, x, cfg, mesh)
    b, s, _ = x.shape
    dp = dp_axes(mesh)
    bspec = None
    if dp and b % int(np.prod([mesh.shape[a] for a in dp])) == 0:
        bspec = tuple(dp)

    def local(x_l, router, wg, wu, wd):
        bl, sl, _ = x_l.shape
        xf = x_l.reshape(bl * sl, d_model)
        gather_axes = tuple(a for a in dp)
        if gather_axes:
            xf = jax.lax.all_gather(xf, gather_axes, axis=0, tiled=True)
        nl = xf.shape[0]
        gate_vals, expert_ids, occ, mp = _route(xf, router, m)
        aux = e * jnp.sum(occ * mp)

        nk = nl * k
        c = capacity(nl, m)
        ids = expert_ids.reshape(nk)
        pos = _positions_in_expert(ids, e)
        keep = pos < c
        dst = jnp.where(keep, ids * c + pos, e * c)
        token_idx = jnp.arange(nk, dtype=jnp.int32) // k
        slot_x = xf[token_idx]

        my_e = jax.lax.axis_index("model")
        my_d = jax.lax.axis_index("data")
        local_dst = dst - my_e * (e_loc * c)
        mine_in = keep & (local_dst >= 0) & (local_dst < e_loc * c)
        bufe = jnp.zeros((e_loc * c, d_model), x.dtype).at[
            jnp.where(mine_in, local_dst, e_loc * c)].set(
            slot_x, mode="drop").reshape(e_loc, c, d_model)
        # contraction partial over the data-sharded D block → psum('data')
        d_blk = d_model // dd
        buf_d = jax.lax.dynamic_slice_in_dim(bufe, my_d * d_blk, d_blk, 2)
        g = jax.lax.psum(jnp.einsum("ecd,edf->ecf", buf_d, wg,
                                    preferred_element_type=jnp.float32),
                         "data")
        u = jax.lax.psum(jnp.einsum("ecd,edf->ecf", buf_d, wu,
                                    preferred_element_type=jnp.float32),
                         "data")
        hh = (jax.nn.silu(g) * u).astype(x.dtype)
        f_blk = f // dd
        h_f = jax.lax.dynamic_slice_in_dim(hh, my_d * f_blk, f_blk, 2)
        y_e = jax.lax.psum(jnp.einsum("ecf,efd->ecd", h_f, wd,
                                      preferred_element_type=jnp.float32),
                           "data")

        safe = jnp.where(mine_in, local_dst, 0)
        y_slots = jnp.where(mine_in[:, None],
                            y_e.reshape(e_loc * c, d_model)[safe], 0.0)
        y_all = jnp.sum(
            (y_slots * gate_vals.reshape(nk, 1)).reshape(nl, k, d_model),
            axis=1)
        y_all = jax.lax.psum(y_all, "model")
        if gather_axes:
            gsz = int(np.prod([mesh.shape[a] for a in gather_axes]))
            gidx = jnp.int32(0)
            for a in gather_axes:
                gidx = gidx * mesh.shape[a] + jax.lax.axis_index(a)
            y_l = jax.lax.dynamic_slice_in_dim(
                y_all, gidx * (nl // gsz), nl // gsz, 0)
        else:
            y_l = y_all
        return y_l.reshape(bl, sl, d_model).astype(x.dtype), aux

    espec_up = P("model", "data", None)
    espec_dn = P("model", "data", None)
    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(bspec, None, None), P(), espec_up, espec_up,
                             espec_dn),
                   out_specs=(P(bspec, None, None), P()),
                   check_vma=False)
    ew = params["experts"]
    y, aux = fn(x, params["router"], ew["w_gate"], ew["w_up"], ew["w_down"])

    if m.num_shared:
        xf = x.reshape(b * s, d_model)
        sh = params["shared"]
        gs = jnp.dot(xf, sh["w_gate"], preferred_element_type=jnp.float32)
        us = jnp.dot(xf, sh["w_up"], preferred_element_type=jnp.float32)
        hs = (jax.nn.silu(gs) * us).astype(x.dtype)
        y = y + jnp.dot(hs, sh["w_down"],
                        preferred_element_type=jnp.float32
                        ).reshape(b, s, d_model).astype(x.dtype)
    return y, aux
