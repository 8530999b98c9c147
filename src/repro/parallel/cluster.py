"""Cluster execution layer: streamed kernels sharded across a ``cores`` mesh.

The paper's headline multi-core results (§5.3–§5.5, Fig. 10/11) run the SSR
kernels on an 8-core RISC-V cluster sharing one TCDM: each core streams its
tile of the iteration space, and reductions finish through the shared
memory + hardware barrier.  This module is that cluster on a JAX device
mesh:

* a **core** is one device on a 1-D mesh axis named ``cores``
  (:func:`repro.launch.mesh.make_cluster_mesh`);
* the **iteration space** of a :class:`~repro.core.compiler.LoopNest` (or a
  chained sequence of nests) is partitioned on its *outermost* loop level —
  the same work-splitting the paper's OpenMP-style outer loop performs —
  and every shard runs the existing single-core path
  (:func:`~repro.core.lowering.ssr_call` /
  :func:`~repro.core.lowering.ssr_chain_call`) on its tile via
  ``shard_map``;
* the **shared-TCDM combine** of a reduction is one ``psum`` over the
  ``cores`` axis — the only inter-core communication.  Map-mode nests need
  none at all: per-core intermediates stay core-local, which
  :func:`repro.launch.hlo_analysis.check_cluster_locality` audits on the
  compiled HLO.

``cores=1`` degenerates to the plain single-core call (no mesh, no
collective), so the cluster layer is a strict superset of the §3 pipeline.
The matching cost model lives in :func:`repro.core.compiler.cluster_cost`
(Eq. (1)–(3) extended to C cores).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import autotune, resilience
from repro.core.compiler import Direction, LoopNest, MemRef
from repro.core.lowering import (BlockPolicy, DEFAULT_POLICY, Schedule,
                                 _record_fallback, ssr_call, ssr_chain_call)


class ClusterError(ValueError):
    """The nest/operands cannot be partitioned across the requested cores."""


CORES_AXIS = "cores"

#: Provenance of the most recent ``cluster_call``/``cluster_chain_call``:
#: the per-core schedule actually dispatched (tuned or default), the core
#: count, and the per-core tile bounds.  ``benchmarks/cluster_bench.py``
#: reads this to stamp schedule provenance onto its result rows; callers
#: should ``clear()`` it before the call they want attributed.
LAST_DISPATCH: Dict[str, object] = {}


def _record_dispatch(schedule: Optional[Schedule], cores: int,
                     bounds: Tuple[int, ...],
                     policy: BlockPolicy = DEFAULT_POLICY) -> None:
    from repro.core.lowering import DEFAULT_SCHEDULE

    # `tuned` means "came from the autotuner, not the default geometry":
    # an explicitly pinned DEFAULT_SCHEDULE (or a legacy policy=) is
    # still an untuned dispatch.
    if schedule is None:
        effective = DEFAULT_SCHEDULE if policy is DEFAULT_POLICY \
            else Schedule.from_policy(policy)
    else:
        effective = schedule
    LAST_DISPATCH.update(
        schedule=effective,
        tuned=schedule is not None and schedule != DEFAULT_SCHEDULE,
        cores=cores, tile_bounds=tuple(bounds))


def _cluster_mesh(cores: int, mesh: Optional[Mesh]) -> Mesh:
    if mesh is not None:
        if CORES_AXIS not in mesh.axis_names or mesh.shape[CORES_AXIS] != cores:
            raise ClusterError(
                f"mesh axes {mesh.axis_names}/{dict(mesh.shape)} do not "
                f"provide a '{CORES_AXIS}' axis of size {cores}")
        return mesh
    from repro.launch.mesh import make_cluster_mesh

    try:
        return make_cluster_mesh(cores)
    except ValueError as e:
        raise ClusterError(str(e)) from e


def pad_to_cores(arrays: Sequence[jax.Array],
                 cores: int) -> Tuple[Tuple[jax.Array, ...], int]:
    """Zero-pad 1-D operands so ``cores`` divides their length.

    The kernel-wrapper companion to :func:`cluster_call`'s divisibility
    requirement: returns the padded arrays and the padded length.  Only
    valid where zero padding is semantics-neutral — sum-like reductions
    (pad contributes 0) and maps whose tail the caller trims.
    """
    n = arrays[0].shape[0]
    pad = (-n) % cores
    if pad:
        arrays = [jnp.pad(a, (0, pad)) for a in arrays]
    return tuple(arrays), n + pad


def _split_level0(nest: LoopNest, cores: int) -> LoopNest:
    """The per-core tile: the outermost level split ``cores`` ways."""
    b0 = nest.bounds[0]
    if b0 % cores:
        raise ClusterError(
            f"outer bound {b0} not divisible by {cores} cores; pad the "
            "iteration space (zero padding is reduce-neutral) or pick a "
            "divisor core count")
    return dataclasses.replace(
        nest, bounds=(b0 // cores,) + nest.bounds[1:])


def _operand_ref(nests: Sequence[LoopNest],
                 name: str) -> Tuple[MemRef, LoopNest]:
    """The read ref named ``name`` and the nest that owns it."""
    for nest in nests:
        for ref in nest.refs:
            if ref.name == name and ref.kind == Direction.READ:
                return ref, nest
    raise ClusterError(f"operand {name!r} matches no read ref in the nest(s)")


def _shard_layout(ref: MemRef,
                  nest: LoopNest) -> Optional[Tuple[int, ...]]:
    """Logical shape to shard on dim 0, or ``None`` to replicate.

    A ref varying with the outermost level is partitioned with it: because
    lowering requires dense row-major layout over the varying levels, core
    ``c``'s tile is exactly rows ``[c·t, (c+1)·t)`` of the logical array.
    A ref with coefficient 0 at the split level (repeat/loop-invariant
    streams, e.g. GEMV's x) is replicated — every core streams its own
    copy, the TCDM-broadcast of the paper's cluster.
    """
    if ref.coeffs is None:
        raise ClusterError(
            f"ref {ref.name!r} is not affine; it cannot be streamed, let "
            "alone sharded")
    if ref.coeffs[0] == 0:
        return None
    if ref.offset:
        raise ClusterError(
            f"ref {ref.name!r}: base offset {ref.offset} cannot be "
            "partitioned on the outer level")
    return tuple(b for b, c in zip(nest.bounds, ref.coeffs) if c != 0)


def _prepare_operands(nests: Sequence[LoopNest],
                      operands: Dict[str, jax.Array]):
    """Reshape/spec every operand for ``shard_map`` over the cores axis."""
    names = sorted(operands)
    prepared, specs = [], []
    for name in names:
        ref, owner = _operand_ref(nests, name)
        layout = _shard_layout(ref, owner)
        arr = operands[name]
        if layout is None:
            prepared.append(arr)
            specs.append(P())
            continue
        # layout[0] is the outer bound; callers run _split_level0 first,
        # which guarantees it divides `cores`.
        try:
            view = arr.reshape(layout)
        except TypeError as e:  # jax raises TypeError on bad reshape
            raise ClusterError(
                f"operand {name!r} has {arr.size} elements, its stream "
                f"walks {layout}") from e
        prepared.append(view)
        specs.append(P(CORES_AXIS, *([None] * (len(layout) - 1))))
    return names, tuple(prepared), tuple(specs)


def _validate(cores: int, mode: str) -> None:
    if cores < 1:
        raise ClusterError(f"cores must be >= 1, got {cores}")
    if mode not in ("reduce", "map"):
        raise ClusterError(f"unknown cluster mode {mode!r}")


def _shard_operand_sig(nests: Sequence[LoopNest],
                       operands: Dict[str, jax.Array],
                       cores: int) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Per-shard (shape, dtype) of every operand — what one core streams.

    Sharded refs (nonzero outer coefficient) split their leading logical
    dim C ways; replicated refs keep their global shape.  This is the
    schedule-cache identity of the *per-core tile*, so a winner tuned for
    the tile size (via the single-core tuner or a cluster sweep) is found
    regardless of the cluster-global operand shapes.
    """
    sig: Dict[str, Tuple[Tuple[int, ...], str]] = {}
    for name, arr in operands.items():
        ref, owner = _operand_ref(nests, name)
        layout = _shard_layout(ref, owner)
        if layout is None:
            sig[name] = (tuple(arr.shape), str(arr.dtype))
        else:
            sig[name] = ((layout[0] // cores,) + tuple(layout[1:]),
                         str(arr.dtype))
    return sig


def _safe_lookup(nest: LoopNest, operands, *, mode: str, out_dtype,
                 site: str, chain: bool = False) -> Optional[Schedule]:
    """Cache lookup that degrades to the default on typed dispatch faults.

    A broken cache must cost the cluster layer its tuned geometry, never
    the call: cache I/O errors and injected faults are recorded (one
    ``fallbacks`` tick + a :class:`FallbackEvent`) and resolve to ``None``
    — the default per-core schedule.  Returns ``None`` too on an ordinary
    miss, matching the pre-resilience contract.  A ``chain`` keys on its
    stage-0 nest, so it takes a tuned entry only, never that nest's
    traffic-chosen tiles (as ``ssr_chain_call`` does).
    """
    try:
        sched, source = autotune.resolve(nest, operands, mode=mode,
                                         out_dtype=str(jnp.dtype(out_dtype)))
    except resilience.fallback_error_types() as e:
        _record_fallback(site, e, from_schedule="tuned-lookup",
                         to_schedule="default")
        return None
    if sched == autotune.DEFAULT_SCHEDULE or (chain and source == "traffic"):
        return None
    return sched


def _core_schedule(subs: Sequence[LoopNest],
                   operands: Dict[str, jax.Array], *,
                   mode: str, out_dtype) -> Optional[Schedule]:
    """The tuned schedule for one core's tile, or ``None`` (default).

    The per-core tile is a single-core problem of the *sharded* bounds, so
    the lookup keys on the sub-nest + per-shard operand shapes with
    ``cores=1`` — exactly what the tuner commits when it tunes that
    problem size.  Misses fall through to the default schedule.
    """
    try:
        sig = _shard_operand_sig(subs, operands, 1)  # subs are already split
    except ClusterError:
        return None
    # A chain keys on its stage-0 sub-nest; the operand signature (which
    # spans every stage) disambiguates chains sharing a producer shape.
    return _safe_lookup(subs[0], sig, mode=mode, out_dtype=out_dtype,
                        site="cluster:_core_schedule", chain=len(subs) > 1)


def _sharded_call(nests: Sequence[LoopNest], tile_fn: Callable,
                  operands: Dict[str, jax.Array], *, cores: int,
                  mode: str, mesh: Optional[Mesh]) -> jax.Array:
    """Shared shard_map scaffolding for the two clustered entry points.

    ``tile_fn(ops)`` runs one core's tile from its per-shard operand dict;
    reduces finish with the single psum, maps concatenate tiles along the
    split level.
    """
    names, prepared, in_specs = _prepare_operands(nests, operands)
    the_mesh = _cluster_mesh(cores, mesh)

    def per_core(*arrs):
        out = tile_fn(dict(zip(names, arrs)))
        if mode == "reduce":
            return jax.lax.psum(out, CORES_AXIS)
        return out

    out_specs = P() if mode == "reduce" else P(CORES_AXIS)
    fn = shard_map(per_core, mesh=the_mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return fn(*prepared)


def cluster_call(nest: LoopNest, body: Callable[..., jax.Array],
                 operands: Dict[str, jax.Array], *,
                 cores: int,
                 mode: str = "reduce",
                 out_dtype=jnp.float32,
                 policy: BlockPolicy = DEFAULT_POLICY,
                 schedule: Optional[Schedule] = None,
                 num_lanes: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 mesh: Optional[Mesh] = None) -> jax.Array:
    """Execute a :class:`LoopNest` sharded across a C-core device mesh.

    Same contract as :func:`~repro.core.lowering.ssr_call` plus ``cores``:
    the outermost loop level is split C ways, each core runs the single-core
    streamed kernel on its tile, and

    * ``mode="reduce"`` — per-core partials combine with one ``psum`` (the
      shared-TCDM reduction; the result is replicated on every core);
    * ``mode="map"`` — per-core output tiles concatenate along the split
      level; no collective is emitted at all.

    ``cores=1`` bypasses the mesh entirely and is bit-identical to
    ``ssr_call``.  Reduce bodies must be padding-neutral *and* tolerate the
    level-0 split (sum-like reductions are; order-sensitive folds are not).

    ``schedule=None`` resolves the **per-core tile's** schedule from the
    autotuner cache: the tile is a single-core problem of the *sharded*
    bounds, so the tuned block geometry tracks what one core actually
    streams, not the cluster-global shape.
    """
    _validate(cores, mode)
    if cores == 1:
        if schedule is None and policy is DEFAULT_POLICY:
            # Same resolution ssr_call/NestKernel perform (and under the
            # same guard: an explicit non-default policy pins the
            # geometry), so `cores=1` stays bit-identical to the
            # single-core registry path even after a tuner commit.
            schedule = _safe_lookup(nest, operands, mode=mode,
                                    out_dtype=out_dtype,
                                    site="cluster_call")
        _record_dispatch(schedule, 1, nest.bounds, policy)
        return ssr_call(nest, body, operands, mode=mode, out_dtype=out_dtype,
                        policy=policy, schedule=schedule,
                        num_lanes=num_lanes, interpret=interpret)
    sub = _split_level0(nest, cores)
    if schedule is None and policy is DEFAULT_POLICY:
        schedule = _core_schedule([sub], operands, mode=mode,
                                  out_dtype=out_dtype)
    _record_dispatch(schedule, cores, sub.bounds, policy)
    return _sharded_call(
        [nest],
        lambda ops: ssr_call(sub, body, ops, mode=mode, out_dtype=out_dtype,
                             policy=policy, schedule=schedule,
                             num_lanes=num_lanes, interpret=interpret),
        operands, cores=cores, mode=mode, mesh=mesh)


def cluster_chain_call(nests: Sequence[LoopNest],
                       bodies: Sequence[Callable[..., jax.Array]],
                       operands: Dict[str, jax.Array], *,
                       cores: int,
                       mode: str = "reduce",
                       out_dtype=jnp.float32,
                       policy: BlockPolicy = DEFAULT_POLICY,
                       schedule: Optional[Schedule] = None,
                       num_lanes: Optional[int] = None,
                       interpret: Optional[bool] = None,
                       mesh: Optional[Mesh] = None) -> jax.Array:
    """Execute a producer→consumer chain sharded across C cores.

    Each core runs the whole fused chain (ONE Pallas kernel — see
    :func:`~repro.core.lowering.ssr_chain_call`) on its tile of the shared
    iteration space, so the chained intermediates stay in *that core's*
    VMEM scratch: chaining composes with clustering because the link walk
    is dense row-major, hence splits cleanly on the outer level.  Only the
    final reduce (if any) crosses cores, via one ``psum``.
    """
    nests = tuple(nests)
    _validate(cores, mode)
    if cores == 1:
        if schedule is None and policy is DEFAULT_POLICY:
            # mirror ssr_chain_call's internal resolution (stage-0 nest +
            # full operand signature, same default-policy guard) so the
            # recorded provenance is the schedule the delegated call runs
            schedule = _safe_lookup(nests[0], operands, mode=mode,
                                    out_dtype=out_dtype,
                                    site="cluster_chain_call", chain=True)
        _record_dispatch(schedule, 1, nests[0].bounds, policy)
        return ssr_chain_call(nests, bodies, operands, mode=mode,
                              out_dtype=out_dtype, policy=policy,
                              schedule=schedule, num_lanes=num_lanes,
                              interpret=interpret)
    subs = tuple(_split_level0(n, cores) for n in nests)
    if schedule is None and policy is DEFAULT_POLICY:
        schedule = _core_schedule(subs, operands, mode=mode,
                                  out_dtype=out_dtype)
    _record_dispatch(schedule, cores, subs[0].bounds, policy)
    return _sharded_call(
        nests,
        lambda ops: ssr_chain_call(subs, bodies, ops, mode=mode,
                                   out_dtype=out_dtype, policy=policy,
                                   schedule=schedule, num_lanes=num_lanes,
                                   interpret=interpret),
        operands, cores=cores, mode=mode, mesh=mesh)


def factor_cores(cores: int) -> Tuple[int, int]:
    """Closest-to-square (rows, cols) factorisation of a core count.

    The 2-D work split of :func:`cluster_kernel2d`: 8 → (4, 2), 4 → (2, 2),
    6 → (3, 2); a prime count degenerates to a 1-D row split (p, 1).
    """
    if cores < 1:
        raise ClusterError(f"cores must be >= 1, got {cores}")
    c = int(cores ** 0.5)
    while cores % c:
        c -= 1
    return cores // c, c


def cluster_kernel2d(fn: Callable, args: Sequence[jax.Array], *,
                     cores: int,
                     in_dims: Sequence[Tuple[Optional[int], Optional[int]]],
                     out_dims: Tuple[int, int] = (0, 1),
                     mesh: Optional[Mesh] = None):
    """Shard a registry kernel across a 2-D (rows × cols) core grid.

    The §5.3 cluster with *two* partitioned levels — GEMM's row×col split:
    ``cores`` factors into a (Cr, Cc) device grid (:func:`factor_cores`),
    ``in_dims[i] = (row_dim, col_dim)`` names which dim of ``args[i]``
    shards along each axis (``None`` = replicated on that axis), and every
    core runs the unchanged kernel on its tile.  The output tiles
    concatenate along ``out_dims`` — no collective is emitted, because
    each core owns a disjoint output tile (the contraction, if any, stays
    core-local).
    """
    args = tuple(args)
    if cores < 1:
        raise ClusterError(f"cores must be >= 1, got {cores}")
    if len(in_dims) != len(args):
        raise ClusterError(
            f"in_dims has {len(in_dims)} entries for {len(args)} args")
    if cores == 1:
        return fn(*args)
    cr, cc = factor_cores(cores)
    if mesh is None:
        import numpy as np

        devs = jax.devices()
        if len(devs) < cores:
            raise ClusterError(
                f"need {cores} devices for a {cr}x{cc} cluster, have "
                f"{len(devs)}; on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={cores} before "
                "importing jax")
        mesh = Mesh(np.asarray(devs[:cores]).reshape(cr, cc),
                    ("rows", "cols"))
    specs = []
    for a, (rd, cd) in zip(args, in_dims):
        spec = [None] * a.ndim
        for dim, axis, extent in ((rd, "rows", cr), (cd, "cols", cc)):
            if dim is None:
                continue
            if a.shape[dim] % extent:
                raise ClusterError(
                    f"arg dim {dim} extent {a.shape[dim]} not divisible by "
                    f"{extent} ({axis}) cores")
            spec[dim] = axis
        specs.append(P(*spec))
    out_spec = [None] * (max(out_dims) + 1)
    out_spec[out_dims[0]] = "rows"
    out_spec[out_dims[1]] = "cols"
    wrapped = shard_map(fn, mesh=mesh, in_specs=tuple(specs),
                        out_specs=P(*out_spec), check_vma=False)
    return wrapped(*args)


def cluster_kernel(fn: Callable, args: Sequence[jax.Array], *,
                   cores: int,
                   in_dims: Sequence[Optional[int]],
                   out_dim: Optional[int] = None,
                   reduce: bool = False,
                   mesh: Optional[Mesh] = None):
    """Shard an existing registry kernel (not a nest) across C cores.

    For kernels whose iteration structure is neither a pure map nor a full
    reduction (e.g. GEMV: a reduction *per row*), the nest-level
    :func:`cluster_call` does not apply, but the work still splits on an
    output dimension.  ``in_dims[i]`` names the dim of ``args[i]`` to shard
    (``None`` = replicate, the repeat-stream operands); the per-core kernel
    runs unchanged on its slice.  ``reduce=True`` psums the outputs;
    otherwise ``out_dim`` is the concatenation dim.
    """
    args = tuple(args)
    if cores < 1:
        raise ClusterError(f"cores must be >= 1, got {cores}")
    if len(in_dims) != len(args):
        raise ClusterError(
            f"in_dims has {len(in_dims)} entries for {len(args)} args")
    if not reduce and out_dim is None:
        raise ClusterError("need out_dim (concat) or reduce=True (psum)")
    if cores == 1:
        return fn(*args)
    specs = []
    for a, dim in zip(args, in_dims):
        if dim is None:
            specs.append(P())
            continue
        if a.shape[dim] % cores:
            raise ClusterError(
                f"arg dim {dim} extent {a.shape[dim]} not divisible by "
                f"{cores} cores")
        spec = [None] * a.ndim
        spec[dim] = CORES_AXIS
        specs.append(P(*spec))
    the_mesh = _cluster_mesh(cores, mesh)

    def per_core(*arrs):
        out = fn(*arrs)
        if reduce:
            return jax.tree.map(lambda o: jax.lax.psum(o, CORES_AXIS), out)
        return out

    # Partial-rank spec: dims past out_dim are unsharded by convention, so
    # the output rank never needs probing.
    out_specs = P() if reduce else P(*([None] * out_dim), CORES_AXIS)
    wrapped = shard_map(per_core, mesh=the_mesh, in_specs=tuple(specs),
                        out_specs=out_specs, check_vma=False)
    return wrapped(*args)
