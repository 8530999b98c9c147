"""Lower compiler :class:`StreamPlan`\\ s to executable Pallas kernels.

This module closes the §3.2 loop that the paper's LLVM pass closes in MIR:

    LoopNest ──ssrify()──► StreamPlan ──lower_plan()──► (grid, BlockStreams)
                                                         │
                              ssr_call() ◄───────────────┘  → ssr_pallas()

``ssrify`` allocates affine accesses to data-mover lanes and renders the
Eq. (3) verdict; *nothing* in the seed tree executed that plan.  Here each
allocated :class:`StreamSpec` becomes a Pallas ``grid`` + affine ``index_map``
(the AGU at block granularity, derived via :func:`agu.block_grid`), and
:func:`ssr_call` runs the whole pipeline end to end: feed it a nest, a block
body, and the operand arrays, and the loop executes as a streamed Pallas
kernel whose operand delivery *is* the plan's AGU schedule.

Two lowering paths cover the AGU model at block granularity:

**Flat (``lower_plan``)** — read-only map/reduce nests whose operands walk
the iteration space in loop order: unit-stride innermost walk
(``coeffs[-1] == 1``) with *dense row-major* outer levels, levels with
coefficient 0 (the index_map ignores that grid axis, so the pipeline
revisits the block: the paper's **repeat register**), and fully
loop-invariant operands.

**Level-mapped (``lower_nest``)** — the general §3.2 pattern for nests
with an explicit output WRITE ref: one grid axis per loop level, operand
storage orders that may *permute* the loop order (GEMM's B, stored (k, n)
against (m, n, k) loops), and an output revisited across trailing
contraction axes, lowered to a VMEM scratch accumulator with
init-on-first / drain-on-last steps — Fig. 4's accumulator register as a
whole block.  ``ssr_call`` picks the path from the nest itself.

Anything outside both (overlapping halo windows, per-stage power-of-two
strides — expressible by the word-granular hardware AGU but not by
whole-block DMA) raises :class:`LoweringError`; those kernels keep
hand-scheduled layouts under ``repro.kernels``, each behind a declared
``lowering_waiver``.

Every entry point is **schedule-parametric**: a :class:`Schedule` (block
geometry, per-level tile targets, grid-axis order, accumulator dtype) can
be passed explicitly, searched by ``core/autotune.py``, or left at the
default.  Dispatch is **zero-overhead**: prepare (pad/reshape), the Pallas
kernel and the result trim compose into one cached jitted callable per
(nest, schedule, shapes, body), so repeated calls never re-dispatch the
padding traffic eagerly (see ``DISPATCH_STATS``).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
import operator
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from . import agu
from . import nest_analysis
from . import resilience
from .compiler import (Allocation, ChainDAG, ChainedPlan, LoopNest,
                       StreamPlan, _dense_strides, chain, chain_dag, ssrify)
from .ssr import BlockStream, auto_block, ssr_pallas
from .stream import Direction, StreamSpec


class LoweringError(ValueError):
    """The plan's access pattern has no whole-block Pallas schedule."""


@dataclasses.dataclass(frozen=True)
class BlockPolicy:
    """How element streams are blocked into VMEM tiles.

    A TPU "word" for streaming purposes is one (rows × lanes) tile; the
    policy is the stream's element width in the §2 correspondence.
    """

    rows: int = 8
    lanes: int = 128

    @property
    def block_elems(self) -> int:
        return self.rows * self.lanes

    @property
    def block_shape(self) -> Tuple[int, int]:
        return (self.rows, self.lanes)


DEFAULT_POLICY = BlockPolicy()


#: Per-level tile targets, in units of the policy's lane/sublane widths.
#: Lanes-role levels (the last storage dim of some stream) tile up to
#: 4×128 = 512 elements; sublane-role levels up to 32×8 = 256 rows.
#: These are the *defaults* — a :class:`Schedule` overrides both.
_LANES_TILE_FACTOR = 4
_ROWS_TILE_FACTOR = 32


@dataclasses.dataclass(frozen=True)
class Schedule:
    """One complete block-scheduling decision for a lowered kernel.

    :class:`BlockPolicy` says how element streams become VMEM tiles;
    ``Schedule`` is the full searched artifact on top of it — the knobs the
    autotuner (``core/autotune.py``) varies per (nest, shapes, backend):

    * ``rows``/``lanes`` — the block geometry (the policy);
    * ``lanes_tile_factor``/``rows_tile_factor`` — per-level tile targets
      of the level-mapped path, in units of ``lanes``/``rows``
      (:func:`lower_nest`'s ``_nest_tiles``);
    * ``axis_order`` — a permutation of the loop levels giving the grid
      iteration order (outermost first).  Only the level-mapped path honours
      it; contraction axes must stay trailing so the accumulator's revisits
      remain consecutive grid steps.  ``None`` keeps loop order;
    * ``acc_dtype`` — the contraction accumulator dtype (dtype *name*, so
      the dataclass stays hashable/JSON-serialisable).  f32 is the MXU/VPU
      accumulation width and the repo-wide default;
    * ``buffer_depth`` — the data mover's FIFO depth (paper §2.3: the mover
      "proactively performs memory reads").  2 keeps Pallas's synchronous
      double-buffered pipeline; > 2 emits the explicit N-deep DMA rotation
      (``core/ssr.py::_pipelined_call``) that prefetches grid step
      ``i + depth − 1`` while step ``i`` computes.  VMEM budgeting scales
      with it (``ssr.stream_vmem_bytes``), so the autotuner trades depth
      against tile size under one budget;
    * ``stream_depths`` — per-stream FIFO depths (one entry per read
      stream, in allocation order), overriding the uniform
      ``buffer_depth``: the strided operand that misses in HBM gets a
      deep rotation while the unit-stride one stays shallow, each charged
      individually through ``ssr.stream_vmem_bytes``.  ``None`` (the
      default) keeps every stream at ``buffer_depth``.  Searched only in
      full (non-quick) autotune runs;
    * ``cut_edges`` — for fused-DAG calls only (``ssr_dag_call``): the
      edge indices (into ``ChainDAG.edges``) at which the graph is *cut*
      into separate kernels, each cut intermediate materialising in HBM.
      ``None``/``()`` fuses the whole DAG into one kernel.  The fusion
      search (``autotune.autotune_dag``) commits the winning cut here so
      dispatch resolves the best partitioning transparently.

    Frozen + hashable: a ``Schedule`` is a cache key component everywhere
    (kernel cache, schedule cache, benchmark provenance).
    """

    rows: int = 8
    lanes: int = 128
    lanes_tile_factor: int = _LANES_TILE_FACTOR
    rows_tile_factor: int = _ROWS_TILE_FACTOR
    axis_order: Optional[Tuple[int, ...]] = None
    acc_dtype: str = "float32"
    buffer_depth: int = 2
    stream_depths: Optional[Tuple[int, ...]] = None
    cut_edges: Optional[Tuple[int, ...]] = None

    @property
    def policy(self) -> BlockPolicy:
        return BlockPolicy(rows=self.rows, lanes=self.lanes)

    @property
    def block_elems(self) -> int:
        return self.rows * self.lanes

    @classmethod
    def from_policy(cls, policy: BlockPolicy, **kw) -> "Schedule":
        return cls(rows=policy.rows, lanes=policy.lanes, **kw)

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["axis_order"] = list(self.axis_order) if self.axis_order else None
        d["stream_depths"] = (list(self.stream_depths)
                              if self.stream_depths else None)
        d["cut_edges"] = (list(self.cut_edges)
                          if self.cut_edges is not None else None)
        return d

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> "Schedule":
        ao = d.get("axis_order")
        sd = d.get("stream_depths")
        ce = d.get("cut_edges")
        return cls(rows=int(d["rows"]), lanes=int(d["lanes"]),
                   lanes_tile_factor=int(d.get("lanes_tile_factor",
                                               _LANES_TILE_FACTOR)),
                   rows_tile_factor=int(d.get("rows_tile_factor",
                                              _ROWS_TILE_FACTOR)),
                   axis_order=tuple(int(a) for a in ao) if ao else None,
                   acc_dtype=str(d.get("acc_dtype", "float32")),
                   buffer_depth=int(d.get("buffer_depth", 2)),
                   stream_depths=(tuple(int(x) for x in sd)
                                  if sd else None),
                   cut_edges=(tuple(int(x) for x in ce)
                              if ce is not None else None))


DEFAULT_SCHEDULE = Schedule()


def _depths_for(sched: Schedule, n_in: int):
    """The ``buffer_depth`` argument for ``ssr_pallas``: the uniform depth,
    or the schedule's per-stream override (one entry per read stream, in
    allocation order — satellite of the asymmetric-depth search)."""
    if sched.stream_depths is None:
        return sched.buffer_depth
    if len(sched.stream_depths) != n_in:
        raise LoweringError(
            f"schedule.stream_depths has {len(sched.stream_depths)} entries "
            f"for {n_in} read streams; give one depth per stream "
            "(allocation order)")
    return tuple(sched.stream_depths)


def _resolve_schedule(policy: BlockPolicy,
                      schedule: Optional[Schedule]) -> Schedule:
    """``schedule`` wins; a bare policy is promoted to a default Schedule."""
    if schedule is not None:
        return schedule
    if policy is DEFAULT_POLICY:
        return DEFAULT_SCHEDULE
    return Schedule.from_policy(policy)


@dataclasses.dataclass(frozen=True)
class LoweredStream:
    """One allocation lowered to block granularity.

    ``logical_shape`` is the operand view the lowering expects *before*
    padding (``None`` = take the array as-is, e.g. loop-invariant streams);
    ``prepare`` turns the user's flat/logical array into the 2-D padded
    layout whose row-blocks the ``index_map`` addresses.
    """

    name: str
    stream: BlockStream
    spec: StreamSpec                       # compiler allocation, for oracles
    logical_shape: Optional[Tuple[int, ...]]
    padded_last: int                       # innermost extent after padding
    policy: BlockPolicy
    offset: int = 0                        # base-pointer shift (AGU `base`)

    def prepare(self, arr: jax.Array) -> jax.Array:
        """Pad + reshape ``arr`` into the (rows, lanes) layout streamed."""
        lanes = self.policy.lanes
        if self.logical_shape is None:      # loop-invariant: one block
            # The AGU base pointer shifts the view: element 0 of the block
            # is data[offset], exactly what the spec's repeat walk emits.
            flat = arr.reshape(-1)[self.offset:]
            pad = (-flat.shape[0]) % lanes if flat.shape[0] else lanes
            if pad:
                flat = jnp.pad(flat, (0, pad))
            return flat.reshape(-1, lanes)
        want = math.prod(self.logical_shape)
        flat = arr.reshape(-1)
        if flat.shape[0] != want:
            raise ValueError(
                f"stream '{self.name}': operand has {flat.shape[0]} elements, "
                f"plan expects logical shape {self.logical_shape}")
        view = flat.reshape(self.logical_shape)
        pad = self.padded_last - self.logical_shape[-1]
        if pad:
            view = jnp.pad(view, [(0, 0)] * (view.ndim - 1) + [(0, pad)])
        return view.reshape(-1, lanes)


@dataclasses.dataclass(frozen=True)
class LoweredPlan:
    """A StreamPlan turned into a launchable Pallas schedule."""

    plan: StreamPlan
    policy: BlockPolicy
    grid: Tuple[int, ...]
    in_streams: Tuple[LoweredStream, ...]
    out_streams: Tuple[LoweredStream, ...]
    schedule: Schedule = DEFAULT_SCHEDULE

    @property
    def steps(self) -> int:
        return math.prod(self.grid)


def _inner_steps(nest: LoopNest, policy: BlockPolicy) -> int:
    return _inner_steps_of(nest.bounds, policy)


def _inner_steps_of(bounds: Tuple[int, ...], policy: BlockPolicy) -> int:
    return -(-bounds[-1] // policy.block_elems)


def _lower_allocation(alloc: Allocation, nest: LoopNest,
                      policy: BlockPolicy) -> LoweredStream:
    """Turn one lane's affine access into a BlockStream over row-blocks."""
    coeffs = alloc.ref.coeffs
    assert coeffs is not None  # only affine refs are ever allocated
    d = len(nest.bounds)
    E = policy.block_elems
    steps_inner = _inner_steps(nest, policy)
    padded_inner = steps_inner * E

    varying = [k for k, c in enumerate(coeffs) if c != 0]
    if not varying:
        # Loop-invariant operand: one block revisited by every grid step —
        # the repeat register driven to its limit.
        def invariant_map(*_g):
            return (0, 0)

        return LoweredStream(
            name=alloc.ref.name,
            stream=BlockStream(block_shape=(1, policy.lanes),
                               index_map=invariant_map,
                               direction=alloc.ref.kind,
                               name=alloc.ref.name),
            spec=alloc.spec, logical_shape=None, padded_last=policy.lanes,
            policy=policy, offset=alloc.ref.offset)

    if varying[-1] != d - 1 or coeffs[d - 1] != 1:
        raise LoweringError(
            f"stream '{alloc.ref.name}': innermost coefficient "
            f"{coeffs[d - 1]} is not a unit-stride walk of the innermost "
            "loop — the word-granular AGU supports it, whole-block DMA does "
            "not; use a hand-scheduled 2-D block kernel")

    # Dense row-major check for the outer varying levels: each coefficient
    # must equal the extent-product of the faster-varying levels, so the
    # operand is a plain (L_a, …, L_inner) array we can pad on its last dim.
    extents: Dict[int, int] = {d - 1: nest.bounds[d - 1]}
    expect = nest.bounds[d - 1]
    for k in reversed(varying[:-1]):
        if coeffs[k] != expect:
            raise LoweringError(
                f"stream '{alloc.ref.name}': level-{k} coefficient "
                f"{coeffs[k]} != dense row-major stride {expect}; the "
                "operand layout is not a contiguous array over its varying "
                "loops")
        extents[k] = nest.bounds[k]
        expect *= nest.bounds[k]
    if alloc.ref.offset % E:
        raise LoweringError(
            f"stream '{alloc.ref.name}': base offset {alloc.ref.offset} is "
            f"not block-aligned (block = {E} elements)")

    logical_shape = tuple(nest.bounds[k] for k in varying)
    # Padded flat stride of each varying outer level (innermost padded to a
    # whole number of blocks), expressed in *row-blocks*.
    base_block = alloc.ref.offset // E
    block_coeff: Dict[int, int] = {}
    stride_blocks = steps_inner
    for k in reversed(varying[:-1]):
        block_coeff[k] = stride_blocks
        stride_blocks *= nest.bounds[k]

    def index_map(*g):
        # grid axes = (outer nest levels …, tiled innermost); levels with a
        # zero coefficient simply don't appear — Pallas sees an unchanged
        # index and skips the re-fetch (repeat register).
        row = base_block + g[d - 1]
        for k, bc in block_coeff.items():
            row = row + g[k] * bc
        return (row, 0)

    return LoweredStream(
        name=alloc.ref.name,
        stream=BlockStream(block_shape=policy.block_shape,
                           index_map=index_map,
                           direction=alloc.ref.kind,
                           name=alloc.ref.name),
        spec=alloc.spec,
        logical_shape=logical_shape,
        padded_last=padded_inner,
        policy=policy)


def _canonical_grid(bounds: Tuple[int, ...],
                    policy: BlockPolicy) -> Tuple[int, ...]:
    """Grid for a nest's iteration space, innermost level tiled by blocks.

    Derived through :func:`agu.block_grid` on the canonical dense row-major
    spec so the schedule provably *is* the AGU pattern at block granularity.
    """
    E = policy.block_elems
    padded_inner = _inner_steps_of(bounds, policy) * E
    padded_bounds = tuple(bounds[:-1]) + (padded_inner,)
    canonical = StreamSpec(bounds=padded_bounds,
                           strides=_dense_strides(padded_bounds))
    return agu.block_grid(canonical, (E,))


def lower_plan(plan: StreamPlan,
               policy: BlockPolicy = DEFAULT_POLICY, *,
               schedule: Optional[Schedule] = None) -> LoweredPlan:
    """Lower every allocated lane of ``plan`` to Pallas block schedules.

    The grid is the nest's loop structure with the innermost level tiled by
    the policy block — computed through :func:`agu.block_grid` on the nest's
    canonical (dense row-major) iteration-space spec, so the kernel's block
    schedule provably *is* the AGU pattern at block granularity.

    ``schedule`` (when given) wins over ``policy``; the flat path honours
    only its block geometry — ``axis_order`` permutes *loop levels*, which
    this path has already flattened, so a non-``None`` order is rejected.
    """
    resilience.inject("lowering")
    sched = _resolve_schedule(policy, schedule)
    if sched.axis_order is not None:
        raise LoweringError(
            "schedule.axis_order applies to the level-mapped path "
            "(lower_nest) only; the flat schedule's grid IS the AGU walk")
    policy = sched.policy
    if not plan.allocations:
        raise LoweringError(
            "plan has no stream allocations (Eq. (3) verdict was 'keep "
            "baseline'); lower the force=True plan for the runtime-decision "
            "path")
    nest = plan.nest
    for r in nest.refs:
        if r.is_indirect():
            raise LoweringError(
                f"indirect ref '{r.name}': gathers take the level-mapped "
                "nest path (lower_nest); give the nest an explicit WRITE "
                "ref so ssr_call routes it there")
    grid = _canonical_grid(nest.bounds, policy)

    lowered = [_lower_allocation(a, nest, policy) for a in plan.allocations]
    ins = tuple(s for s in lowered if s.stream.direction == Direction.READ)
    outs = tuple(s for s in lowered if s.stream.direction == Direction.WRITE)
    return LoweredPlan(plan=plan, policy=policy, grid=grid,
                       in_streams=ins, out_streams=outs, schedule=sched)


# --------------------------------------------------------------------------
# Level-mapped lowering: multi-level nests with contraction axes.
#
# The flattened schedule above serves read-only map/reduce nests whose
# operands walk the iteration space in loop order.  The general §3.2
# pattern — GEMM is the flagship — needs more: operands whose storage
# order *permutes* the loop order (B is stored (k, n) while the loops run
# (m, n, k)), read streams revisited across inner levels (the repeat
# register at block granularity), and an output WRITE ref revisited across
# a contraction level, which the paper's accumulator register absorbs.
#
# ``lower_nest`` maps each loop level to its own grid axis (tiled by a
# per-level block factor), derives every allocated lane's block walk from
# its dense storage order (``nest_analysis.storage_order``), and lowers the
# single output WRITE ref to a VMEM scratch accumulator that initialises on
# the first visit of the contraction axes and drains on the last.  A READ
# ref whose coefficient is zero on an inner grid axis simply drops that
# axis from its index_map: Pallas sees an unchanged block index and skips
# the re-fetch, exactly as the FIFO re-emits a repeated datum.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class NestStream:
    """One allocation lowered to a level-mapped block schedule.

    ``levels`` is the ref's dense storage order (outermost first, possibly
    a permutation of the loop order); ``logical_shape``/``padded_shape``
    the operand array before/after per-level padding; ``layout_shape`` the
    (at least 2-D) array the BlockStream actually addresses — rank-1 refs
    gain a leading singleton, loop-invariant refs collapse to a flat
    ``(rows, lanes)`` view served as one revisited block.
    """

    name: str
    stream: BlockStream
    levels: Tuple[int, ...]
    logical_shape: Tuple[int, ...]
    padded_shape: Tuple[int, ...]
    layout_shape: Tuple[int, ...]
    policy: BlockPolicy
    offset: int = 0

    def prepare(self, arr: jax.Array) -> jax.Array:
        """Pad + reshape ``arr`` into the layout the index_map addresses."""
        if not self.levels:                 # loop-invariant: one block
            flat = arr.reshape(-1)[self.offset:]
            if flat.shape[0] == 0:
                raise ValueError(
                    f"stream '{self.name}': loop-invariant operand has no "
                    f"elements past offset {self.offset} — the block would "
                    "be all padding")
            if flat.shape[0] > self.policy.lanes:
                # The stream serves exactly ONE block; silently windowing a
                # larger constant would drop data the body never sees.
                # (The flat lower_plan path keeps its documented
                # base-pointer-window semantics; this path is stricter.)
                raise ValueError(
                    f"stream '{self.name}': loop-invariant operand has "
                    f"{flat.shape[0]} elements past offset {self.offset}, "
                    f"but an invariant stream serves one "
                    f"(1, {self.policy.lanes}) block; give the operand a "
                    "varying loop level instead")
            pad = (-flat.shape[0]) % self.policy.lanes
            if pad:
                flat = jnp.pad(flat, (0, pad))
            return flat.reshape(-1, self.policy.lanes)
        want = math.prod(self.logical_shape)
        flat = arr.reshape(-1)
        if flat.shape[0] != want:
            raise ValueError(
                f"stream '{self.name}': operand has {flat.shape[0]} "
                f"elements, plan expects logical shape {self.logical_shape}")
        view = flat.reshape(self.logical_shape)
        pads = [(0, p - l) for l, p in zip(self.logical_shape,
                                           self.padded_shape)]
        if any(p for _, p in pads):
            view = jnp.pad(view, pads)
        return view.reshape(self.layout_shape)


@dataclasses.dataclass(frozen=True)
class IndirectGather:
    """One indirect ref lowered to an in-kernel gather (arXiv 2011.08070).

    The index stream arrives as a normal dense block (``index_pos`` names
    its position in ``in_streams``); the gather *table* (the indirectly
    addressed operand) rides along whole in VMEM as a trailing invariant
    block.  The kernel body computes, per index-block element::

        addr = scale · index_value + Σ_l coeffs[l]·(program_id(grid_pos[l])
                                                    ·tiles[l] + intra_l)
               + offset

    and serves the body a ``jnp.take`` gather of the flattened table —
    each affine additive level prepends one block dimension.
    """

    name: str
    index_of: str
    index_pos: int               # index stream's slot in in_streams
    scale: int
    offset: int
    levels: Tuple[int, ...]      # affine additive levels (outermost first)
    grid_pos: Tuple[int, ...]    # grid-axis position per level
    tiles: Tuple[int, ...]       # tile extent per level
    coeffs: Tuple[int, ...]      # address coefficient per level


@dataclasses.dataclass(frozen=True)
class HaloRead:
    """One windowed READ ref lowered to shifted streams + in-kernel taps.

    A ref with ``window[l] = w > 1`` revisits ``w - 1`` neighbouring
    elements per step on level ``l`` — the stencil halo.  Whole-block DMA
    cannot fetch a block-and-a-bit, so the lowering emits ``2**k`` copies
    of the stream (``k`` halo'd levels): slot bit ``j`` adds a +1 grid
    shift on halo level ``j``.  The kernel concatenates each shifted pair
    along the level's block axis and slices the first ``tile + w - 1``
    columns — the widened block the body sees (DESIGN.md §13).

    ``slots`` are the shifted streams' positions in ``in_streams`` (binary
    order, bit 0 first); ``axes``/``tiles``/``windows`` are per halo'd
    level, in slot-bit order.
    """

    name: str
    slots: Tuple[int, ...]
    axes: Tuple[int, ...]
    tiles: Tuple[int, ...]
    windows: Tuple[int, ...]


def _int_ops(x):
    """(max, min, floor-div) for Python ints, or for int32 grid indices
    (whose operands the band keeps non-negative, so division truncates)."""
    if isinstance(x, int):
        return max, min, operator.floordiv
    return jnp.maximum, jnp.minimum, jax.lax.div


@dataclasses.dataclass(frozen=True)
class LoweredBand:
    """A nest's :class:`~repro.core.nest_analysis.Band` at tile granularity.

    Outer block i keeps inner tiles ``first(i) .. last(i)`` (:meth:`span`,
    from the band's element edges and both tiles).  The banded level's
    grid axis walks ``max(counts)`` steps, not the rectangle's ``n_inner``
    tiles: step j maps to tile ``first(i) + j`` clamped to ``last(i)``
    (:meth:`block`), so a step past the band repeats the previous block
    index (no DMA) and the kernel skips it under ``pl.when``.
    """

    outer_level: int
    inner_level: int
    outer_axis: int              # grid positions of the two levels
    inner_axis: int
    outer_tile: int
    inner_tile: int
    lo: Optional[int]
    hi: Optional[int]
    n_inner: int                 # inner tiles of the rectangle
    counts: Tuple[int, ...]      # inner tiles each outer block visits

    def span(self, i):
        """(first, last) inner tile of outer block ``i``: Python ints, or
        int32 scalars when ``i`` is a grid index inside the kernel."""
        mx, mn, div = _int_ops(i)
        first = 0 if self.lo is None else div(
            mx(i * self.outer_tile + self.lo, 0), self.inner_tile)
        last = self.n_inner - 1
        if self.hi is not None:
            last = mn(div(mx((i + 1) * self.outer_tile - 1 + self.hi, 0),
                          self.inner_tile), last)
        return first, last

    def block(self, i, j):
        """The inner tile grid step ``j`` of outer block ``i`` reads."""
        first, last = self.span(i)
        return _int_ops(j)[1](first + j, last)

    @property
    def visits(self) -> int:
        return sum(self.counts)


@dataclasses.dataclass(frozen=True)
class LoweredNest:
    """A StreamPlan with an output ref, lowered level-by-level.

    ``grid[k]`` covers loop level ``axis_order[k]`` (padded bound / tile;
    ``axis_order`` is the identity unless the schedule permutes it);
    ``tiles``/``padded_bounds`` stay in *loop-level* order.
    ``contraction_axes`` are the output's revisited levels as **grid-axis
    positions** — declared ``arbitrary`` (sequential) so the accumulator
    carries, every other axis ``parallel``.  ``gathers`` are the plan's
    indirect refs: the body receives their gathered blocks *after* the
    ``in_streams`` blocks, in declaration order.  ``band`` is the nest's
    band at tile granularity: the banded level's grid axis then walks the
    band's tiles, not the rectangle's.
    """

    plan: StreamPlan
    policy: BlockPolicy
    grid: Tuple[int, ...]
    tiles: Tuple[int, ...]
    in_streams: Tuple[NestStream, ...]
    out_stream: NestStream
    contraction_axes: Tuple[int, ...]
    schedule: Schedule = DEFAULT_SCHEDULE
    axis_order: Tuple[int, ...] = ()
    padded_bounds: Tuple[int, ...] = ()
    gathers: Tuple[IndirectGather, ...] = ()
    halos: Tuple[HaloRead, ...] = ()
    rescale: bool = False
    band: Optional[LoweredBand] = None

    @property
    def semantics(self) -> Tuple[str, ...]:
        return tuple("arbitrary" if l in self.contraction_axes else "parallel"
                     for l in range(len(self.grid)))

    @property
    def steps(self) -> int:
        return math.prod(self.grid)

    def _others(self) -> int:
        """Grid steps of the axes the band does not involve."""
        b = self.band
        return math.prod(n for k, n in enumerate(self.grid)
                         if k not in (b.outer_axis, b.inner_axis))

    @property
    def visited_steps(self) -> int:
        """Grid steps that compute: the band's tiles, or every step."""
        if self.band is None:
            return self.steps
        return self.band.visits * self._others()

    @property
    def rect_steps(self) -> int:
        """Grid steps the rectangle of the padded bounds would walk."""
        if self.band is None:
            return self.steps
        return (self.grid[self.band.outer_axis] * self.band.n_inner
                * self._others())


def _storage_order_or_raise(ref, nest: LoopNest) -> Tuple[int, ...]:
    order = nest_analysis.storage_order(ref, nest)
    if order is None:
        raise LoweringError(
            f"stream '{ref.name}': coefficients {ref.coeffs} admit no dense "
            "row-major storage order — overlapping or strided layouts are "
            "word-granular AGU territory, not whole-block DMA")
    return order


def _nest_tiles(nest: LoopNest, orders: Dict[str, Tuple[int, ...]],
                sched: Schedule) -> Tuple[Tuple[int, ...],
                                          Tuple[int, ...]]:
    """Per-level (tile, padded bound) from the streams' storage roles.

    A level that is the *last* storage dim of any stream is a lanes level
    (tile aligned to ``sched.lanes``, target ``lanes·lanes_tile_factor``);
    a level appearing only in outer positions is a sublane level (aligned
    to ``sched.rows``, target ``rows·rows_tile_factor``); a level no
    stream varies with is a pure iteration axis (tile 1).

    A lanes level that fits its tile target is one full-extent block,
    unpadded, as whole-array block dims are legal at any width: a head
    dim of 80 streams 80 lanes, not 128 of which 48 are zeros (1.6× the
    bytes and the contraction, plus a pad and a trim around the kernel).

    A halo'd level (some ref reads a ``window[l] = w > 1`` neighbourhood)
    additionally needs ``w - 1`` overlap columns served by ONE +1-shifted
    neighbour block, so its tile target is raised to at least the aligned
    overlap — candidates whose tile still undershoots it fail loudly in
    :func:`_lower_halo_streams` and are filtered by the autotuner.
    """
    policy = sched.policy
    roles: Dict[int, str] = {}
    for order in orders.values():
        if order:
            roles[order[-1]] = "lanes"
    for order in orders.values():
        for lvl in order[:-1]:
            roles.setdefault(lvl, "sublane")
    halo_need: Dict[int, int] = {}
    for ref in nest.refs:
        if ref.name in orders and ref.has_window():
            for lvl, w in enumerate(ref.window):
                if w > 1:
                    halo_need[lvl] = max(halo_need.get(lvl, 0), w - 1)
    tiles, padded = [], []
    for lvl, b in enumerate(nest.bounds):
        role = roles.get(lvl)
        if role == "lanes":
            align = policy.lanes
            target = policy.lanes * sched.lanes_tile_factor
        elif role == "sublane":
            align = policy.rows
            target = policy.rows * sched.rows_tile_factor
        else:
            tiles.append(1)
            padded.append(b)
            continue
        need = halo_need.get(lvl, 0)
        if role == "lanes" and not need and b <= target:
            tiles.append(b)
            padded.append(b)
            continue
        if need:
            target = max(target, -(-need // align) * align)
        pb = -(-b // align) * align
        tiles.append(auto_block(pb, target, align))
        padded.append(pb)
    return tuple(tiles), tuple(padded)


def _grid_axis_order(sched: Schedule, d: int,
                     zaxes: Tuple[int, ...]) -> Tuple[int, ...]:
    """Validated grid-axis order: a permutation keeping contractions last.

    The accumulator lowering requires every revisit of one output block to
    be *consecutive* grid steps, i.e. the contraction axes must be the
    fastest-varying (trailing) grid axes — any permutation of the parallel
    axes ahead of them is legal and only changes traversal locality.
    """
    order = sched.axis_order
    if order is None:
        return tuple(range(d))
    if sorted(order) != list(range(d)):
        raise LoweringError(
            f"schedule.axis_order {order} is not a permutation of the "
            f"{d} loop levels")
    if zaxes and set(order[d - len(zaxes):]) != set(zaxes):
        raise LoweringError(
            f"schedule.axis_order {order} does not keep the contraction "
            f"axes {zaxes} trailing; the accumulator's revisits must be "
            "consecutive grid steps")
    return tuple(order)


def _block_index(lvl: int, pos: Dict[int, int],
                 band: Optional[LoweredBand]) -> Callable:
    """Grid indices → level ``lvl``'s block index: its own grid axis, or
    for the banded level the band's clamped tile of the outer block."""
    if band is not None and lvl == band.inner_level:
        return lambda g: band.block(g[band.outer_axis], g[band.inner_axis])
    p = pos[lvl]
    return lambda g: g[p]


def _lower_nest_stream(alloc: Allocation, nest: LoopNest,
                       tiles: Tuple[int, ...], padded: Tuple[int, ...],
                       policy: BlockPolicy, pos: Dict[int, int],
                       band: Optional[LoweredBand] = None) -> NestStream:
    """One lane's level-mapped block walk.

    ``pos[lvl]`` is the grid-axis position of loop level ``lvl`` (identity
    unless the schedule permutes the axis order).
    """
    ref = alloc.ref
    order = _storage_order_or_raise(ref, nest)
    if not order:
        # Loop-invariant: a read is one block revisited by every grid step;
        # a write is the scalar accumulator drained once at the end.
        shape = (1, 1) if ref.kind == Direction.WRITE else (1, policy.lanes)
        return NestStream(
            name=ref.name,
            stream=BlockStream(shape, lambda *_g: (0, 0),
                               direction=ref.kind, name=ref.name),
            levels=(), logical_shape=(), padded_shape=(),
            layout_shape=shape, policy=policy, offset=ref.offset)
    if ref.offset:
        raise LoweringError(
            f"stream '{ref.name}': base offset {ref.offset} cannot shift a "
            "level-mapped block walk; fold it into the operand view")
    logical = tuple(nest.bounds[l] for l in order)
    pad_shape = tuple(padded[l] for l in order)
    idx = tuple(_block_index(l, pos, band) for l in order)
    if len(order) == 1:
        block = (1, tiles[order[0]])
        layout = (1, pad_shape[0])

        def index_map(*g):
            return (0, idx[0](g))
    else:
        block = tuple(tiles[l] for l in order)
        layout = pad_shape

        def index_map(*g):
            return tuple(f(g) for f in idx)

    return NestStream(
        name=ref.name,
        stream=BlockStream(block, index_map, direction=ref.kind,
                           name=ref.name),
        levels=order, logical_shape=logical, padded_shape=pad_shape,
        layout_shape=layout, policy=policy)


def _lower_halo_streams(alloc: Allocation, nest: LoopNest,
                        tiles: Tuple[int, ...], padded: Tuple[int, ...],
                        policy: BlockPolicy, pos: Dict[int, int]
                        ) -> Tuple[list, Tuple[int, ...], Tuple[int, ...],
                                   Tuple[int, ...]]:
    """Lower one windowed READ ref to ``2**k`` shifted streams.

    Whole-block DMA cannot deliver a ``tile + w - 1`` widened block
    directly (index_maps address whole tiles), so each halo'd level
    doubles the stream: the copy's index_map is shifted +1 grid step on
    that level, and the kernel stitches ``block ++ shifted`` back into the
    widened view (:func:`_halo_widen`).  The operand layout is padded by
    one extra tile per halo'd level so the shifted walk stays in range at
    the grid edge.

    Returns ``(streams, axes, halo_tiles, windows)`` — the shifted
    :class:`NestStream`\\ s in binary slot order plus the per-halo'd-level
    metadata for :class:`HaloRead` (block axis, tile, window width).
    """
    ref = alloc.ref
    order = _storage_order_or_raise(ref, nest)
    if ref.offset:
        raise LoweringError(
            f"stream '{ref.name}': base offset {ref.offset} cannot shift a "
            "level-mapped block walk; fold it into the operand view")
    halo_lvls = tuple(lvl for lvl, w in enumerate(ref.window) if w > 1)
    for lvl in halo_lvls:
        w = ref.window[lvl]
        if w - 1 > tiles[lvl]:
            raise LoweringError(
                f"stream '{ref.name}': halo window {ref.window} needs "
                f"{w - 1} overlap columns on level {lvl}, but the block "
                f"tile is only {tiles[lvl]} wide; widen the tile so one "
                "block plus its +1-shifted neighbour covers the window")
    halo_set = set(halo_lvls)
    logical = tuple(nest_analysis.level_extent(ref, nest, l) for l in order)
    pad_shape = tuple(padded[l] + (tiles[l] if l in halo_set else 0)
                      for l in order)
    streams = []
    for m in range(1 << len(halo_lvls)):
        shift = {lvl: (m >> j) & 1 for j, lvl in enumerate(halo_lvls)}
        if len(order) == 1:
            lvl = order[0]
            block = (1, tiles[lvl])
            layout = (1, pad_shape[0])

            def index_map(*g, _p=pos[lvl], _s=shift[lvl]):
                return (0, g[_p] + _s)
        else:
            block = tuple(tiles[l] for l in order)
            layout = pad_shape

            def index_map(*g, _ps=tuple(pos[l] for l in order),
                          _ss=tuple(shift.get(l, 0) for l in order)):
                return tuple(g[p] + s for p, s in zip(_ps, _ss))

        streams.append(NestStream(
            name=ref.name,
            stream=BlockStream(block, index_map, direction=ref.kind,
                               name=ref.name),
            levels=order, logical_shape=logical, padded_shape=pad_shape,
            layout_shape=layout, policy=policy))
    axes = tuple(1 if len(order) == 1 else order.index(lvl)
                 for lvl in halo_lvls)
    return (streams, axes, tuple(tiles[lvl] for lvl in halo_lvls),
            tuple(ref.window[lvl] for lvl in halo_lvls))


def _lower_band(nest: LoopNest, rescale: bool, zaxes: Tuple[int, ...],
                out_varying: Tuple[int, ...], tiles: Tuple[int, ...],
                padded: Tuple[int, ...], pos: Dict[int, int],
                gathers_or_halos: bool) -> LoweredBand:
    """The nest's band at tile granularity, or a LoweringError where the
    kernel cannot honour it.

    Only the online-softmax kernel skips tiles: its body receives the
    steps' element offsets and masks the pairs a band's edge tiles hold
    beyond the band, which a plain accumulator's body cannot.
    """
    b = nest.band
    if not rescale:
        raise LoweringError(
            f"band {b}: only the online_softmax accumulator lowers a band "
            "(its body masks the band's edge tiles from their offsets)")
    if zaxes != (b.inner,) or b.outer not in out_varying:
        raise LoweringError(
            f"band {b}: the banded level must be the output's contraction "
            f"level {zaxes} and its outer level one the output varies with "
            f"{out_varying}")
    if gathers_or_halos:
        raise LoweringError(
            f"band {b}: a banded nest with gathers or halo reads is not "
            "lowered")
    n_outer = padded[b.outer] // tiles[b.outer]
    proto = LoweredBand(
        outer_level=b.outer, inner_level=b.inner, outer_axis=pos[b.outer],
        inner_axis=pos[b.inner], outer_tile=tiles[b.outer],
        inner_tile=tiles[b.inner], lo=b.lo, hi=b.hi,
        n_inner=padded[b.inner] // tiles[b.inner], counts=())
    counts = []
    for i in range(n_outer):
        first, last = proto.span(i)
        counts.append(max(0, last - first + 1))
    return dataclasses.replace(proto, counts=tuple(counts))


def lower_nest(plan: StreamPlan,
               policy: BlockPolicy = DEFAULT_POLICY, *,
               schedule: Optional[Schedule] = None) -> LoweredNest:
    """Lower a plan with an output WRITE ref to a level-mapped schedule.

    Requirements (each a :class:`LoweringError` otherwise):

    * exactly one WRITE ref, affine and *allocated* (it needs a lane);
    * every allocated ref has a dense storage order (possibly permuting
      the loop order — GEMM's B);
    * the output's contraction axes are the innermost loop levels, so all
      revisits of one output block are consecutive grid steps and a single
      VMEM accumulator carries them (init on first, drain on last).

    ``schedule`` (when given) wins over ``policy`` and additionally sets
    the per-level tile targets, the grid-axis order (parallel axes may
    permute; contraction axes stay trailing) and the accumulator dtype.

    A nest with a band (causal or windowed attention) gets a contraction
    axis as long as the most kv tiles any q block's band meets, and K/V
    index maps that start at that block's first tile (:class:`LoweredBand`).
    """
    resilience.inject("lowering")
    sched = _resolve_schedule(policy, schedule)
    policy = sched.policy
    nest = plan.nest
    try:
        out_ref = nest_analysis.output_ref(nest)
    except ValueError as e:
        raise LoweringError(str(e)) from e
    if out_ref is None:
        raise LoweringError(
            "nest has no WRITE ref; use lower_plan with an ssr_call "
            "map/reduce mode to synthesise the output")
    if not out_ref.is_affine():
        raise LoweringError(
            f"output ref '{out_ref.name}' is not affine; it cannot be "
            "served by a write stream")
    out_allocs = [a for a in plan.allocations
                  if a.ref.kind == Direction.WRITE]
    if not out_allocs:
        raise LoweringError(
            f"output ref '{out_ref.name}' was not allocated a lane "
            f"({len(plan.allocations)} lanes used); raise num_lanes so the "
            "write stream gets a data mover")

    zaxes = nest_analysis.contraction_axes(out_ref, nest)
    out_varying = nest_analysis.varying_levels(out_ref)
    if zaxes and out_varying and max(out_varying) > min(zaxes):
        raise LoweringError(
            f"output ref '{out_ref.name}': contraction axes {zaxes} are not "
            f"the innermost levels (output varies with {out_varying}); the "
            "accumulator would be drained and re-initialised mid-reduction")

    rescale = out_ref.acc_kind == "online_softmax"
    if rescale:
        if not zaxes:
            # A one-key KV level (bound 1) still carries the (m, l, acc)
            # triple — over a single step.  ``contraction_axes`` drops
            # bound-1 levels, so recover the revisited level here.
            zaxes = tuple(k for k, c in enumerate(out_ref.coeffs) if c == 0)
        if len(zaxes) != 1:
            raise LoweringError(
                f"output ref '{out_ref.name}': online_softmax needs exactly "
                f"one contraction axis to carry the (m, l, acc) triple "
                f"across, got {zaxes}")
        if set(out_varying) | set(zaxes) != set(range(len(nest.bounds))):
            raise LoweringError(
                f"output ref '{out_ref.name}': online_softmax requires the "
                "output plus the contraction axis to cover every loop "
                f"level (output varies with {out_varying}, contraction "
                f"{zaxes}, nest depth {len(nest.bounds)})")

    for r in plan.residual:
        if r.is_indirect():
            raise LoweringError(
                f"indirect ref '{r.name}' was not allocated a lane; a "
                "gather cannot stay residual on the block path — raise "
                "num_lanes so every indirect ref gets a data mover")
    ind_allocs = [a for a in plan.allocations if a.ref.is_indirect()]
    dense_allocs = [a for a in plan.allocations if not a.ref.is_indirect()]

    orders = {a.ref.name: _storage_order_or_raise(a.ref, nest)
              for a in dense_allocs}
    tiles, padded = _nest_tiles(nest, orders, sched)
    axis_order = _grid_axis_order(sched, len(nest.bounds), zaxes)
    pos = {lvl: k for k, lvl in enumerate(axis_order)}
    grid = tuple(padded[l] // tiles[l] for l in axis_order)
    band = None
    if nest.band is not None:
        band = _lower_band(nest, rescale, zaxes, out_varying, tiles, padded,
                           pos, bool(ind_allocs) or any(
                               a.ref.has_window() for a in dense_allocs))
        grid = tuple(max(1, max(band.counts)) if k == band.inner_axis else n
                     for k, n in enumerate(grid))

    if rescale:
        out_order = orders[out_ref.name]
        if len(out_order) != 2:
            raise LoweringError(
                f"output ref '{out_ref.name}': online_softmax carries a "
                "(rows, lanes) accumulator block, so the output needs "
                f"exactly two varying levels, got storage order {out_order}")
        lanes_lvl = out_order[-1]
        if padded[lanes_lvl] != tiles[lanes_lvl]:
            raise LoweringError(
                f"output ref '{out_ref.name}': online_softmax needs the "
                f"lanes level {lanes_lvl} served in one grid step "
                f"(padded {padded[lanes_lvl]} vs tile {tiles[lanes_lvl]}); "
                "the rescaled accumulator cannot split its lane dim")
        if jnp.dtype(sched.acc_dtype) != jnp.dtype("float32"):
            raise LoweringError(
                f"output ref '{out_ref.name}': online_softmax pins "
                f"acc_dtype=float32 (running max/sum rescaling is not "
                f"stable in {sched.acc_dtype}); adjust the schedule")

    ins_list: list = []
    outs: list = []
    halos: list = []
    in_slot: Dict[str, int] = {}
    for a in dense_allocs:
        ref = a.ref
        if ref.kind == Direction.READ and ref.has_window():
            streams, axes, htiles, hwins = _lower_halo_streams(
                a, nest, tiles, padded, policy, pos)
            slots = tuple(range(len(ins_list),
                                len(ins_list) + len(streams)))
            in_slot.setdefault(ref.name, slots[0])
            ins_list.extend(streams)
            halos.append(HaloRead(name=ref.name, slots=slots, axes=axes,
                                  tiles=htiles, windows=hwins))
        else:
            s = _lower_nest_stream(a, nest, tiles, padded, policy, pos,
                                   band)
            if s.stream.direction == Direction.WRITE:
                outs.append(s)
            else:
                in_slot.setdefault(s.name, len(ins_list))
                ins_list.append(s)
    ins = tuple(ins_list)

    gathers = []
    for a in ind_allocs:
        ref = a.ref
        if ref.index_of not in in_slot:
            raise LoweringError(
                f"indirect ref '{ref.name}': its index stream "
                f"'{ref.index_of}' must itself be an allocated read "
                "stream — the gather addresses come off that lane")
        idx_ref = nest_analysis.index_stream_of(ref, nest)
        affine_lvls = tuple(k for k, c in enumerate(ref.coeffs) if c != 0)
        overlap = set(affine_lvls) & set(
            nest_analysis.varying_levels(idx_ref))
        if overlap:
            raise LoweringError(
                f"indirect ref '{ref.name}': affine additive levels "
                f"{sorted(overlap)} coincide with the index stream's "
                "varying levels; the gather address would double-count "
                "those loop indices")
        gathers.append(IndirectGather(
            name=ref.name, index_of=ref.index_of,
            index_pos=in_slot[ref.index_of],
            scale=ref.index_scale, offset=ref.offset,
            levels=affine_lvls,
            grid_pos=tuple(pos[l] for l in affine_lvls),
            tiles=tuple(tiles[l] for l in affine_lvls),
            coeffs=tuple(ref.coeffs[l] for l in affine_lvls)))

    return LoweredNest(plan=plan, policy=policy, grid=grid, tiles=tiles,
                       in_streams=ins, out_stream=outs[0],
                       contraction_axes=tuple(sorted(pos[z] for z in zaxes)),
                       schedule=sched, axis_order=axis_order,
                       padded_bounds=tuple(padded), gathers=tuple(gathers),
                       halos=tuple(halos), rescale=rescale, band=band)


# --------------------------------------------------------------------------
# Stream chaining: a ChainedPlan lowers to ONE Pallas kernel whose
# intermediates live in VMEM scratch blocks and never touch HBM.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LoweredChain:
    """A ChainedPlan *or* ChainDAG turned into one launchable schedule.

    All stages share one grid (the unified iteration space, innermost level
    tiled by the policy block).  ``stage_in_streams[k]`` are stage k's
    external read streams; the link intermediates have *no* streams at all —
    they exist only as VMEM scratch inside the kernel.  For a ChainDAG the
    scratch slots are refcounted: a produced block's slot is reused once
    its last consumer stage has read it (see ``_dag_slots``).
    """

    chained: Any                    # ChainedPlan | ChainDAG
    policy: BlockPolicy
    grid: Tuple[int, ...]
    stage_in_streams: Tuple[Tuple[LoweredStream, ...], ...]
    schedule: Schedule = DEFAULT_SCHEDULE

    @property
    def in_streams(self) -> Tuple[LoweredStream, ...]:
        return tuple(s for stage in self.stage_in_streams for s in stage)

    @property
    def steps(self) -> int:
        return math.prod(self.grid)


def lower_chain(chained, policy: BlockPolicy = DEFAULT_POLICY, *,
                schedule: Optional[Schedule] = None) -> LoweredChain:
    """Lower a chain (or chain DAG) to one fused Pallas schedule.

    Accepts a linear :class:`ChainedPlan` or a :class:`ChainDAG` — both
    expose ``stages``/``links``/``bounds``, and the stage emission is
    topologically ordered either way (a linear chain is the special case
    where stage k's only consumer is stage k+1).

    Block-granular chaining requires each link to walk the canonical dense
    row-major pattern of the shared iteration space: then grid step ``g``'s
    produced block is *exactly* the block the consumer eats at step ``g``,
    so the intermediate can live in a VMEM scratch block instead of an HBM
    buffer.  Anything else (strided/offset intermediate layouts) raises
    :class:`LoweringError` — the word-granular chaining hardware could
    stagger streams, whole-block fusion cannot.
    """
    resilience.inject("lowering")
    sched = _resolve_schedule(policy, schedule)
    if sched.axis_order is not None:
        raise LoweringError(
            "schedule.axis_order applies to the level-mapped path "
            "(lower_nest) only; a chain's grid IS the unified AGU walk")
    policy = sched.policy
    bounds = chained.bounds
    dense = _dense_strides(bounds)
    for link in chained.links:
        if link.coeffs != dense or link.offset != 0:
            raise LoweringError(
                f"link '{link.name}': intermediate walk {link.coeffs}+"
                f"{link.offset} is not the dense row-major walk {dense}+0 of "
                "the iteration space — the producer's block is not the "
                "consumer's block, so the intermediate cannot stay in VMEM")
    if not any(p.allocations for p in chained.stages):
        raise LoweringError(
            "chained plan has no stream allocations (every stage kept the "
            "baseline verdict); chain with force=True for the "
            "runtime-decision path")

    stage_streams = []
    for k, plan in enumerate(chained.stages):
        lowered = [_lower_allocation(a, plan.nest, policy)
                   for a in plan.allocations]
        writes = [s.name for s in lowered
                  if s.stream.direction == Direction.WRITE]
        if writes:
            raise LoweringError(
                f"chain stage {k} carries write streams {writes}; only the "
                "final output (synthesised from the call mode) may leave "
                "the fused region")
        stage_streams.append(tuple(lowered))

    return LoweredChain(chained=chained, policy=policy,
                        grid=_canonical_grid(bounds, policy),
                        stage_in_streams=tuple(stage_streams),
                        schedule=sched)


# --------------------------------------------------------------------------
# End-to-end execution: ssr_call / ssr_chain_call
# --------------------------------------------------------------------------


#: One bound for every lowering-layer cache: the plan/lowered caches below
#: and the built-kernel cache share it, so sizing is tuned in one place and
#: ``clear_caches()`` provably empties the whole layer.
CACHE_MAX = 256


@functools.lru_cache(maxsize=CACHE_MAX)
def _plan_for(nest: LoopNest, num_lanes: int) -> StreamPlan:
    """Plan cache keyed on the nest signature (frozen dataclass hash).

    ``force=True`` is the paper's runtime-decision path: the caller asked to
    *execute* the streamed variant, so allocation must happen regardless of
    the static Eq. (3) verdict (which remains available via ``plan_stats``).
    """
    return ssrify(nest, num_lanes=num_lanes, force=True)


@functools.lru_cache(maxsize=CACHE_MAX)
def plan_stats(nest: LoopNest, num_lanes: int = 2) -> StreamPlan:
    """The static-verdict plan (no force) — Eq. (1)–(3) cost accounting."""
    return ssrify(nest, num_lanes=num_lanes)


@functools.lru_cache(maxsize=CACHE_MAX)
def _chain_for(nests: Tuple[LoopNest, ...],
               num_lanes: Optional[int]) -> ChainedPlan:
    """Chained-plan cache (force=True: the caller asked to execute fused)."""
    return chain(nests, num_lanes=num_lanes, force=True)


@functools.lru_cache(maxsize=CACHE_MAX)
def _dag_for(nests: Tuple[LoopNest, ...],
             num_lanes: Optional[int]) -> ChainDAG:
    """Chain-DAG cache (force=True: the caller asked to execute fused)."""
    return chain_dag(nests, num_lanes=num_lanes, force=True)


@functools.lru_cache(maxsize=CACHE_MAX)
def _lowered_for(plan: StreamPlan, sched: Schedule, nested: bool):
    """Lowered-schedule cache: the pure-Python lowering per (plan, sched)."""
    if nested:
        return lower_nest(plan, schedule=sched)
    return lower_plan(plan, schedule=sched)


@functools.lru_cache(maxsize=CACHE_MAX)
def _lowered_chain_for(chained: ChainedPlan,
                       sched: Schedule) -> LoweredChain:
    return lower_chain(chained, schedule=sched)


#: Every LRU in this layer, for clear/inspection: the plan caches…
_PLAN_CACHES = (_plan_for, plan_stats, _chain_for, _dag_for, _lowered_for,
                _lowered_chain_for)


#: Dispatch-layer instrumentation.  ``builds`` counts jitted pipelines
#: constructed, ``traces`` counts actual jit traces of those pipelines
#: (incremented *inside* the traced function, so it only moves when XLA
#: re-traces), ``calls`` counts ``ssr_call``/``ssr_chain_call`` entries.
#: A second identical call must move ``calls`` only — that is the
#: zero-overhead-dispatch contract the tests assert.  The resilience
#: family: ``fallbacks`` counts schedule lookups abandoned for the
#: default (cache I/O fault before any kernel was built), ``degraded``
#: counts committed tuned schedules that failed to lower/compile/run and
#: were quarantined + re-dispatched on the default schedule.  Healthy
#: runs keep both at zero.  ``pipelined`` counts ``ssr_call`` kernels
#: built on the explicit N-deep DMA rotation (``fn.pipelined`` of
#: :func:`~repro.core.ssr.ssr_pallas`) — proof that a ``buffer_depth > 2``
#: schedule did not silently take the synchronous path.  ``band_tiles``
#: and ``rect_tiles`` count, over the attention (online-softmax) kernels
#: built, the (q tile, kv tile) grid steps each visits and the steps the
#: rectangle of its padded bounds would have had: equal without a band.
DISPATCH_STATS: Dict[str, int] = {"builds": 0, "traces": 0, "calls": 0,
                                  "fallbacks": 0, "degraded": 0,
                                  "pipelined": 0, "band_tiles": 0,
                                  "rect_tiles": 0}


def _record_fallback(site: str, error: BaseException, *,
                     from_schedule: str, to_schedule: str,
                     key: Optional[str] = None,
                     counter: str = "fallbacks") -> None:
    """Count + log one degradation step (see ``core/resilience.py``)."""
    DISPATCH_STATS[counter] += 1
    resilience.record_fallback(seam=resilience.classify(error), site=site,
                               error=error, from_schedule=from_schedule,
                               to_schedule=to_schedule, key=key)


def reset_dispatch_stats() -> None:
    for k in DISPATCH_STATS:
        DISPATCH_STATS[k] = 0


def _named(pipeline: Callable, name: Optional[str]) -> Callable:
    """``pipeline`` renamed ``name``, so ``jax.jit`` compiles it as
    ``jit_<name>``; with no name it stays ``jit_pipeline``."""
    if name is not None:
        pipeline.__name__ = pipeline.__qualname__ = name
    return pipeline


def _body_key(body: Callable) -> Any:
    """Stable cache identity for a block body.

    Keying on the function *object* is a footgun: an inline lambda is a
    fresh object every call, so every call silently rebuilds (and re-jits)
    the kernel.  Python compiles the lambda's code object once per source
    location, so ``(code, bound self, closure values, defaults,
    kw-defaults)`` identifies the body's behaviour — two lambdas from the
    same line with equal closures share a kernel, while bound methods of
    *different* instances (per-instance state lives on ``__self__``, not
    in the code or closure) and factories varying a keyword-only default
    do not collide.  Unhashable contents (e.g. captured arrays) fall back
    to object identity: never stale, just uncached across re-creations.
    """
    code = getattr(body, "__code__", None)
    if code is None:
        return body
    cells = getattr(body, "__closure__", None) or ()
    kwdefs = getattr(body, "__kwdefaults__", None) or {}
    try:
        key = (code, getattr(body, "__self__", None),
               tuple(c.cell_contents for c in cells),
               getattr(body, "__defaults__", None) or (),
               tuple(sorted(kwdefs.items())))
        hash(key)
    except (TypeError, ValueError):  # unhashable content / empty cell
        return body
    return key


# …and the built-kernel cache, LRU-bounded by the same CACHE_MAX.  Keys
# include the body's ``_body_key``: inline lambdas hit the cache as long as
# their closure values are hashable and equal (see the footgun note above).
_KERNEL_CACHE_MAX = CACHE_MAX
_kernel_cache: "collections.OrderedDict[Any, Callable]" = \
    collections.OrderedDict()


def _kernel_cache_get(key):
    fn = _kernel_cache.get(key)
    if fn is not None:
        _kernel_cache.move_to_end(key)
    return fn


def _kernel_cache_put(key, fn) -> None:
    _kernel_cache[key] = fn
    _kernel_cache.move_to_end(key)
    while len(_kernel_cache) > _KERNEL_CACHE_MAX:
        _kernel_cache.popitem(last=False)


def clear_caches() -> None:
    """Empty every lowering-layer cache: plans, chains, built kernels."""
    for c in _PLAN_CACHES:
        c.cache_clear()
    _kernel_cache.clear()


def _first_last(grid: Tuple[int, ...]):
    """Predicates for the first/last step of a (possibly multi-dim) grid."""
    from jax.experimental import pallas as pl

    first = pl.program_id(0) == 0
    last = pl.program_id(0) == pl.num_programs(0) - 1
    for k in range(1, len(grid)):
        first = jnp.logical_and(first, pl.program_id(k) == 0)
        last = jnp.logical_and(last, pl.program_id(k) == pl.num_programs(k) - 1)
    return first, last


def _assemble_kernel(grid: Tuple[int, ...], policy: BlockPolicy,
                     in_streams: Sequence[BlockStream],
                     compute: Callable, n_links: int, mode: str,
                     out_dtype, part_shape: Optional[Tuple[int, ...]],
                     interpret: Optional[bool],
                     buffer_depth: int = 2,
                     uniforms: Sequence[jax.ShapeDtypeStruct] = (),
                     name: Optional[str] = None) -> Callable:
    """Shared kernel assembler for single-nest and chained plans.

    ``compute(in_refs, link_refs)`` returns the per-step value; ``n_links``
    VMEM scratch blocks hold chained intermediates (zero for plain plans).
    ``uniforms`` appends whole-array operands (weights, tables) delivered
    to every grid step as ONE block — a loop-invariant stream whose block
    *is* the array, fetched once (the pipelined emitter already special-
    cases invariant streams).  Their refs trail the streamed inputs in
    ``in_refs``.
    Reduce mode accumulates into a *vector* accumulator when the partial is
    a multi-element 2-D block — the whole (rows, lanes) vreg adds every
    step, folded to the scalar exactly once on the last step — and keeps
    the legacy scalar ``(1, 1)`` accumulator for scalar partials.  Map-mode
    grid axes are independent and declared ``parallel``; only reduce mode
    needs sequential (``arbitrary``) semantics for its carried accumulator.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if uniforms:
        def _whole(nd: int):
            return lambda *_g: (0,) * nd

        in_streams = list(in_streams) + [
            BlockStream(tuple(u.shape), _whole(len(u.shape)),
                        Direction.READ, name=f"_uniform{i}")
            for i, u in enumerate(uniforms)]
        if isinstance(buffer_depth, tuple):
            buffer_depth = buffer_depth + (2,) * len(uniforms)
    n_in = len(in_streams)
    link_scratch = [pltpu.VMEM(policy.block_shape, out_dtype)
                    for _ in range(n_links)]

    if mode == "reduce":
        vector_acc = (part_shape is not None and len(part_shape) == 2
                      and math.prod(part_shape) > 1)
        acc_shape = tuple(part_shape) if vector_acc else (1, 1)

        def kernel(*refs):
            in_refs, o_ref = refs[:n_in], refs[n_in]
            links = refs[n_in + 1:n_in + 1 + n_links]
            acc_ref = refs[n_in + 1 + n_links]
            first, last = _first_last(grid)

            @pl.when(first)
            def _init():
                acc_ref[...] = jnp.zeros_like(acc_ref)

            part = jnp.asarray(compute(in_refs, links), out_dtype)
            acc_ref[...] += part.reshape(acc_shape)

            @pl.when(last)
            def _write():
                if vector_acc:
                    o_ref[...] = jnp.sum(acc_ref[...]).reshape(1, 1)
                else:
                    o_ref[...] = acc_ref[...]

        out_streams = [BlockStream((1, 1), lambda *g: (0, 0),
                                   Direction.WRITE, name="acc")]
        out_shapes = [jax.ShapeDtypeStruct((1, 1), out_dtype)]
        scratch = link_scratch + [pltpu.VMEM(acc_shape, out_dtype)]
        semantics = ("arbitrary",) * len(grid)
    elif mode == "map":
        steps = math.prod(grid)
        # Output walks the grid dense row-major: one block per step.
        place = _dense_strides(grid)

        def out_map(*g):
            row = g[0] * place[0]
            for k in range(1, len(g)):
                row = row + g[k] * place[k]
            return (row, 0)

        def kernel(*refs):
            in_refs, o_ref = refs[:n_in], refs[n_in]
            links = refs[n_in + 1:n_in + 1 + n_links]
            o_ref[...] = jnp.asarray(
                compute(in_refs, links), out_dtype
            ).reshape(policy.block_shape)

        out_streams = [BlockStream(policy.block_shape, out_map,
                                   Direction.WRITE, name="out")]
        out_shapes = [jax.ShapeDtypeStruct(
            (steps * policy.rows, policy.lanes), out_dtype)]
        scratch = list(link_scratch)
        semantics = ("parallel",) * len(grid)
    else:
        raise ValueError(f"unknown ssr_call mode {mode!r}")

    return ssr_pallas(
        kernel, grid=grid,
        in_streams=list(in_streams), out_streams=out_streams,
        out_shapes=out_shapes, scratch_shapes=scratch,
        interpret=interpret,
        dimension_semantics=semantics,
        buffer_depth=buffer_depth,
        name=name,
    )


def _probe_part_shape(fn: Callable, in_shapes: Sequence[Tuple[int, ...]],
                      dtype) -> Tuple[int, ...]:
    """Trace ``fn`` on abstract blocks to learn the partial's shape."""
    structs = [jax.ShapeDtypeStruct(s, dtype) for s in in_shapes]
    return tuple(jax.eval_shape(lambda *xs: fn(*xs), *structs).shape)


def _build_kernel(lowered: LoweredPlan, body: Callable, mode: str,
                  out_dtype, interpret: Optional[bool],
                  uniforms: Sequence[jax.ShapeDtypeStruct] = (),
                  name: Optional[str] = None) -> Callable:
    """Wrap a block-level ``body`` into a full ssr_pallas kernel.

    ``body(*stream_blocks, *uniform_arrays)`` — uniform refs trail the
    streamed inputs in ``in_refs``, so the pass-through read below hands
    the body exactly that order.
    """
    part_shape = None
    if mode == "reduce":
        part_shape = _probe_part_shape(
            body, [s.stream.block_shape for s in lowered.in_streams]
            + [tuple(u.shape) for u in uniforms],
            out_dtype)

    def compute(in_refs, _links):
        return body(*[r[...] for r in in_refs])

    return _assemble_kernel(lowered.grid, lowered.policy,
                            [s.stream for s in lowered.in_streams],
                            compute, 0, mode, out_dtype, part_shape,
                            interpret,
                            _depths_for(lowered.schedule,
                                        len(lowered.in_streams)),
                            uniforms=uniforms, name=name)


def _table_view_shape(shape: Tuple[int, ...]) -> Tuple[int, ...]:
    """The ≥2-D view a gather table occupies in VMEM (rank-1 gains a row)."""
    return shape if len(shape) >= 2 else (1,) + tuple(shape)


def _gather_block(pl, gather: IndirectGather, idx_block, table_ref):
    """Materialise one indirect ref's block from its VMEM-resident table.

    ``idx_block`` is the index stream's dense block for this grid step; each
    affine additive level of the gather prepends one block dimension (the
    level's tile extent), so the body sees a
    ``(tile_l0, ..., *idx_block.shape)`` gather result.
    """
    addr = idx_block.astype(jnp.int32) * gather.scale + gather.offset
    for p, tile, coeff in zip(reversed(gather.grid_pos),
                              reversed(gather.tiles),
                              reversed(gather.coeffs)):
        intra = jax.lax.broadcasted_iota(jnp.int32, (tile,) + addr.shape, 0)
        addr = addr[None] + coeff * (pl.program_id(p) * tile + intra)
    table = table_ref[...].reshape(-1)
    # Padded grid steps can address past the table; clip keeps them in
    # range — their products land only in trimmed output padding.
    return jnp.take(table, addr.reshape(-1), mode="clip").reshape(addr.shape)


def _halo_widen(parts, halo: HaloRead):
    """Stitch a HaloRead's ``2**k`` shifted blocks into one widened block.

    Pass ``j`` pairs blocks differing only in halo bit ``j`` (adjacent
    after earlier passes), concatenates each pair along that level's
    block axis and keeps the first ``tile + w - 1`` columns — the
    in-kernel slice taps of DESIGN.md §13.
    """
    for ax, t, w in zip(halo.axes, halo.tiles, halo.windows):
        nxt = []
        for m in range(0, len(parts), 2):
            cat = jnp.concatenate([parts[m], parts[m + 1]], axis=ax)
            nxt.append(jax.lax.slice_in_dim(cat, 0, t + w - 1, axis=ax))
        parts = nxt
    return parts[0]


def _build_nest_kernel(lowered: LoweredNest, body: Callable,
                       out_dtype, interpret: Optional[bool],
                       tables: Sequence[jax.ShapeDtypeStruct] = (),
                       name: Optional[str] = None) -> Callable:
    """Wrap a block-level ``body`` into a level-mapped ssr_pallas kernel.

    ``body(*read_blocks)`` returns the output block's partial for one grid
    step.  With contraction axes the partial accumulates into a VMEM
    scratch block: zeroed on the first visit of the contraction axes,
    drained to the write stream on the last — the paper's accumulator
    register at block granularity (GEMM's ``C += A·B`` k-walk).  Without
    contraction axes every step owns its output block and writes directly.

    With ``lowered.gathers``, ``tables`` carries one ShapeDtypeStruct per
    gather (the whole indirectly addressed operand, normalised to ≥2-D);
    each rides as a revisited invariant block and the body receives the
    gathered blocks appended after the streamed ones.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_in = len(lowered.in_streams)
    gathers = lowered.gathers
    assert len(tables) == len(gathers), "one table operand per gather"
    zaxes = lowered.contraction_axes
    acc_shape = lowered.out_stream.stream.block_shape

    # Halo refs arrive as 2**k shifted copies in in_streams ("raw" slots);
    # the body sees ONE widened block per ref, stitched in-kernel.
    halos = lowered.halos
    halo_at = {h.slots[0]: h for h in halos}
    halo_skip = {s for h in halos for s in h.slots[1:]}

    def _blocks(in_refs, tab_refs):
        raw = [r[...] for r in in_refs]
        blocks = []
        for k, b in enumerate(raw):
            if k in halo_skip:
                continue
            h = halo_at.get(k)
            blocks.append(b if h is None
                          else _halo_widen([raw[s] for s in h.slots], h))
        blocks += [_gather_block(pl, g, raw[g.index_pos], t)
                   for g, t in zip(gathers, tab_refs)]
        return blocks

    # The accumulator defaults to the f32 compute width (the MXU/VPU
    # accumulation dtype — the repo-wide policy), regardless of the storage
    # out_dtype: accumulating k-tile partials in bf16 would compound
    # rounding across grid steps.  The cast to out_dtype happens once, at
    # the drain.  The schedule may widen it (e.g. f64 on CPU interpret
    # runs) — a searched knob like the rest of the geometry.
    acc_dtype = jnp.dtype(lowered.schedule.acc_dtype)

    if lowered.rescale:
        # Online-rescaled accumulator (flash-attention recurrence): the
        # kernel carries a (max, sum, acc) triple in VMEM across the
        # contraction walk.  ``body(*blocks, offs)`` returns the raw score
        # block ``s`` (rows × contraction-tile, already scaled AND masked —
        # ``offs`` gives the per-level global offsets for the mask iotas)
        # and the value block ``v`` (contraction-tile × lanes); the kernel
        # owns the m/l rescaling:  m' = max(m, rowmax(s)); α = e^{m−m'};
        # l' = αl + Σ e^{s−m'}; acc' = α·acc + e^{s−m'}·v; drain acc/l.
        #
        # With a band, the contraction axis walks the band's tiles: step j
        # of outer block i is tile first(i) + j, and steps past last(i)
        # (whose block index the index maps clamp, so nothing is fetched)
        # leave the triple untouched — a clamped block is never accumulated
        # twice.  ``offs`` carries the tile actually visited.
        z = zaxes[0]
        d = len(lowered.tiles)
        pos_of = {lvl: k for k, lvl in enumerate(lowered.axis_order)}
        n_rows = acc_shape[0]
        band = lowered.band

        def kernel(*refs):
            in_refs = refs[:n_in]
            tab_refs = refs[n_in:n_in + len(gathers)]
            o_ref = refs[n_in + len(gathers)]
            m_ref = refs[n_in + len(gathers) + 1]
            l_ref = refs[n_in + len(gathers) + 2]
            acc_ref = refs[n_in + len(gathers) + 3]
            first = pl.program_id(z) == 0
            last = pl.program_id(z) == pl.num_programs(z) - 1

            @pl.when(first)
            def _init():
                m_ref[...] = jnp.full_like(m_ref, -1e30)
                l_ref[...] = jnp.zeros_like(l_ref)
                acc_ref[...] = jnp.zeros_like(acc_ref)

            offs = [pl.program_id(pos_of[l]) * lowered.tiles[l]
                    for l in range(d)]

            def _update():
                s, v = body(*_blocks(in_refs, tab_refs), tuple(offs))
                s = jnp.asarray(s, acc_dtype)
                m_prev = m_ref[...]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                          keepdims=True)
                acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                    p, jnp.asarray(v, acc_dtype),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=acc_dtype)
                m_ref[...] = m_new

            if band is None:
                _update()
            else:
                lo_t, hi_t = band.span(pl.program_id(band.outer_axis))
                tile = lo_t + pl.program_id(band.inner_axis)
                offs[band.inner_level] = tile * band.inner_tile
                pl.when(tile <= hi_t)(_update)

            @pl.when(last)
            def _drain():
                o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                              ).astype(o_ref.dtype)

        scratch = [pltpu.VMEM((n_rows, 1), acc_dtype),
                   pltpu.VMEM((n_rows, 1), acc_dtype),
                   pltpu.VMEM(acc_shape, acc_dtype)]
    elif zaxes:
        def kernel(*refs):
            in_refs = refs[:n_in]
            tab_refs = refs[n_in:n_in + len(gathers)]
            o_ref = refs[n_in + len(gathers)]
            acc_ref = refs[n_in + len(gathers) + 1]
            first = pl.program_id(zaxes[0]) == 0
            last = pl.program_id(zaxes[0]) == pl.num_programs(zaxes[0]) - 1
            for z in zaxes[1:]:
                first = jnp.logical_and(first, pl.program_id(z) == 0)
                last = jnp.logical_and(
                    last, pl.program_id(z) == pl.num_programs(z) - 1)

            @pl.when(first)
            def _init():
                acc_ref[...] = jnp.zeros_like(acc_ref)

            part = jnp.asarray(body(*_blocks(in_refs, tab_refs)), acc_dtype)
            acc_ref[...] += part.reshape(acc_shape)

            @pl.when(last)
            def _drain():
                o_ref[...] = acc_ref[...].astype(o_ref.dtype)

        scratch = [pltpu.VMEM(acc_shape, acc_dtype)]
    else:
        def kernel(*refs):
            in_refs = refs[:n_in]
            tab_refs = refs[n_in:n_in + len(gathers)]
            o_ref = refs[n_in + len(gathers)]
            o_ref[...] = jnp.asarray(
                body(*_blocks(in_refs, tab_refs)), out_dtype
            ).reshape(acc_shape)

        scratch = []

    # Gather tables ride whole as revisited invariant blocks: every grid
    # step sees the same (full) table, exactly like the flat path's
    # uniforms — the "dense index block in VMEM" of the indirection papers.
    table_streams = [
        BlockStream(tuple(t.shape), lambda *_g, _nd=len(t.shape): (0,) * _nd,
                    direction=Direction.READ, name=g.name)
        for g, t in zip(gathers, tables)]

    depths = _depths_for(lowered.schedule, len(lowered.in_streams))
    if gathers:
        if isinstance(depths, int):
            depths = (depths,) * len(lowered.in_streams)
        depths = tuple(depths) + (2,) * len(gathers)

    return ssr_pallas(
        kernel, grid=lowered.grid,
        in_streams=[s.stream for s in lowered.in_streams] + table_streams,
        out_streams=[lowered.out_stream.stream],
        out_shapes=[jax.ShapeDtypeStruct(lowered.out_stream.layout_shape,
                                         out_dtype)],
        scratch_shapes=scratch,
        interpret=interpret,
        dimension_semantics=lowered.semantics,
        # a band's K/V maps are affine between clamps, which the affine
        # probe cannot tell from a non-affine map; _lower_band derives them
        validate=lowered.band is None,
        buffer_depth=depths,
        name=name,
    )


def _trim_nest_output(out: jax.Array, lowered: LoweredNest) -> jax.Array:
    """Drop per-level padding; return the output's logical nd array."""
    ns = lowered.out_stream
    if not ns.levels:
        return out[0, 0]
    if len(ns.levels) == 1:
        return out[0, :ns.logical_shape[0]]
    return out[tuple(slice(0, e) for e in ns.logical_shape)]


def _chain_stage_shapes(lowered: LoweredChain, bodies: Sequence[Callable],
                        out_dtype,
                        require_final_block: bool = False) -> Tuple[int, ...]:
    """Shape-check every stage and return the final partial's shape.

    Each linked intermediate must fill exactly one policy block — that is
    the VMEM scratch the next stage reads.  ``require_final_block`` extends
    the check to the last stage (map mode, where the value feeds the dense
    write stream).
    """
    policy = lowered.policy
    cur: Any = None
    for k, stage in enumerate(lowered.stage_in_streams):
        ins = [jax.ShapeDtypeStruct(s.stream.block_shape, out_dtype)
               for s in stage]
        if k == 0:
            cur = jax.eval_shape(lambda *xs: bodies[0](*xs), *ins)
        else:
            carried = jax.ShapeDtypeStruct(policy.block_shape, out_dtype)
            cur = jax.eval_shape(
                lambda c, *xs, _b=bodies[k]: _b(c, *xs), carried, *ins)
        must_block = k < len(bodies) - 1 or require_final_block
        if must_block and math.prod(cur.shape) != policy.block_elems:
            what = ("a linked intermediate" if k < len(bodies) - 1
                    else "the map-mode output")
            raise LoweringError(
                f"chain stage {k} body returns shape {cur.shape} "
                f"({math.prod(cur.shape)} elements); {what} "
                f"must fill one {policy.block_shape} VMEM block")
    return tuple(cur.shape)


def _build_chain_kernel(lowered: LoweredChain, bodies: Sequence[Callable],
                        mode: str, out_dtype,
                        interpret: Optional[bool],
                        name: Optional[str] = None) -> Callable:
    """Fuse all stage bodies into ONE Pallas kernel.

    Per grid step: stage 0 computes its block from its read streams; each
    intermediate is written to a VMEM scratch block (never HBM) and read
    back by the next stage's body the same step; the final stage's value
    feeds the usual map/reduce epilogue.
    """
    policy = lowered.policy
    counts = [len(stage) for stage in lowered.stage_in_streams]
    offsets = [0]
    for c in counts[:-1]:
        offsets.append(offsets[-1] + c)
    n_links = len(lowered.chained.links)

    final_shape = _chain_stage_shapes(lowered, bodies, out_dtype,
                                      require_final_block=(mode == "map"))
    part_shape = final_shape if mode == "reduce" else None

    def compute(in_refs, link_refs):
        vals = [r[...] for r in in_refs[:counts[0]]]
        cur = bodies[0](*vals)
        for k in range(1, len(bodies)):
            s_ref = link_refs[k - 1]
            s_ref[...] = jnp.asarray(cur, out_dtype).reshape(
                policy.block_shape)
            args = [r[...] for r in
                    in_refs[offsets[k]:offsets[k] + counts[k]]]
            cur = bodies[k](s_ref[...], *args)
        return cur

    return _assemble_kernel(lowered.grid, policy,
                            [s.stream for s in lowered.in_streams],
                            compute, n_links, mode, out_dtype, part_shape,
                            interpret,
                            _depths_for(lowered.schedule,
                                        len(lowered.in_streams)),
                            name=name)


def _dag_slots(dag: ChainDAG) -> Tuple[Dict[str, int], int]:
    """Refcounted VMEM scratch-slot assignment for a ChainDAG's values.

    Walking the stages in (topological) order: a produced block takes a
    slot; once its *last* consumer stage has read it the slot returns to
    the free list and the next producer reuses it.  Reuse within one stage
    is safe because bodies receive block *values* (``ref[...]`` copies) —
    every read of a dying slot happens before the producing write.  Returns
    ``(slot_of, n_slots)``: the per-intermediate slot index and the peak
    number of live blocks (what the kernel actually allocates — a diamond
    needs 2 slots, not one per edge).
    """
    slot_of: Dict[str, int] = {}
    free: list = []
    n_slots = 0
    n = len(dag.stages)
    for k in range(n):
        for name in sorted({e.name for e in dag.in_edges(k)}):
            if dag.last_consumer(name) == k:
                free.append(slot_of[name])
        if k == n - 1:
            continue        # the final value exits via the call epilogue
        produced = sorted({e.name for e in dag.out_edges(k)})
        if len(produced) != 1:
            raise LoweringError(
                f"dag stage {k} produces intermediates {produced}; a stage "
                "body returns one block, so each non-final stage must "
                "write exactly one intermediate")
        if free:
            slot_of[produced[0]] = free.pop()
        else:
            slot_of[produced[0]] = n_slots
            n_slots += 1
    return slot_of, n_slots


def _dag_stage_shapes(lowered: LoweredChain, bodies: Sequence[Callable],
                      out_dtype, require_final_block: bool = False,
                      uniforms: Sequence[jax.ShapeDtypeStruct] = ()
                      ) -> Tuple[int, ...]:
    """Shape-check every DAG stage and return the final partial's shape.

    Stage ``k``'s body receives one carried block per incoming edge (in
    ``ChainDAG.in_edges`` order) followed by its external stream blocks,
    then every uniform array; every non-final stage must return exactly
    one policy block — the VMEM scratch its consumers read.
    """
    policy = lowered.policy
    dag = lowered.chained
    cur: Any = None
    for k, stage in enumerate(lowered.stage_in_streams):
        carried = [jax.ShapeDtypeStruct(policy.block_shape, out_dtype)
                   for _ in dag.in_edges(k)]
        ins = [jax.ShapeDtypeStruct(s.stream.block_shape, out_dtype)
               for s in stage]
        cur = jax.eval_shape(lambda *xs, _b=bodies[k]: _b(*xs),
                             *carried, *ins, *uniforms)
        must_block = k < len(bodies) - 1 or require_final_block
        if must_block and math.prod(cur.shape) != policy.block_elems:
            what = ("a dag intermediate" if k < len(bodies) - 1
                    else "the map-mode output")
            raise LoweringError(
                f"dag stage {k} body returns shape {cur.shape} "
                f"({math.prod(cur.shape)} elements); {what} "
                f"must fill one {policy.block_shape} VMEM block")
    return tuple(cur.shape)


def _build_dag_kernel(lowered: LoweredChain, bodies: Sequence[Callable],
                      mode: str, out_dtype, interpret: Optional[bool],
                      uniforms: Sequence[jax.ShapeDtypeStruct] = ()
                      ) -> Callable:
    """Fuse a ChainDAG's stage bodies into ONE Pallas kernel.

    Topologically-ordered stage emission: per grid step each stage reads
    its carried blocks from the refcounted VMEM scratch slots
    (:func:`_dag_slots`), computes, and (when non-final) writes its product
    block into its own slot.  A multi-consumer intermediate is written once
    and read by every consumer stage from the same slot — the store and all
    K loads that an unfused composition would pay never touch HBM.
    """
    policy = lowered.policy
    dag = lowered.chained
    counts = [len(stage) for stage in lowered.stage_in_streams]
    offsets = [0]
    for c in counts[:-1]:
        offsets.append(offsets[-1] + c)
    slot_of, n_slots = _dag_slots(dag)
    n_stream = len(lowered.in_streams)

    final_shape = _dag_stage_shapes(lowered, bodies, out_dtype,
                                    require_final_block=(mode == "map"),
                                    uniforms=uniforms)
    part_shape = final_shape if mode == "reduce" else None

    def compute(in_refs, link_refs):
        uni = [r[...] for r in in_refs[n_stream:]]
        cur: Any = None
        for k in range(len(bodies)):
            carried = [link_refs[slot_of[e.name]][...]
                       for e in dag.in_edges(k)]
            ext = [r[...] for r in
                   in_refs[offsets[k]:offsets[k] + counts[k]]]
            cur = bodies[k](*carried, *ext, *uni)
            if k < len(bodies) - 1:
                name = dag.out_edges(k)[0].name
                link_refs[slot_of[name]][...] = jnp.asarray(
                    cur, out_dtype).reshape(policy.block_shape)
        return cur

    return _assemble_kernel(lowered.grid, policy,
                            [s.stream for s in lowered.in_streams],
                            compute, n_slots, mode, out_dtype, part_shape,
                            interpret,
                            _depths_for(lowered.schedule,
                                        len(lowered.in_streams)),
                            uniforms=uniforms)


def _uniform_items(uniforms: Optional[Dict[str, jax.Array]]
                   ) -> Tuple[Tuple[str, jax.Array], ...]:
    """Normalise a uniforms dict to ``((name, array), ...)`` in dict order.

    1-D arrays gain a leading singleton (Pallas blocks are at least 2-D);
    scalars are rejected — a Python float in the body's closure is already
    hashable, cacheable, and free.
    """
    if not uniforms:
        return ()
    items = []
    for name, arr in uniforms.items():
        a = jnp.asarray(arr)
        if a.ndim == 0:
            raise ValueError(
                f"uniform {name!r} is a scalar; close over the Python "
                "value instead — scalar closures hash and cache fine")
        if a.ndim == 1:
            a = a.reshape(1, -1)
        items.append((name, a))
    return tuple(items)


def _uniform_sig(items: Tuple[Tuple[str, jax.Array], ...]) -> Tuple:
    return tuple((nm, tuple(a.shape), str(a.dtype)) for nm, a in items)


def ssr_call(nest: LoopNest, body: Callable[..., jax.Array],
             operands: Dict[str, jax.Array], *,
             mode: str = "reduce",
             out_dtype=jnp.float32,
             policy: BlockPolicy = DEFAULT_POLICY,
             schedule: Optional[Schedule] = None,
             num_lanes: Optional[int] = None,
             interpret: Optional[bool] = None,
             uniforms: Optional[Dict[str, jax.Array]] = None,
             name: Optional[str] = None) -> jax.Array:
    """Execute a :class:`LoopNest` as a streamed Pallas kernel.

    ``body(*blocks)`` is the pure compute region: it receives one VMEM block
    per allocated read stream (in allocation order — deepest-first, i.e. the
    order ``plan.allocations`` lists them) and returns

    * ``mode="reduce"`` — a partial accumulated across all grid steps (the
      Fig. 4 ``%x`` accumulator register).  A *block-shaped* partial (e.g.
      ``lambda a, b: a * b``) uses a vectorised (rows, lanes) accumulator
      folded to the scalar once on the last step — the whole VPU vreg adds
      every step; a scalar partial (e.g. ``jnp.sum(a * b)``) keeps the
      legacy (1, 1) accumulator;
    * ``mode="map"`` — one output block, written to a dense write stream
      walking the grid (the output AGU); the result is trimmed to the
      nest's iteration count.

    A nest with an explicit output WRITE ref (e.g. :func:`compiler.gemm_nest`)
    takes the **level-mapped** path instead: ``mode`` is ignored, the body
    returns one output-block partial per grid step, contraction axes
    accumulate in VMEM (see :func:`lower_nest`), and the result comes back
    in the output ref's logical nd shape (``(m, n)`` for GEMM).

    ``operands`` maps :class:`MemRef` names to arrays.  Zero padding is
    applied per stream, so bodies must be padding-neutral for ``reduce``
    and for contraction axes (sum/dot-style bodies are).  Plans are cached
    on the nest signature, built kernels on (nest, schedule, mode, body
    key, dtypes, interpret) — see :func:`_body_key`: inline lambdas hit
    the cache as long as their closure values are hashable and equal.

    **Zero-overhead dispatch**: prepare (pad/reshape) → engine → trim fuse
    into ONE cached jitted callable, so the padding traffic compiles into
    the same XLA program as the Pallas kernel instead of dispatching
    eagerly per call.  A repeated call with the same (nest, schedule,
    shapes, body) is a dict hit plus one jitted-function invocation.

    **Transparent tuning**: with no explicit ``schedule`` (and the default
    ``policy``), the autotuner's persistent cache is consulted — every
    entry point (direct ``ssr_call``, ``NestKernel``, ``cluster_call``)
    resolves the same winner for the same problem, so they stay
    bit-identical to each other before and after a tuner commit.

    ``name`` is the kernel's family (``"gemm"``): the built pipeline
    compiles as ``jit_ssr_<name>`` around the Pallas kernel ``ssr_<name>``.
    The schedule resolution runs in the profiler span ``ssr.lookup``, the
    planning, lowering and kernel emission in ``ssr.lower``.
    """
    tuned_key: Optional[str] = None
    source = "explicit"
    if schedule is None and policy is DEFAULT_POLICY:
        from . import autotune as _autotune

        try:
            with TraceAnnotation("ssr.lookup"):
                schedule, source = _autotune.resolve(
                    nest, operands, mode=mode,
                    out_dtype=str(jnp.dtype(out_dtype)))
        except resilience.fallback_error_types() as e:
            _record_fallback("ssr_call", e, from_schedule="tuned-lookup",
                             to_schedule="default")
            schedule = DEFAULT_SCHEDULE
        else:
            if source == "tuned" and schedule != DEFAULT_SCHEDULE:
                tuned_key = _autotune.cache_key(
                    nest, operands, mode=mode,
                    out_dtype=str(jnp.dtype(out_dtype)))
    num_lanes = nest_analysis.auto_lanes(nest, num_lanes)
    with TraceAnnotation("ssr.lower"):
        plan = _plan_for(nest, num_lanes)
    has_output = any(r.kind == Direction.WRITE for r in nest.refs)
    if has_output:
        mode = "nest"          # the output ref, not the mode, shapes the call
    uni = _uniform_items(uniforms)
    if uni and has_output:
        raise LoweringError(
            "uniform operands are not supported on the level-mapped "
            "(explicit WRITE ref) path; use a map/reduce nest")
    DISPATCH_STATS["calls"] += 1

    def _kernel(sched: Schedule):
        """The cached or newly built pipeline, and its operands."""
        lowered = _lowered_for(plan, sched, has_output)
        gathers = lowered.gathers if has_output else ()
        missing = [s.name for s in lowered.in_streams
                   if s.name not in operands]
        missing += [g.name for g in gathers if g.name not in operands]
        if missing:
            raise ValueError(f"missing operands for streams {missing}")
        arrays = [operands[s.name] for s in lowered.in_streams]
        # Gather tables travel after the streamed operands, normalised to
        # the ≥2-D VMEM view their invariant block addresses.
        tables = [jnp.reshape(operands[g.name],
                              _table_view_shape(tuple(operands[g.name]
                                                      .shape)))
                  for g in gathers]
        args = (*arrays, *tables, *[a for _, a in uni])
        key = (nest, sched, mode, _body_key(body), str(jnp.dtype(out_dtype)),
               tuple((tuple(a.shape), str(a.dtype)) for a in arrays + tables),
               _uniform_sig(uni), num_lanes, interpret, name)
        fn = _kernel_cache_get(key)
        if fn is not None:
            return fn, args
        if has_output:
            kernel = _build_nest_kernel(
                lowered, body, jnp.dtype(out_dtype), interpret,
                tables=tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                             for a in tables), name=name)
        else:
            kernel = _build_kernel(
                lowered, body, mode, jnp.dtype(out_dtype), interpret,
                uniforms=tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                               for _, a in uni), name=name)

        def pipeline(*arrs, _lowered=lowered, _kernel=kernel,
                     _sched=sched):
            DISPATCH_STATS["traces"] += 1   # moves only while tracing
            ns = len(_lowered.in_streams)
            prepared = [s.prepare(a)
                        for s, a in zip(_lowered.in_streams, arrs[:ns])]
            out = _kernel(*prepared, *arrs[ns:])
            if has_output:
                return _trim_nest_output(out, _lowered)
            return _trim_output(out, nest.bounds, mode, _sched.policy)

        resilience.inject("compile")
        fn = jax.jit(_named(pipeline, name and f"ssr_{name}"))
        DISPATCH_STATS["builds"] += 1
        DISPATCH_STATS["pipelined"] += int(kernel.pipelined)
        if has_output and lowered.rescale:
            DISPATCH_STATS["band_tiles"] += lowered.visited_steps
            DISPATCH_STATS["rect_tiles"] += lowered.rect_steps
        _kernel_cache_put(key, fn)
        return fn, args

    def _dispatch(sched: Schedule) -> jax.Array:
        with TraceAnnotation("ssr.lower"):
            fn, args = _kernel(sched)
        return fn(*args)

    if tuned_key is None and source != "traffic":
        return _dispatch(_resolve_schedule(policy, schedule))
    try:
        return _dispatch(_resolve_schedule(policy, schedule))
    except resilience.fallback_error_types() as e:
        # a tuned entry is quarantined; a traffic-chosen schedule has none
        if tuned_key is not None:
            from . import autotune as _autotune

            _autotune.global_cache().quarantine(tuned_key)
        _record_fallback("ssr_call", e, from_schedule=source,
                         to_schedule="default", key=tuned_key,
                         counter="degraded")
        return _dispatch(DEFAULT_SCHEDULE)


def _trim_output(out: jax.Array, bounds: Tuple[int, ...], mode: str,
                 policy: BlockPolicy) -> jax.Array:
    if mode == "reduce":
        return out[0, 0]
    # map: drop the inner-level padding (it interleaves for d > 1 nests),
    # then flatten back to one value per nest iteration.
    padded_inner = _inner_steps_of(bounds, policy) * policy.block_elems
    out_nd = out.reshape(*bounds[:-1], padded_inner)
    return out_nd[..., :bounds[-1]].reshape(-1)


def ssr_chain_call(nests: Sequence[LoopNest],
                   bodies: Sequence[Callable[..., jax.Array]],
                   operands: Dict[str, jax.Array], *,
                   mode: str = "map",
                   out_dtype=jnp.float32,
                   policy: BlockPolicy = DEFAULT_POLICY,
                   schedule: Optional[Schedule] = None,
                   num_lanes: Optional[int] = None,
                   interpret: Optional[bool] = None,
                   name: Optional[str] = None) -> jax.Array:
    """Execute a producer→consumer chain of nests as ONE Pallas kernel.

    ``nests[k]`` and ``nests[k+1]`` must be chainable (see
    :func:`repro.core.compiler.chain`): the producer's WRITE ref unifies
    with the consumer's READ ref over one shared iteration space.  The
    intermediates live in VMEM scratch blocks — they are never stored to
    (or re-loaded from) HBM, which is the whole point.

    ``bodies[0](*stage0_blocks)`` computes the first intermediate;
    ``bodies[k](carried_block, *stagek_blocks)`` receives the previous
    stage's block first.  ``mode`` applies to the *final* stage, with the
    same contract as :func:`ssr_call` — including the vectorised reduce
    accumulator when the last body returns a block-shaped partial.  Reduce
    bodies must be padding-neutral at every stage: the padded tail flows
    through the whole chain.  ``name`` and the profiler spans as in
    :func:`ssr_call`.
    """
    nests = tuple(nests)
    bodies = tuple(bodies)
    if len(bodies) != len(nests):
        raise ValueError(
            f"need one body per nest, got {len(bodies)} bodies for "
            f"{len(nests)} nests")
    tuned_key: Optional[str] = None
    if schedule is None and policy is DEFAULT_POLICY:
        # chains key on their stage-0 nest + the full operand signature,
        # matching the cluster layer's per-core lookup convention
        from . import autotune as _autotune

        try:
            with TraceAnnotation("ssr.lookup"):
                schedule, source = _autotune.resolve(
                    nests[0], operands, mode=mode,
                    out_dtype=str(jnp.dtype(out_dtype)))
            if source == "traffic":       # tiles of one nest, not a chain's
                schedule = DEFAULT_SCHEDULE
        except resilience.fallback_error_types() as e:
            _record_fallback("ssr_chain_call", e,
                             from_schedule="tuned-lookup",
                             to_schedule="default")
            schedule = DEFAULT_SCHEDULE
        else:
            if schedule != DEFAULT_SCHEDULE:
                tuned_key = _autotune.cache_key(
                    nests[0], operands, mode=mode,
                    out_dtype=str(jnp.dtype(out_dtype)))
    with TraceAnnotation("ssr.lower"):
        chained = _chain_for(nests, num_lanes)
    DISPATCH_STATS["calls"] += 1

    def _kernel(sched: Schedule):
        """The cached or newly built pipeline, and its operands."""
        lowered = _lowered_chain_for(chained, sched)
        flat = lowered.in_streams
        missing = sorted({s.name for s in flat} - set(operands))
        if missing:
            raise ValueError(f"missing operands for streams {missing}")
        arrays = [operands[s.name] for s in flat]
        key = ("chain", nests, sched, mode,
               tuple(_body_key(b) for b in bodies), str(jnp.dtype(out_dtype)),
               tuple((tuple(a.shape), str(a.dtype)) for a in arrays),
               num_lanes, interpret, name)
        fn = _kernel_cache_get(key)
        if fn is not None:
            return fn, arrays
        kernel = _build_chain_kernel(lowered, bodies, mode,
                                     jnp.dtype(out_dtype), interpret,
                                     name=name)

        def pipeline(*arrs, _lowered=lowered, _kernel=kernel,
                     _sched=sched):
            DISPATCH_STATS["traces"] += 1   # moves only while tracing
            prepared = [s.prepare(a)
                        for s, a in zip(_lowered.in_streams, arrs)]
            out = _kernel(*prepared)
            return _trim_output(out, chained.bounds, mode, _sched.policy)

        resilience.inject("compile")
        fn = jax.jit(_named(pipeline, name and f"ssr_{name}"))
        DISPATCH_STATS["builds"] += 1
        _kernel_cache_put(key, fn)
        return fn, arrays

    def _dispatch(sched: Schedule) -> jax.Array:
        with TraceAnnotation("ssr.lower"):
            fn, arrays = _kernel(sched)
        return fn(*arrays)

    if tuned_key is None:
        return _dispatch(_resolve_schedule(policy, schedule))
    try:
        return _dispatch(_resolve_schedule(policy, schedule))
    except resilience.fallback_error_types() as e:
        from . import autotune as _autotune

        _autotune.global_cache().quarantine(tuned_key)
        _record_fallback("ssr_chain_call", e, from_schedule="tuned",
                         to_schedule="default", key=tuned_key,
                         counter="degraded")
        return _dispatch(DEFAULT_SCHEDULE)


def _dag_components(dag: ChainDAG,
                    cut: frozenset) -> Tuple[Tuple[int, ...], ...]:
    """Connected stage components over the *non-cut* edges, ordered by
    their maximum stage index — a valid topological order of the
    partition (every cut edge points from one component's exit to a
    higher-indexed stage of a later component)."""
    parent = list(range(len(dag.stages)))

    def find(x: int) -> int:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for i, e in enumerate(dag.edges):
        if i not in cut:
            parent[find(e.producer_stage)] = find(e.consumer_stage)
    groups: Dict[int, list] = {}
    for s in range(len(dag.stages)):
        groups.setdefault(find(s), []).append(s)
    return tuple(sorted((tuple(sorted(g)) for g in groups.values()),
                        key=max))


def _component_exit(dag: ChainDAG, comp: Tuple[int, ...],
                    cut: frozenset) -> int:
    """The component's unique exit stage — the one whose value leaves.

    A value leaves through a cut out-edge or by being the whole DAG's
    final stage; a legal cut gives every component exactly one such stage
    (otherwise more than one HBM buffer would have to exit a single fused
    kernel, which the map/reduce epilogue cannot express).
    """
    inside = set(comp)
    exits = set()
    for i, e in enumerate(dag.edges):
        if i in cut and e.producer_stage in inside:
            exits.add(e.producer_stage)
    if len(dag.stages) - 1 in inside:
        exits.add(len(dag.stages) - 1)
    if len(exits) != 1:
        raise LoweringError(
            f"cut {tuple(sorted(cut))} gives component {comp} exit stages "
            f"{sorted(exits)}; a legal cut leaves each fused component "
            "exactly one stage whose value exits the kernel")
    return exits.pop()


def _reorder_body(body: Callable, callee_names: Sequence[str],
                  want_names: Sequence[str], where: str) -> Callable:
    """Adapt a DAG-convention body to a callee's block-argument order.

    ``callee_names`` is the order the executing kernel passes blocks in;
    ``want_names`` is the order ``body`` expects (incoming-edge blocks in
    ``in_edges`` order, then the stage's external streams in allocation
    order).  Cut edges turn carried blocks into external operand streams,
    so the two orders differ per partition — the adapter permutes by name.
    """
    pos = {nm: i for i, nm in enumerate(callee_names)}
    missing = [nm for nm in want_names if nm not in pos]
    if missing or len(callee_names) != len(want_names):
        raise LoweringError(
            f"{where}: body expects blocks {list(want_names)} but the "
            f"partitioned kernel streams {list(callee_names)}")
    perm = tuple(pos[nm] for nm in want_names)
    if perm == tuple(range(len(perm))):
        return body
    # trailing args beyond the named blocks (uniform arrays, appended
    # after every stage's streams) pass through unpermuted
    return lambda *blocks, _b=body, _p=perm: _b(
        *(blocks[i] for i in _p), *blocks[len(_p):])


def _stage_arg_names(dag: ChainDAG, k: int) -> list:
    """Stage ``k``'s body-argument names in the fused-DAG convention."""
    return ([e.name for e in dag.in_edges(k)]
            + [a.ref.name for a in dag.stages[k].allocations])


def _dag_partition_call(dag: ChainDAG, nests: Tuple[LoopNest, ...],
                        bodies: Tuple[Callable, ...],
                        operands: Dict[str, jax.Array], sched: Schedule, *,
                        mode: str, out_dtype, num_lanes: Optional[int],
                        interpret: Optional[bool],
                        uniforms: Optional[Dict[str, jax.Array]] = None
                        ) -> jax.Array:
    """Execute a ChainDAG under a committed cut: one kernel per component.

    Each cut edge materialises its intermediate as a flat HBM array (a
    map-mode output) that downstream components stream back in as a plain
    dense operand; within a component the DAG fuses as usual.  Components
    run in topological order, so by the time one launches every cut value
    it reads already exists.
    """
    cut = frozenset(sched.cut_edges or ())
    for i in cut:
        if not 0 <= i < len(dag.edges):
            raise LoweringError(
                f"schedule.cut_edges index {i} is out of range for a dag "
                f"with {len(dag.edges)} edges")
    # Sub-calls are separate kernels with their own stream counts; the
    # committed geometry/depth carries over, the partition fields do not.
    sub_sched = dataclasses.replace(sched, cut_edges=None,
                                    stream_depths=None)
    env = dict(operands)
    n = len(nests)
    result: Optional[jax.Array] = None
    for comp in _dag_components(dag, cut):
        exit_stage = _component_exit(dag, comp, cut)
        final = (n - 1) in comp
        comp_mode = mode if final else "map"
        exported = (None if final
                    else dag.out_edges(exit_stage)[0].name)
        sub_nests = []
        for s in comp:
            nest = nests[s]
            if exported is not None and s == exit_stage:
                refs = tuple(r for r in nest.refs
                             if not (r.kind == Direction.WRITE
                                     and r.name == exported))
                nest = dataclasses.replace(nest, refs=refs)
            sub_nests.append(nest)
        if len(comp) == 1:
            s = comp[0]
            nest = sub_nests[0]
            lanes = nest_analysis.auto_lanes(nest, num_lanes)
            lowered = _lowered_for(_plan_for(nest, lanes), sub_sched, False)
            body = _reorder_body(
                bodies[s], [st.name for st in lowered.in_streams],
                _stage_arg_names(dag, s), f"dag stage {s}")
            result = ssr_call(nest, body, env, mode=comp_mode,
                              out_dtype=out_dtype, schedule=sub_sched,
                              num_lanes=num_lanes, interpret=interpret,
                              uniforms=uniforms)
        else:
            sub_dag = _dag_for(tuple(sub_nests), num_lanes)
            sub_bodies = []
            for j, s in enumerate(comp):
                callee = ([e.name for e in sub_dag.in_edges(j)]
                          + [st.name for st in
                             _lowered_chain_for(sub_dag,
                                                sub_sched)
                             .stage_in_streams[j]])
                sub_bodies.append(_reorder_body(
                    bodies[s], callee, _stage_arg_names(dag, s),
                    f"dag stage {s}"))
            result = ssr_dag_call(tuple(sub_nests), tuple(sub_bodies), env,
                                  mode=comp_mode, out_dtype=out_dtype,
                                  schedule=sub_sched, num_lanes=num_lanes,
                                  interpret=interpret, uniforms=uniforms)
        if exported is not None:
            env[exported] = result
    assert result is not None
    return result


def ssr_dag_call(nests: Sequence[LoopNest],
                 bodies: Sequence[Callable[..., jax.Array]],
                 operands: Dict[str, jax.Array], *,
                 mode: str = "map",
                 out_dtype=jnp.float32,
                 policy: BlockPolicy = DEFAULT_POLICY,
                 schedule: Optional[Schedule] = None,
                 num_lanes: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 uniforms: Optional[Dict[str, jax.Array]] = None
                 ) -> jax.Array:
    """Execute a DAG of nests — diamonds included — as ONE Pallas kernel.

    Dataflow is discovered by name (see :func:`repro.core.compiler.chain_dag`):
    a ref WRITTEN by stage p and READ by any later stages becomes VMEM-
    carried edges; one producer may feed several consumers.  The carried
    intermediates live in refcounted VMEM scratch slots and never touch
    HBM.

    ``bodies[k]`` receives stage ``k``'s *incoming-edge blocks first* (in
    ``ChainDAG.in_edges`` order: sorted by producer stage, then name),
    followed by its external stream blocks in allocation order, then every
    ``uniforms`` array (in dict order).  Uniforms are whole arrays — MLP
    weights, lookup tables — delivered to the kernel as one loop-invariant
    block each and appended to EVERY stage body's arguments; Pallas
    forbids kernels closing over array constants, and block streams can't
    carry an operand that every grid step needs in full.  ``mode`` applies
    to the final stage with the :func:`ssr_call` contract; reduce bodies
    must be padding-neutral at every stage.

    **Transparent partitioning**: with no explicit ``schedule`` (and the
    default ``policy``) the autotuner's cache is consulted under the DAG's
    own key; a committed ``Schedule.cut_edges`` from the fusion search
    (``autotune.autotune_dag``) splits the graph into several kernels with
    the cut intermediates materialised in HBM — the cost model and
    measurements decide where fusion stops paying, dispatch just follows.
    """
    nests = tuple(nests)
    bodies = tuple(bodies)
    if len(bodies) != len(nests):
        raise ValueError(
            f"need one body per nest, got {len(bodies)} bodies for "
            f"{len(nests)} nests")
    dag = _dag_for(nests, num_lanes)
    uni = _uniform_items(uniforms)
    if uni:
        clash = sorted({nm for nm, _ in uni} & set(operands))
        if clash:
            raise ValueError(
                f"uniform names {clash} collide with streamed operands; "
                "uniforms are a separate argument namespace")
    tuned_key: Optional[str] = None
    if schedule is None and policy is DEFAULT_POLICY:
        from . import autotune as _autotune

        try:
            schedule = _autotune.lookup_dag(
                nests, operands, mode=mode,
                out_dtype=str(jnp.dtype(out_dtype)), uniforms=dict(uni))
        except resilience.fallback_error_types() as e:
            _record_fallback("ssr_dag_call", e, from_schedule="tuned-lookup",
                             to_schedule="default")
            schedule = DEFAULT_SCHEDULE
        else:
            if schedule != DEFAULT_SCHEDULE:
                tuned_key = _autotune.dag_cache_key(
                    nests, operands, mode=mode,
                    out_dtype=str(jnp.dtype(out_dtype)), uniforms=dict(uni))

    def _dispatch(resolved: Schedule) -> jax.Array:
        sched = resolved
        if sched.cut_edges:
            return _dag_partition_call(dag, nests, bodies, operands, sched,
                                       mode=mode, out_dtype=out_dtype,
                                       num_lanes=num_lanes,
                                       interpret=interpret,
                                       uniforms=dict(uni))
        if sched.cut_edges is not None:  # () — all-fused, same kernel as None
            sched = dataclasses.replace(sched, cut_edges=None)
        lowered = _lowered_chain_for(dag, sched)
        flat = lowered.in_streams
        missing = sorted({s.name for s in flat} - set(operands))
        if missing:
            raise ValueError(f"missing operands for streams {missing}")
        arrays = [operands[s.name] for s in flat]

        DISPATCH_STATS["calls"] += 1
        key = ("dag", nests, sched, mode,
               tuple(_body_key(b) for b in bodies), str(jnp.dtype(out_dtype)),
               tuple((tuple(a.shape), str(a.dtype)) for a in arrays),
               _uniform_sig(uni), num_lanes, interpret)
        fn = _kernel_cache_get(key)
        if fn is None:
            kernel = _build_dag_kernel(
                lowered, bodies, mode, jnp.dtype(out_dtype), interpret,
                uniforms=tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                               for _, a in uni))

            def pipeline(*arrs, _lowered=lowered, _kernel=kernel,
                         _sched=sched):
                DISPATCH_STATS["traces"] += 1   # moves only while tracing
                ns = len(_lowered.in_streams)
                prepared = [s.prepare(a)
                            for s, a in zip(_lowered.in_streams, arrs[:ns])]
                out = _kernel(*prepared, *arrs[ns:])
                return _trim_output(out, dag.bounds, mode, _sched.policy)

            resilience.inject("compile")
            fn = jax.jit(pipeline)
            DISPATCH_STATS["builds"] += 1
            _kernel_cache_put(key, fn)
        return fn(*arrays, *[a for _, a in uni])

    if tuned_key is None:
        return _dispatch(_resolve_schedule(policy, schedule))
    try:
        return _dispatch(_resolve_schedule(policy, schedule))
    except resilience.fallback_error_types() as e:
        from . import autotune as _autotune

        _autotune.global_cache().quarantine(tuned_key)
        _record_fallback("ssr_dag_call", e, from_schedule="tuned",
                         to_schedule="default", key=tuned_key,
                         counter="degraded")
        return _dispatch(DEFAULT_SCHEDULE)
