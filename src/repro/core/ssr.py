"""``ssr_pallas`` — lower stream-semantic operands to a Pallas TPU kernel.

This is the TPU-native embodiment of the SSR extension.  The correspondence
(DESIGN.md §2):

* **stream register**  → a kernel ``Ref`` whose delivery schedule is owned by
  the framework.  The compute body reads/writes whole blocks with *zero*
  address arithmetic — the invariant the paper buys with its register-file
  wrapper.
* **AGU (bound/stride/repeat)** → the Pallas ``grid`` plus an affine
  ``index_map``.  We *verify* affinity (``agu.affine_coefficients``): a
  schedule the paper's AGU could not generate is rejected.
* **data mover + FIFO prefetch** → Pallas's double-buffered HBM→VMEM DMA
  pipeline.  Block ``i+1`` is fetched while block ``i`` computes, exactly the
  "proactively performs memory reads" behaviour of §2.3.  The FIFO depth is
  a *schedule knob* (``Schedule.buffer_depth``): depth 2 is Pallas's own
  pipeline; depth > 2 emits an explicit N-deep rotation of VMEM scratch
  buffers driven by ``make_async_copy`` DMAs, prefetching grid step
  ``i+N−1`` while step ``i`` computes (see :func:`ssr_pallas`'s
  ``buffer_depth``).  ``pltpu.emit_pipeline`` is *not* the emitter because
  it exposes no buffer-depth knob — the rotation is hand-rolled so the
  depth is actually honoured.
* **repeat register** → an ``index_map`` that revisits the same block across
  consecutive grid steps (e.g. a GEMM A-panel reused for every N-tile); the
  pipeline recognises the unchanged index and skips the re-fetch, as the FIFO
  re-emits a datum.
* **ssrcfg CSR** → ``region.ssr_enabled()``: modules pick streamed kernels or
  plain XLA ops; semantics are identical either way (tested).

Word- vs block-granularity is the deliberate hardware adaptation: a TPU
"word" for streaming purposes is a VMEM tile (the MXU consumes 128×128
operand panels; the VPU (8,128) vregs), so ``block_shape`` plays the role of
the stream's element width.  All *structural* properties — affine pattern,
run-ahead prefetch, read/write exclusivity, no address math in the body —
are preserved.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
import os
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import agu
from .stream import Direction

# TPU v5e VMEM is 128 MiB/core; we budget conservatively for double buffering.
VMEM_BUDGET_BYTES = 64 * 1024 * 1024
#: Mosaic's default scoped-VMEM limit on v5e.  A kernel whose working set
#: fits under it declares no limit and compiles as Mosaic chooses.
MOSAIC_DEFAULT_VMEM_BYTES = 16 * 1024 * 1024
#: The most a kernel declares: v5e's 128 MiB less room for Mosaic's own
#: internal scratch.
VMEM_LIMIT_CAP_BYTES = 112 * 1024 * 1024
_LANE = 128
_SUBLANE = {4: 8, 2: 16, 1: 32}  # min sublane tile per dtype byte width

#: The data mover's FIFO depth bounds.  Depth 2 is the classic
#: double-buffered pipeline (Pallas's own); deeper buffering trades VMEM
#: for DMA-latency run-ahead.  ``MAX_BUFFER_DEPTH`` bounds the schedule
#: search and keeps a runaway depth from eating the whole VMEM budget.
DEFAULT_BUFFER_DEPTH = 2
MAX_BUFFER_DEPTH = 8


def stream_vmem_bytes(block_bytes: int,
                      depth: int = DEFAULT_BUFFER_DEPTH) -> int:
    """VMEM footprint of one stream's in-flight blocks: ``depth`` buffers.

    THE single source of truth for the per-stream working-set budget —
    :meth:`StreamReport` (here) and ``core/autotune.py``'s legality check
    both call it, so the depth-aware accounting cannot drift between the
    executor and the search.  Conservative by design: loop-invariant
    streams rotate through one slot at run time but are still budgeted at
    full depth.
    """
    return depth * block_bytes


def pipeline_supported() -> bool:
    """Whether the explicit N-deep DMA rotation can be emitted here.

    The rotation needs the Pallas TPU primitives (``make_async_copy``,
    DMA semaphores, VMEM scratch) and the ANY memory space (``pl.ANY``) —
    available on TPU *and* in interpret mode.  Only an explicit
    ``REPRO_DISABLE_PIPELINE`` opt-out falls back to the synchronous
    (depth-2 Pallas pipeline) path; semantics are identical.
    """
    return not os.environ.get("REPRO_DISABLE_PIPELINE")


def _on_tpu() -> bool:
    try:
        return jax.devices()[0].platform == "tpu"
    except RuntimeError:  # pragma: no cover - no backend
        return False


@dataclasses.dataclass(frozen=True)
class BlockStream:
    """One SSR lane at block granularity.

    ``index_map(*grid_indices) -> block indices`` must be affine — the AGU
    constraint.  ``count_reuse`` marks streams whose map revisits blocks
    (the repeat register), which the cost model credits as FIFO reuse.
    """

    block_shape: Tuple[int, ...]
    index_map: Callable[..., Tuple[Any, ...]]
    direction: Direction = Direction.READ
    name: str = "stream"

    def block_bytes(self, dtype) -> int:
        return math.prod(self.block_shape) * jnp.dtype(dtype).itemsize

    def spec(self) -> pl.BlockSpec:
        return pl.BlockSpec(self.block_shape, self.index_map)


@dataclasses.dataclass(frozen=True)
class StreamReport:
    """Static data-movement accounting for one ``ssr_pallas`` kernel.

    The software analogue of the paper's Fig. 8 right axis: bytes that the
    data movers stream per invocation, the VMEM working set (double-
    buffered), and the FIFO-reuse savings from repeat-style index maps.
    """

    grid: Tuple[int, ...]
    vmem_bytes: int
    hbm_bytes_streamed: int
    hbm_bytes_unique: int
    scratch_bytes: int = 0   # kernel-resident VMEM (accumulators, chain links)

    @property
    def reuse_factor(self) -> float:
        return self.hbm_bytes_streamed / max(1, self.hbm_bytes_unique)


def _validate_affine(stream: BlockStream, grid: Tuple[int, ...]) -> None:
    got = agu.affine_coefficients(stream.index_map, grid)
    if got is None:
        raise ValueError(
            f"stream '{stream.name}': index_map is not affine in the grid "
            "indices — not expressible by the SSR AGU (bound/stride model)"
        )


def _unique_blocks(stream: BlockStream, grid: Tuple[int, ...]) -> int:
    """Number of distinct blocks the AGU touches over the whole grid.

    Exact for affine maps: walk the (small) grid index space.  Grids here
    are kernel-tile counts (≤ a few thousand), so this stays cheap.
    """
    seen = set()
    total = 1
    for g in grid:
        total *= g
    if total > 65536:  # closed-form fallback for very large grids
        affine = agu.affine_coefficients(stream.index_map, grid)
        if affine is None:
            # Non-affine map (possible when the kernel was built with
            # validate=False): no closed form — count conservatively, as if
            # every grid step touched a fresh block (no FIFO reuse credit).
            return total
        _, coeffs = affine
        # distinct blocks = product over grid dims with nonzero coeff
        distinct = 1
        for dim, c in enumerate(coeffs):
            if any(int(x) != 0 for x in c):
                distinct *= grid[dim]
        return distinct
    for idx in itertools.product(*[range(g) for g in grid]):
        seen.add(tuple(int(x) for x in stream.index_map(*idx)))
    return len(seen)


def vmem_limit_bytes(working_set: int) -> Optional[int]:
    """The scoped-VMEM limit a kernel with this working set declares.

    ``None`` where the working set fits Mosaic's default scoped limit: the
    kernel then compiles exactly as Mosaic would choose.  Otherwise the
    working set plus half again for Mosaic's own temporaries (the body's
    block-sized values, such as a dot's result before it is added to the
    accumulator), held under :data:`VMEM_LIMIT_CAP_BYTES`.
    """
    if working_set <= MOSAIC_DEFAULT_VMEM_BYTES:
        return None
    return min(VMEM_LIMIT_CAP_BYTES, working_set + working_set // 2)


def _compiler_params(dimension_semantics: Optional[Tuple[str, ...]],
                     vmem_limit: Optional[int] = None):
    """Mosaic compiler params: the grid axes' dimension semantics and, where
    the working set needs more than Mosaic's default, the VMEM limit."""
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=vmem_limit)


def _flat_strides(grid: Tuple[int, ...]) -> Tuple[int, ...]:
    """Row-major strides of the grid's flat step index."""
    strides = [1] * len(grid)
    for k in range(len(grid) - 2, -1, -1):
        strides[k] = strides[k + 1] * grid[k + 1]
    return tuple(strides)


def _stream_is_invariant(stream: BlockStream, grid: Tuple[int, ...]) -> bool:
    """True when the index map ignores every grid axis (repeat register)."""
    affine = agu.affine_coefficients(stream.index_map, grid)
    if affine is None:
        return False
    _, coeffs = affine
    return all(int(x) == 0 for dim in coeffs for x in dim)


def _pipelined_call(
    body: Callable[..., None],
    *,
    grid: Tuple[int, ...],
    in_streams: Sequence[BlockStream],
    out_streams: Sequence[BlockStream],
    out_shapes: Sequence[jax.ShapeDtypeStruct],
    scratch_shapes: Sequence[Any],
    buffer_depth: Tuple[int, ...],
    interpret: bool,
    call_kwargs: Callable[[Sequence[Any]], dict],
    name: Optional[str] = None,
) -> Callable[..., Any]:
    """Emit the explicit N-deep HBM→VMEM rotation (pipelined emission).

    Inputs move to the ANY memory space (no Pallas block pipeline); read
    stream ``i`` gets ``depths[i]`` rotating VMEM scratch buffers and a
    DMA semaphore array — depths are *per stream* (``buffer_depth`` is a
    tuple, one entry per input), so a strided operand that misses in HBM
    can run deep while a unit-stride one stays shallow.  At flat grid step
    ``s`` the kernel *starts* stream ``i``'s fetch of step
    ``s + depths[i] − 1`` (into slot ``(s+depths[i]−1) % depths[i]``),
    *waits* on slot ``s % depths[i]``, and hands the body that slot's
    block — so ``depths[i] − 1`` fetches are in flight per stream while
    one block computes, the paper's "proactively performs memory reads" at
    configurable per-stream run-ahead.  Step 0 primes each stream's first
    ``depths[i] − 1`` fetches.  Loop-invariant streams (the repeat
    register) are fetched ONCE at step 0 and re-read from slot 0 every
    step — no re-fetch traffic at all.  Other revisit patterns (e.g. a
    GEMM A-panel reused across N-tiles) re-fetch each step: the rotation
    trades the sync pipeline's unchanged-index elision for run-ahead
    depth.  Outputs keep their normal BlockSpecs — only operand *delivery*
    changes, so numerics are bit-identical to the sync path.

    The grid (and therefore ``pl.program_id``-based accumulator logic in
    bodies) is preserved; every axis is sequential (``arbitrary``) because
    the rotation state threads through consecutive steps.
    """
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    n_in = len(in_streams)
    n_out = len(out_streams)
    steps = math.prod(grid)
    strides = _flat_strides(grid)
    depths = tuple(buffer_depth)
    invariant = tuple(_stream_is_invariant(s, grid) for s in in_streams)
    zeros = tuple(0 for _ in grid)

    def _slices(stream: BlockStream, g) -> Tuple[Any, ...]:
        idx = stream.index_map(*g)
        return tuple(pl.ds(i * b, b)
                     for i, b in zip(idx, stream.block_shape))

    def wrapped(*refs):
        hbm = refs[:n_in]
        outs = refs[n_in:n_in + n_out]
        sc = refs[n_in + n_out:]
        bufs, sems = sc[:n_in], sc[n_in:2 * n_in]
        rest = sc[2 * n_in:]
        ids = tuple(pl.program_id(k) for k in range(len(grid)))
        s = ids[0]
        for k in range(1, len(grid)):
            s = s * grid[k] + ids[k]

        def unflatten(step):
            # works for python ints (priming) and traced ints (run-ahead)
            return tuple((step // st) % g for st, g in zip(strides, grid))

        def start(i, step, slot):
            g = unflatten(step)
            pltpu.make_async_copy(
                hbm[i].at[_slices(in_streams[i], g)],
                bufs[i].at[slot], sems[i].at[slot]).start()

        @pl.when(s == 0)
        def _prime():
            for i in range(n_in):      # repeat register: one fetch, ever
                if invariant[i]:
                    copy = pltpu.make_async_copy(
                        hbm[i].at[_slices(in_streams[i], zeros)],
                        bufs[i].at[0], sems[i].at[0])
                    copy.start()
                    copy.wait()
                    continue
                for j in range(min(depths[i] - 1, steps)):
                    start(i, j, j)

        for i in range(n_in):          # per-stream run-ahead fetch
            if invariant[i]:
                continue
            nxt = s + depths[i] - 1

            @pl.when(nxt < steps)
            def _prefetch(i=i, nxt=nxt):
                start(i, nxt, nxt % depths[i])

        blocks = []
        for i in range(n_in):
            if invariant[i]:
                blocks.append(bufs[i].at[0])
                continue
            slot = s % depths[i]
            pltpu.make_async_copy(
                hbm[i].at[_slices(in_streams[i], ids)],
                bufs[i].at[slot], sems[i].at[slot]).wait()
            blocks.append(bufs[i].at[slot])
        body(*blocks, *outs, *rest)

    def run(*arrays):
        if len(arrays) != n_in:
            raise ValueError(
                f"pipelined kernel expects {n_in} operands, got "
                f"{len(arrays)}")
        for a, st in zip(arrays, in_streams):
            if a.ndim != len(st.block_shape):
                raise ValueError(
                    f"stream '{st.name}': operand rank {a.ndim} != block "
                    f"rank {len(st.block_shape)} — pipelined emission "
                    "slices the prepared layout directly")
        rot = [pltpu.VMEM((d, *st.block_shape), jnp.dtype(a.dtype))
               for d, st, a in zip(depths, in_streams, arrays)]
        dma_sems = [pltpu.SemaphoreType.DMA((d,)) for d in depths]
        call = pl.pallas_call(
            wrapped,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)
                      for _ in in_streams],
            out_specs=[s.spec() for s in out_streams]
            if n_out != 1 else out_streams[0].spec(),
            out_shape=list(out_shapes) if len(out_shapes) != 1
            else out_shapes[0],
            scratch_shapes=rot + dma_sems + list(scratch_shapes),
            interpret=interpret,
            name=name,
            **call_kwargs(arrays),
        )
        return call(*arrays)

    return jax.jit(run)


def ssr_pallas(
    body: Callable[..., None],
    *,
    grid: Tuple[int, ...],
    in_streams: Sequence[BlockStream],
    out_streams: Sequence[BlockStream],
    out_shapes: Sequence[jax.ShapeDtypeStruct],
    scratch_shapes: Sequence[Any] = (),
    interpret: Optional[bool] = None,
    dimension_semantics: Optional[Tuple[str, ...]] = None,
    validate: bool = True,
    cost_estimate: Optional[pl.CostEstimate] = None,
    buffer_depth=DEFAULT_BUFFER_DEPTH,
    name: Optional[str] = None,
) -> Callable[..., Any]:
    """Build a streamed Pallas kernel from SSR-style block streams.

    ``body(*in_refs, *out_refs, *scratch_refs)`` is the pure compute region —
    the "SSR region" of Fig. 4 ③.  Returns a jitted callable; the attached
    ``.report(*, dtypes)`` computes the :class:`StreamReport`.

    ``buffer_depth`` sets the data mover's FIFO depth — a uniform ``int``,
    or a tuple with one depth per *input* stream (asymmetric run-ahead:
    deep for the strided operand, shallow for the unit-stride one).
    Depth 2 (default) is Pallas's own double-buffered pipeline; any depth
    > 2 emits the explicit N-deep rotation (:func:`_pipelined_call`) when
    the platform supports it (:func:`pipeline_supported`) and the grid has
    more than one step, falling back to the synchronous path otherwise —
    numerics are identical either way.  The attached ``fn.pipelined`` flag
    records which emitter actually ran; the VMEM report always budgets at
    the *requested* depths (:func:`stream_vmem_bytes`) — each input at its
    own depth, outputs at the maximum — so a schedule legal here is legal
    on the deepest path it might take.

    ``name`` is the kernel's family (``"gemm"``): the Pallas kernel is
    named ``ssr_<name>`` in the lowered program and on the device's trace.

    On Mosaic, a kernel whose working set (the report's ``vmem_bytes`` plus
    scratch, from the operands' dtypes) exceeds Mosaic's default scoped
    VMEM declares the limit :func:`vmem_limit_bytes` gives it.
    """
    kernel_name = None if name is None else f"ssr_{name}"
    for s in in_streams:
        if s.direction != Direction.READ:
            raise ValueError(f"input stream '{s.name}' must be a read stream")
    for s in out_streams:
        if s.direction != Direction.WRITE:
            raise ValueError(f"output stream '{s.name}' must be a write stream")
    if len(out_streams) != len(out_shapes):
        raise ValueError("one out_shape per output stream")
    if isinstance(buffer_depth, (tuple, list)):
        depths = tuple(int(d) for d in buffer_depth)
        if len(depths) != len(in_streams):
            raise ValueError(
                f"buffer_depth tuple has {len(depths)} entries for "
                f"{len(in_streams)} input streams; give one depth per "
                "stream")
        check = depths
    else:
        depths = (int(buffer_depth),) * len(in_streams)
        check = (int(buffer_depth),)
    for d in check:
        if not DEFAULT_BUFFER_DEPTH <= d <= MAX_BUFFER_DEPTH:
            raise ValueError(
                f"buffer_depth {d} outside "
                f"[{DEFAULT_BUFFER_DEPTH}, {MAX_BUFFER_DEPTH}] — depth < 2 "
                "cannot overlap fetch with compute, deeper than "
                f"{MAX_BUFFER_DEPTH} would eat the VMEM budget")
    max_depth = max(depths) if depths else DEFAULT_BUFFER_DEPTH
    if validate:
        for s in (*in_streams, *out_streams):
            _validate_affine(s, grid)

    if interpret is None:
        interpret = not _on_tpu()

    pipelined = (max_depth > DEFAULT_BUFFER_DEPTH
                 and pipeline_supported()
                 and len(grid) >= 1 and math.prod(grid) > 1)

    if pipelined:
        # rotation state threads through consecutive steps: every axis is
        # sequential regardless of the caller's declared semantics
        dimension_semantics = ("arbitrary",) * len(grid)

    def working_set(dtypes: Sequence[Any]) -> Tuple[int, int]:
        """(depth-buffered stream blocks, kernel-resident scratch) bytes:
        inputs at their own FIFO depth, outputs at the deepest."""
        streams = (*in_streams, *out_streams)
        if len(dtypes) != len(streams):
            raise ValueError("one dtype per stream")
        vmem = sum(
            stream_vmem_bytes(s.block_bytes(dt),
                              depths[i] if i < len(in_streams) else max_depth)
            for i, (s, dt) in enumerate(zip(streams, dtypes)))
        # Kernel-resident scratch (reduce accumulators, chained-intermediate
        # blocks) is single-buffered but counts against the same budget.
        scratch = 0
        for sc in scratch_shapes:
            shape = getattr(sc, "shape", None)
            dt = getattr(sc, "dtype", None)
            if shape is not None and dt is not None:
                scratch += math.prod(shape) * jnp.dtype(dt).itemsize
        return vmem, scratch

    def call_kwargs(arrays: Sequence[Any]) -> dict:
        """``pallas_call`` keywords for these operands: the cost estimate,
        and on Mosaic the semantics and any VMEM limit the blocks need."""
        kwargs: dict = {}
        if cost_estimate is not None:
            kwargs["cost_estimate"] = cost_estimate
        if interpret:
            return kwargs
        limit = vmem_limit_bytes(sum(working_set(
            [a.dtype for a in arrays] + [o.dtype for o in out_shapes])))
        if dimension_semantics is not None or limit is not None:
            kwargs["compiler_params"] = _compiler_params(dimension_semantics,
                                                         limit)
        return kwargs

    if pipelined:
        fn = _pipelined_call(
            body, grid=grid, in_streams=in_streams,
            out_streams=out_streams, out_shapes=out_shapes,
            scratch_shapes=scratch_shapes, buffer_depth=depths,
            interpret=interpret, call_kwargs=call_kwargs, name=kernel_name)
    else:
        def run(*arrays):
            return pl.pallas_call(
                body,
                grid=grid,
                in_specs=[s.spec() for s in in_streams],
                out_specs=[s.spec() for s in out_streams]
                if len(out_streams) != 1
                else out_streams[0].spec(),
                out_shape=list(out_shapes) if len(out_shapes) != 1
                else out_shapes[0],
                scratch_shapes=list(scratch_shapes),
                interpret=interpret,
                name=kernel_name,
                **call_kwargs(arrays),
            )(*arrays)

        fn = jax.jit(run)

    def report(dtypes: Sequence[Any]) -> StreamReport:
        streams = (*in_streams, *out_streams)
        vmem, scratch = working_set(dtypes)
        steps = math.prod(grid)
        streamed = 0
        unique = 0
        for s, dt in zip(streams, dtypes):
            bb = s.block_bytes(dt)
            streamed += bb * steps
            unique += bb * _unique_blocks(s, grid)
        if vmem + scratch > VMEM_BUDGET_BYTES:
            raise ValueError(
                f"VMEM working set {(vmem + scratch)/2**20:.1f} MiB exceeds "
                f"budget {VMEM_BUDGET_BYTES/2**20:.0f} MiB — shrink "
                "block_shape"
            )
        return StreamReport(grid=grid, vmem_bytes=vmem,
                            hbm_bytes_streamed=streamed,
                            hbm_bytes_unique=unique,
                            scratch_bytes=scratch)

    fn.report = report  # type: ignore[attr-defined]
    fn.grid = grid  # type: ignore[attr-defined]
    fn.buffer_depth = buffer_depth  # type: ignore[attr-defined]
    fn.pipelined = pipelined  # type: ignore[attr-defined]
    return fn


def check_mxu_alignment(block_shape: Tuple[int, ...], dtype) -> bool:
    """True if the trailing dims are hardware-aligned (lane=128, sublane)."""
    if len(block_shape) < 2:
        return block_shape[-1] % _LANE == 0
    itemsize = jnp.dtype(dtype).itemsize
    sub = _SUBLANE.get(itemsize, 8)
    return block_shape[-1] % _LANE == 0 and block_shape[-2] % sub == 0


def auto_block(dim: int, target: int, align: int) -> int:
    """Largest aligned block ≤ target that tiles ``dim`` exactly."""
    b = min(dim, max(align, (target // align) * align))
    while b > align and dim % b != 0:
        b -= align
    if dim % b != 0:
        b = math.gcd(dim, b) or dim
    return b
