"""Shared kernel frontend: pad → reshape → stream dispatch → trim, once.

This is the loop prologue/epilogue of the paper's Fig. 4 (steps ① and ④ —
stream setup before the region, result write-back after), factored out of
the §4.2 kernel suite.  Every kernel used to re-implement the same four
steps around its compute body: zero-pad operands to whole VMEM blocks, reshape to the
2-D (rows, lanes) layout the streams address, build + jit the ``ssr_pallas``
call, and trim the padding off the result.  :class:`StreamKernel` owns that
pipeline; a kernel module now declares only

* ``prepare`` — operand canonicalisation (pure jnp pad/reshape, usually one
  of the helpers below),
* ``launch``  — the stream geometry (grid, BlockStreams, out shapes,
  scratch) as a :class:`Launch`,
* ``body``    — the compute region builder (``body(static) -> callable``),
* ``finish``  — result trimming.

The whole pipeline — prepare, engine, finish — composes into ONE cached
jitted callable keyed on the *raw* call inputs (shapes/dtypes + static
values + schedule), so the pad/trim traffic fuses into the same XLA
program as the kernel and a repeated call is a dict probe plus one jitted
invocation (``DISPATCH_STATS`` counts builds/traces/calls; the trace-count
tests pin the zero-overhead contract).  ``interpret=None`` autodetects:
Mosaic on a real TPU, interpreter elsewhere.

Dtype policy: bodies compute in :data:`COMPUTE_DTYPE` (f32 — the MXU/VPU
accumulation width) regardless of storage dtype; :func:`promote` is the one
place that states it.

:class:`MonolithicKernel` is the same contract for the *baseline* variants:
a single-step ``pallas_call`` whose body walks blocks with explicit loads —
the paper's serialised load→compute issue — with the identical caching and
pad/trim treatment, so "baseline" and "ssr" differ only in how operands are
delivered.

**One path to silicon.**  :class:`NestKernel` is the preferred declarative
shell: the kernel states a :class:`~repro.core.LoopNest` (the §3.2
compiler's input) plus a block body, and the whole schedule — grid, index
maps, repeat streams, contraction accumulators — comes out of
``ssrify``/``lower_plan``/``lower_nest`` via :func:`repro.core.ssr_call`,
under a block :class:`~repro.core.Schedule` resolved from the autotuner's
persistent cache (``schedule=None``) or pinned explicitly per call.
A module may still hand a raw :class:`Launch` to :class:`StreamKernel` /
:class:`ChainedKernel`, but only with a ``lowering_waiver``: one sentence
stating why the pattern is outside the block-granular AGU model (halo
overlap, carried state, power-of-two shuffle networks, …).  The waiver is
mandatory — an undeclared escape hatch is a compiler-coverage bug.
"""

from __future__ import annotations

import contextlib
import dataclasses
import inspect
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.profiler import TraceAnnotation

from repro.core import BlockStream  # noqa: F401  (re-export for kernels)
from repro.core import autotune, resilience
from repro.core.lowering import (DEFAULT_SCHEDULE, Schedule, _body_key,
                                 _named, ssr_call)
from repro.core.ssr import _on_tpu, ssr_pallas

ROWS = 8
LANES = 128
BLOCK_ELEMS = ROWS * LANES
COMPUTE_DTYPE = jnp.float32

#: Frontend dispatch instrumentation, mirroring
#: ``lowering.DISPATCH_STATS``: ``builds`` counts jitted prepare→finish
#: pipelines constructed, ``traces`` moves only while one is being traced,
#: ``calls`` per ``__call__``.  The trace-count tests assert a repeated
#: call is a pure cache hit.  ``fallbacks``/``degraded`` mirror the
#: lowering counters: lookups abandoned for the default schedule vs tuned
#: pipelines quarantined and rebuilt on the default — zero when healthy.
#: ``build_ns`` is the wall time, in integer nanoseconds, spent in
#: ``ssr.build`` spans (a cache miss up to the return of the new pipeline's
#: first call: prepare, lookup, lowering, trace, compile); the lowering
#: layer's builds run inside them and are not counted again.
#: ``traffic_tiles`` counts builds whose schedule came from the HBM-traffic
#: model (``autotune.traffic_schedule``), not from the tuned cache or
#: ``DEFAULT_SCHEDULE``.  ``vmap_launches`` counts warm calls whose
#: arguments carried ``jax.vmap``'s batch tracers and were served by a
#: cached batched pipeline (:class:`_Peeled`), one per call.
DISPATCH_STATS: Dict[str, int] = {"builds": 0, "traces": 0, "calls": 0,
                                  "fallbacks": 0, "degraded": 0,
                                  "build_ns": 0, "traffic_tiles": 0,
                                  "vmap_launches": 0}


#: Built-pipeline cap per kernel instance: epoch bumps retire old entries,
#: so the bound only needs to stop pathological shape churn.
_PIPELINE_CACHE_MAX = 512


def reset_dispatch_stats() -> None:
    for k in DISPATCH_STATS:
        DISPATCH_STATS[k] = 0


@contextlib.contextmanager
def _build_span():
    """The ``ssr.build`` profiler span of a pipeline-cache miss, timed into
    ``DISPATCH_STATS["build_ns"]`` whether or not a profiler runs."""
    t0 = time.perf_counter_ns()
    try:
        with TraceAnnotation("ssr.build"):
            yield
    finally:
        DISPATCH_STATS["build_ns"] += time.perf_counter_ns() - t0


class MosaicUnsupported(NotImplementedError):
    """A kernel declared as one Mosaic refuses, called for the chip.

    Raised before lowering when such a kernel is called with
    ``interpret=False`` (or on a TPU, where ``interpret=None`` means
    Mosaic).  Not a dispatch-fallback type: the chip never quietly
    interprets it or hands it to ``ref``.
    """


def _is_arraylike(x: Any) -> bool:
    return hasattr(x, "shape") and hasattr(x, "dtype")


def _static_key(v: Any) -> Any:
    """Hashable identity for a non-array call ingredient (param or arg)."""
    if callable(v):
        return _body_key(v)
    try:
        hash(v)
        return v
    except TypeError:
        return repr(v)


def _call_key(args: tuple, params: Dict[str, Any]) -> Any:
    """Cache key over raw call inputs: array shapes/dtypes + static values.

    Keying on *raw* inputs (not prepared arrays) is what lets the whole
    prepare→engine→finish pipeline live behind one dict probe.
    """
    arg_key = tuple(
        (tuple(a.shape), str(a.dtype)) if _is_arraylike(a) else
        ("static", _static_key(a))
        for a in args)
    param_key = tuple(sorted((k, _static_key(v)) for k, v in params.items()))
    return arg_key, param_key


# -- calls under jax.vmap -----------------------------------------------------
#
# Called eagerly under ``jax.vmap``, a jitted pipeline receives batch
# tracers, and JAX re-runs pjit's batching rule over the pipeline's jaxpr
# (Pallas's included) and dispatches through the Python pjit path on every
# call.  ``NestKernel`` instead peels the tracers (:class:`_Peeled`) and
# calls a cached ``jax.jit(jax.vmap(pipeline))`` on the values under them.
# The batch tracer, its trace and the tracing context are JAX internals,
# read only in ``_Peeled``; without them every call takes the per-example
# path.

try:
    from jax._src import core as _jax_core
    from jax._src.interpreters.batching import BatchTrace as _BatchTrace
    from jax._src.interpreters.batching import BatchTracer as _BatchTracer
    _NO_AXIS_NAME = _jax_core.no_axis_name
    _TRACE_CTX = _jax_core.trace_ctx
    _EVAL_TRACE = _jax_core.eval_trace
    _TOP_AXIS_ENV = _jax_core.top_axis_env
    _Tracer = _jax_core.Tracer
except (ImportError, AttributeError):  # pragma: no cover - other JAX
    _BatchTracer = None


class _Peeled:
    """A call's arguments with the unnamed ``jax.vmap`` levels they share
    peeled off.

    ``levels`` lists ``(trace, axes)`` innermost first, one per enclosing
    vmap that maps some argument, with each argument's batch axis at that
    level (``None`` where it does not map it, and for every static
    argument); ``values`` are the arguments under every peeled level;
    ``base`` is the trace the batched call runs in (eager, or a
    ``jax.jit`` being traced).
    """

    __slots__ = ("levels", "values", "base")

    def __init__(self, levels, values, base):
        self.levels, self.values, self.base = levels, values, base

    @classmethod
    def of(cls, args: Sequence[Any],
           params: Dict[str, Any]) -> Optional["_Peeled"]:
        """The call's peeled form, or ``None`` when it is not peeled: no
        argument is a batch tracer of the current trace (a concrete call
        costs one type check an argument), a parameter is a tracer, or
        this JAX lacks what is read here."""
        if _BatchTracer is None or \
                not any(type(a) is _BatchTracer for a in args) or \
                any(isinstance(v, _Tracer) for v in params.values()):
            return None
        try:
            trace, vals, levels = _TRACE_CTX.trace, tuple(args), []
            while (type(trace) is _BatchTrace
                   and trace.axis_data.name is _NO_AXIS_NAME
                   and trace.axis_data.spmd_name is None
                   and trace.axis_data.explicit_mesh_axis is None):
                inner, axes = zip(*map(trace.to_batch_info, vals))
                if all(d is None for d in axes) or \
                        any(d is not None and type(d) is not int
                            for d in axes):
                    break
                levels.append((trace, axes))
                vals, trace = inner, trace.parent_trace
        except AttributeError:  # pragma: no cover - other JAX
            return None
        return cls(tuple(levels), vals, trace) if levels else None

    def axes(self) -> Tuple[Tuple[Optional[int], ...], ...]:
        """Each level's batch axes, innermost first: part of the key."""
        return tuple(axes for _trace, axes in self.levels)

    def batch(self, pipeline: Callable) -> Callable:
        """The per-example ``pipeline`` (over the array arguments) under
        each peeled level, innermost first: batched once, when the jitted
        program is traced."""
        idx = [i for i, a in enumerate(self.values) if _is_arraylike(a)]
        for _trace, axes in self.levels:
            pipeline = jax.vmap(pipeline, in_axes=tuple(axes[i] for i in idx))
        return pipeline

    def call(self, fn: Callable, arrays: Sequence[Any]) -> Any:
        """Call the batched pipeline ``fn`` in ``base`` and wrap each output
        back as a batch tracer of every peeled level, batch axis 0.

        An eager call under unnamed vmaps runs at JAX's top level (eval
        trace, empty axis env), where a warm jitted call takes the C++
        fast path.
        """
        ctx = _TRACE_CTX
        trace, env = ctx.trace, ctx.axis_env
        top = self.base is _EVAL_TRACE and not (
            env.axis_sizes or env.spmd_axis_names
            or env.explicit_mesh_axis_names)
        ctx.set_trace(self.base)
        if top:
            ctx.set_axis_env(_TOP_AXIS_ENV)
        try:
            out = fn(*arrays)
        finally:
            ctx.set_trace(trace)
            if top:
                ctx.set_axis_env(env)

        def wrap(o):
            for level, _axes in reversed(self.levels):
                o = _BatchTracer(level, o, 0)
            return o

        return jax.tree.map(wrap, out)


def promote(x: jax.Array) -> jax.Array:
    """Cast a block to the compute dtype (f32 accumulation everywhere)."""
    return x.astype(COMPUTE_DTYPE)


# -- operand canonicalisation helpers ---------------------------------------


def pad_vector(x: jax.Array, *, block: int = BLOCK_ELEMS,
               lanes: int = LANES) -> jax.Array:
    """Zero-pad a 1-D array to whole blocks; reshape to (rows, lanes)."""
    n = x.shape[0]
    pad = (-n) % block
    if pad:
        x = jnp.pad(x, (0, pad))
    return x.reshape(-1, lanes)


def trim_vector(out: jax.Array, n: int) -> jax.Array:
    """Undo :func:`pad_vector`: flatten and drop the padding tail."""
    return out.reshape(-1)[:n]


def pad_leading(a: jax.Array, mult: int) -> jax.Array:
    """Zero-pad the leading (row) dim of a matrix to a multiple of ``mult``."""
    pad = (-a.shape[0]) % mult
    if pad:
        a = jnp.pad(a, ((0, pad), (0, 0)))
    return a


def require_power_of_two(n: int, what: str) -> None:
    # n & (n - 1) alone silently accepts n == 0 (0 & -1 == 0): an empty
    # operand would sail into log2/stage loops and fail far from the cause.
    if n <= 0 or n & (n - 1):
        raise ValueError(f"{what} needs a power-of-two length, got {n}")


# -- declarative kernel shells ----------------------------------------------


@dataclasses.dataclass(frozen=True)
class Launch:
    """One kernel's stream geometry, as declared by its module."""

    grid: Tuple[int, ...]
    in_streams: Tuple[BlockStream, ...]
    out_streams: Tuple[BlockStream, ...]
    out_shapes: Tuple[jax.ShapeDtypeStruct, ...]
    scratch_shapes: Tuple[Any, ...] = ()
    dimension_semantics: Optional[Tuple[str, ...]] = None


class _KernelBase:
    """Shared call pipeline: prepare → build → run → finish, ONE jit.

    The whole pipeline — operand canonicalisation (pad/reshape), the
    engine call, and result trimming — composes into a single cached
    jitted callable keyed on the *raw* call inputs, so the pad/trim
    traffic fuses into the same XLA program as the kernel instead of
    dispatching eagerly per call.  The first call for a signature runs
    ``prepare`` once eagerly (to learn the static meta the builder needs)
    and then traces the fused pipeline; every later call is a dict probe
    plus one jitted invocation.
    """

    #: Prefix of the kernel's name on the device (``ssr_gemv``): streamed.
    _prefix = "ssr"

    def __init__(self, name: str, *, prepare: Callable,
                 finish: Optional[Callable] = None):
        self.name = name
        #: The name of the pipeline's program and of its Pallas kernel.
        self.kernel_name = f"{self._prefix}_{name}"
        self._prepare = prepare
        self._finish = finish
        self._cache: Dict[Any, Callable] = {}
        # A ``schedule=`` param is routed to prepare only when it asks for
        # one (geometry consumers like the stencil); otherwise it stays a
        # builder-level knob (buffer_depth) and prepare never sees it.
        try:
            sig = inspect.signature(prepare)
            self._prepare_takes_schedule = (
                "schedule" in sig.parameters
                or any(p.kind is inspect.Parameter.VAR_KEYWORD
                       for p in sig.parameters.values()))
        except (TypeError, ValueError):  # pragma: no cover - C callables
            self._prepare_takes_schedule = True

    def _build(self, static, arrays, interpret: bool,
               schedule: Optional[Schedule]) -> Callable:
        raise NotImplementedError

    def __call__(self, *args, interpret: Optional[bool] = None, **params):
        if interpret is None:
            interpret = not _on_tpu()
        DISPATCH_STATS["calls"] += 1
        schedule = params.get("schedule")
        prep_params = params if self._prepare_takes_schedule else \
            {k: v for k, v in params.items() if k != "schedule"}
        key = (_call_key(args, params), bool(interpret))
        fn = self._cache.get(key)
        if fn is not None:
            with TraceAnnotation("ssr.launch"):
                return fn(*[a for a in args if _is_arraylike(a)])
        with _build_span():
            arrays, static, _final = self._prepare(*args, **prep_params)
            built = self._build(static, tuple(arrays), bool(interpret),
                                schedule)
            arr_idx = tuple(i for i, a in enumerate(args)
                            if _is_arraylike(a))
            # Capture only the static (non-array) positions: closing over
            # the first call's arrays would pin their device buffers for
            # the cache entry's lifetime.
            statics = tuple(None if _is_arraylike(a) else a for a in args)

            def pipeline(*arrs, _st=statics, _idx=arr_idx, _built=built):
                DISPATCH_STATS["traces"] += 1
                full = list(_st)
                for i, a in zip(_idx, arrs):
                    full[i] = a
                prepared, _s, final = self._prepare(*full, **prep_params)
                out = _built(*prepared)
                return self._finish(out, final) if self._finish else out

            fn = jax.jit(_named(pipeline, self.kernel_name))
            DISPATCH_STATS["builds"] += 1
            if len(self._cache) >= _PIPELINE_CACHE_MAX:
                self._cache.clear()
            self._cache[key] = fn
            return fn(*[a for a in args if _is_arraylike(a)])


def _require_waiver(name: str, waiver: Optional[str]) -> str:
    """Hand-scheduled geometry must state why the compiler cannot emit it."""
    if not waiver or not waiver.strip():
        raise ValueError(
            f"kernel {name!r} constructs a Launch without a lowering_waiver; "
            "declare why the pattern is outside the block-granular AGU "
            "model, or migrate to NestKernel")
    return waiver


class NestKernel:
    """A kernel whose schedule IS its :class:`~repro.core.LoopNest`.

    The declarative replacement for ``StreamKernel``'s ``launch=`` escape
    hatch: the module supplies

    * ``prepare(*args, **params) -> (operands, static, final)`` — operand
      canonicalisation; ``operands`` maps the nest's :class:`MemRef` names
      to arrays (no padding/reshaping — the lowering owns the layout);
    * ``nest(static) -> LoopNest`` — the §3.2 compiler input; a nest with
      an output WRITE ref takes the level-mapped contraction path, a
      read-only nest uses ``mode`` (``"reduce"``/``"map"``);
    * ``body(static) -> fn(*blocks)`` — the pure compute region;
    * ``finish(out, final)`` — result post-processing (dtype cast, …).

    Everything between prepare and finish — grid, index maps, repeat
    streams, accumulators, padding, kernel caching — is
    :func:`repro.core.ssr_call`, i.e. the same pipeline the compiler tests
    verify, so the kernel is covered by the Eq. (1)–(3) cost model
    (``plan_stats``) and the cluster layer for free.
    """

    def __init__(self, name: str, *, prepare: Callable, nest: Callable,
                 body: Callable, mode: str = "reduce",
                 finish: Optional[Callable] = None,
                 out_dtype: Optional[Callable] = None,
                 mosaic_refusal: str = ""):
        self.name = name
        # Why Mosaic cannot compile this kernel (the compiler's own reason);
        # empty for kernels that run on the chip.
        self.mosaic_refusal = mosaic_refusal
        self._prepare = prepare
        self._nest = nest
        self._body = body
        self._mode = mode
        self._finish = finish
        # out_dtype(static) -> dtype; None keeps ssr_call's f32 accumulation
        # default.  Dtype-preserving kernels (integer relu) need this so the
        # streamed engine stays bit-exact with the baseline.
        self._out_dtype = out_dtype
        self._cache: Dict[Any, Callable] = {}

    def loop_nest(self, static):
        """The nest this kernel executes — exposed for cost-model oracles."""
        return self._nest(static)

    def schedule_for(self, *args, **params) -> Schedule:
        """The schedule this call would run: tuned (cache hit), chosen by
        the HBM-traffic model, or the default."""
        operands, static, _final = self._prepare(*args, **params)
        out_dtype = "float32" if self._out_dtype is None else \
            str(jnp.dtype(self._out_dtype(static)))
        return autotune.lookup(self._nest(static), dict(operands),
                               mode=self._mode, out_dtype=out_dtype)

    def __call__(self, *args, interpret: Optional[bool] = None,
                 schedule: Optional[Schedule] = None, **params):
        """Run the kernel as ONE jitted prepare→ssr_call→finish pipeline.

        ``schedule=None`` consults the autotuner's persistent schedule
        cache (:func:`repro.core.autotune.lookup`) — so registry/``ops``
        callers pick up tuned schedules transparently.  The pipeline cache
        keys on the autotune epoch: committing a new winner rebuilds the
        pipeline on the next call instead of serving the stale schedule.

        **Degradation**: because the pipeline hands ``ssr_call`` an
        *explicit* resolved schedule, the lowering layer cannot degrade it
        — this level owns the ladder.  A typed dispatch failure (injected
        fault, cache I/O, :class:`LoweringError`, compile error) under a
        *tuned* schedule quarantines the cache entry and rebuilds on the
        default schedule; under a traffic-chosen schedule it rebuilds on
        the default with nothing to quarantine.  An explicit ``schedule=``
        always propagates the error (the caller pinned it, masking would
        hide their bug).
        """
        DISPATCH_STATS["calls"] += 1
        mosaic = _on_tpu() if interpret is None else not interpret
        if self.mosaic_refusal and mosaic:
            raise MosaicUnsupported(
                f"kernel {self.name!r} does not compile through Mosaic: "
                f"{self.mosaic_refusal}")
        try:
            return self._dispatch(args, params, interpret, schedule)
        except resilience.fallback_error_types() as e:
            if schedule is not None:
                raise
            rung = self._quarantine_tuned(args, params)
            if rung is None:
                raise
            source, key = rung
            DISPATCH_STATS["degraded"] += 1
            resilience.record_fallback(
                seam=resilience.classify(e), site=f"nest_kernel:{self.name}",
                error=e, from_schedule=source, to_schedule="default",
                key=key)
            return self._dispatch(args, params, interpret, DEFAULT_SCHEDULE)

    def _quarantine_tuned(self, args,
                          params) -> Optional[Tuple[str, Optional[str]]]:
        """The rung this call degrades from: ``(source, key)``.

        A committed tuned entry is quarantined (``("tuned", key)``); a
        traffic-chosen schedule has no entry to sideline
        (``("traffic", None)``).  ``None`` when the call was already
        running the default schedule (nothing to degrade from — the
        failure is genuine and must propagate).
        """
        try:
            operands, static, _final = self._prepare(*args, **params)
            nest = self._nest(static)
            out_dtype = "float32" if self._out_dtype is None else \
                str(jnp.dtype(self._out_dtype(static)))
            sched, source = autotune.resolve(nest, dict(operands),
                                             mode=self._mode,
                                             out_dtype=out_dtype)
            if sched == DEFAULT_SCHEDULE:
                return None
            if source == "traffic":
                return source, None
            return source, autotune.quarantine(
                nest, dict(operands), mode=self._mode, out_dtype=out_dtype)
        except Exception:  # re-probe failed: keep the original error
            return None

    def _dispatch(self, args, params, interpret: Optional[bool],
                  schedule: Optional[Schedule]):
        # Under jax.vmap the key holds the batched shapes of the values
        # under the tracers and each peeled level's axes.
        peeled = _Peeled.of(args, params)
        values = args if peeled is None else peeled.values
        key = (_call_key(values, params), schedule, interpret,
               autotune.epoch() if schedule is None else -1,
               None if peeled is None else peeled.axes())
        arrays = [a for a in values if _is_arraylike(a)]
        fn = self._cache.get(key)
        if fn is not None:
            with TraceAnnotation("ssr.launch"):
                if peeled is None:
                    return fn(*arrays)
                DISPATCH_STATS["vmap_launches"] += 1
                return peeled.call(fn, arrays)
        with _build_span():
            pipeline = self._pipeline(args, params, interpret, schedule)
            if peeled is not None:
                pipeline = peeled.batch(pipeline)
            resilience.inject("compile")
            fn = jax.jit(_named(pipeline, f"ssr_{self.name}"))
            DISPATCH_STATS["builds"] += 1
            if len(self._cache) >= _PIPELINE_CACHE_MAX:
                self._cache.clear()
            self._cache[key] = fn
            return fn(*arrays) if peeled is None else peeled.call(fn, arrays)

    def _pipeline(self, args, params, interpret: Optional[bool],
                  schedule: Optional[Schedule]) -> Callable:
        """The per-example prepare→ssr_call→finish function over a call's
        array arguments, its schedule resolved here (tuned, traffic-chosen
        or default)."""
        operands, static, _final = self._prepare(*args, **params)
        nest = self._nest(static)
        sched = schedule
        if sched is None:
            out_dtype = "float32" if self._out_dtype is None else \
                str(jnp.dtype(self._out_dtype(static)))
            try:
                with TraceAnnotation("ssr.lookup"):
                    sched, source = autotune.resolve(
                        nest, dict(operands), mode=self._mode,
                        out_dtype=out_dtype)
                DISPATCH_STATS["traffic_tiles"] += int(source == "traffic")
            except resilience.fallback_error_types() as e:
                DISPATCH_STATS["fallbacks"] += 1
                resilience.record_fallback(
                    seam=resilience.classify(e),
                    site=f"nest_kernel:{self.name}", error=e,
                    from_schedule="tuned-lookup", to_schedule="default")
                sched = DEFAULT_SCHEDULE
        arr_idx = tuple(i for i, a in enumerate(args) if _is_arraylike(a))
        # static positions only — see the _KernelBase note: closing over
        # first-call arrays would pin their buffers
        statics = tuple(None if _is_arraylike(a) else a for a in args)

        def pipeline(*arrs, _st=statics, _idx=arr_idx, _sched=sched):
            DISPATCH_STATS["traces"] += 1
            full = list(_st)
            for i, a in zip(_idx, arrs):
                full[i] = a
            ops, s, final = self._prepare(*full, **params)
            okw = {} if self._out_dtype is None else \
                {"out_dtype": self._out_dtype(s)}
            out = ssr_call(self._nest(s), self._body(s), dict(ops),
                           mode=self._mode, schedule=_sched,
                           interpret=interpret, name=self.name, **okw)
            return self._finish(out, final) if self._finish else out

        return pipeline


class StreamKernel(_KernelBase):
    """A streamed (SSR) kernel: geometry from ``launch``, body per block.

    Requires a ``lowering_waiver`` naming why the §3.2 pipeline cannot
    emit this schedule (see module docstring) — prefer :class:`NestKernel`.
    """

    def __init__(self, name: str, *, prepare: Callable, launch: Callable,
                 body: Callable, finish: Optional[Callable] = None,
                 lowering_waiver: Optional[str] = None):
        super().__init__(name, prepare=prepare, finish=finish)
        self.lowering_waiver = _require_waiver(name, lowering_waiver)
        self._launch = launch
        self._body = body

    def _build(self, static, arrays, interpret: bool,
               schedule: Optional[Schedule]) -> Callable:
        lc: Launch = self._launch(static, *arrays)
        return ssr_pallas(
            self._body(static),
            grid=lc.grid,
            in_streams=list(lc.in_streams),
            out_streams=list(lc.out_streams),
            out_shapes=list(lc.out_shapes),
            scratch_shapes=list(lc.scratch_shapes),
            interpret=interpret,
            dimension_semantics=lc.dimension_semantics,
            buffer_depth=(schedule or DEFAULT_SCHEDULE).buffer_depth,
            name=self.name,
        )


class MonolithicKernel(_KernelBase):
    """A baseline kernel: one grid step, explicit in-body block walk."""

    _prefix = "baseline"

    def __init__(self, name: str, *, prepare: Callable, body: Callable,
                 out_shape: Callable, finish: Optional[Callable] = None):
        super().__init__(name, prepare=prepare, finish=finish)
        self._body = body
        self._out_shape = out_shape

    def _build(self, static, arrays, interpret: bool,
               schedule: Optional[Schedule]) -> Callable:
        # the serialised baseline has no streams to pipeline: schedule
        # (buffer_depth included) is deliberately ignored
        call = pl.pallas_call(
            self._body(static),
            out_shape=self._out_shape(static, *arrays),
            interpret=interpret,
            name=self.kernel_name,
        )
        return jax.jit(call)


class ChainedKernel(_KernelBase):
    """A fused producer→consumer kernel (stream chaining).

    Reuses a producer's stream geometry (its ``launch``) unchanged; per grid
    step the producer body's output block is written to a VMEM scratch —
    never to HBM — and the ``consumer`` body (a unary block→block post-op)
    reads it back the same step.  Fusing eliminates the intermediate HBM
    buffer the unfused two-kernel composition materialises: one store and
    one load per element, the dominant cost of short chained kernels.

    ``producer(static) -> fn(*in_blocks) -> block`` and
    ``consumer(static) -> fn(block) -> block`` follow the same builder
    contract as :class:`StreamKernel` bodies.  The scratch block's shape and
    dtype come from the launch's (single) output stream, so the consumer
    must be shape-preserving — chains that change the iteration space need
    the nest-level :func:`repro.core.ssr_chain_call` instead.
    """

    def __init__(self, name: str, *, prepare: Callable, launch: Callable,
                 producer: Callable, consumer: Callable,
                 finish: Optional[Callable] = None,
                 lowering_waiver: Optional[str] = None):
        super().__init__(name, prepare=prepare, finish=finish)
        self.lowering_waiver = _require_waiver(name, lowering_waiver)
        self._launch = launch
        self._producer = producer
        self._consumer = consumer

    def _build(self, static, arrays, interpret: bool,
               schedule: Optional[Schedule]) -> Callable:
        from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

        lc: Launch = self._launch(static, *arrays)
        if len(lc.out_streams) != 1:
            raise ValueError(
                f"{self.name}: ChainedKernel needs exactly one output "
                "stream to size the intermediate scratch")
        prod_body = self._producer(static)
        cons_body = self._consumer(static)
        n_in = len(lc.in_streams)
        inter_shape = lc.out_streams[0].block_shape
        inter_dtype = lc.out_shapes[0].dtype

        def kernel(*refs):
            in_refs, o_ref = refs[:n_in], refs[n_in]
            s_ref = refs[n_in + 1]
            # the intermediate lands in VMEM scratch, never HBM
            s_ref[...] = jnp.asarray(
                prod_body(*[r[...] for r in in_refs]),
                inter_dtype).reshape(inter_shape)
            o_ref[...] = jnp.asarray(cons_body(s_ref[...]),
                                     inter_dtype).reshape(inter_shape)

        return ssr_pallas(
            kernel,
            grid=lc.grid,
            in_streams=list(lc.in_streams),
            out_streams=list(lc.out_streams),
            out_shapes=list(lc.out_shapes),
            scratch_shapes=[pltpu.VMEM(inter_shape, inter_dtype),
                            *lc.scratch_shapes],
            interpret=interpret,
            dimension_semantics=lc.dimension_semantics,
            buffer_depth=(schedule or DEFAULT_SCHEDULE).buffer_depth,
            name=self.name,
        )
