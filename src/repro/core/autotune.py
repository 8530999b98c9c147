"""Schedule autotuner: cost-model-guided block-policy search + dispatch cache.

The paper's speedup comes from matching the loop schedule to the memory
system (Snitch tunes the same ISA to 2× more on the right schedule —
PAPERS.md); our lowering used to pin every kernel, shape and core count to
the single hard-coded ``DEFAULT_POLICY``.  This module makes the schedule a
**searched artifact**:

1. **candidate generation** — :func:`candidate_schedules` enumerates legal
   :class:`~repro.core.lowering.Schedule` variants for a nest: block
   geometries (rows × lanes), per-level tile targets, and grid-axis orders.
   Legality (:func:`schedule_is_legal`) is decided by the lowering itself
   (a candidate the §3.2 pipeline rejects is discarded), plus the hardware
   constraints the lowering does not own: lane divisibility (a TPU lane is
   128 wide) and the *depth-aware* VMEM working-set budget
   (``buffer_depth`` buffers per stream block via :func:`repro.core.ssr.
   stream_vmem_bytes` + kernel-resident scratch, the :class:`~repro.core.
   ssr.StreamReport` accounting);
2. **model prune** — :func:`model_cost` ranks candidates with the
   Eq. (1)–(3) instruction model (``ssrify`` on the *padded* iteration
   space, so padding blowup is charged) plus a per-grid-step dispatch
   charge, and :func:`rank_candidates` keeps a deterministic top-K;
3. **measure** — :func:`autotune` wall-clocks the survivors (the default
   schedule always races) and commits the winner;
4. **persist** — a :class:`ScheduleCache`: JSON-per-key files under a
   cache directory (``REPRO_SCHEDULE_CACHE`` env var, else
   ``~/.cache/repro-ssr``), an in-memory LRU in front, explicit
   invalidation, and a version stamp so stale formats never load.  Keys
   are :func:`cache_key`: nest signature + operand shapes/dtypes + mode +
   out dtype + backend + cores.

Dispatch integration: ``kernels.frontend.NestKernel`` and
``parallel.cluster.cluster_call`` consult :func:`lookup` when no explicit
schedule is passed, so ``ops.py`` callers get tuned schedules transparently
once a cache entry exists; :func:`epoch` lets their built-pipeline caches
invalidate when the tuner commits a new winner.  On a miss, a plain
contraction nest (GEMM) gets the tiles that its modelled HBM traffic
favours (:func:`traffic_schedule`), and everything else the default.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import itertools
import json
import math
import os
import tempfile
import time

import numpy as np
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from . import nest_analysis, resilience
from .compiler import ChainDAG, LoopNest, _fused_region_count, ssrify
from .lowering import (DEFAULT_SCHEDULE, LoweredChain, LoweredNest,
                       LoweredPlan, LoweringError, Schedule, _plan_for,
                       lower_nest)
from .nest_analysis import auto_lanes
from .ssr import (DEFAULT_BUFFER_DEPTH, MAX_BUFFER_DEPTH, VMEM_BUDGET_BYTES,
                  stream_vmem_bytes)
from .stream import Direction

#: Bump when the on-disk entry format (or the meaning of a schedule's
#: fields) changes: old entries are ignored, never mis-parsed.
SCHEDULE_CACHE_VERSION = 1

_ENV_CACHE_DIR = "REPRO_SCHEDULE_CACHE"

#: Eq. (1)-style charge per grid step: loop bookkeeping + DMA descriptor
#: issue for the double-buffered block fetches.  This is what makes the
#: model prefer fewer/bigger blocks until padding waste outweighs it.
STEP_COST = 32

#: Search space of the generic generator (kernels with bespoke geometry,
#: e.g. the waivered stencil, pass their own ``candidates=``).
_ROWS_CHOICES = (4, 8, 16, 32)
_LANES_CHOICES = (128, 256, 512)
_LANES_FACTORS = (1, 2, 4)
_ROWS_FACTORS = (8, 32)
_QUICK_ROWS = (8, 16)
_QUICK_LANES = (128, 256)
#: Data-mover FIFO depths the generator explores (2 = the synchronous
#: Pallas double-buffer; deeper = explicit N-deep DMA rotation).
_DEPTH_CHOICES = (2, 3, 4)
_QUICK_DEPTHS = (2, 3)


def default_cache_dir() -> str:
    env = os.environ.get(_ENV_CACHE_DIR)
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro-ssr")


# --------------------------------------------------------------------------
# Cache keys: nest signature + shapes + backend + cores
# --------------------------------------------------------------------------


def nest_signature(nest: LoopNest) -> str:
    """Canonical text form of a nest — the schedule cache's identity.

    Any change to bounds, refs (name/kind/coeffs/offset, plus the index
    stream + scale of an indirect ref, a halo window, or a non-default
    accumulator kind), per-level compute or a band yields a different
    signature, so editing a kernel's nest invalidates its cached schedules
    by construction.  Refs without the newer features keep their older text
    form, so existing cached schedules stay addressable.
    """
    def _ref_sig(r) -> str:
        sig = f"{r.name}:{r.kind.name}:{r.coeffs}:{r.offset}"
        if r.is_indirect():
            sig += f":ix={r.index_of}*{r.index_scale}"
        if r.window is not None:
            sig += f":win={r.window}"
        if r.acc_kind != "sum":
            sig += f":acc={r.acc_kind}"
        return sig

    refs = ";".join(_ref_sig(r) for r in nest.refs)
    sig = f"b={nest.bounds}|refs={refs}|c={nest.compute_per_level}"
    if nest.band is not None:
        b = nest.band
        sig += f"|band={b.outer}>{b.inner}:{b.lo}:{b.hi}"
    return sig


def operand_signature(operands: Dict[str, Any]) -> Tuple[Tuple[str, str], ...]:
    """Sorted (name, shape×dtype) pairs; accepts arrays or (shape, dtype)."""
    sig = []
    for name in sorted(operands):
        v = operands[name]
        if hasattr(v, "shape") and hasattr(v, "dtype"):
            sig.append((name, f"{tuple(v.shape)}:{v.dtype}"))
        else:
            shape, dtype = v
            sig.append((name, f"{tuple(shape)}:{dtype}"))
    return tuple(sig)


def _backend() -> str:
    import jax

    try:
        return jax.default_backend()
    except RuntimeError:  # pragma: no cover - no backend at all
        return "none"


def cache_key(nest: LoopNest, operands: Dict[str, Any], *,
              mode: str = "reduce", out_dtype: str = "float32",
              backend: Optional[str] = None, cores: int = 1) -> str:
    """Stable hex digest identifying one tuning problem."""
    backend = backend or _backend()
    blob = json.dumps({
        "v": SCHEDULE_CACHE_VERSION,
        "nest": nest_signature(nest),
        "operands": operand_signature(operands),
        "mode": mode,
        "out_dtype": str(out_dtype),
        "backend": backend,
        "cores": int(cores),
    }, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


# --------------------------------------------------------------------------
# Persistent schedule cache: JSON-per-key + in-memory LRU + invalidation
# --------------------------------------------------------------------------


#: Sentinel for "generation never observed" — the first probe adopts the
#: on-disk token silently instead of spuriously invalidating local state.
_GEN_UNSET = object()

#: Bounded retry budget for transient I/O on the commit path.
_PUT_ATTEMPTS = 3


class ScheduleCache:
    """On-disk schedule store with an in-memory LRU in front.

    One JSON file per key under ``path`` (atomic tmp+rename writes), so
    concurrent tuners never corrupt each other's entries and per-key
    invalidation is an unlink.  Misses return ``None`` and are
    negative-cached **per epoch**: the transparent-dispatch hot path
    (``ssr_call`` with ``schedule=None``) probes on every call, and a
    filesystem miss per kernel invocation would tax exactly the path this
    layer exists to speed up.

    **Cross-process visibility.** Any commit/invalidate/quarantine —
    local *or* from another process — must bust stale negative-cache
    entries.  The local path bumps ``_EPOCH`` directly; cross-process
    changes are detected by stat'ing one ``GENERATION`` file every
    writer touches (atomic replace, so the (inode, mtime_ns) token
    changes on every write): when the token moves, the in-memory
    positive + negative caches drop and the epoch bumps, so
    built-pipeline caches keyed on :func:`epoch` rebuild too.  Cost on
    the hot path: one ``os.stat``.

    **Crash safety.** Torn/truncated/garbage/version-skewed entry files
    are treated as misses AND quarantined — renamed to
    ``<key>.json.corrupt`` (counted in :attr:`stats`) so they cannot
    shadow a later healthy commit; a subsequent :meth:`put` recovers the
    key.  Commits retry transient ``OSError`` with jittered backoff
    (bounded — see :func:`repro.core.resilience.retry`) and never leave
    a ``.tmp`` behind.  The ``cache.read``/``cache.write`` fault seams
    fire here.
    """

    def __init__(self, path: Optional[str] = None, max_entries: int = 256):
        self.path = path or default_cache_dir()
        self.max_entries = max_entries
        self._mem: "collections.OrderedDict[str, Schedule]" = \
            collections.OrderedDict()
        self._miss: Dict[str, int] = {}   # key -> epoch of the probed miss
        self._last_gen: Any = _GEN_UNSET  # last observed GENERATION token
        self.stats: Dict[str, int] = {"hits": 0, "misses": 0,
                                      "quarantined": 0, "retries": 0,
                                      "generation_busts": 0}

    def _file(self, key: str) -> str:
        return os.path.join(self.path, f"{key}.json")

    def _gen_file(self) -> str:
        return os.path.join(self.path, "GENERATION")

    def _disk_generation(self) -> Optional[Tuple[int, int]]:
        """Cheap change token of the cache dir: (inode, mtime_ns) of the
        GENERATION file, ``None`` while no writer has touched it yet.
        Every touch goes through an atomic replace, so the inode alone
        already changes per write — mtime_ns is belt and braces."""
        try:
            st = os.stat(self._gen_file())
        except OSError:
            return None
        return (st.st_ino, st.st_mtime_ns)

    def _touch_generation(self) -> None:
        """Advance the cross-process change token (atomic, retried)."""
        def _write() -> None:
            os.makedirs(self.path, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self.path, suffix=".tmp")
            try:
                with os.fdopen(fd, "w") as f:
                    f.write(f"{os.getpid()}:{time.time_ns()}\n")
                os.replace(tmp, self._gen_file())
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise

        try:
            resilience.retry(_write, attempts=_PUT_ATTEMPTS,
                             on_retry=self._count_retry)
        except OSError:
            # the token is an optimisation for OTHER processes' negative
            # caches; local state is already correct, so a sick filesystem
            # must not fail the commit that just landed
            pass
        self._last_gen = self._disk_generation()
        self._miss.clear()

    def _count_retry(self, attempt: int, error: BaseException) -> None:
        self.stats["retries"] += 1

    def _sync_generation(self) -> None:
        """Adopt the on-disk token; on change, drop local caches so a
        commit/invalidate from another process becomes visible NOW (not
        after an unrelated local epoch bump — the staleness hole this
        probe closes)."""
        gen = self._disk_generation()
        if self._last_gen is _GEN_UNSET:
            self._last_gen = gen
            return
        if gen != self._last_gen:
            self._last_gen = gen
            self._miss.clear()
            self._mem.clear()
            self.stats["generation_busts"] += 1
            _bump_epoch()

    def _note_miss(self, key: str) -> None:
        if len(self._miss) >= 4096:
            self._miss.clear()
        self._miss[key] = _EPOCH

    def quarantine(self, key: str) -> bool:
        """Sideline one entry as poisoned: rename to ``.json.corrupt``
        (forensics survive; the key reads as a miss), negative-cache it,
        and advance the generation token so other processes re-probe.
        Returns True if a disk file was actually sidelined."""
        self._mem.pop(key, None)
        sidelined = False
        try:
            os.replace(self._file(key), self._file(key) + ".corrupt")
            sidelined = True
        except OSError:
            try:
                os.unlink(self._file(key))
                sidelined = True
            except OSError:
                pass
        if sidelined:
            self.stats["quarantined"] += 1
            self._touch_generation()
        _bump_epoch()
        self._note_miss(key)
        return sidelined

    def get(self, key: str) -> Optional[Schedule]:
        resilience.inject("cache.read")
        self._sync_generation()
        hit = self._mem.get(key)
        if hit is not None:
            self._mem.move_to_end(key)
            self.stats["hits"] += 1
            return hit
        if self._miss.get(key) == _EPOCH:
            self.stats["misses"] += 1
            return None
        try:
            with open(self._file(key)) as f:
                doc = json.load(f)
        except OSError:                  # absent file: a plain miss
            self._note_miss(key)
            self.stats["misses"] += 1
            return None
        except ValueError:               # torn/garbage JSON: quarantine
            self.quarantine(key)
            self.stats["misses"] += 1
            return None
        if not isinstance(doc, dict) or \
                doc.get("version") != SCHEDULE_CACHE_VERSION:
            self.quarantine(key)         # version skew: never mis-parse
            self.stats["misses"] += 1
            return None
        try:
            sched = Schedule.from_json(doc["schedule"])
        except (KeyError, TypeError, ValueError):
            self.quarantine(key)
            self.stats["misses"] += 1
            return None
        self._remember(key, sched)
        self.stats["hits"] += 1
        return sched

    def meta(self, key: str) -> Optional[Dict[str, Any]]:
        """The full stored document (schedule + provenance), or ``None``."""
        resilience.inject("cache.read")
        try:
            with open(self._file(key)) as f:
                doc = json.load(f)
        except OSError:
            return None
        except ValueError:
            self.quarantine(key)
            return None
        return doc if doc.get("version") == SCHEDULE_CACHE_VERSION else None

    def put(self, key: str, schedule: Schedule,
            meta: Optional[Dict[str, Any]] = None) -> None:
        doc = {"version": SCHEDULE_CACHE_VERSION,
               "schedule": schedule.to_json(),
               "committed_unix": time.time()}
        if meta:
            doc["meta"] = meta

        def _write() -> None:
            resilience.inject("cache.write")
            os.makedirs(self.path, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self.path, suffix=".tmp")
            try:
                with os.fdopen(fd, "w") as f:
                    json.dump(doc, f, indent=1, sort_keys=True)
                os.replace(tmp, self._file(key))
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise

        # transient OSError (full disk blip, NFS hiccup, injected
        # cache.write:oserror) is retried with jittered backoff; a typed
        # InjectedFault or a persistent failure propagates to the caller,
        # who degrades gracefully (see autotune()'s commit)
        resilience.retry(_write, attempts=_PUT_ATTEMPTS,
                         on_retry=self._count_retry)
        self._remember(key, schedule)
        self._touch_generation()
        _bump_epoch()

    def invalidate(self, key: str) -> bool:
        """Drop one entry (memory + disk); True if anything was removed."""
        self._miss.pop(key, None)
        dropped = self._mem.pop(key, None) is not None
        try:
            os.unlink(self._file(key))
            dropped = True
        except OSError:
            pass
        if dropped:
            self._touch_generation()
            _bump_epoch()
        return dropped

    def clear(self) -> int:
        """Drop every entry; returns the number of disk entries removed."""
        self._mem.clear()
        self._miss.clear()
        n = 0
        try:
            names = os.listdir(self.path)
        except OSError:
            names = []
        for name in names:
            if name.endswith(".json"):
                try:
                    os.unlink(os.path.join(self.path, name))
                    n += 1
                except OSError:
                    pass
        self._touch_generation()
        _bump_epoch()
        return n

    def keys(self) -> List[str]:
        try:
            return sorted(n[:-5] for n in os.listdir(self.path)
                          if n.endswith(".json"))
        except OSError:
            return []

    def _remember(self, key: str, sched: Schedule) -> None:
        self._mem[key] = sched
        self._mem.move_to_end(key)
        while len(self._mem) > self.max_entries:
            self._mem.popitem(last=False)


_GLOBAL_CACHE: Optional[ScheduleCache] = None
_EPOCH = 0


def global_cache() -> ScheduleCache:
    """The process-wide cache (respects ``REPRO_SCHEDULE_CACHE``)."""
    global _GLOBAL_CACHE
    if _GLOBAL_CACHE is None or \
            _GLOBAL_CACHE.path != default_cache_dir():
        _GLOBAL_CACHE = ScheduleCache()
    return _GLOBAL_CACHE


def epoch() -> int:
    """Monotonic commit counter — built-pipeline caches key on it so a
    newly tuned schedule takes effect without restarting the process."""
    return _EPOCH


def _bump_epoch() -> None:
    global _EPOCH
    _EPOCH += 1


def resolve(nest: LoopNest, operands: Dict[str, Any], *,
            mode: str = "reduce", out_dtype: str = "float32",
            cores: int = 1,
            cache: Optional[ScheduleCache] = None) -> Tuple[Schedule, str]:
    """``(schedule, source)``: the committed tuned winner (``"tuned"``),
    else the tiles the HBM-traffic model picks for a plain contraction
    (``"traffic"``, :func:`traffic_schedule`), else ``DEFAULT_SCHEDULE``
    (``"default"``).  It never measures: one dict/file probe, and on a
    miss one memoised model evaluation per (nest, shapes)."""
    cache = cache or global_cache()
    key = cache_key(nest, operands, mode=mode, out_dtype=str(out_dtype),
                    cores=cores)
    tuned = cache.get(key)
    if tuned is not None:
        return tuned, "tuned"
    picked = traffic_schedule(nest, operands, out_dtype=str(out_dtype))
    if picked is not None:
        return picked, "traffic"
    return DEFAULT_SCHEDULE, "default"


def lookup(nest: LoopNest, operands: Dict[str, Any], *,
           mode: str = "reduce", out_dtype: str = "float32",
           cores: int = 1,
           cache: Optional[ScheduleCache] = None) -> Schedule:
    """Cache-only schedule resolution: the schedule of :func:`resolve`.

    This is the transparent-dispatch hook — it never measures, so calling
    it on every kernel build costs one dict/file probe.
    """
    return resolve(nest, operands, mode=mode, out_dtype=out_dtype,
                   cores=cores, cache=cache)[0]


# --------------------------------------------------------------------------
# Traffic-chosen tiles: a plain contraction's schedule on a cache miss
# --------------------------------------------------------------------------

#: A traffic-chosen schedule replaces the default only where it saves at
#: least this share of the default's modelled HBM bytes.  A GEMV streams its
#: matrix once under any tiling; kilobytes of re-read vector do not pay for
#: new tiles.
TRAFFIC_MIN_SAVING = 0.125


def _fetches(levels: Tuple[int, ...], lowered: LoweredNest) -> int:
    """Grid steps on which a stream's block index changes.

    Pallas fetches a block only when its index differs from the previous
    step's, and the grid walks row-major: the index changes at every step
    of the grid axes up to the innermost one it varies with.
    """
    axes = [k for k, lvl in enumerate(lowered.axis_order)
            if lvl in levels and lowered.grid[k] > 1]
    return math.prod(lowered.grid[:max(axes) + 1]) if axes else 1


def traffic_bytes(lowered: LoweredNest,
                  itemsizes: Dict[str, int]) -> int:
    """Modelled HBM bytes of one call of a level-mapped kernel.

    For every stream, its block bytes times the grid steps on which its
    block index changes (:func:`_fetches`); the output's block changes once
    per output block, so it is written once.  For a GEMM with the
    contraction innermost and split, that is A·⌈N/tn⌉ + B·⌈M/tm⌉ + C.
    ``itemsizes`` maps stream names to bytes per element (4 if absent).
    """
    return sum(math.prod(s.stream.block_shape) * itemsizes.get(s.name, 4)
               * _fetches(s.levels, lowered)
               for s in (*lowered.in_streams, lowered.out_stream))


def _is_plain_contraction(lowered) -> bool:
    """An output accumulator revisited across trailing contraction levels,
    with no online-softmax rescale, gather or halo."""
    return (isinstance(lowered, LoweredNest) and bool(lowered.contraction_axes)
            and not lowered.rescale and not lowered.gathers
            and not lowered.halos)


def _aligned_factors(bounds: Sequence[int], align: int,
                     least: int) -> List[int]:
    """Tile factors (in units of ``align``) giving every distinct tile of
    these padded bounds at or above ``least`` units: a level's tile is its
    largest aligned divisor under the target, so only divisors matter."""
    out = {least}
    for b in bounds:
        units = b // align
        for f in range(1, math.isqrt(units) + 1):
            if units % f == 0:
                out.update(g for g in (f, units // f) if g > least)
    return sorted(out)


def traffic_schedule(nest: LoopNest, operands: Dict[str, Any], *,
                     out_dtype: str = "float32") -> Optional[Schedule]:
    """The tiles that least HBM traffic favours, for a plain contraction.

    Candidates keep the default block geometry (rows 8, lanes 128) and vary
    the tile factors over every distinct tile of the padded bounds, and the
    order of the parallel grid axes.  Each is scored by
    :func:`traffic_bytes`; those whose working set (every stream at its
    depth plus the accumulator, as :func:`schedule_is_legal` counts it)
    exceeds ``VMEM_BUDGET_BYTES`` are dropped.  The least bytes win, then
    the smaller working set.  ``None`` where the nest is not a plain
    contraction, or where the winner saves less than
    :data:`TRAFFIC_MIN_SAVING` of the default's bytes: the caller then runs
    ``DEFAULT_SCHEDULE``.
    """
    return _traffic_pick(nest, operand_signature(operands), str(out_dtype))


@functools.lru_cache(maxsize=256)
def _traffic_pick(nest: LoopNest, sig: Tuple[Tuple[str, str], ...],
                  out_dtype: str) -> Optional[Schedule]:
    if not _nest_has_output(nest):
        return None
    try:
        plan = _plan_for(nest, auto_lanes(nest))
        base = lower_nest(plan, schedule=DEFAULT_SCHEDULE)
    except (LoweringError, ValueError):
        return None
    if not _is_plain_contraction(base):
        return None
    sizes = {name: np.dtype(desc.rsplit(":", 1)[1]).itemsize
             for name, desc in sig}
    sizes[base.out_stream.name] = np.dtype(out_dtype).itemsize
    # the working set at the widest element, as the accumulator is f32
    itemsize = max(4, *sizes.values())
    lanes_lvls = {s.levels[-1] for s in (*base.in_streams, base.out_stream)
                  if s.levels}
    row_lvls = {l for s in (*base.in_streams, base.out_stream)
                for l in s.levels[:-1]} - lanes_lvls
    pad = base.padded_bounds
    d = DEFAULT_SCHEDULE
    rows_f = _aligned_factors([pad[l] for l in row_lvls], d.rows,
                              d.rows_tile_factor)
    lanes_f = _aligned_factors([pad[l] for l in lanes_lvls], d.lanes,
                               d.lanes_tile_factor)
    default_bytes = traffic_bytes(base, sizes)
    best = None
    for order in [None] + _axis_orders(nest):
        for rf, lf in itertools.product(rows_f, lanes_f):
            sched = dataclasses.replace(d, rows_tile_factor=rf,
                                        lanes_tile_factor=lf,
                                        axis_order=order)
            try:
                lowered = lower_nest(plan, schedule=sched)
            except (LoweringError, ValueError):
                continue
            ws = _stream_block_bytes(lowered, itemsize)
            if ws > VMEM_BUDGET_BYTES:
                continue
            score = (traffic_bytes(lowered, sizes), ws)
            if best is None or score < best[0]:
                best = (score, sched)
    if best is None or \
            best[0][0] > (1 - TRAFFIC_MIN_SAVING) * default_bytes:
        return None
    return best[1]


# --------------------------------------------------------------------------
# Candidate generation + legality
# --------------------------------------------------------------------------


def _nest_has_output(nest: LoopNest) -> bool:
    return any(r.kind == Direction.WRITE for r in nest.refs)


def _lower_candidate(nest: LoopNest, sched: Schedule):
    """The lowering's own verdict on a candidate (raises LoweringError).

    Routed through the lowering layer's ``_lowered_for`` LRU: legality,
    cost-model and fingerprint checks all ask for the same (plan, sched)
    lowering, and a later ``ssr_call`` under the winner hits it warm.
    """
    from .lowering import _lowered_for

    plan = _plan_for(nest, auto_lanes(nest))
    return _lowered_for(plan, sched, _nest_has_output(nest))


def _depth_of(sched: Schedule, i: int, n_in: int) -> int:
    """Stream ``i``'s FIFO depth under ``sched`` (asymmetric when set)."""
    if sched.stream_depths and len(sched.stream_depths) == n_in:
        return sched.stream_depths[i]
    return sched.buffer_depth


def _max_depth(sched: Schedule) -> int:
    if sched.stream_depths:
        return max(sched.stream_depths)
    return sched.buffer_depth


def _operand_bytes(v: Any, itemsize: int = 4) -> int:
    """Whole-operand VMEM footprint; accepts arrays or (shape, dtype)."""
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        shape, dtype = tuple(v.shape), v.dtype
    else:
        shape, dtype = v
    try:
        size = np.dtype(dtype).itemsize
    except TypeError:
        size = itemsize
    return math.prod(tuple(shape)) * size


def _gather_table_bytes(lowered, operands: Optional[Dict[str, Any]],
                        itemsize: int = 4) -> int:
    """VMEM charge for indirect refs: the whole gather table is resident.

    This is the indirect-ref legality rule — index blocks stream like any
    other lane (charged above), but the indirectly addressed operand rides
    double-buffered as an invariant block, so its *full* extent counts
    against the budget.  Without operands the geometry-only charge is 0
    (table sizes are operand facts, not nest facts).
    """
    gathers = getattr(lowered, "gathers", ())
    if not gathers or not operands:
        return 0
    total = 0
    for g in gathers:
        if g.name in operands:
            total += stream_vmem_bytes(
                _operand_bytes(operands[g.name], itemsize), 2)
    return total


def _stream_block_bytes(lowered, itemsize: int = 4) -> int:
    """Depth-buffered stream blocks + kernel-resident scratch, in bytes.

    Mirrors :meth:`repro.core.ssr.StreamReport` accounting exactly — both
    route every stream block through :func:`repro.core.ssr.
    stream_vmem_bytes` at the schedule's (possibly per-stream) FIFO
    depths, so the budget the tuner enforces is the budget the emitter
    allocates (the depth knob cannot drift between them).  The
    contraction / reduce accumulator is single-buffered scratch
    (``scratch_bytes``); a fused chain/DAG additionally charges one VMEM
    block per *live* intermediate slot (refcounted — a diamond's peak is
    2 slots, not one per edge).
    """
    sched = lowered.schedule
    depth = sched.buffer_depth
    total = 0
    if isinstance(lowered, LoweredNest):
        n_in = len(lowered.in_streams)
        for i, s in enumerate(lowered.in_streams):
            total += stream_vmem_bytes(
                math.prod(s.stream.block_shape) * itemsize,
                _depth_of(sched, i, n_in))
        out_block = math.prod(lowered.out_stream.stream.block_shape)
        total += stream_vmem_bytes(out_block * itemsize, _max_depth(sched))
        if lowered.contraction_axes:     # the VMEM accumulator scratch
            total += out_block * itemsize
        return total
    if isinstance(lowered, LoweredChain):
        from .lowering import _dag_slots

        flat = lowered.in_streams
        n_in = len(flat)
        for i, s in enumerate(flat):
            total += stream_vmem_bytes(
                math.prod(s.stream.block_shape) * itemsize,
                _depth_of(sched, i, n_in))
        block = lowered.policy.rows * lowered.policy.lanes
        total += stream_vmem_bytes(block * itemsize, _max_depth(sched))
        if isinstance(lowered.chained, ChainDAG):
            _, n_slots = _dag_slots(lowered.chained)
        else:
            n_slots = len(lowered.chained.links)
        total += n_slots * block * itemsize  # intermediate scratch slots
        total += block * itemsize            # reduce accumulator scratch
        return total
    assert isinstance(lowered, LoweredPlan)
    n_in = len(lowered.in_streams)
    for i, s in enumerate(lowered.in_streams):
        total += stream_vmem_bytes(
            math.prod(s.stream.block_shape) * itemsize,
            _depth_of(sched, i, n_in))
    block = lowered.policy.rows * lowered.policy.lanes
    total += stream_vmem_bytes(block * itemsize,
                               _max_depth(sched))  # synthesised output
    total += block * itemsize            # reduce accumulator scratch
    return total


def schedule_is_legal(nest: LoopNest, sched: Schedule, *,
                      itemsize: int = 4,
                      operands: Optional[Dict[str, Any]] = None
                      ) -> Tuple[bool, str]:
    """(legal, reason).  Lowering + lane divisibility + VMEM budget.

    ``operands`` (when given) enables the indirect-ref rule: each gather
    table's full footprint joins the depth-buffered stream blocks against
    the VMEM budget — see :func:`_gather_table_bytes`.
    """
    if sched.lanes % 128 != 0 or sched.lanes < 128:
        return False, f"lanes {sched.lanes} not a multiple of the 128-wide " \
                      "hardware lane"
    if sched.rows < 1:
        return False, f"rows {sched.rows} < 1"
    if sched.lanes_tile_factor < 1 or sched.rows_tile_factor < 1:
        return False, "tile factors must be >= 1"
    if not DEFAULT_BUFFER_DEPTH <= sched.buffer_depth <= MAX_BUFFER_DEPTH:
        return False, (f"buffer_depth {sched.buffer_depth} outside "
                       f"[{DEFAULT_BUFFER_DEPTH}, {MAX_BUFFER_DEPTH}]")
    if sched.stream_depths is not None:
        for d in sched.stream_depths:
            if not DEFAULT_BUFFER_DEPTH <= d <= MAX_BUFFER_DEPTH:
                return False, (f"stream depth {d} outside "
                               f"[{DEFAULT_BUFFER_DEPTH}, "
                               f"{MAX_BUFFER_DEPTH}]")
    try:
        lowered = _lower_candidate(nest, sched)
    except LoweringError as e:
        return False, f"lowering rejected: {e}"
    except ValueError as e:              # MAX_DIMS / malformed nest
        return False, f"nest rejected: {e}"
    if sched.stream_depths is not None \
            and len(sched.stream_depths) != len(lowered.in_streams):
        return False, (f"stream_depths has {len(sched.stream_depths)} "
                       f"entries for {len(lowered.in_streams)} read "
                       "streams")
    vmem = _stream_block_bytes(lowered, itemsize)
    vmem += _gather_table_bytes(lowered, operands, itemsize)
    if vmem > VMEM_BUDGET_BYTES:
        return False, (f"VMEM working set {vmem / 2**20:.1f} MiB exceeds "
                       f"budget {VMEM_BUDGET_BYTES / 2**20:.0f} MiB")
    return True, "ok"


def _axis_orders(nest: LoopNest) -> List[Tuple[int, ...]]:
    """Legal grid-axis permutations: parallel axes shuffle, contractions
    stay trailing.  Bounded to 3-deep nests (≤ 2 extra orders)."""
    if not _nest_has_output(nest):
        return []
    try:
        out = nest_analysis.output_ref(nest)
    except ValueError:
        return []
    if out is None or out.coeffs is None:
        return []
    zaxes = nest_analysis.contraction_axes(out, nest)
    par = [l for l in range(len(nest.bounds)) if l not in zaxes]
    if len(par) < 2:
        return []
    orders = []
    for perm in itertools.permutations(par):
        order = tuple(perm) + tuple(z for z in range(len(nest.bounds))
                                    if z in zaxes)
        if order != tuple(range(len(nest.bounds))):
            orders.append(order)
    return orders[:2]


def candidate_schedules(nest: LoopNest, *, quick: bool = False,
                        max_candidates: Optional[int] = None,
                        operands: Optional[Dict[str, Any]] = None
                        ) -> List[Schedule]:
    """Legal candidates for a nest, default schedule always first.

    Enumerates block geometries (rows × lanes) and — for level-mapped
    nests — tile-factor and grid-axis-order variants, filtered through
    :func:`schedule_is_legal` (with ``operands``, gather tables count
    against the VMEM budget too).  Deterministic order (the generator is
    pure enumeration), so ranking + tie-breaks reproduce run to run.
    """
    rowses = _QUICK_ROWS if quick else _ROWS_CHOICES
    laneses = _QUICK_LANES if quick else _LANES_CHOICES
    depths = _QUICK_DEPTHS if quick else _DEPTH_CHOICES
    raw: List[Schedule] = [DEFAULT_SCHEDULE]
    for rows, lanes in itertools.product(rowses, laneses):
        raw.append(Schedule(rows=rows, lanes=lanes))
    if _nest_has_output(nest):
        factors = _LANES_FACTORS if not quick else _LANES_FACTORS[:2]
        for lf in factors:
            for rf in _ROWS_FACTORS:
                raw.append(Schedule(lanes_tile_factor=lf,
                                    rows_tile_factor=rf))
        for order in _axis_orders(nest):
            raw.append(Schedule(axis_order=order))
    # Depth × geometry cross: every geometry candidate at every FIFO
    # depth, so the tuner can trade run-ahead against tile size under the
    # depth-aware VMEM budget (a deep+large candidate that busts it is
    # simply filtered below).
    for s in list(raw):
        for d in depths:
            if d != s.buffer_depth:
                raw.append(dataclasses.replace(s, buffer_depth=d))
    if not quick:
        # Asymmetric per-stream FIFO depths (full runs only): deep
        # run-ahead for one operand, shallow for the other.  Only 2-read-
        # stream nests get the treatment — legality filters any schedule
        # whose entry count mismatches the lowered stream count.
        n_reads = sum(1 for r in nest.refs if r.kind == Direction.READ)
        if n_reads == 2:
            for sd in ((4, 2), (2, 4), (3, 2), (2, 3)):
                raw.append(Schedule(stream_depths=sd))

    seen, out = set(), []
    for s in raw:
        if s in seen:
            continue
        seen.add(s)
        if schedule_is_legal(nest, s, operands=operands)[0]:
            out.append(s)
    if max_candidates is not None:
        out = out[:max_candidates]
    return out


# --------------------------------------------------------------------------
# Model prune: Eq. (1)–(3) on the padded iteration space + step charge
# --------------------------------------------------------------------------


def _padded_bounds(nest: LoopNest, sched: Schedule) -> Tuple[Tuple[int, ...],
                                                             int]:
    """(padded bounds, grid steps) of the schedule — lowering-accurate
    where the lowering accepts the nest, closed-form otherwise."""
    try:
        lowered = _lower_candidate(nest, sched)
    except (LoweringError, ValueError):
        E = sched.block_elems
        inner = -(-nest.bounds[-1] // E) * E
        padded = tuple(nest.bounds[:-1]) + (inner,)
        return padded, math.prod(nest.bounds[:-1]) * (inner // E)
    if isinstance(lowered, LoweredNest):
        return lowered.padded_bounds, lowered.steps
    padded = tuple(lowered.plan.nest.bounds[:-1]) + (
        lowered.grid[-1] * sched.block_elems,)
    return padded, lowered.steps


def model_cost(nest: LoopNest, sched: Schedule, *,
               step_cost: int = STEP_COST) -> float:
    """Eq. (1) instruction count on the *padded* iteration space, plus a
    per-grid-step dispatch charge.

    The instruction model alone is block-geometry-blind (it counts loop
    iterations, not tiles); padding the bounds to what the schedule
    actually executes charges ragged-shape blowup, and the step charge
    models the per-block loop/DMA overhead that makes tiny blocks slow.
    Never raises for lane-legal candidates — geometry the lowering cannot
    express falls back to the closed-form block count.

    The step charge splits evenly into loop bookkeeping and DMA latency;
    the latency half shrinks as ``buffer_depth − 1`` in-flight fetches
    cover it (the data mover's run-ahead hides the fetch behind compute).
    At the default depth 2 the charge is exactly ``step_cost`` — the
    historical model — so deeper buffering is strictly cheaper per step
    and the tuner can justify smaller tiles at deeper FIFOs for
    bandwidth-bound nests.  Measurement still decides: the model only
    ranks who gets wall-clocked.
    """
    padded, steps = _padded_bounds(nest, sched)
    padded_nest = dataclasses.replace(nest, bounds=padded)
    plan = ssrify(padded_nest, num_lanes=auto_lanes(padded_nest), force=True)
    half = step_cost / 2.0
    per_step = half + half / (_max_depth(sched) - 1)
    return float(plan.n_ssr + per_step * steps)


def schedule_fingerprint(nest: LoopNest, sched: Schedule) -> Any:
    """What the schedule *lowers to*: grid, tiles, block shapes.

    Two schedules with the same fingerprint build byte-identical kernels
    (e.g. every tile-factor variant of a problem whose tiles all clamp to
    the padded dims), so measuring them separately would just race noise
    against itself.  Falls back to the schedule's own identity where the
    generic lowering cannot express the nest (hand-geometry kernels own
    their knob semantics).
    """
    try:
        lowered = _lower_candidate(nest, sched)
    except (LoweringError, ValueError):
        return ("sched", sched)
    if isinstance(lowered, LoweredNest):
        # Axis order only matters across axes that actually iterate:
        # permuting unit grid axes yields a byte-identical kernel.
        eff_order = tuple(l for k, l in enumerate(lowered.axis_order)
                          if lowered.grid[k] > 1)
        return ("nest", lowered.grid, lowered.tiles, eff_order,
                tuple(s.stream.block_shape for s in lowered.in_streams),
                lowered.out_stream.stream.block_shape, sched.acc_dtype,
                sched.buffer_depth, sched.stream_depths)
    return ("flat", lowered.grid,
            tuple(s.stream.block_shape for s in lowered.in_streams),
            sched.acc_dtype, sched.buffer_depth, sched.stream_depths)


def rank_candidates(nest: LoopNest, candidates: Sequence[Schedule], *,
                    top_k: int = 8,
                    step_cost: int = STEP_COST) -> List[Schedule]:
    """Deterministic model ranking; the default schedule always survives.

    Sort key is (model cost, schedule identity) so equal-cost candidates
    order reproducibly.  Candidates that lower to the same geometry
    (:func:`schedule_fingerprint`) collapse to one survivor — the default
    schedule claims its own fingerprint, so an equal-geometry variant can
    never displace it — and the default is re-inserted if the prune would
    drop it: the measurement phase must always race the baseline.
    """
    def ident(s: Schedule):
        return (s.rows, s.lanes, s.lanes_tile_factor, s.rows_tile_factor,
                s.axis_order or (), s.acc_dtype, s.buffer_depth,
                s.stream_depths or ())

    ranked = sorted(candidates,
                    key=lambda s: (model_cost(nest, s,
                                              step_cost=step_cost),
                                   ident(s)))
    default_fp = schedule_fingerprint(nest, DEFAULT_SCHEDULE) \
        if DEFAULT_SCHEDULE in candidates else None
    kept: List[Schedule] = []
    seen = set()
    for s in ranked:
        fp = schedule_fingerprint(nest, s)
        if fp in seen:
            continue
        seen.add(fp)
        kept.append(DEFAULT_SCHEDULE if fp == default_fp else s)
    kept = kept[:max(1, top_k)]
    if DEFAULT_SCHEDULE in candidates and DEFAULT_SCHEDULE not in kept:
        kept[-1] = DEFAULT_SCHEDULE
    return kept


# --------------------------------------------------------------------------
# Measure + commit
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TuneResult:
    """Outcome of one autotune run (or cache hit).

    ``degraded`` marks a run whose measurement phase hit a typed
    infrastructure failure and fell back to the default schedule without
    committing; ``committed`` is False when the winner was measured fine
    but the cache commit itself failed (the winner is still returned and
    used this process); ``stragglers`` counts timing samples the
    :class:`~repro.runtime.fault.StragglerMonitor` flagged and re-raced.
    """

    key: str
    schedule: Schedule
    tuned_us: float
    default_us: float
    candidates: int
    measured: int
    from_cache: bool = False
    degraded: bool = False
    committed: bool = True
    stragglers: int = 0

    @property
    def speedup(self) -> float:
        return self.default_us / self.tuned_us if self.tuned_us else 1.0

    @property
    def is_default(self) -> bool:
        return self.schedule == DEFAULT_SCHEDULE


def autotune(nest: LoopNest, body: Callable, operands: Dict[str, Any], *,
             mode: str = "reduce", out_dtype="float32",
             num_lanes: Optional[int] = None,
             interpret: Optional[bool] = None,
             call: Optional[Callable[[Schedule], Any]] = None,
             candidates: Optional[Sequence[Schedule]] = None,
             top_k: int = 8, warmup: int = 1, iters: int = 3,
             cores: int = 1,
             cache: Optional[ScheduleCache] = None,
             use_cache: bool = True, force: bool = False,
             clock: Optional[Callable[[], float]] = None,
             straggler: Optional[Any] = None) -> TuneResult:
    """Search → prune → measure → commit the winning schedule.

    ``call(schedule)`` executes the kernel under one candidate; the default
    routes through :func:`~repro.core.lowering.ssr_call` with ``nest``/
    ``body``/``operands``, but a whole-kernel callable (e.g. a registry
    entry's ``ssr`` function taking ``schedule=``) slots in so hand-
    scheduled kernels with their own geometry vocabulary tune through the
    same machinery.  The default schedule is always among the measured
    survivors, so the committed winner is never slower than the default
    *as measured* — the gate ``benchmarks/kernel_bench.py`` re-checks.

    **Straggler-hardened measurement**: every timed sample passes through
    a :class:`~repro.runtime.fault.StragglerMonitor` (injectable via
    ``straggler``; ``clock`` is the injectable time source, mirroring
    ``Supervisor.clock``).  A flagged sample — a GC pause, a noisy
    neighbour, an injected clock skew — is re-raced immediately instead
    of entering the race, so one poisoned timing cannot commit a
    slower-than-default winner.  A typed infrastructure failure during
    measurement (the ``measure`` seam) degrades the run to the default
    schedule without committing; a failed cache commit is recorded and
    tolerated (the measured winner still serves this process).

    A cache hit short-circuits everything unless ``force=True``.
    """
    from .lowering import ssr_call

    cache = cache or (global_cache() if use_cache else None)
    key = cache_key(nest, operands, mode=mode, out_dtype=str(out_dtype),
                    cores=cores)
    if cache is not None and not force:
        hit = cache.get(key)
        if hit is not None:
            meta = cache.meta(key) or {}
            m = meta.get("meta", {})
            return TuneResult(key=key, schedule=hit,
                              tuned_us=float(m.get("tuned_us", 0.0)),
                              default_us=float(m.get("default_us", 0.0)),
                              candidates=int(m.get("candidates", 0)),
                              measured=0, from_cache=True)

    if call is None:
        def call(sched: Schedule):
            return ssr_call(nest, body, operands, mode=mode,
                            out_dtype=out_dtype, schedule=sched,
                            num_lanes=num_lanes, interpret=interpret)

    cands = list(candidates) if candidates is not None \
        else candidate_schedules(nest, operands=operands)
    if DEFAULT_SCHEDULE not in cands:
        cands.insert(0, DEFAULT_SCHEDULE)
    survivors = rank_candidates(nest, cands, top_k=top_k)

    # Round-robin measurement: one timed call per survivor per round, so
    # machine drift (thermal, background load) hits every candidate
    # equally instead of biasing whichever was measured last.
    import jax

    clock = clock or time.perf_counter
    monitor = straggler
    if monitor is None:
        from repro.runtime.fault import StragglerMonitor

        # warmup = the first full round, so the baseline mixes every
        # candidate's step time before any sample can be flagged
        monitor = StragglerMonitor(warmup_steps=len(survivors))
    sample = 0
    stragglers = 0

    def _timed(sched: Schedule) -> float:
        nonlocal sample, stragglers
        resilience.inject("measure")
        t0 = clock()
        jax.block_until_ready(jax.tree.leaves(call(sched)))
        dt = clock() - t0
        if monitor.observe(sample, dt):
            # poisoned sample: re-race once rather than let a transient
            # stall decide (or distort) the committed winner
            stragglers += 1
            sample += 1
            resilience.inject("measure")
            t0 = clock()
            jax.block_until_ready(jax.tree.leaves(call(sched)))
            dt = clock() - t0
        sample += 1
        return dt

    best = [float("inf")] * len(survivors)
    try:
        for _ in range(max(0, warmup)):
            for sched in survivors:
                jax.block_until_ready(jax.tree.leaves(call(sched)))
        for _ in range(max(1, iters)):
            for i, sched in enumerate(survivors):
                best[i] = min(best[i], _timed(sched))
    except resilience.fallback_error_types() as e:
        resilience.record_fallback(
            seam=resilience.classify(e), site="autotune", error=e,
            from_schedule="measure", to_schedule="default", key=key)
        return TuneResult(key=key, schedule=DEFAULT_SCHEDULE, tuned_us=0.0,
                          default_us=0.0, candidates=len(cands), measured=0,
                          degraded=True, committed=False,
                          stragglers=stragglers)
    timings = [(us * 1e6, i, sched)
               for i, (us, sched) in enumerate(zip(best, survivors))]
    default_us = next(us for us, _, s in timings if s == DEFAULT_SCHEDULE)
    tuned_us, _, winner = min(timings)

    committed = False
    if cache is not None:
        try:
            cache.put(key, winner, meta={
                "tuned_us": tuned_us, "default_us": default_us,
                "candidates": len(cands), "measured": len(survivors),
                "stragglers": stragglers,
                "nest": nest_signature(nest), "mode": mode,
                "out_dtype": str(out_dtype), "cores": cores,
                "backend": _backend(),
            })
            committed = True
        except resilience.fallback_error_types() as e:
            # the winner is still valid for this process; only the
            # persistence failed — record it, don't crash the tuner
            resilience.record_fallback(
                seam=resilience.classify(e), site="autotune", error=e,
                from_schedule="winner", to_schedule="uncommitted", key=key)
    return TuneResult(key=key, schedule=winner, tuned_us=tuned_us,
                      default_us=default_us, candidates=len(cands),
                      measured=len(survivors), committed=committed,
                      stragglers=stragglers)


def invalidate(nest: LoopNest, operands: Dict[str, Any], *,
               mode: str = "reduce", out_dtype: str = "float32",
               cores: int = 1,
               cache: Optional[ScheduleCache] = None) -> bool:
    """Explicitly drop the cached schedule for one tuning problem."""
    cache = cache or global_cache()
    return cache.invalidate(
        cache_key(nest, operands, mode=mode, out_dtype=str(out_dtype),
                  cores=cores))


def quarantine(nest: LoopNest, operands: Dict[str, Any], *,
               mode: str = "reduce", out_dtype: str = "float32",
               cores: int = 1,
               cache: Optional[ScheduleCache] = None) -> str:
    """Sideline the committed schedule for one tuning problem.

    Dispatch calls this when a *tuned* schedule fails to lower or compile:
    the entry is renamed to ``.corrupt`` (invalidate + negative-cache +
    cross-process generation bump), so the poisoned winner cannot be
    served again while the default schedule carries the traffic.  Returns
    the quarantined key.
    """
    cache = cache or global_cache()
    key = cache_key(nest, operands, mode=mode, out_dtype=str(out_dtype),
                    cores=cores)
    cache.quarantine(key)
    return key


def quarantine_dag(nests: Sequence[LoopNest], operands: Dict[str, Any], *,
                   mode: str = "map", out_dtype: str = "float32",
                   cores: int = 1,
                   cache: Optional[ScheduleCache] = None,
                   uniforms: Optional[Dict[str, Any]] = None) -> str:
    """DAG-keyed twin of :func:`quarantine` for ``ssr_dag_call`` dispatch."""
    cache = cache or global_cache()
    key = dag_cache_key(nests, operands, mode=mode,
                        out_dtype=str(out_dtype), cores=cores,
                        uniforms=uniforms)
    cache.quarantine(key)
    return key


# --------------------------------------------------------------------------
# DAG fusion search: enumerate legal graph cuts, prune by the Eq. (1)–(3)
# model + VMEM budget, measure survivors, commit the winning partition.
#
# A "cut" is a set of edge indices into ``ChainDAG.edges``.  Every cut
# edge materialises its intermediate as an HBM buffer (one kernel stops,
# another reloads); every fused edge keeps it in VMEM scratch and credits
# the eliminated store+loads exactly as ``chain_dag``'s accounting does.
# The committed winner lands in the same :class:`ScheduleCache` under a
# DAG-specific key, so ``ssr_dag_call`` resolves the best partitioning
# transparently on the next dispatch.
# --------------------------------------------------------------------------


def dag_cache_key(nests: Sequence[LoopNest], operands: Dict[str, Any], *,
                  mode: str = "map", out_dtype: str = "float32",
                  backend: Optional[str] = None, cores: int = 1,
                  uniforms: Optional[Dict[str, Any]] = None) -> str:
    """Stable hex digest identifying one DAG fusion problem."""
    backend = backend or _backend()
    blob = json.dumps({
        "v": SCHEDULE_CACHE_VERSION,
        "dag": [nest_signature(n) for n in nests],
        "operands": operand_signature(operands),
        "uniforms": operand_signature(uniforms or {}),
        "mode": mode,
        "out_dtype": str(out_dtype),
        "backend": backend,
        "cores": int(cores),
    }, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


def lookup_dag(nests: Sequence[LoopNest], operands: Dict[str, Any], *,
               mode: str = "map", out_dtype: str = "float32",
               cores: int = 1,
               cache: Optional[ScheduleCache] = None,
               uniforms: Optional[Dict[str, Any]] = None) -> Schedule:
    """Cache-only partition resolution for ``ssr_dag_call`` dispatch."""
    cache = cache or global_cache()
    key = dag_cache_key(nests, operands, mode=mode,
                        out_dtype=str(out_dtype), cores=cores,
                        uniforms=uniforms)
    return cache.get(key) or DEFAULT_SCHEDULE


def enumerate_cuts(dag: ChainDAG) -> List[Tuple[int, ...]]:
    """Every subset of edge indices, smallest cuts first.

    DAGs here have a handful of edges (a diamond has 3–4), so the 2^E
    enumeration is exact; the legality and model prunes below do the
    narrowing.  ``()`` (all-fused) is always first, the full cut
    (all-unfused) always last.
    """
    idx = range(len(dag.edges))
    cuts: List[Tuple[int, ...]] = []
    for r in range(len(dag.edges) + 1):
        cuts.extend(itertools.combinations(idx, r))
    return cuts


def dag_cut_is_legal(dag: ChainDAG, cut: Sequence[int], *,
                     sched: Schedule = DEFAULT_SCHEDULE,
                     itemsize: int = 4) -> Tuple[bool, str]:
    """(legal, reason) for one cut: single-exit components + VMEM budget.

    A fused component must have exactly one stage whose value leaves it
    (the map/reduce epilogue writes one output), and each component's
    depth-buffered working set — external streams + cut-edge reloads +
    refcounted intermediate slots — must fit the VMEM budget.
    """
    from .lowering import _component_exit, _dag_components

    cutset = frozenset(int(i) for i in cut)
    for i in cutset:
        if not 0 <= i < len(dag.edges):
            return False, (f"edge index {i} out of range for "
                           f"{len(dag.edges)} edges")
    comps = _dag_components(dag, cutset)
    block = sched.rows * sched.lanes * itemsize
    depth = _max_depth(sched)
    for comp in comps:
        try:
            _component_exit(dag, comp, cutset)
        except LoweringError as e:
            return False, str(e)
        inside = set(comp)
        n_ext = sum(len(dag.stages[s].allocations) for s in comp)
        n_cut_in = sum(1 for i, e in enumerate(dag.edges)
                       if i in cutset and e.consumer_stage in inside)
        intra = {e.name for i, e in enumerate(dag.edges)
                 if i not in cutset and e.producer_stage in inside}
        vmem = (stream_vmem_bytes(block, depth) * (n_ext + n_cut_in)
                + stream_vmem_bytes(block, depth)   # the output stream
                + len(intra) * block                # intermediate slots
                + block)                            # reduce accumulator
        if vmem > VMEM_BUDGET_BYTES:
            return False, (f"component {comp} working set "
                           f"{vmem / 2**20:.1f} MiB exceeds budget "
                           f"{VMEM_BUDGET_BYTES / 2**20:.0f} MiB")
    return True, "ok"


def dag_model_cost(dag: ChainDAG, cut: Sequence[int], *,
                   sched: Schedule = DEFAULT_SCHEDULE,
                   step_cost: int = STEP_COST) -> float:
    """Eq. (1)–(3) cost of executing the DAG under one cut.

    Each fused component is ONE stream region (:func:`repro.core.compiler.
    _fused_region_count`: single setup, bodies summed, union of lanes);
    each cut edge charges the store its producer pays and the load its
    consumer re-issues (2·ΠL explicit accesses — exactly the accesses
    ``chain_dag`` credits as eliminated when the edge fuses); and every
    component pays the per-grid-step dispatch charge of its own kernel.
    """
    from .lowering import _dag_components

    cutset = frozenset(int(i) for i in cut)
    comps = _dag_components(dag, cutset)
    elems = math.prod(dag.bounds)
    steps = -(-dag.bounds[-1] // sched.block_elems) * \
        math.prod(dag.bounds[:-1])
    half = step_cost / 2.0
    per_step = half + half / (_max_depth(sched) - 1)
    total = 0.0
    for comp in comps:
        total += _fused_region_count([dag.stages[s] for s in comp],
                                     dag.bounds)
        total += per_step * steps
    cut_names = {dag.edges[i].name for i in cutset}
    total += elems * (len(cutset) + len(cut_names))  # loads + stores
    return float(total)


def autotune_dag(nests: Sequence[LoopNest], bodies: Sequence[Callable],
                 operands: Dict[str, Any], *,
                 mode: str = "map", out_dtype="float32",
                 num_lanes: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 top_k: int = 4, warmup: int = 1, iters: int = 3,
                 cores: int = 1,
                 cache: Optional[ScheduleCache] = None,
                 use_cache: bool = True, force: bool = False,
                 uniforms: Optional[Dict[str, Any]] = None) -> TuneResult:
    """Search the DAG's legal cuts → prune by model → measure → commit.

    The all-fused cut ``()`` and the full cut (all edges materialised —
    the unfused composition) always race, so the committed partition is
    never slower than either endpoint *as measured* — the gate
    ``bench_dag`` re-checks.  The winner is committed as a
    :class:`Schedule` whose ``cut_edges`` records the partition, under
    :func:`dag_cache_key`, so a subsequent plain ``ssr_dag_call`` resolves
    it transparently.
    """
    import jax

    from .lowering import _dag_for, _uniform_items, ssr_dag_call

    nests = tuple(nests)
    bodies = tuple(bodies)
    dag = _dag_for(nests, num_lanes)
    cache = cache or (global_cache() if use_cache else None)
    # normalise exactly like ssr_dag_call so the committed key matches the
    # one its transparent dispatch looks up
    uniforms = dict(_uniform_items(uniforms))
    key = dag_cache_key(nests, operands, mode=mode,
                        out_dtype=str(out_dtype), cores=cores,
                        uniforms=uniforms)
    if cache is not None and not force:
        hit = cache.get(key)
        if hit is not None:
            meta = cache.meta(key) or {}
            m = meta.get("meta", {})
            return TuneResult(key=key, schedule=hit,
                              tuned_us=float(m.get("tuned_us", 0.0)),
                              default_us=float(m.get("default_us", 0.0)),
                              candidates=int(m.get("candidates", 0)),
                              measured=0, from_cache=True)

    legal = [c for c in enumerate_cuts(dag)
             if dag_cut_is_legal(dag, c)[0]]
    full = tuple(range(len(dag.edges)))
    ranked = sorted(legal, key=lambda c: (dag_model_cost(dag, c), c))
    survivors = ranked[:max(1, top_k)]
    for anchor in ((), full):            # both endpoints always race
        if anchor in legal and anchor not in survivors:
            survivors.append(anchor)

    def call(cut: Tuple[int, ...]):
        sched = dataclasses.replace(DEFAULT_SCHEDULE, cut_edges=cut)
        return ssr_dag_call(nests, bodies, operands, mode=mode,
                            out_dtype=out_dtype, schedule=sched,
                            num_lanes=num_lanes, interpret=interpret,
                            uniforms=uniforms)

    best = [float("inf")] * len(survivors)
    try:
        for _ in range(max(0, warmup)):
            for cut in survivors:
                jax.block_until_ready(jax.tree.leaves(call(cut)))
        for _ in range(max(1, iters)):
            for i, cut in enumerate(survivors):
                resilience.inject("measure")
                t0 = time.perf_counter()
                jax.block_until_ready(jax.tree.leaves(call(cut)))
                best[i] = min(best[i], time.perf_counter() - t0)
    except resilience.fallback_error_types() as e:
        resilience.record_fallback(
            seam=resilience.classify(e), site="autotune_dag", error=e,
            from_schedule="measure", to_schedule="default", key=key)
        return TuneResult(key=key, schedule=DEFAULT_SCHEDULE, tuned_us=0.0,
                          default_us=0.0, candidates=len(legal), measured=0,
                          degraded=True, committed=False)
    timings = [(us * 1e6, cut) for us, cut in zip(best, survivors)]
    fused_us = next((us for us, c in timings if c == ()), float("inf"))
    tuned_us, winner_cut = min(timings, key=lambda t: (t[0], t[1]))
    winner = dataclasses.replace(DEFAULT_SCHEDULE, cut_edges=winner_cut)

    committed = False
    if cache is not None:
        try:
            cache.put(key, winner, meta={
                "tuned_us": tuned_us, "default_us": fused_us,
                "candidates": len(legal), "measured": len(survivors),
                "dag": [nest_signature(n) for n in nests],
                "edges": len(dag.edges), "cut_edges": list(winner_cut),
                "mode": mode, "out_dtype": str(out_dtype), "cores": cores,
                "backend": _backend(),
            })
            committed = True
        except resilience.fallback_error_types() as e:
            resilience.record_fallback(
                seam=resilience.classify(e), site="autotune_dag", error=e,
                from_schedule="winner", to_schedule="uncommitted", key=key)
    return TuneResult(key=key, schedule=winner, tuned_us=tuned_us,
                      default_us=fused_us, candidates=len(legal),
                      measured=len(survivors), committed=committed)
