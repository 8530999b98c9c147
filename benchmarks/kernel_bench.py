"""Wall-clock microbenchmarks of the kernel suite (CPU harness).

On this CPU container, Pallas interpret mode measures the *interpreter*, not
TPU silicon, so the honest comparison is: XLA-compiled reference path
(μs/call, real) + static stream-analysis (bytes streamed, FIFO reuse, VMEM
footprint — the quantities that decide TPU speed).  On a real TPU this file
runs unchanged with ``interpret=False`` to time Mosaic kernels.

Two kernel sets are enumerated with zero edits here:

* every ``@register_kernel`` entry with an ``example`` factory is timed
  (``ref`` path) and smoke-run (``ssr`` path);
* every fused (stream-chained) variant from ``kernels.chained.fused_cases``
  is raced against its unfused two-kernel composition — interleaved
  best-of-N of the real call path, plus the compiled-HLO audit that the
  intermediate buffer is gone.  Numeric disagreement beyond the case's
  tolerance is a hard failure (exit 1): a fast wrong kernel is not a win.

Run as a script to persist ``BENCH_kernels.json`` (schema below), the
machine-readable perf trajectory tracked across PRs::

    PYTHONPATH=src python benchmarks/kernel_bench.py [--quick] [--out PATH]

Schema (version 6): ``{"schema": 6, "generated_unix": float, "quick": bool,
"results": [{"name", "group", "variant", "value", "units", "rows",
"lanes", "grid", "tuned", "buffer_depth", ...}, ...]}`` — every row
carries schedule provenance (the block geometry that produced it, the data
mover's FIFO depth, and whether it came from the autotuner).  The
``autotune`` group races tuned-vs-default schedules across every
NestKernel family head — the §13 halo (stencil1d/stencil2d) and
online-rescale (attention) migrations included — and is gated: tuned may
never be slower than default beyond noise, and — in full (non ``--quick``)
runs, where iteration counts rise above CI-box noise — at least one kernel
must win with a non-default schedule.  The ``pipeline`` group is the
bandwidth-bound buffer-depth sweep (large-stride gemv + stencil1d +
causal attention): the
autotuned pipelined schedule races the synchronous depth-2 default under a
≤ 1e-5 agreement gate, and a full run must find a depth > 2 winner.  The
``dag`` group (v4) runs the whole-program fusion search of
``autotune_dag`` over every ``kernels.dag`` DagCase (layernorm,
softmax_xent, mlp_block) and races the committed graph cut against both
endpoints — all-fused and all-unfused; dag rows additionally carry
``cut_edges`` (the materialised edge indices) and ``fused_stages`` (the
largest fused component's stage count).  The ``sparse`` group (v5) gates
the CSR indirection-stream kernels (spmv, spmm): streamed-vs-baseline
agreement ≤ 1e-5, Eq. (1)–(3) model speedup > 1, and a non-zero count of
eliminated index-handling instructions; sparse rows carry problem
provenance — ``nnz`` and ``density`` of the CSR operand — alongside
``eliminated_idx_instrs``.  The ``chaos`` group (v6, run via
``--chaos-smoke``) injects one fault per resilience seam (cache read,
lowering, compile) into a dispatch with a committed tuned schedule and
gates the degradation ladder: the degraded result must agree with the
healthy one ≤ 1e-5 and the steady-state post-fault path must stay within
a bounded overhead of the healthy tuned path.

Each run also appends one summary line to ``BENCH_history.jsonl`` (date,
git sha, per-kernel speedups, committed dag cuts, and a ``degraded``
resilience summary — zero in healthy runs) — the cheap longitudinal
record raced across PRs without diffing full artifacts.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lowering import DEFAULT_SCHEDULE, Schedule
from repro.kernels import registry
from repro.kernels.chained import fused_cases
from repro.launch.compile_cache import enable_compile_cache

RNG = np.random.default_rng(0)

#: v2: every row carries schedule provenance — the block geometry that
#: produced it (``rows``/``lanes``), the grid it launched (``None`` where
#: no Pallas grid is involved, e.g. pure-model rows) and a ``tuned`` flag
#: (True when the schedule came from the autotuner, not the default).
#: v3: adds ``buffer_depth`` — the data mover's FIFO depth the row ran
#: under (2 = synchronous Pallas double-buffer, > 2 = explicit N-deep DMA
#: rotation) — and the gated ``pipeline`` group.
#: v4: adds the gated ``dag`` group (whole-program fusion search); dag
#: rows carry ``cut_edges`` (materialised edge indices of the committed
#: partition) and ``fused_stages`` (largest fused component's stage
#: count) alongside the schedule provenance fields.
#: v5: adds the gated ``sparse`` group (CSR indirection streams); sparse
#: rows carry ``nnz``/``density`` problem provenance and the model rows
#: additionally ``eliminated_idx_instrs`` — the per-nnz index loads +
#: pointer arithmetic the indirect AGU removes from the hot loop.
#: v6: adds the gated ``chaos`` group (``--chaos-smoke``): per-seam
#: degraded-vs-healthy agreement and steady-state overhead rows, and the
#: history line's ``degraded`` resilience summary (fallback/degraded
#: dispatch counters + structured fallback-event count).
BENCH_SCHEMA = 6


def _row(name: str, group: str, variant: str, value: float, units: str,
         **extras) -> Dict:
    row = {"name": name, "group": group, "variant": variant,
           "value": float(value), "units": units,
           # schedule provenance defaults: the untuned default geometry
           "rows": DEFAULT_SCHEDULE.rows, "lanes": DEFAULT_SCHEDULE.lanes,
           "grid": None, "tuned": False,
           "buffer_depth": DEFAULT_SCHEDULE.buffer_depth}
    row.update(extras)
    return row


def _sched_extras(sched: Schedule, grid=None, *, tuned: bool) -> Dict:
    """Provenance fields for a row that ran under ``sched``."""
    return {"rows": sched.rows, "lanes": sched.lanes,
            "grid": list(grid) if grid is not None else None,
            "tuned": bool(tuned), "buffer_depth": sched.buffer_depth}


def _time(fn: Callable, *args, warmup: int = 2, iters: int = 5) -> float:
    """Best-of-N μs/call (min over iters absorbs scheduler noise)."""
    for _ in range(warmup):
        jax.block_until_ready(jax.tree.leaves(fn(*args)))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(jax.tree.leaves(out))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6  # μs


def bench_reference_paths(iters: int = 5) -> List[Dict]:
    """Best-of-N of the jitted XLA reference path of every registered
    kernel (problem sizes as in §4.2, from each entry's example factory)."""
    rows = []
    print("\n== kernel reference path timings (XLA:CPU, best-of-N μs/call) ==")
    for entry in registry.entries():
        if entry.example is None:
            continue
        args, kwargs = entry.example(RNG)
        if entry.name in SPARSE_GATED:
            # CSR refs validate + densify host-side (the ELL width is
            # data-dependent), so they cannot be traced — time them eagerly.
            fn = lambda *a, _e=entry, _kw=kwargs: _e.ref(*a, **_kw)
        else:
            fn = jax.jit(lambda *a, _e=entry, _kw=kwargs: _e.ref(*a, **_kw))
        us = _time(fn, *args, iters=iters)
        print(f"{entry.name:16s} {entry.problem:26s} {us:10.1f} μs")
        rows.append(_row(f"kernel_ref/{entry.name}", "kernel_ref", "ref",
                         us, "us/call", iters=iters))
    return rows


def smoke_ssr_paths() -> List[Dict]:
    """One interpret-mode call per registered streamed kernel (CI smoke)."""
    rows = []
    print("\n== kernel ssr-path smoke (Pallas interpret) ==")
    for entry in registry.entries():
        if entry.example is None:
            continue
        args, kwargs = entry.example(RNG)
        t0 = time.perf_counter()
        jax.block_until_ready(
            jax.tree.leaves(entry.ssr(*args, **kwargs)))
        ms = (time.perf_counter() - t0) * 1e3
        print(f"{entry.name:16s} ok ({ms:7.1f} ms incl. trace)")
        rows.append(_row(f"kernel_ssr_smoke/{entry.name}", "kernel_ssr_smoke",
                         "ssr", ms, "interpret ms"))
    return rows


def bench_stream_reports() -> List[Dict]:
    """Static stream analysis of the production matmul (FIFO reuse etc.)."""
    from repro.core import BlockStream, Direction, ssr_pallas
    from jax.experimental.pallas import tpu as pltpu

    rows = []
    print("\n== stream-analysis of ssr_matmul tiles ==")
    for (m, n, k, bm, bn, bk) in [(512, 512, 512, 128, 128, 128),
                                  (1024, 1024, 1024, 256, 256, 256)]:
        def body(a_ref, b_ref, o_ref, acc_ref):  # noqa: ANN001
            pass  # analysis only

        grid = (m // bm, n // bn, k // bk)
        fn = ssr_pallas(
            body, grid=grid,
            in_streams=[
                BlockStream((bm, bk), lambda i, j, kk: (i, kk), name="A"),
                BlockStream((bk, bn), lambda i, j, kk: (kk, j), name="B"),
            ],
            out_streams=[BlockStream((bm, bn), lambda i, j, kk: (i, j),
                                     Direction.WRITE, name="C")],
            out_shapes=[jax.ShapeDtypeStruct((m, n), jnp.bfloat16)],
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            validate=True,
        )
        rep = fn.report(dtypes=[jnp.bfloat16, jnp.bfloat16, jnp.bfloat16])
        ai = 2 * m * n * k / rep.hbm_bytes_unique
        print(f"matmul {m}x{n}x{k} tiles ({bm},{bn},{bk}): "
              f"VMEM {rep.vmem_bytes / 2**20:.1f} MiB, "
              f"streamed {rep.hbm_bytes_streamed / 2**20:.0f} MiB, "
              f"unique {rep.hbm_bytes_unique / 2**20:.0f} MiB, "
              f"reuse {rep.reuse_factor:.1f}x, AI {ai:.0f} flop/byte")
        rows.append(_row(f"stream/matmul{m}", "stream", "ssr",
                         rep.reuse_factor, "reuse_factor",
                         vmem_bytes=rep.vmem_bytes,
                         hbm_bytes_streamed=rep.hbm_bytes_streamed))
    return rows


# --------------------------------------------------------------------------
# Compiled-nest kernels: ssr-vs-baseline agreement + cost-model gate
# --------------------------------------------------------------------------

#: Numeric agreement required between a compiled-nest kernel's streamed
#: and baseline engines (same problem, same dtype — only delivery differs).
NEST_AGREEMENT_TOL = 1e-5


def _nest_models():
    """(kernel name, cost-model LoopNest) for the compiled-nest gate."""
    from repro.core import compiler
    from repro.kernels.stencil import TAPS

    return [("gemm", compiler.gemm_nest(32, 32, 32)),
            ("stencil1d", compiler.stencil_nest(1024, TAPS)),
            ("gemv", compiler.gemv_nest(64, 64))]


def bench_nest_gate() -> List[Dict]:
    """Gate the registry's compiled-nest kernels (gemm, stencil1d).

    Two hard requirements per kernel, mirrored in ``validate_bench_json``:
    the streamed engine must agree with the baseline engine within
    ``NEST_AGREEMENT_TOL`` (a fast wrong kernel is not a win), and the
    Eq. (1)–(3) model must predict a speedup > 1 for the paper-size nest
    (otherwise streaming it is pointless and the registry entry is wrong).
    """
    from repro.core.lowering import plan_stats

    rows = []
    print("\n== compiled-nest gate: ssr vs baseline + cost model ==")
    for name, nest in _nest_models():
        entry = registry.get(name)
        args, kwargs = entry.example(RNG)
        ssr_out = entry.ssr(*args, **kwargs)
        base_out = entry.baseline(*args, **kwargs)
        diff = max(float(jnp.max(jnp.abs(jnp.asarray(g) - jnp.asarray(w))))
                   for g, w in zip(jax.tree.leaves(ssr_out),
                                   jax.tree.leaves(base_out)))
        if diff > NEST_AGREEMENT_TOL:
            print(f"FAIL {name}: ssr disagrees with baseline by {diff:.2e} "
                  f"> {NEST_AGREEMENT_TOL}", file=sys.stderr)
            raise SystemExit(1)
        # score the configuration the registry actually executes: every
        # affine ref streamed (auto lanes), not the 2-mover default the
        # nest-output path cannot even lower
        from repro.core.nest_analysis import auto_lanes

        stats = plan_stats(nest, num_lanes=auto_lanes(nest))
        speedup = stats.n_base / stats.n_ssr
        if not (stats.ssrified and speedup > 1.0):
            print(f"FAIL {name}: Eq. (3) model speedup {speedup:.2f} <= 1",
                  file=sys.stderr)
            raise SystemExit(1)
        print(f"{name:12s} agreement {diff:.1e}  model speedup "
              f"{speedup:4.2f}x (N {stats.n_base} -> {stats.n_ssr})")
        rows.append(_row(f"nest/{name}", "nest", "agreement", diff,
                         "max_abs_diff"))
        rows.append(_row(f"nest/{name}", "nest", "model", speedup,
                         "model_speedup", n_base=stats.n_base,
                         n_ssr=stats.n_ssr))
    return rows


# --------------------------------------------------------------------------
# CSR indirection streams: sparse gate (agreement + eliminated-instr model)
# --------------------------------------------------------------------------

#: The CSR kernels the sparse gate covers — the indirection-stream path
#: (Indirection-SSR / Sparse-SSR follow-ups to the base paper).
SPARSE_GATED = ("spmv", "spmm")


def _sparse_cases(quick: bool):
    """(name, args, nest, nnz, density) per gated sparse kernel."""
    from repro.core import compiler
    from repro.kernels import sparse as sp

    cases = []
    m, n, c = (32, 48, 16) if quick else (96, 128, 32)
    for name, density in (("spmv", 0.15), ("spmm", 0.15)):
        data, indices, indptr = sp.random_csr(RNG, m, n, density)
        if name == "spmv":
            x = RNG.standard_normal(n).astype(np.float32)
            args = (data, indices, indptr, x)
        else:
            x = RNG.standard_normal((n, c)).astype(np.float32)
            args = (data, indices, indptr, x)
        vals, cidx, rows_m, k = sp.csr_to_ell(data, indices, indptr, n)
        if name == "spmv":
            nest = compiler.spmv_nest(rows_m, k)
        else:
            pitch = -(-c // sp._TABLE_PITCH) * sp._TABLE_PITCH
            nest = compiler.spmm_nest(rows_m, c, k, pitch)
        cases.append((name, args, nest, int(data.size),
                      float(data.size) / float(m * n)))
    return cases


def bench_sparse(quick: bool = False) -> List[Dict]:
    """Gate the CSR indirection-stream kernels (spmv, spmm).

    Hard failures (exit 1), mirrored in ``validate_sparse_rows``:

    * the streamed gather engine disagrees with the explicit-``jnp.take``
      baseline beyond ``NEST_AGREEMENT_TOL`` (a fast wrong gather is not
      a win);
    * the Eq. (1)–(3) model — extended with the per-nnz index loads +
      pointer arithmetic the indirect AGU eliminates — predicts speedup
      ≤ 1, or eliminates zero index-handling instructions (then the
      indirect ref never reached the streamer and the lowering is wrong).
    """
    from repro.core.lowering import plan_stats
    from repro.core.nest_analysis import auto_lanes

    rows: List[Dict] = []
    print("\n== CSR sparse gate: ssr vs baseline + indirection model ==")
    for name, args, nest, nnz, density in _sparse_cases(quick):
        entry = registry.get(name)
        ssr_out = np.asarray(entry.ssr(*args))
        base_out = np.asarray(entry.baseline(*args))
        diff = float(np.max(np.abs(ssr_out - base_out))) if ssr_out.size \
            else 0.0
        if diff > NEST_AGREEMENT_TOL:
            print(f"FAIL {name}: ssr disagrees with baseline by {diff:.2e} "
                  f"> {NEST_AGREEMENT_TOL}", file=sys.stderr)
            raise SystemExit(1)
        stats = plan_stats(nest, num_lanes=auto_lanes(nest))
        speedup = stats.n_base / stats.n_ssr
        if not (stats.ssrified and speedup > 1.0):
            print(f"FAIL {name}: Eq. (3) model speedup {speedup:.2f} <= 1",
                  file=sys.stderr)
            raise SystemExit(1)
        if stats.eliminated_idx_instrs <= 0:
            print(f"FAIL {name}: indirect ref eliminated no index-handling "
                  "instructions — the gather never reached the streamer",
                  file=sys.stderr)
            raise SystemExit(1)
        print(f"{name:12s} nnz {nnz:6d} (density {density:5.3f})  "
              f"agreement {diff:.1e}  model speedup {speedup:4.2f}x  "
              f"idx instrs eliminated {stats.eliminated_idx_instrs}")
        rows.append(_row(f"sparse/{name}", "sparse", "agreement", diff,
                         "max_abs_diff", nnz=nnz, density=density))
        rows.append(_row(f"sparse/{name}", "sparse", "model", speedup,
                         "model_speedup", nnz=nnz, density=density,
                         n_base=stats.n_base, n_ssr=stats.n_ssr,
                         eliminated_idx_instrs=stats.eliminated_idx_instrs))
    return rows


def validate_sparse_rows(results: Sequence[Dict]) -> None:
    """The sparse acceptance gate, re-applied to persisted rows.

    Every gated CSR kernel must have agreement + model rows; every sparse
    row must carry the v5 problem provenance (integer ``nnz``, ``density``
    in [0, 1]); agreement must hold to ``NEST_AGREEMENT_TOL``; and the
    model row must record a speedup > 1 with a positive
    ``eliminated_idx_instrs`` count.
    """
    by_kernel: Dict[str, Dict[str, Dict]] = {}
    for r in results:
        if r.get("group") == "sparse":
            if not isinstance(r.get("nnz"), int) or r["nnz"] < 0:
                raise ValueError(f"sparse row missing integer nnz: {r}")
            d = r.get("density")
            if not isinstance(d, (int, float)) or not 0.0 <= d <= 1.0:
                raise ValueError(f"sparse row density outside [0, 1]: {r}")
            by_kernel.setdefault(r["name"].split("/")[1], {})[r["variant"]] = r
    for kern in SPARSE_GATED:
        pair = by_kernel.get(kern)
        if not pair or "agreement" not in pair or "model" not in pair:
            raise ValueError(f"no sparse gate rows for {kern!r}")
        if pair["agreement"]["value"] > NEST_AGREEMENT_TOL:
            raise ValueError(
                f"{kern}: ssr-vs-baseline disagreement "
                f"{pair['agreement']['value']} > {NEST_AGREEMENT_TOL}")
        model = pair["model"]
        if model["value"] <= 1.0:
            raise ValueError(f"{kern}: model speedup {model['value']} <= 1")
        if not isinstance(model.get("eliminated_idx_instrs"), int) \
                or model["eliminated_idx_instrs"] <= 0:
            raise ValueError(
                f"{kern}: model row must record a positive "
                "eliminated_idx_instrs count")


# --------------------------------------------------------------------------
# Schedule autotuner sweep: tuned-vs-default gate + provenance rows
# --------------------------------------------------------------------------

#: The kernels the autotune gate covers (the CI ``autotune-smoke`` job):
#: every ``ssr_call``-routed NestKernel family head, incl. the §13
#: halo-read (stencil1d/stencil2d) and online-rescaled-accumulator
#: (attention) lowerings.
TUNE_GATED = ("reduction", "relu", "gemm", "stencil1d", "stencil2d",
              "gemv", "attention")

#: Wall-clock tolerance of the tuned-never-slower gate: the tuner measures
#: then the gate *re-races* winner vs default interleaved, so a winner that
#: only won by scheduler noise may regress a little — but not this much.
TUNE_GATE_TOL = 1.15


def _autotune_cases(quick: bool):
    """(name, nest, operands, mode, candidates, call, grid_of) per kernel.

    ``operands``/``mode`` replicate exactly what ``NestKernel`` passes to
    ``autotune.lookup``, so the committed winners are the ones transparent
    dispatch later finds.  Every gated kernel — the §13 halo/rescale
    migrations included — searches the standard lowering-derived candidate
    set; illegal geometries (e.g. a tile too narrow for a halo window) are
    auto-filtered by the legality walk.
    """
    from repro.core import autotune, compiler
    from repro.kernels.stencil import TAPS

    cases = []

    def add(name, nest, operands, mode, candidates=None, grid_of=None):
        entry = registry.get(name)
        args, kwargs = entry.example(RNG)

        def call(sched, _e=entry, _a=args, _k=kwargs):
            return _e.ssr(*_a, schedule=sched, **_k)

        if grid_of is None:
            def grid_of(sched, _nest=nest):
                try:
                    return autotune._lower_candidate(_nest, sched).grid
                except Exception:
                    return None
        cases.append((name, nest, operands, mode, candidates, call, grid_of))

    (x, y), _ = registry.get("reduction").example(RNG)
    add("reduction", compiler.dot_product_nest(x.shape[0]),
        {"A": x, "B": y}, "reduce")

    (xr,), _ = registry.get("relu").example(RNG)
    add("relu", compiler.elementwise_nest(xr.shape[0]), {"X": xr}, "map")

    (a, b), _ = registry.get("gemm").example(RNG)
    add("gemm", compiler.gemm_nest(a.shape[0], b.shape[1], a.shape[1]),
        {"A": a, "B": b}, "reduce")

    (xs, ws), _ = registry.get("stencil1d").example(RNG)
    add("stencil1d", compiler.stencil_nest(xs.shape[0] - (TAPS - 1), TAPS),
        {"x": xs, "w": ws}, "reduce")

    (x2, wx2, wy2), _ = registry.get("stencil2d").example(RNG)
    h2, wd2 = x2.shape[0] - (TAPS - 1), x2.shape[1] - (TAPS - 1)
    add("stencil2d", compiler.stencil2d_nest(h2, wd2, TAPS),
        {"x": x2, "wx": wx2, "wy": wy2}, "reduce")

    (ag, xg), _ = registry.get("gemv").example(RNG)
    add("gemv", compiler.gemv_nest(*ag.shape), {"A": ag, "x": xg}, "reduce")

    (q, k, v), _ = registry.get("attention").example(RNG)
    add("attention", compiler.attention_nest(q.shape[0], k.shape[0],
                                             q.shape[1]),
        {"Q": q, "K": k, "V": v}, "reduce")
    return cases


def bench_autotune(quick: bool = False) -> List[Dict]:
    """Run the schedule search per gated kernel; gate tuned ≥ default.

    Hard failures (exit 1), mirrored in ``validate_autotune_rows``:

    * the tuned schedule's output disagrees with the default schedule's
      beyond the entry tolerance (a fast wrong schedule is not a win);
    * the tuned schedule re-races slower than ``TUNE_GATE_TOL`` × default
      on any gated kernel;
    * no kernel picked a measurably faster non-default schedule — the
      whole point of the search.
    """
    from repro.core import autotune

    rows: List[Dict] = []
    iters = 3 if quick else 7
    nondefault_wins = 0
    print(f"\n== schedule autotune sweep (best-of-{iters} μs/call) ==")
    for name, nest, operands, mode, cands, call, grid_of \
            in _autotune_cases(quick):
        entry = registry.get(name)
        if cands is None:
            cands = autotune.candidate_schedules(nest, quick=quick)
        res = autotune.autotune(
            nest, None, operands, mode=mode, out_dtype="float32",
            call=call, candidates=cands, top_k=4 if quick else 8,
            warmup=1, iters=iters, force=True)

        tuned_out = call(res.schedule)
        default_out = call(DEFAULT_SCHEDULE)
        for g, w in zip(jax.tree.leaves(tuned_out),
                        jax.tree.leaves(default_out)):
            if not np.allclose(np.asarray(g), np.asarray(w), **entry.tol):
                # drop the committed winner before failing: a schedule
                # that changes the answer must never stay in the
                # persistent cache for transparent dispatch to pick up
                autotune.global_cache().invalidate(res.key)
                print(f"FAIL {name}: tuned schedule disagrees with default "
                      f"beyond tol {entry.tol} (cache entry invalidated)",
                      file=sys.stderr)
                raise SystemExit(1)

        # Final interleaved race, winner vs default: the screening pass
        # (round-robin best-of-N inside the tuner) picks a candidate, the
        # race validates it — and its verdict is what gets committed.  A
        # screening pick that loses the race is replaced by the default,
        # so the persisted schedule is never slower than the default *as
        # measured here*.  A default winner has nothing to race: both
        # thunks would be the same cached pipeline, pure jitter.
        import dataclasses as _dc

        nondefault = not res.is_default
        if nondefault:
            tf, td = _interleaved_best(lambda: call(res.schedule),
                                       lambda: call(DEFAULT_SCHEDULE),
                                       (), {}, warmup=2, iters=max(7, iters))
            if tf > td:
                print(f"  {name}: screening winner lost the final race "
                      f"({tf:.1f} vs {td:.1f} μs) — committing default")
                autotune.global_cache().put(res.key, DEFAULT_SCHEDULE, meta={
                    "tuned_us": td, "default_us": td,
                    "candidates": res.candidates, "raced_back": True})
                res = _dc.replace(res, schedule=DEFAULT_SCHEDULE,
                                  tuned_us=td, default_us=td)
                tf, nondefault = td, False
        else:
            tf = td = _time(lambda: call(res.schedule), iters=max(5, iters))
        if tf > td * TUNE_GATE_TOL:   # tripwire: unreachable by design
            print(f"FAIL {name}: tuned schedule {tf:.1f} μs is slower than "
                  f"default {td:.1f} μs × {TUNE_GATE_TOL}", file=sys.stderr)
            raise SystemExit(1)
        if nondefault and tf < td:
            nondefault_wins += 1
        s = res.schedule
        grid = grid_of(s)
        print(f"{name:12s} tuned ({s.rows}x{s.lanes}"
              + (f", order={s.axis_order}" if s.axis_order else "")
              + f") {tf:10.1f} μs  default {td:10.1f} μs  "
              f"speedup {td / tf:4.2f}x  candidates {res.candidates}")
        rows.append(_row(f"autotune/{name}", "autotune", "tuned", tf,
                         "us/call", speedup=td / tf,
                         candidates=res.candidates,
                         measured=res.measured, nondefault=nondefault,
                         cache_key=res.key,
                         # tuned = "came from the autotuner, not the
                         # default" — a default winner is not tuned
                         **_sched_extras(s, grid, tuned=nondefault)))
        rows.append(_row(f"autotune/{name}", "autotune", "default", td,
                         "us/call",
                         **_sched_extras(DEFAULT_SCHEDULE,
                                         grid_of(DEFAULT_SCHEDULE),
                                         tuned=False)))
    if nondefault_wins == 0:
        # Whether a non-default geometry wins is a measurement, not an
        # invariant: on a noisy box with --quick iteration counts every
        # final race can (correctly) fall back to the default.  The full
        # run gates it hard — that is the artifact committed per PR; the
        # CI smoke gates only the robust half (tuned never slower,
        # outputs agree).
        if not quick:
            print("FAIL autotune: no kernel picked a measurably faster "
                  "non-default schedule", file=sys.stderr)
            raise SystemExit(1)
        print("WARN autotune: no non-default winner in this --quick run "
              "(noise-dominated); the full run gates this hard")
    print(f"non-default winners: {nondefault_wins}/{len(TUNE_GATED)}")
    return rows


def validate_autotune_rows(results: Sequence[Dict],
                           require_nondefault: bool = True) -> None:
    """The autotune acceptance gate, re-applied to persisted rows.

    ``require_nondefault=False`` (quick/CI-smoke runs) keeps only the
    robust half of the gate — tuned never slower than default — because a
    non-default win is a measurement, not an invariant (see
    :func:`bench_autotune`).
    """
    by_kernel: Dict[str, Dict[str, Dict]] = {}
    for r in results:
        if r.get("group") == "autotune":
            by_kernel.setdefault(r["name"].split("/")[1], {})[r["variant"]] = r
    for kern in TUNE_GATED:
        pair = by_kernel.get(kern)
        if not pair or "tuned" not in pair or "default" not in pair:
            raise ValueError(f"no autotune rows for {kern!r}")
        if pair["tuned"]["value"] > pair["default"]["value"] * TUNE_GATE_TOL:
            raise ValueError(
                f"{kern}: tuned {pair['tuned']['value']} slower than "
                f"default {pair['default']['value']} x {TUNE_GATE_TOL}")
    if require_nondefault and not any(
            p["tuned"].get("nondefault") and
            p["tuned"]["value"] < p["default"]["value"]
            for p in by_kernel.values() if "tuned" in p
            and "default" in p):
        raise ValueError("no kernel won with a non-default schedule")


# --------------------------------------------------------------------------
# Pipelined emission sweep: buffer-depth race on bandwidth-bound kernels
# --------------------------------------------------------------------------

#: Numeric agreement gate of the pipeline sweep: the pipelined schedule
#: must match the synchronous default to ≤ 1e-5 — tighter than the entry
#: tolerances because only operand *delivery* changes, never arithmetic.
PIPE_AGREEMENT_TOL = 1e-5

#: The kernels the pipeline gate covers: the bandwidth-bound entries
#: (GEMV streams the whole matrix once per call; the stencil is ~1 fmadd
#: per byte; attention's kv walk streams K and V once per query tile),
#: where hiding the fetch behind compute is the whole game.
PIPE_GATED = ("gemv", "stencil1d", "attention")


def _pipeline_cases(quick: bool):
    """(name, nest, operands, mode, candidates, call, grid, tol) per kernel.

    Large-stride shapes — bigger than the §4.2 example sizes — so the
    per-step fetch the rotation hides is resolvable above timing noise.
    Candidates cross the depth choices with each kernel's native geometry
    knob (the stencil's block width); depth 2 is always among them, so the
    sweep races the synchronous default by construction.  ``mode`` is what
    ``NestKernel`` passes to the schedule-cache lookup, so the committed
    winners are the ones transparent dispatch later finds.
    """
    from repro.core import compiler
    from repro.kernels.attention import ssr_flash_attention
    from repro.kernels.gemv import ssr_gemv
    from repro.kernels.stencil import TAPS, ssr_stencil1d

    depths = (2, 3) if quick else (2, 3, 4)
    tol = {"rtol": PIPE_AGREEMENT_TOL, "atol": PIPE_AGREEMENT_TOL}
    cases = []

    m, n = (64, 1024) if quick else (256, 4096)
    a = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    xv = jnp.asarray(RNG.standard_normal(n), jnp.float32)
    cases.append((
        "gemv", compiler.gemv_nest(m, n), {"A": a, "x": xv}, "reduce",
        [Schedule(buffer_depth=d) for d in depths],
        lambda s, _a=a, _x=xv: ssr_gemv(_a, _x, schedule=s),
        (m // 8,), tol))

    n_st = (1 << 14) if quick else (1 << 16)
    xs = jnp.asarray(RNG.standard_normal(n_st + TAPS - 1), jnp.float32)
    ws = jnp.asarray(RNG.standard_normal(TAPS) * 0.3, jnp.float32)
    widths = (128, 512) if quick else (128, 512, 1024)
    cases.append((
        "stencil1d", compiler.stencil_nest(n_st, TAPS),
        {"x": xs, "w": ws}, "reduce",
        [Schedule(lanes=w, buffer_depth=d)
         for w in widths for d in depths],
        lambda s, _x=xs, _w=ws: ssr_stencil1d(_x, _w, schedule=s),
        None, tol))

    sq = 256 if quick else 1024
    q = jnp.asarray(RNG.standard_normal((sq, 64)), jnp.float32)
    kk = jnp.asarray(RNG.standard_normal((sq, 64)), jnp.float32)
    vv = jnp.asarray(RNG.standard_normal((sq, 64)), jnp.float32)
    cases.append((
        "attention", compiler.attention_nest(sq, sq, 64),
        {"Q": q, "K": kk, "V": vv}, "reduce",
        [Schedule(buffer_depth=d) for d in depths],
        lambda s, _q=q, _k=kk, _v=vv: ssr_flash_attention(
            _q, _k, _v, causal=True, schedule=s),
        None, tol))
    return cases


def bench_pipeline(quick: bool = False) -> List[Dict]:
    """Race the autotuned pipelined schedule vs the synchronous default.

    Hard failures (exit 1), mirrored in ``validate_pipeline_rows``:

    * the pipelined winner's output disagrees with the synchronous
      depth-2 default beyond ``PIPE_AGREEMENT_TOL`` (delivery must never
      change the numbers);
    * the committed winner re-races slower than ``TUNE_GATE_TOL`` ×
      default on any gated kernel (never-slower is structural: a race
      loser is replaced by the default before commit);
    * in full runs, no kernel won with ``buffer_depth > 2`` — the
      latency-hiding claim this sweep exists to gate.
    """
    import dataclasses as _dc

    from repro.core import autotune

    rows: List[Dict] = []
    iters = 3 if quick else 7
    deep_wins = 0
    print(f"\n== pipelined emission sweep (best-of-{iters} μs/call) ==")
    for name, nest, operands, mode, cands, call, grid, tol \
            in _pipeline_cases(quick):
        res = autotune.autotune(
            nest, None, operands, mode=mode, out_dtype="float32",
            call=call, candidates=cands, top_k=len(cands),
            warmup=1, iters=iters, force=True)

        tuned_out = call(res.schedule)
        sync_out = call(DEFAULT_SCHEDULE)
        for g, w in zip(jax.tree.leaves(tuned_out),
                        jax.tree.leaves(sync_out)):
            if not np.allclose(np.asarray(g), np.asarray(w), **tol):
                autotune.global_cache().invalidate(res.key)
                print(f"FAIL {name}: pipelined schedule disagrees with the "
                      f"synchronous default beyond {PIPE_AGREEMENT_TOL} "
                      "(cache entry invalidated)", file=sys.stderr)
                raise SystemExit(1)

        # Final interleaved race vs the synchronous default — same
        # commit-the-race-verdict contract as bench_autotune: a screening
        # winner that loses here is replaced by the default in the cache,
        # so the persisted schedule is never slower as measured.
        pipelined = res.schedule.buffer_depth > 2
        if res.schedule != DEFAULT_SCHEDULE:
            tf, td = _interleaved_best(lambda: call(res.schedule),
                                       lambda: call(DEFAULT_SCHEDULE),
                                       (), {}, warmup=2, iters=max(7, iters))
            if tf > td:
                print(f"  {name}: pipelined winner lost the final race "
                      f"({tf:.1f} vs {td:.1f} μs) — committing default")
                autotune.global_cache().put(res.key, DEFAULT_SCHEDULE, meta={
                    "tuned_us": td, "default_us": td,
                    "candidates": res.candidates, "raced_back": True})
                res = _dc.replace(res, schedule=DEFAULT_SCHEDULE,
                                  tuned_us=td, default_us=td)
                tf, pipelined = td, False
        else:
            tf = td = _time(lambda: call(res.schedule), iters=max(5, iters))
        if tf > td * TUNE_GATE_TOL:   # tripwire: unreachable by design
            print(f"FAIL {name}: pipelined {tf:.1f} μs slower than "
                  f"sync default {td:.1f} μs × {TUNE_GATE_TOL}",
                  file=sys.stderr)
            raise SystemExit(1)
        if pipelined and tf < td:
            deep_wins += 1
        s = res.schedule
        print(f"{name:12s} depth={s.buffer_depth} lanes={s.lanes} "
              f"{tf:10.1f} μs  sync default {td:10.1f} μs  "
              f"speedup {td / tf:5.2f}x  candidates {res.candidates}")
        rows.append(_row(f"pipeline/{name}", "pipeline", "pipelined", tf,
                         "us/call", speedup=td / tf,
                         candidates=res.candidates, cache_key=res.key,
                         agreement_tol=PIPE_AGREEMENT_TOL,
                         **_sched_extras(s, grid, tuned=pipelined)))
        rows.append(_row(f"pipeline/{name}", "pipeline", "sync", td,
                         "us/call",
                         **_sched_extras(DEFAULT_SCHEDULE, grid,
                                         tuned=False)))
    if deep_wins == 0:
        if not quick:
            print("FAIL pipeline: no bandwidth-bound kernel won with "
                  "buffer_depth > 2", file=sys.stderr)
            raise SystemExit(1)
        print("WARN pipeline: no depth > 2 winner in this --quick run "
              "(noise-dominated); the full run gates this hard")
    print(f"pipelined winners: {deep_wins}/{len(PIPE_GATED)}")
    return rows


def validate_pipeline_rows(results: Sequence[Dict],
                           require_deep: bool = True) -> None:
    """The pipeline acceptance gate, re-applied to persisted rows.

    ``require_deep=False`` (quick/CI-smoke runs) keeps only the robust
    half — pipelined never slower than the synchronous default; a full
    artifact must additionally record a ``buffer_depth > 2`` winner.
    """
    by_kernel: Dict[str, Dict[str, Dict]] = {}
    for r in results:
        if r.get("group") == "pipeline":
            by_kernel.setdefault(r["name"].split("/")[1], {})[r["variant"]] = r
    for kern in PIPE_GATED:
        pair = by_kernel.get(kern)
        if not pair or "pipelined" not in pair or "sync" not in pair:
            raise ValueError(f"no pipeline rows for {kern!r}")
        if pair["pipelined"]["value"] > pair["sync"]["value"] * TUNE_GATE_TOL:
            raise ValueError(
                f"{kern}: pipelined {pair['pipelined']['value']} slower "
                f"than sync {pair['sync']['value']} x {TUNE_GATE_TOL}")
        if pair["sync"].get("buffer_depth") != 2:
            raise ValueError(f"{kern}: sync row must record depth 2")
    if require_deep and not any(
            p["pipelined"].get("buffer_depth", 2) > 2 and
            p["pipelined"]["value"] < p["sync"]["value"]
            for p in by_kernel.values()
            if "pipelined" in p and "sync" in p):
        raise ValueError("no kernel won with buffer_depth > 2")


# --------------------------------------------------------------------------
# Fused (stream-chained) variants vs their unfused compositions
# --------------------------------------------------------------------------

# Bench problem sizes, chosen so the quantity chaining eliminates (the
# intermediate HBM round-trip + the second kernel dispatch) is resolvable
# above CPU timing noise.  gemv_relu uses the paper's §4.2 GEMV size.
_FUSED_BENCH_ARGS: Dict[str, Callable[[bool], Tuple[tuple, dict]]] = {
    "gemv_relu": lambda quick: (
        (jnp.asarray(RNG.standard_normal((64, 64)), jnp.float32),
         jnp.asarray(RNG.standard_normal(64), jnp.float32)), {}),
    "stencil1d_relu": lambda quick: (
        (jnp.asarray(RNG.standard_normal((2048 if quick else 16384) + 10),
                     jnp.float32),
         jnp.asarray(RNG.standard_normal(11) * 0.3, jnp.float32)), {}),
    "sum_sq_diff": lambda quick: (
        (jnp.asarray(RNG.standard_normal(16384 if quick else 262144),
                     jnp.float32),
         jnp.asarray(RNG.standard_normal(16384 if quick else 262144),
                     jnp.float32)), {}),
    "axpy_dot": lambda quick: (
        (jnp.asarray(RNG.standard_normal(16384 if quick else 262144),
                     jnp.float32),
         jnp.asarray(RNG.standard_normal(16384 if quick else 262144),
                     jnp.float32),
         jnp.asarray(RNG.standard_normal(16384 if quick else 262144),
                     jnp.float32)), {"alpha": 0.5}),
}


def _interleaved_best(f: Callable, u: Callable, args: tuple, kwargs: dict,
                      warmup: int, iters: int) -> Tuple[float, float]:
    """Race two callables back-to-back so drift hits both equally."""
    for _ in range(warmup):
        jax.block_until_ready(jax.tree.leaves(f(*args, **kwargs)))
        jax.block_until_ready(jax.tree.leaves(u(*args, **kwargs)))
    bf = bu = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(jax.tree.leaves(f(*args, **kwargs)))
        bf = min(bf, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(jax.tree.leaves(u(*args, **kwargs)))
        bu = min(bu, time.perf_counter() - t0)
    return bf * 1e6, bu * 1e6


def bench_fused(quick: bool = False, check_hlo: bool = True) -> List[Dict]:
    """Fused single kernel vs unfused two-kernel composition, best-of-N.

    Numeric disagreement beyond the case tolerance raises ``SystemExit`` —
    the benchmark doubles as the CI agreement gate.  When ``check_hlo`` is
    set, the compiled-HLO fusion audit (intermediate buffer counts) is also
    recorded per case.
    """
    from repro.launch.hlo_analysis import check_fusion

    rows = []
    warmup, iters = (1, 3) if quick else (2, 9)
    print("\n== fused (stream-chained) vs unfused composition "
          f"(interpret, best-of-{iters} μs/call) ==")
    for case in fused_cases():
        bench_args = _FUSED_BENCH_ARGS.get(case.name)
        # a case without a tuned bench size still benches at its example
        # size — new FusedCases land here with zero edits
        args, kwargs = (bench_args(quick) if bench_args
                        else case.example(RNG))

        fused_out = case.fused(*args, **kwargs)
        unfused_out = case.unfused(*args, **kwargs)
        for g, w in zip(jax.tree.leaves(fused_out),
                        jax.tree.leaves(unfused_out)):
            if not np.allclose(np.asarray(g), np.asarray(w), **case.tol):
                print(f"FAIL {case.name}: fused disagrees with unfused "
                      f"beyond tol {case.tol}", file=sys.stderr)
                raise SystemExit(1)

        tf, tu = _interleaved_best(case.fused, case.unfused, args, kwargs,
                                   warmup, iters)
        speedup = tu / tf
        extras: Dict = {"iters": iters}
        if check_hlo:
            dtype, dims = case.inter_type(*args, **kwargs)
            chk = check_fusion(case.fused, case.unfused, args, kwargs,
                               dtype, dims)
            extras.update(
                intermediate=f"{dtype}{list(dims)}",
                fused_buffers=chk.fused_buffers,
                unfused_buffers=chk.unfused_buffers,
                intermediate_eliminated=chk.intermediate_eliminated)
        print(f"{case.name:16s} fused {tf:10.1f} μs  unfused {tu:10.1f} μs  "
              f"speedup {speedup:4.2f}x"
              + (f"  intermediate_eliminated={extras.get('intermediate_eliminated')}"
                 if check_hlo else ""))
        rows.append(_row(f"fused/{case.name}", "fused", "fused",
                         tf, "us/call", **extras))
        rows.append(_row(f"fused/{case.name}", "fused", "unfused",
                         tu, "us/call", speedup=speedup, **extras))
    return rows


# --------------------------------------------------------------------------
# Fused DAGs: cost-model-guided cut search + committed-partition gate
# --------------------------------------------------------------------------

#: The DAG kernels the fusion-search gate covers (the registry's
#: ``kernels.dag`` cases — each a 3-stage graph with a multi-consumer
#: intermediate).
DAG_GATED = ("layernorm", "softmax_xent", "mlp_block")


def _dag_fused_stages(dag, cut: Sequence[int]) -> int:
    """Largest fused component's stage count under ``cut`` (3 = the whole
    diamond in one kernel, 1 = fully unfused)."""
    from repro.core.lowering import _dag_components

    comps = _dag_components(dag, frozenset(int(i) for i in cut))
    return max(len(c) for c in comps)


def _interleaved3(a: Callable, b: Callable, c: Callable,
                  warmup: int, iters: int) -> Tuple[float, float, float]:
    """Three-way interleaved best-of-N (μs) so drift hits all equally."""
    for _ in range(warmup):
        for fn in (a, b, c):
            jax.block_until_ready(jax.tree.leaves(fn()))
    best = [float("inf")] * 3
    for _ in range(iters):
        for i, fn in enumerate((a, b, c)):
            t0 = time.perf_counter()
            jax.block_until_ready(jax.tree.leaves(fn()))
            best[i] = min(best[i], time.perf_counter() - t0)
    return best[0] * 1e6, best[1] * 1e6, best[2] * 1e6


def bench_dag(quick: bool = False, check_hlo: bool = True) -> List[Dict]:
    """Whole-program fusion search per DagCase; gate the committed cut.

    Per case: (a) fused / unfused / reference outputs must agree within
    the case tolerance (a fast wrong partition is not a win); (b) the
    compiled-HLO audit must show the fused graph materialises no more
    intermediate-shaped buffers than the unfused composition, with fewer
    bytes written; (c) ``autotune_dag`` searches the legal graph cuts and
    the committed cut is re-raced three-way against both endpoints —
    all-fused (``()``) and all-unfused (every edge materialised).  A
    committed cut that loses the race is replaced by the winning endpoint
    in the schedule cache (same race-back contract as ``bench_autotune``),
    so the persisted partition is never slower than either endpoint as
    measured; ``TUNE_GATE_TOL`` is the tripwire.
    """
    import dataclasses as _dc

    from repro.core import autotune
    from repro.core.lowering import _dag_for
    from repro.kernels.dag import dag_cases
    from repro.launch.hlo_analysis import check_dag_fusion

    rows: List[Dict] = []
    iters = 3 if quick else 7
    print(f"\n== fused-DAG cut search (interpret, best-of-{iters} μs/call) ==")
    for case in dag_cases():
        args, kwargs = case.example(RNG)
        nests, bodies, operands, mode, uniforms = case.spec(*args, **kwargs)
        dag = _dag_for(tuple(nests), None)
        full = tuple(range(len(dag.edges)))

        def run(cut, _c=case, _a=args, _k=kwargs):
            sched = _dc.replace(DEFAULT_SCHEDULE, cut_edges=tuple(cut))
            return _c.fused(*_a, schedule=sched, **_k)

        fused_out = run(())
        unfused_out = case.unfused(*args, **kwargs)
        ref_out = case.ref(*args, **kwargs)
        for label, other in (("unfused", unfused_out), ("ref", ref_out)):
            for g, w in zip(jax.tree.leaves(fused_out),
                            jax.tree.leaves(other)):
                if not np.allclose(np.asarray(g), np.asarray(w),
                                   **case.tol):
                    print(f"FAIL {case.name}: fused DAG disagrees with "
                          f"{label} beyond tol {case.tol}", file=sys.stderr)
                    raise SystemExit(1)

        extras: Dict = {"iters": iters, "edges": len(dag.edges)}
        if check_hlo:
            chk = check_dag_fusion(
                lambda *a, _c=case, **k: _c.fused(
                    *a, schedule=DEFAULT_SCHEDULE, **k),
                case.unfused, args, kwargs,
                case.inters(*args, **kwargs))
            if not chk.intermediates_eliminated:
                print(f"FAIL {case.name}: fused HLO still materialises the "
                      f"intermediates (buffers {chk.fused_buffers} vs "
                      f"{chk.unfused_buffers}, bytes {chk.fused_bytes_out} "
                      f"vs {chk.unfused_bytes_out})", file=sys.stderr)
                raise SystemExit(1)
            extras.update(fused_buffers=chk.fused_buffers,
                          unfused_buffers=chk.unfused_buffers,
                          intermediates_eliminated=True,
                          bytes_saved=chk.bytes_saved)

        res = autotune.autotune_dag(
            nests, bodies, operands, mode=mode, out_dtype="float32",
            uniforms=uniforms, top_k=4 if quick else 8,
            warmup=1, iters=iters, force=True)
        committed = tuple(res.schedule.cut_edges or ())

        t_cut, t_fused, t_unfused = _interleaved3(
            lambda: run(committed), lambda: run(()), lambda: run(full),
            warmup=2, iters=max(7, iters))
        if t_cut > min(t_fused, t_unfused) and committed not in ((), full):
            better = () if t_fused <= t_unfused else full
            t_best = min(t_fused, t_unfused)
            print(f"  {case.name}: committed cut {list(committed)} lost the "
                  f"final race ({t_cut:.1f} vs {t_best:.1f} μs) — "
                  f"committing endpoint {list(better)}")
            sched = _dc.replace(DEFAULT_SCHEDULE, cut_edges=better)
            autotune.global_cache().put(res.key, sched, meta={
                "tuned_us": t_best, "default_us": t_fused,
                "candidates": res.candidates, "raced_back": True,
                "cut_edges": list(better)})
            res = _dc.replace(res, schedule=sched, tuned_us=t_best)
            committed, t_cut = better, t_best
        elif committed == ():
            t_cut = t_fused
        elif committed == full:
            t_cut = t_unfused
        if t_cut > min(t_fused, t_unfused) * TUNE_GATE_TOL:  # tripwire
            print(f"FAIL {case.name}: committed cut {list(committed)} "
                  f"{t_cut:.1f} μs is slower than the best endpoint "
                  f"{min(t_fused, t_unfused):.1f} μs × {TUNE_GATE_TOL}",
                  file=sys.stderr)
            raise SystemExit(1)

        print(f"{case.name:14s} cut={list(committed)!s:10s} "
              f"{t_cut:10.1f} μs  all-fused {t_fused:10.1f} μs  "
              f"unfused {t_unfused:10.1f} μs  "
              f"vs-unfused {t_unfused / t_cut:4.2f}x  "
              f"candidates {res.candidates}")
        rows.append(_row(f"dag/{case.name}", "dag", "cut", t_cut,
                         "us/call", speedup=t_unfused / t_cut,
                         candidates=res.candidates,
                         measured=res.measured, cache_key=res.key,
                         cut_edges=list(committed),
                         fused_stages=_dag_fused_stages(dag, committed),
                         tuned=committed not in ((), full), **extras))
        rows.append(_row(f"dag/{case.name}", "dag", "fused", t_fused,
                         "us/call", cut_edges=[],
                         fused_stages=_dag_fused_stages(dag, ()), **extras))
        rows.append(_row(f"dag/{case.name}", "dag", "unfused", t_unfused,
                         "us/call", cut_edges=list(full),
                         fused_stages=1, **extras))
    return rows


def validate_dag_rows(results: Sequence[Dict]) -> None:
    """The dag acceptance gate, re-applied to persisted rows.

    Every gated kernel must have cut/fused/unfused rows; every dag row
    must carry the v4 partition provenance (``cut_edges`` list +
    ``fused_stages``); and the committed cut may never be slower than the
    better endpoint beyond ``TUNE_GATE_TOL`` (never-slower is structural:
    a race loser is replaced by an endpoint before commit).
    """
    by_kernel: Dict[str, Dict[str, Dict]] = {}
    for r in results:
        if r.get("group") == "dag":
            if not isinstance(r.get("cut_edges"), list):
                raise ValueError(f"dag row missing cut_edges list: {r}")
            if not isinstance(r.get("fused_stages"), int):
                raise ValueError(f"dag row missing fused_stages: {r}")
            by_kernel.setdefault(r["name"].split("/")[1], {})[r["variant"]] = r
    for kern in DAG_GATED:
        trio = by_kernel.get(kern)
        if not trio or {"cut", "fused", "unfused"} - set(trio):
            raise ValueError(f"no complete dag rows for {kern!r}")
        best = min(trio["fused"]["value"], trio["unfused"]["value"])
        if trio["cut"]["value"] > best * TUNE_GATE_TOL:
            raise ValueError(
                f"{kern}: committed cut {trio['cut']['value']} slower than "
                f"best endpoint {best} x {TUNE_GATE_TOL}")
        if trio["fused"]["cut_edges"]:
            raise ValueError(f"{kern}: all-fused row must record cut_edges "
                             "[]")
        if trio["unfused"]["fused_stages"] != 1:
            raise ValueError(f"{kern}: unfused row must record "
                             "fused_stages 1")


# --------------------------------------------------------------------------
# Machine-readable output: BENCH_kernels.json
# --------------------------------------------------------------------------


def write_bench_json(rows: Sequence[Dict], path: str, quick: bool,
                     **extra) -> None:
    """Persist one BENCH_*.json document (shared with cluster_bench)."""
    doc = {"schema": BENCH_SCHEMA, "generated_unix": time.time(),
           "quick": bool(quick), "results": list(rows), **extra}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(f"\nwrote {len(rows)} results to {path}")


def validate_bench_json(path: str) -> None:
    """Schema gate for CI: malformed output fails loudly."""
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") != BENCH_SCHEMA:
        raise ValueError(f"bad schema: {doc.get('schema')!r}")
    results = doc.get("results")
    if not isinstance(results, list) or not results:
        raise ValueError("results must be a non-empty list")
    for row in results:
        # schema 3+: every row carries schedule provenance, FIFO depth
        # included
        for field in ("name", "group", "variant", "value", "units",
                      "rows", "lanes", "grid", "tuned", "buffer_depth"):
            if field not in row:
                raise ValueError(f"row missing {field!r}: {row}")
        if not isinstance(row["value"], (int, float)):
            raise ValueError(f"non-numeric value: {row}")
    groups = {r["group"] for r in results}
    if "fused" not in groups:
        raise ValueError(f"no fused results recorded (groups: {groups})")
    if "autotune" not in groups:
        raise ValueError(f"no autotune results recorded (groups: {groups})")
    if "pipeline" not in groups:
        raise ValueError(f"no pipeline results recorded (groups: {groups})")
    if "dag" not in groups:
        raise ValueError(f"no dag results recorded (groups: {groups})")
    if "sparse" not in groups:
        raise ValueError(f"no sparse results recorded (groups: {groups})")
    validate_autotune_rows(results, require_nondefault=not doc.get("quick"))
    validate_pipeline_rows(results, require_deep=not doc.get("quick"))
    validate_dag_rows(results)
    validate_sparse_rows(results)
    # compiled-nest gate: gemm/stencil1d must be present, numerically in
    # agreement, and model-profitable
    nest_rows = {(r["name"].split("/")[1], r["variant"]): r
                 for r in results if r["group"] == "nest"}
    for kern in ("gemm", "stencil1d", "gemv"):
        agree = nest_rows.get((kern, "agreement"))
        model = nest_rows.get((kern, "model"))
        if agree is None or model is None:
            raise ValueError(f"no nest gate rows for {kern!r}")
        if agree["value"] > NEST_AGREEMENT_TOL:
            raise ValueError(f"{kern}: ssr-vs-baseline disagreement "
                             f"{agree['value']} > {NEST_AGREEMENT_TOL}")
        if model["value"] <= 1.0:
            raise ValueError(f"{kern}: model speedup {model['value']} <= 1")


def validate_autotune_json(path: str) -> None:
    """Schema + autotune + pipeline gates for the standalone
    ``--autotune-only`` run (the CI ``autotune-smoke`` job)."""
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") != BENCH_SCHEMA:
        raise ValueError(f"bad schema: {doc.get('schema')!r}")
    results = doc.get("results") or []
    for row in results:
        for field in ("name", "group", "variant", "value", "units",
                      "rows", "lanes", "grid", "tuned", "buffer_depth"):
            if field not in row:
                raise ValueError(f"row missing {field!r}: {row}")
    validate_autotune_rows(results, require_nondefault=not doc.get("quick"))
    validate_pipeline_rows(results, require_deep=not doc.get("quick"))


# --------------------------------------------------------------------------
# Chaos smoke: degraded dispatch must stay correct and bounded
# --------------------------------------------------------------------------

#: The dispatch seams the chaos smoke injects into.  ``cache.write`` and
#: ``measure`` are autotune-side seams with no dispatch-path effect, so
#: the exhaustive sweep for those lives in ``tests/test_resilience.py``.
CHAOS_SEAMS = ("cache.read", "lowering", "compile")
CHAOS_AGREEMENT_TOL = 1e-5
#: Steady-state post-fault dispatch (default schedule after quarantine, or
#: tuned again after a transient cache-read miss) vs the healthy tuned
#: path.  Generous: both are jitted XLA paths, the bound only has to catch
#: a degradation ladder that re-lowers or re-compiles on every call.
CHAOS_OVERHEAD_X = 25.0


def bench_chaos(quick: bool = False) -> List[Dict]:
    """Inject one fault per dispatch seam and gate the degradation ladder.

    For each seam in :data:`CHAOS_SEAMS`: commit a tuned schedule, arm a
    one-shot fault, dispatch, and require (hard failures, exit 1):

    * the faulted dispatch still returns — degraded, never dead — and its
      result agrees with the healthy tuned result ≤ 1e-5;
    * the fault actually fired and was absorbed by the matching ladder
      rung (lookup fallback for ``cache.read``; quarantine + default
      re-dispatch for ``lowering``/``compile``), visible in
      ``DISPATCH_STATS`` and the structured fallback log;
    * after the fault drains, steady-state dispatch stays within
      :data:`CHAOS_OVERHEAD_X` of the healthy tuned path — degradation
      may not leave the dispatcher re-lowering forever.
    """
    from repro.core import autotune, compiler, lowering, resilience
    from repro.kernels import frontend

    n = 2048 if quick else 8192
    nest = compiler.dot_product_nest(n)
    x = jnp.asarray(RNG.standard_normal(n), jnp.float32)
    y = jnp.asarray(RNG.standard_normal(n), jnp.float32)
    operands = {"A": x, "B": y}

    def call():
        return lowering.ssr_call(nest, lambda a, b: a * b, operands,
                                 mode="reduce")

    tuned = Schedule(rows=16)
    cache = autotune.global_cache()
    key = autotune.cache_key(nest, operands, mode="reduce",
                             out_dtype="float32")
    iters = 3 if quick else 5

    resilience.reset()
    lowering.reset_dispatch_stats()
    frontend.reset_dispatch_stats()
    cache.put(key, tuned)
    lowering.clear_caches()
    healthy = np.asarray(call())
    t_healthy = _time(call, warmup=1, iters=iters)

    rows: List[Dict] = []
    print("\n== chaos smoke: one injected fault per dispatch seam ==")
    for seam in CHAOS_SEAMS:
        # restore a healthy tuned entry and cold kernel caches so the
        # seam is actually on this dispatch's path
        cache.invalidate(key)
        cache.put(key, tuned)
        lowering.clear_caches()
        before = dict(lowering.DISPATCH_STATS)
        n_events = len(resilience.fallback_events())
        with resilience.inject_faults(seam) as specs:
            degraded_out = np.asarray(call())
        stats = lowering.DISPATCH_STATS
        # relative: different schedules reduce in different orders, so the
        # honest float32 agreement scale is the result's own magnitude
        diff = float(np.max(np.abs(degraded_out - healthy))
                     / max(1.0, float(np.max(np.abs(healthy)))))
        counter = "fallbacks" if seam == "cache.read" else "degraded"
        if specs[0].fired != 1:
            print(f"FAIL chaos/{seam}: fault never fired — the seam is "
                  "not on the dispatch path", file=sys.stderr)
            raise SystemExit(1)
        if stats[counter] != before[counter] + 1:
            print(f"FAIL chaos/{seam}: {counter!r} counter did not "
                  f"advance ({before[counter]} -> {stats[counter]})",
                  file=sys.stderr)
            raise SystemExit(1)
        if len(resilience.fallback_events()) <= n_events:
            print(f"FAIL chaos/{seam}: no structured FallbackEvent "
                  "recorded", file=sys.stderr)
            raise SystemExit(1)
        if diff > CHAOS_AGREEMENT_TOL:
            print(f"FAIL chaos/{seam}: degraded result disagrees with "
                  f"healthy by {diff:.2e} > {CHAOS_AGREEMENT_TOL}",
                  file=sys.stderr)
            raise SystemExit(1)
        # fault drained (one-shot): steady state must be bounded
        t_degraded = _time(call, warmup=1, iters=iters)
        overhead = t_degraded / t_healthy
        if overhead > CHAOS_OVERHEAD_X:
            print(f"FAIL chaos/{seam}: steady-state degraded dispatch "
                  f"{overhead:.1f}x healthy > {CHAOS_OVERHEAD_X}x",
                  file=sys.stderr)
            raise SystemExit(1)
        print(f"chaos/{seam:12s} agreement {diff:.1e}  steady-state "
              f"overhead {overhead:4.2f}x  ladder rung {counter}")
        rows.append(_row(f"chaos/{seam}", "chaos", "agreement", diff,
                         "max_rel_diff", seam=seam, ladder=counter))
        rows.append(_row(f"chaos/{seam}", "chaos", "overhead", overhead,
                         "x_healthy", seam=seam, ladder=counter))
    return rows


def validate_chaos_rows(results: Sequence[Dict]) -> None:
    """The chaos acceptance gate, re-applied to persisted rows."""
    chaos = {(r["name"].split("/")[1], r["variant"]): r
             for r in results if r.get("group") == "chaos"}
    for seam in CHAOS_SEAMS:
        agree = chaos.get((seam, "agreement"))
        over = chaos.get((seam, "overhead"))
        if agree is None or over is None:
            raise ValueError(f"no chaos rows for seam {seam!r}")
        if agree["value"] > CHAOS_AGREEMENT_TOL:
            raise ValueError(
                f"chaos/{seam}: degraded disagreement {agree['value']} > "
                f"{CHAOS_AGREEMENT_TOL}")
        if over["value"] > CHAOS_OVERHEAD_X:
            raise ValueError(
                f"chaos/{seam}: steady-state overhead {over['value']} > "
                f"{CHAOS_OVERHEAD_X}")


# --------------------------------------------------------------------------
# Longitudinal record: BENCH_history.jsonl (one summary line per run)
# --------------------------------------------------------------------------


def _git_sha() -> str:
    """Short sha of the bench's repo, ``"unknown"`` outside a checkout."""
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else "unknown"
    except Exception:
        return "unknown"


def append_bench_history(rows: Sequence[Dict], path: str,
                         quick: bool) -> Dict:
    """Append one JSONL summary line for this run and return it.

    The full ``BENCH_kernels.json`` artifact is overwritten per run; the
    history file accumulates, one line per run, the handful of numbers a
    perf-trajectory review actually reads — per-kernel speedups of the
    raced groups and the graph cuts the fusion search committed — keyed by
    date and git sha.  Kept as JSONL so appends are atomic-ish and old
    lines never need rewriting.
    """
    speedups = {r["name"]: round(float(r["speedup"]), 4)
                for r in rows
                if isinstance(r.get("speedup"), (int, float))}
    dag_cuts = {r["name"].split("/")[1]: r["cut_edges"]
                for r in rows
                if r.get("group") == "dag" and r.get("variant") == "cut"}
    sparse = {r["name"].split("/")[1]: {
                  "nnz": r["nnz"], "density": r["density"],
                  "eliminated_idx_instrs": r["eliminated_idx_instrs"]}
              for r in rows
              if r.get("group") == "sparse" and r.get("variant") == "model"}
    # v6: resilience summary — how often this run's dispatches degraded.
    # Zero across the board in a healthy run; non-zero under --chaos-smoke
    # or an ambient REPRO_FAULTS matrix, where it records that the
    # degradation ladder (not a crash) absorbed the faults.
    from repro.core import lowering as _lowering
    from repro.core import resilience as _resilience
    from repro.kernels import frontend as _frontend
    degraded = {
        "fallbacks": int(_lowering.DISPATCH_STATS["fallbacks"]
                         + _frontend.DISPATCH_STATS["fallbacks"]),
        "degraded": int(_lowering.DISPATCH_STATS["degraded"]
                        + _frontend.DISPATCH_STATS["degraded"]),
        "events": len(_resilience.fallback_events()),
    }
    entry = {
        "schema": BENCH_SCHEMA,
        "date": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_sha": _git_sha(),
        "quick": bool(quick),
        "rows": len(rows),
        "groups": sorted({r["group"] for r in rows}),
        "speedups": speedups,
        "dag_cuts": dag_cuts,
        "sparse": sparse,
        "degraded": degraded,
    }
    with open(path, "a") as f:
        f.write(json.dumps(entry, sort_keys=True) + "\n")
    print(f"appended run summary to {path} ({len(speedups)} speedups, "
          f"{len(dag_cuts)} dag cuts, {len(sparse)} sparse gates, "
          f"{degraded['degraded']} degraded dispatches)")
    return entry


def validate_bench_history(path: str) -> int:
    """Validate every line of the history file; return the line count.

    Each line must be a self-contained JSON object with the summary
    fields — a truncated append or a hand-edit that breaks one line fails
    loudly here rather than corrupting the trajectory silently.
    """
    count = 0
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                entry = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(
                    f"{path}:{lineno}: not valid JSON ({e})") from None
            for field, typ in (("schema", int), ("date", str),
                               ("git_sha", str), ("quick", bool),
                               ("rows", int), ("groups", list),
                               ("speedups", dict), ("dag_cuts", dict)):
                if not isinstance(entry.get(field), typ):
                    raise ValueError(
                        f"{path}:{lineno}: missing/mistyped {field!r}")
            if not (1 <= entry["schema"] <= BENCH_SCHEMA):
                raise ValueError(
                    f"{path}:{lineno}: schema {entry['schema']} outside "
                    f"1..{BENCH_SCHEMA}")
            for name, val in entry["speedups"].items():
                if not isinstance(val, (int, float)):
                    raise ValueError(
                        f"{path}:{lineno}: non-numeric speedup {name!r}")
            for kern, cut in entry["dag_cuts"].items():
                if not isinstance(cut, list):
                    raise ValueError(
                        f"{path}:{lineno}: dag cut for {kern!r} is not a "
                        "list")
            # v5 lines carry the sparse-gate summary; older lines (1–4)
            # legitimately lack it, so the field is optional-but-typed
            if "sparse" in entry:
                if not isinstance(entry["sparse"], dict):
                    raise ValueError(
                        f"{path}:{lineno}: sparse summary is not a dict")
                for kern, info in entry["sparse"].items():
                    if not isinstance(info, dict) or not isinstance(
                            info.get("nnz"), int):
                        raise ValueError(
                            f"{path}:{lineno}: sparse summary for {kern!r} "
                            "missing integer nnz")
            # v6 lines must carry the resilience summary; older lines
            # (1–5) legitimately lack it, so below v6 it is
            # optional-but-typed
            if entry["schema"] >= 6 and "degraded" not in entry:
                raise ValueError(
                    f"{path}:{lineno}: schema-{entry['schema']} line "
                    "missing 'degraded' resilience summary")
            if "degraded" in entry:
                deg = entry["degraded"]
                if not isinstance(deg, dict):
                    raise ValueError(
                        f"{path}:{lineno}: degraded summary is not a dict")
                for field in ("fallbacks", "degraded", "events"):
                    if not isinstance(deg.get(field), int):
                        raise ValueError(
                            f"{path}:{lineno}: degraded summary "
                            f"missing/mistyped integer {field!r}")
            count += 1
    if count == 0:
        raise ValueError(f"{path}: empty history")
    return count


def isolate_schedule_cache() -> None:
    """Point the schedule cache at a fresh tempdir unless the operator
    opted into a shared one.

    Two reasons: (a) determinism — a warm user-global cache would make the
    non-autotune rows (smoke, nest gate) silently run tuned geometry that
    their schedule-provenance fields could not honestly describe, and make
    results differ between the first and later runs on one machine;
    (b) hygiene — a benchmark should not mutate user-global state as a
    side effect.  Set ``REPRO_SCHEDULE_CACHE`` explicitly to tune into
    (and read from) a persistent cache, e.g. the default
    ``~/.cache/repro-ssr`` that registry dispatch consults.
    """
    if not os.environ.get("REPRO_SCHEDULE_CACHE"):
        tmp = tempfile.mkdtemp(prefix="repro-sched-bench-")
        os.environ["REPRO_SCHEDULE_CACHE"] = tmp
        print(f"schedule cache isolated at {tmp} "
              "(set REPRO_SCHEDULE_CACHE to persist winners)")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="small sizes + few iters (CI smoke)")
    ap.add_argument("--out", default="BENCH_kernels.json",
                    help="output JSON path (default: %(default)s)")
    ap.add_argument("--no-hlo", action="store_true",
                    help="skip the compiled-HLO fusion audit")
    ap.add_argument("--autotune-only", action="store_true",
                    help="run only the schedule-autotune sweep + gate "
                         "(the CI autotune-smoke job)")
    ap.add_argument("--dag-only", action="store_true",
                    help="run only the fused-DAG cut search + gate "
                         "(the CI bench-smoke dag leg)")
    ap.add_argument("--chaos-smoke", action="store_true",
                    help="run only the fault-injection chaos gate: one "
                         "injected fault per dispatch seam, degraded "
                         "result must agree with healthy and stay within "
                         "bounded overhead (the CI chaos-smoke job)")
    ap.add_argument("--history", default="BENCH_history.jsonl",
                    help="per-run summary JSONL (default: %(default)s); "
                         "'' disables")
    args = ap.parse_args(argv)
    enable_compile_cache()
    isolate_schedule_cache()

    if args.chaos_smoke:
        rows = bench_chaos(quick=args.quick)
        write_bench_json(rows, args.out, args.quick, subset="chaos")
        validate_chaos_rows(rows)
        if args.history:
            append_bench_history(rows, args.history, args.quick)
            validate_bench_history(args.history)
        return 0

    if args.autotune_only:
        rows = bench_autotune(quick=args.quick)
        rows += bench_pipeline(quick=args.quick)
        write_bench_json(rows, args.out, args.quick, subset="autotune")
        validate_autotune_json(args.out)
        return 0

    if args.dag_only:
        rows = bench_dag(quick=args.quick, check_hlo=not args.no_hlo)
        write_bench_json(rows, args.out, args.quick, subset="dag")
        validate_dag_rows(rows)
        if args.history:
            append_bench_history(rows, args.history, args.quick)
            validate_bench_history(args.history)
        return 0

    rows: List[Dict] = []
    rows += bench_reference_paths(iters=2 if args.quick else 5)
    rows += smoke_ssr_paths()
    rows += bench_stream_reports()
    rows += bench_nest_gate()
    rows += bench_sparse(quick=args.quick)
    rows += bench_autotune(quick=args.quick)
    rows += bench_pipeline(quick=args.quick)
    rows += bench_fused(quick=args.quick, check_hlo=not args.no_hlo)
    rows += bench_dag(quick=args.quick, check_hlo=not args.no_hlo)
    write_bench_json(rows, args.out, args.quick)
    validate_bench_json(args.out)
    if args.history:
        append_bench_history(rows, args.history, args.quick)
        validate_bench_history(args.history)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
