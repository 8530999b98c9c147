"""Cluster sweep: registry kernels sharded over C ∈ {1,2,4,8} cores (§V).

Reproduces the paper's multi-core claims on the ``parallel/cluster.py``
layer (§5.3–5.5 / Fig. 10–11):

* **speedup vs cores** — the architectural speedup of the C-core cluster
  from the explicit per-core Eq. (1)–(3) model
  (:func:`repro.core.compiler.cluster_cost`).  Instruction/cycle accounting
  is technology-independent (§5.1), so these are the *reproduction*
  numbers, and the validation gate requires them to increase with C;
* **iso-performance core counts** — smallest SSR cluster matching a C-core
  baseline cluster (the "3× fewer cores" claim);
* **numeric agreement** — every clustered kernel is *executed* on a forced
  C-device host mesh and must match its single-core streamed output within
  1e-5 (hard failure otherwise: a fast wrong cluster is not a win);
* **locality audit** — the compiled HLO of a reduce-mode cluster call may
  contain exactly one ``all-reduce`` (the shared-TCDM psum) and a map-mode
  call none at all;
* **wall clock** — best-of-N μs/call per C, recorded but *not* gated: on
  this CPU container Pallas interpret mode times the interpreter, and the
  forced host devices share one machine.  On a real multi-chip backend
  this file runs unchanged.

Run from the repo root (forces 8 host devices before importing jax)::

    python benchmarks/cluster_bench.py [--quick] [--out BENCH_cluster.json]

Schema (version 2, shared with ``kernel_bench``): ``{"schema": 2,
"generated_unix": float, "quick": bool, "cores": [...], "results":
[{"name", "group", "variant", "value", "units", "rows", "lanes", "grid",
"tuned", ...}, ...]}`` — executed rows carry the per-core schedule the
cluster layer actually dispatched (autotuned or default).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Sequence

# Must run before any jax import: the cluster sweep needs C host devices.
_DEVICES = int(os.environ.get("REPRO_CLUSTER_DEVICES", "8"))
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_DEVICES}").strip()

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.kernel_bench import (BENCH_SCHEMA, _row, _time,
                                     isolate_schedule_cache,
                                     write_bench_json)  # noqa: E402
from repro.core import compiler  # noqa: E402
from repro.core.compiler import (cluster_cost,
                                 iso_performance_cores)  # noqa: E402
from repro.kernels import registry  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

RNG = np.random.default_rng(0)

AGREEMENT_TOL = 1e-5
CORES_SWEEP = (1, 2, 4, 8)


def _normal(n: int) -> jnp.ndarray:
    """Unit-scale inputs: reduce outputs stay O(1), so the 1e-5 absolute
    agreement gate measures the cluster split, not the operand magnitude."""
    return jnp.asarray(RNG.standard_normal(n) / np.sqrt(n), jnp.float32)


def _model_nests(quick: bool):
    """(name, nest-or-chain) for the cost model — no device arrays."""
    from repro.kernels.chained import _chain_nests
    from repro.kernels.stencil import TAPS

    n = 8192 if not quick else 2048
    m = 512 if not quick else 128
    return [
        ("reduction", compiler.dot_product_nest(n)),
        ("relu", compiler.elementwise_nest(n)),
        # the real compiled nest (§13 migration): A row-panel walk +
        # x repeat stream + the revisited output accumulator ref
        ("gemv", compiler.gemv_nest(m, 64)),
        ("gemm", compiler.gemm_nest(m, 64, 64)),
        ("stencil1d", compiler.stencil_nest(n, TAPS)),
        ("sum_sq_diff", _chain_nests(n, consumer_reads_w=False)),
        ("axpy_dot", _chain_nests(n, consumer_reads_w=True)),
    ]


def _bench_cases(quick: bool):
    """(name, args, kwargs, nest-or-chain): executable inputs per kernel."""
    from repro.kernels.stencil import TAPS

    n = 8192 if not quick else 2048
    m = 512 if not quick else 128
    inputs = {
        "reduction": ((_normal(n), _normal(n)), {}),
        "relu": ((_normal(n),), {}),
        "gemv": ((jnp.asarray(RNG.standard_normal((m, 64)) / 8.0,
                              jnp.float32), _normal(64) * 8.0), {}),
        "gemm": ((jnp.asarray(RNG.standard_normal((m, 64)) / 8.0,
                              jnp.float32),
                  jnp.asarray(RNG.standard_normal((64, 64)) / 8.0,
                              jnp.float32)), {}),
        "stencil1d": ((jnp.asarray(RNG.standard_normal(n + TAPS - 1) / 4.0,
                                   jnp.float32),
                       jnp.asarray(RNG.standard_normal(TAPS) * 0.3,
                                   jnp.float32)), {}),
        "sum_sq_diff": ((_normal(n), _normal(n)), {}),
        "axpy_dot": ((_normal(n), _normal(n), _normal(n)), {"alpha": 0.5}),
    }
    return [(name, *inputs[name], nests)
            for name, nests in _model_nests(quick)]


def _max_abs_diff(a, b) -> float:
    return max(float(jnp.max(jnp.abs(jnp.asarray(x) - jnp.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _dispatch_provenance() -> Dict:
    """Schedule provenance of the last executed cluster call (schema 3).

    ``cluster_call``/``cluster_chain_call`` record the per-core schedule
    they actually dispatched (tuned from the autotuner cache, or default)
    in ``parallel.cluster.LAST_DISPATCH``; kernels routed through other
    shims (``cluster_kernel``/``cluster_kernel2d``) leave it empty and
    keep the default-provenance row fields.
    """
    from repro.parallel.cluster import LAST_DISPATCH

    if not LAST_DISPATCH:
        return {}
    sched = LAST_DISPATCH["schedule"]
    # "grid" in schema 2 means the launched Pallas grid; the cluster layer
    # records the per-core *iteration-space* tile, which is a different
    # quantity — keep the shared field None and expose the tile separately
    # so cross-file consumers never read bounds as grid dimensions.
    return {"rows": sched.rows, "lanes": sched.lanes,
            "grid": None,
            "tile_bounds": list(LAST_DISPATCH["tile_bounds"]),
            "tuned": bool(LAST_DISPATCH["tuned"]),
            "buffer_depth": sched.buffer_depth}


def sweep(quick: bool = False) -> List[Dict]:
    """Agreement + wall clock + cost model across the core sweep.

    Model rows cover the full ``CORES_SWEEP`` unconditionally (the cost
    model needs no devices — its monotonicity gate must hold on any host);
    execution (agreement/wall-clock) rows only for core counts the
    machine can actually place.
    """
    runnable = [c for c in CORES_SWEEP if c <= len(jax.devices())]
    rows: List[Dict] = []
    print(f"\n== cluster sweep: model C={list(CORES_SWEEP)}, executed "
          f"C={runnable} ({len(jax.devices())} devices) ==")
    for name, args, kwargs, nests in _bench_cases(quick):
        entry = registry.get(name)
        assert entry.cluster is not None, name
        single = entry.ssr(*args, **kwargs)
        line = f"{name:14s}"
        for c in CORES_SWEEP:
            rep = cluster_cost(nests, c)
            rows.append(_row(
                f"cluster/{name}/C{c}", "cluster_model", "model",
                rep.speedup, "model_speedup", cores=c,
                n_cluster=rep.n_cluster, n_single=rep.n_single,
                eta_cluster=rep.eta_cluster,
                total_fetches=rep.total_fetches,
                bytes_moved=rep.bytes_moved))
            line += f"  C{c}: S={rep.speedup:4.2f} η={rep.eta_cluster:.2f}"
            if c not in runnable:
                continue
            from repro.parallel.cluster import LAST_DISPATCH

            LAST_DISPATCH.clear()
            out = entry.cluster(*args, cores=c, **kwargs)
            prov = _dispatch_provenance()
            diff = _max_abs_diff(out, single)
            if diff > AGREEMENT_TOL:
                print(f"\nFAIL {name} C={c}: sharded output differs from "
                      f"single-core by {diff:.2e} > {AGREEMENT_TOL}",
                      file=sys.stderr)
                raise SystemExit(1)
            us = _time(lambda *a, _c=c: entry.cluster(*a, cores=_c, **kwargs),
                       *args, iters=2 if quick else 5)
            rows.append(_row(f"cluster/{name}/C{c}", "cluster_agreement",
                             "cluster", diff, "max_abs_diff", cores=c,
                             **prov))
            rows.append(_row(f"cluster/{name}/C{c}", "cluster_wall",
                             "cluster", us, "us/call", cores=c, **prov))
            line += f" Δ={diff:.0e}"
        print(line)
    return rows


def iso_curve() -> List[Dict]:
    """§5.5 iso-performance: SSR cores matching a C-core baseline cluster."""
    rows: List[Dict] = []
    print("\n== iso-performance (SSR cores matching baseline cluster) ==")
    for name, nests in _model_nests(quick=True):
        pts = []
        for base_c in (2, 4, 6, 8):
            iso = iso_performance_cores(nests, base_c)
            pts.append((base_c, iso))
            rows.append(_row(f"iso/{name}/base{base_c}", "cluster_iso",
                             "model", float(iso), "ssr_cores",
                             baseline_cores=base_c,
                             ratio=base_c / iso))
        print(f"{name:14s} " + "  ".join(
            f"{b} base -> {i} ssr ({b / i:.1f}x fewer)" for b, i in pts))
    return rows


def locality_audit() -> List[Dict]:
    """Compiled-HLO audit: one psum for reduces, none for maps (§5.3)."""
    from repro.launch.hlo_analysis import check_cluster_locality

    rows: List[Dict] = []
    c = min(4, len(jax.devices()))
    if c < 2:
        # cores=1 degenerates to the meshless single-core path: there is
        # no collective to audit, so the check is vacuous here.
        print("\n== HLO locality audit skipped (needs >= 2 devices) ==")
        return rows
    print(f"\n== HLO locality audit (C={c}) ==")
    cases = [("reduction", "reduce"), ("relu", "map"),
             ("sum_sq_diff", "reduce")]
    for name, mode in cases:
        entry = registry.get(name)
        args, kwargs = entry.example(RNG)
        chk = check_cluster_locality(
            lambda *a: entry.cluster(*a, cores=c, **kwargs), args,
            mode=mode, world=c)
        print(f"{name:14s} mode={mode:6s} collectives={chk.counts} "
              f"ok={chk.ok}")
        if not chk.ok:
            print(f"\nFAIL {name}: per-core intermediates leaked off core "
                  f"(collectives {chk.counts})", file=sys.stderr)
            raise SystemExit(1)
        rows.append(_row(f"locality/{name}", "cluster_locality", "cluster",
                         1.0, "ok", mode=mode, cores=c,
                         collectives=chk.counts))
    return rows


# --------------------------------------------------------------------------
# Machine-readable output: BENCH_cluster.json
# --------------------------------------------------------------------------


def validate_cluster_json(path: str) -> None:
    """Schema + acceptance gate for CI.

    Fails unless (a) the schema holds, (b) every agreement row is within
    tolerance, (c) the model speedup increases with C for at least three
    kernels, and (d) every locality row passed.
    """
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") != BENCH_SCHEMA:
        raise ValueError(f"bad schema: {doc.get('schema')!r}")
    results = doc.get("results")
    if not isinstance(results, list) or not results:
        raise ValueError("results must be a non-empty list")
    for row in results:
        # schema 3: every row carries schedule provenance, FIFO depth
        # included (cluster rows record the dispatched per-core schedule's
        # depth via LAST_DISPATCH)
        for field in ("name", "group", "variant", "value", "units",
                      "rows", "lanes", "grid", "tuned", "buffer_depth"):
            if field not in row:
                raise ValueError(f"row missing {field!r}: {row}")
    for row in results:
        if row["group"] == "cluster_agreement" \
                and row["value"] > AGREEMENT_TOL:
            raise ValueError(f"agreement beyond {AGREEMENT_TOL}: {row}")
        if row["group"] == "cluster_locality" and row["value"] != 1.0:
            raise ValueError(f"locality audit failed: {row}")
    by_kernel: Dict[str, List] = {}
    for row in results:
        if row["group"] == "cluster_model":
            kern = row["name"].split("/")[1]
            by_kernel.setdefault(kern, []).append(
                (row["cores"], row["value"]))
    # the compiled-nest kernels must ride the sweep (gemm: the 2-D split)
    for required in ("gemm", "stencil1d"):
        if required not in by_kernel:
            raise ValueError(
                f"{required!r} missing from the cluster sweep "
                f"(kernels: {sorted(by_kernel)})")
    increasing = 0
    for kern, pts in by_kernel.items():
        pts.sort()
        if len(pts) >= 3 and all(b > a for (_, a), (_, b)
                                 in zip(pts, pts[1:])):
            increasing += 1
    if increasing < 3:
        raise ValueError(
            f"need >= 3 kernels with speedup increasing in C, got "
            f"{increasing} (kernels: {sorted(by_kernel)})")
    if not any(r["group"] == "cluster_iso" for r in results):
        raise ValueError("no iso-performance results recorded")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="small sizes + few iters (CI smoke)")
    ap.add_argument("--out", default="BENCH_cluster.json",
                    help="output JSON path (default: %(default)s)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    # deterministic provenance: executed rows resolve per-core schedules
    # from the cache, so the sweep isolates it unless explicitly shared
    isolate_schedule_cache()

    rows: List[Dict] = []
    rows += sweep(quick=args.quick)
    rows += iso_curve()
    rows += locality_audit()
    write_bench_json(rows, args.out, args.quick, cores=list(CORES_SWEEP))
    validate_cluster_json(args.out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
