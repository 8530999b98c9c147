"""The harness: find a cell's files by name, set it up, time it, check it.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``bench/configs/<config>.json`` — the sizes as run, with the source, the
  keys reduced and the deployment; its ``architecture`` names
  ``bench/configs/<architecture>.py`` (the plain reference and the weight
  generator) and ``bench/stages/<architecture>.py`` (the program's step);
* ``bench/traffic/<mix>.json`` — parameters that :mod:`bench.inputs` reads;
* ``bench/metrics/<metric>.py`` — ``read(ctx)`` returns the metric, or
  ``None`` where the run has nothing to read;
* ``bench/limits/<cell>.json`` — the limit of each number that decides
  ``correct``, with the readings it was set from.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional, Tuple

#: Variables that would let a failure hide behind a fallback or an injected
#: fault, or switch the pipelined emitter off.
REFUSED_ENV = ("REPRO_BASELINE_FALLBACK", "REPRO_FAULTS",
               "REPRO_DISABLE_PIPELINE")

#: Seconds of the window the profiler records in a ``--trace 1`` run (the
#: step that crosses it is the last one traced).
TRACE_SECONDS = 4.0


class BenchError(RuntimeError):
    """The cell cannot run here, or its files are missing."""


def use_checkout_caches(root: Path) -> Tuple[Path, Path]:
    """Keep every cache of the run inside the checkout ``root``, at fixed
    paths: JAX's persistent compilation cache in ``.jax_cache/`` (whatever
    the environment says, so two checkouts never share one), and the
    compiler's schedule cache in ``.bench_out/schedules/``, emptied, so every
    cell runs the schedule the program picks with no tuned entry."""
    import jax

    cache = root / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(cache))
    schedules = root / ".bench_out" / "schedules"
    shutil.rmtree(schedules, ignore_errors=True)
    schedules.mkdir(parents=True)
    os.environ["REPRO_SCHEDULE_CACHE"] = str(schedules)
    return cache, schedules


@dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int


class Bench:
    """The benchmark's files under ``root``, found by name."""

    def __init__(self, root: Path):
        self.root = Path(root)
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise BenchError(f"no BENCHMARK.json in {self.root}")
        self.spec = json.loads(path.read_text())
        self.dir = self.root / "bench"

    def cell(self, name: str) -> Cell:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return Cell(w["name"], w["config"], w["traffic"], w["chips"])
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")

    def _json(self, kind: str, name: str) -> dict:
        path = self.dir / kind / f"{name}.json"
        if not path.is_file():
            raise BenchError(f"missing {path.relative_to(self.root)}")
        return json.loads(path.read_text())

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def limits(self, cell: str) -> dict:
        return self._json("limits", cell)

    def module(self, kind: str, name: str) -> ModuleType:
        """``bench/<kind>/<name>.py`` as ``bench.<kind>.<name>``."""
        path = self.dir / kind / f"{name}.py"
        if not path.is_file():
            raise BenchError(f"missing {path.relative_to(self.root)}")
        modname = f"bench.{kind}.{name.replace('-', '_')}"
        if modname in sys.modules and \
                getattr(sys.modules[modname], "__file__", None) == str(path):
            return sys.modules[modname]
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        spec.loader.exec_module(mod)
        return mod

    def reference(self, cfg: dict) -> ModuleType:
        return self.module("configs", cfg["architecture"])

    def stage(self, cfg: dict) -> ModuleType:
        return self.module("stages", cfg["architecture"])

    def metric(self, name: str) -> ModuleType:
        return self.module("metrics", name)

    def metrics_for(self, cell: str, kind: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.spec[kind]
                if "workloads" not in m or cell in m["workloads"]]


# ---------------------------------------------------------------------------
# Spans and counters
# ---------------------------------------------------------------------------


class Spans:
    """Calls through the benchmark's spans: a profiler annotation named for
    the call (``ops.<family>`` or ``glue.<name>``) and the host seconds
    spent inside it until it returned."""

    def __init__(self):
        import jax

        self._annotate = jax.profiler.TraceAnnotation
        self.host: Dict[str, float] = defaultdict(float)

    def __call__(self, name: str, fn: Callable, *args):
        with self._annotate(name):
            t = time.perf_counter()
            out = fn(*args)
            self.host[name] += time.perf_counter() - t
        return out

    def reset(self) -> None:
        self.host.clear()


def counters() -> Dict[str, int]:
    """The compiler's dispatch counters, by module."""
    from repro.core import lowering
    from repro.kernels import frontend

    return {f"{mod}.{k}": v
            for mod, stats in (("lowering", lowering.DISPATCH_STATS),
                               ("frontend", frontend.DISPATCH_STATS))
            for k, v in stats.items()}


def _moved(before: Dict[str, int], after: Dict[str, int],
           keys: Tuple[str, ...]) -> int:
    return sum(after[k] - before[k] for k in after
               if k.split(".", 1)[1] in keys)


# ---------------------------------------------------------------------------
# Set-up, window, check
# ---------------------------------------------------------------------------


@dataclass
class Ctx:
    """What a per-layer metric reader may read."""

    cell: Cell
    cfg: dict
    traffic: dict
    peaks: object
    work: List[Tuple[str, object]]         # (span, Work) of one step
    traced_steps: int = 0
    traced_wall_s: float = 0.0             # host seconds of those steps
    dispatch_s: Dict[str, float] = field(default_factory=dict)
    counters_before: Dict[str, int] = field(default_factory=dict)
    counters_after: Dict[str, int] = field(default_factory=dict)
    trace: object = None                   # bench.trace.Reduced

    def span_work(self, span: str):
        return [w for s, w in self.work if s == span]

    def roofline(self, span: str) -> Optional[float]:
        """Σ least time of the span's calls over their Σ device time, %."""
        calls = self.span_work(span)
        dev = (self.trace.span_device_s().get(span, 0.0)
               if self.trace is not None else 0.0)
        if not calls or dev <= 0 or self.traced_steps == 0:
            return None
        # the calls' work is shared evenly by the cell's chips, and ``dev``
        # is each chip's device time
        least = self.traced_steps * sum(w.least_s(self.peaks) for w in calls)
        return 100.0 * least / self.cell.chips / dev


def row_err(out, want, x) -> float:
    """Worst row: ‖out − ref‖ / ‖ref − input‖ (the stage's own update)."""
    import jax.numpy as jnp

    out, want, x = (jnp.atleast_2d(a) for a in (out, want, x))
    num = jnp.linalg.norm(out - want, axis=-1)
    den = jnp.linalg.norm(want - x, axis=-1)
    return float(jnp.max(num / jnp.maximum(den, 1e-30)))


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(bench: Bench, cell: Cell, seed: int, seconds: float,
             trace: bool, *, out_dir: Path, t_start: float,
             log=print) -> dict:
    """Set the cell up, run its window, check it; return the result line."""
    import jax

    from . import inputs as gen
    from . import trace as trace_mod
    from .peaks import peaks_for

    split = {"init_s": time.perf_counter() - t_start}
    t = time.perf_counter()
    cfg = bench.config(cell.config)
    traffic = bench.traffic(cell.traffic)
    limits = bench.limits(cell.name)
    ref = bench.reference(cfg)
    stage_mod = bench.stage(cfg)
    dev0 = jax.devices()[0]
    peaks = peaks_for(dev0.device_kind) if dev0.platform == "tpu" else None
    split["imports_s"] = time.perf_counter() - t

    t = time.perf_counter()
    k_w, k_in = jax.random.split(gen.seed_key(seed))
    weights = jax.block_until_ready(ref.init_weights(
        cfg, k_w, traffic["phase"],
        stage_mod.weight_shardings(cfg, traffic, cell.chips)))
    split["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    data = jax.block_until_ready(gen.make(cfg, traffic, k_in))
    split["inputs_s"] = time.perf_counter() - t

    t = time.perf_counter()
    spans = Spans()
    stage = stage_mod.Stage(cfg, traffic, weights, data, spans,
                            chips=cell.chips)
    n = traffic["prompts"]
    split["stage_s"] = time.perf_counter() - t
    builds0 = counters().get("frontend.builds", 0)
    t = time.perf_counter()
    jax.block_until_ready(stage.step(0))
    split["compile_s"] = time.perf_counter() - t
    t = time.perf_counter()
    for i in range(n):                    # every prompt once, all warm
        jax.block_until_ready(stage.step(i))
    split["warm_s"] = time.perf_counter() - t
    spans.reset()
    kernels_built = counters().get("frontend.builds", 0) - builds0
    eager = stage.eager

    ctx = Ctx(cell, cfg, traffic, peaks, stage.work())
    visit = gen.order(seed, n)
    tdir = out_dir / "trace" / cell.name
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        tdir.mkdir(parents=True)
    ctx.counters_before = counters()
    setup_s = time.perf_counter() - t_start
    log(f"setup: {' '.join(f'{k}={v:.3f}' for k, v in split.items())} "
        f"setup_s={setup_s:.3f}")

    kept: Dict[int, object] = {}
    lat: List[float] = []
    failed = 0
    tracing = False
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        tracing = True
    t0 = time.perf_counter()
    step = 0
    while True:
        p = visit[step % n]
        before = counters()
        ts = time.perf_counter()
        try:
            out = stage.step(p)
            out.block_until_ready()
            kept[p] = out
        except Exception as e:            # a failed step counts, run goes on
            failed += 1
            log(f"step {step} raised {type(e).__name__}: {e}")
        te = time.perf_counter()
        if _moved(before, counters(), ("fallbacks", "degraded")):
            failed += 1                   # served by a fallback: not the SUT
        lat.append(te - ts)
        step += 1
        if tracing and te - t0 >= min(TRACE_SECONDS, seconds):
            jax.profiler.stop_trace()
            tracing = False
            ctx.traced_steps = step
            ctx.traced_wall_s = te - t0
            ctx.dispatch_s = {k: v for k, v in spans.host.items()}
        if te - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    ctx.counters_after = counters()
    q = statistics.quantiles(lat, n=20, method="inclusive") \
        if len(lat) > 1 else lat * 19
    log(f"window: steps={step} window_s={window_s:.4f} failed={failed} "
        f"step_s min={min(lat):.5f} p5={q[0]:.5f} median={q[9]:.5f} "
        f"p95={q[18]:.5f} max={max(lat):.5f} first={lat[0]:.5f}")
    if trace:
        host = {k: round(1e3 * v / max(ctx.traced_steps, 1), 4)
                for k, v in sorted(ctx.dispatch_s.items())}
        log(f"traced: steps={ctx.traced_steps} wall_s="
            f"{ctx.traced_wall_s:.4f} host ms/step by span: {host}")
    log("counters before: " + json.dumps(ctx.counters_before))
    log("counters after:  " + json.dumps(ctx.counters_after))

    device = device_info(cell.chips)
    del stage                            # the program's state goes first

    # -- the comparison with the plain reference -----------------------
    import jax.numpy as jnp

    ref_data = gen.make(cfg, traffic, k_in)     # anew: nothing the run made
    errs = []
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        for p in sorted(kept):
            want = ref.forward(cfg, weights, ref_data, p)
            got = kept[p]
            if not bool(jnp.all(jnp.isfinite(got))):
                errs.append(float("inf"))
                continue
            errs.append(row_err(got, want, ref_data["x"][p]))
    log(f"reference: {len(errs)} outputs compared in "
        f"{time.perf_counter() - t:.3f} s")
    worst = max(errs) if errs else float("inf")
    # the compiler built a kernel for each family the step calls; and where
    # the step calls it eagerly, every ops.* span is one call into its
    # kernel frontend: a step that fewer reached was served by something else
    ops_spans = [s for s, _ in ctx.work if s.startswith("ops.")]
    checks = {
        "row_err": {"value": worst, "limit": limits["row_err"]["limit"]},
        "prompts_compared": {"value": len(errs), "limit": n},
        "failed_steps": {"value": failed, "limit": 0},
        "kernels_built": {"value": kernels_built,
                          "limit": len(set(ops_spans))},
    }
    if eager:
        checks["compiler_calls_per_step"] = {
            "value": (ctx.counters_after.get("frontend.calls", 0)
                      - ctx.counters_before.get("frontend.calls", 0)) / step,
            "limit": len(ops_spans)}
    correct = (worst <= checks["row_err"]["limit"] and len(errs) == n
               and failed == 0
               and all(checks[k]["value"] >= checks[k]["limit"]
                       for k in ("kernels_built", "compiler_calls_per_step")
                       if k in checks))

    result: dict = {"correct": bool(correct), "attempted": step,
                    "failed": failed}
    if trace:
        red = trace_mod.reduce_xplane(trace_mod.find_xplane(str(tdir)),
                                      set(s for s, _ in ctx.work))
        ctx.trace = red
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s()
        metrics = {}
        for m in bench.metrics_for(cell.name, "per_layer"):
            v = bench.metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": red.idle_gaps(10)}
    else:
        e2e = {"step_ms": 1e3 * window_s / step,
               "step_p95_ms": 1e3 * _p95(lat),
               "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in bench.metrics_for(cell.name, "end_to_end")}
        result["device"] = device
    result["checks"] = checks
    return result


def _p95(values: List[float]) -> float:
    return statistics.quantiles(values, n=20, method="inclusive")[18] \
        if len(values) > 1 else values[0]


def check_lines(checks: dict) -> List[str]:
    return [f"check {k}: {v['value']!r} limit {v['limit']!r}"
            for k, v in checks.items()]
