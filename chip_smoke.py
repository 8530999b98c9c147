"""Chip smoke: drive the stream compiler's main path once on a TPU.

    python chip_smoke.py             # one chip: main-path kernels
    python chip_smoke.py --chips 4   # four chips: the cluster split only

Each phase calls a kernel the way a user does — ``registry.dispatch`` (the
function behind ``repro.kernels.ops``) with ``ssr=True`` — so the call
runs ``NestKernel`` → ``ssr_call`` → ``lower_nest``/``lower_plan`` →
``ssr_pallas`` → ``pl.pallas_call`` with ``interpret=False``, i.e. through
Mosaic.  Operands are random, made on the device from a fixed seed.  The
one-chip phases run at the layer widths of qwen3-14b
(``src/repro/configs/qwen3_14b.py``: d_model 5120, d_ff 17408, head_dim
128, 40 query heads over 8 KV heads), all in float32.

A phase prints one line: kernel and shapes, compile seconds, the largest
relative error against the kernel's plain-jnp reference (run under
``jax.default_matmul_precision("highest")``), the tolerance with its
reason, and whether the compiled HLO holds a ``tpu_custom_call`` (the
Mosaic kernel).  The relative error is ``max|out − ref| / max|ref|`` over
the whole output.

The last line of standard output is the JSON verdict, printed only when
every phase passed.  The script exits non-zero, printing no verdict, when
JAX finds no TPU, when a fault-injection or fallback variable is set, or
when any phase fails (the failing phases are named).  It starts no other
process.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
import traceback
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import lowering  # noqa: E402
from repro.core.lowering import Schedule  # noqa: E402
from repro.kernels import frontend, ref, registry  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

#: Variables that would let a failure hide behind a fallback or an
#: injected fault, or switch the pipelined emitter off.
REFUSED_ENV = ("REPRO_BASELINE_FALLBACK", "REPRO_FAULTS",
               "REPRO_DISABLE_PIPELINE")

#: Fixed, checkout-local schedule cache, emptied at start: every call runs
#: the default schedule unless the phase pins one.
SCHEDULE_DIR = ROOT / ".chip_smoke" / "schedules"

#: Phases with their own sizes; every other registered kernel runs at its
#: registry ``example`` size.
MAIN_PATH = ("gemm", "gemv", "attention", "reduction", "stencil1d",
             "mlp_block")

# Tolerances (on max|out − ref| / max|ref|) and their one-line reasons.
MXU_TOL = (1e-2, "f32 matmul on the MXU: a bf16-pass contraction keeps "
                 "~2^-9 relative error per output")
VPU_TOL = (1e-5, "f32 adds and multiplies on the VPU, in another order "
                 "than the reference")
SUM_TOL = (1e-4, "f32 sums of 64K+ terms per lane, in another order than "
                 "the reference")


@dataclasses.dataclass
class Phase:
    """What one phase measured and whether it passed."""

    name: str
    shapes: str
    compile_s: float
    rel_err: float
    tol: str
    why: str
    custom_call: bool
    passed: bool
    builds: int
    note: str = ""

    def line(self) -> str:
        return (f"phase {self.name} [{self.shapes}] compile_s="
                f"{self.compile_s:.2f} rel_err={self.rel_err:.3e} "
                f"tol={self.tol} ({self.why}) "
                f"tpu_custom_call={self.custom_call} builds={self.builds}"
                f"{' ' + self.note if self.note else ''} "
                f"{'PASS' if self.passed else 'FAIL'}")


def _builds() -> int:
    return lowering.DISPATCH_STATS["builds"] + frontend.DISPATCH_STATS["builds"]


def _normal(seed: int, *shapes) -> List[jax.Array]:
    keys = jax.random.split(jax.random.key(seed), len(shapes))
    return [jax.random.normal(k, s, jnp.float32) for k, s in zip(keys, shapes)]


def _uniform_pair(seed: int, n: int) -> List[jax.Array]:
    """Two [0, 1) vectors: their dot product stays well away from zero, so
    its relative error means something."""
    keys = jax.random.split(jax.random.key(seed))
    return [jax.random.uniform(k, (n,), jnp.float32) for k in keys]


def _rel_err(out, want) -> float:
    errs = []
    for o, w in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
        if o.shape != w.shape:
            raise ValueError(f"output shape {o.shape} != reference {w.shape}")
        den = jnp.maximum(jnp.max(jnp.abs(w)), jnp.finfo(jnp.float32).tiny)
        errs.append(float(jnp.max(jnp.abs(o - w)) / den))
    return max(errs)


def _allclose(out, want, rtol: float, atol: float) -> bool:
    return all(bool(jnp.all(jnp.abs(o - w) <= atol + rtol * jnp.abs(w)))
               for o, w in zip(jax.tree.leaves(out), jax.tree.leaves(want)))


def _measure(name: str, shapes: str, fn: Callable, ref_fn: Callable,
             arrays: Sequence[jax.Array], *, tol, why: str,
             check: Optional[Callable] = None) -> Phase:
    """Compile ``fn`` once, run that executable, compare with ``ref_fn``.

    ``check(out, want, rel_err) -> (passed, note)`` decides the pass; by
    default the relative error must stay within ``tol``.  A phase also
    fails on a non-finite output, or when no streamed kernel was built.
    """
    before = _builds()
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*arrays).compile()
    compile_s = time.perf_counter() - t0
    out = jax.block_until_ready(compiled(*arrays))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref_fn)(*arrays)
    err = _rel_err(out, want)
    ok, note = check(out, want, err) if check is not None else (err <= tol,
                                                                "")
    finite = all(bool(jnp.all(jnp.isfinite(o))) for o in jax.tree.leaves(out))
    builds = _builds() - before
    return Phase(name=name, shapes=shapes, compile_s=compile_s, rel_err=err,
                 tol=str(tol), why=why,
                 custom_call="tpu_custom_call" in compiled.as_text(),
                 passed=bool(ok and finite and builds > 0), builds=builds,
                 note=note)


# --------------------------------------------------------------------------
# One-chip phases (sizes are parameters so a CPU test can shrink them)
# --------------------------------------------------------------------------


def _dispatch(name: str, *, interpret: bool, **kw) -> Callable:
    return lambda *a: registry.dispatch(name, *a, ssr=True,
                                        interpret=interpret, **kw)


def phase_gemm(m: int = 2048, k: int = 5120, n: int = 17408, *,
               interpret: bool = False, seed: int = 0) -> Phase:
    """The qwen3-14b MLP up-projection: (m, d_model) · (d_model, d_ff)."""
    a, b = _normal(seed, (m, k), (k, n))
    return _measure("gemm", f"{m}x{k} @ {k}x{n} f32",
                    _dispatch("gemm", interpret=interpret),
                    registry.get("gemm").ref, (a, b),
                    tol=MXU_TOL[0], why=MXU_TOL[1])


def phase_gemv(m: int = 5120, n: int = 17408, *, depth: int = 2,
               interpret: bool = False, seed: int = 1) -> Phase:
    """The decode-time down-projection: (d_model, d_ff) · (d_ff,).

    ``depth > 2`` pins ``Schedule(buffer_depth=depth)`` and passes only if
    the kernel was built on the N-deep DMA emitter.
    """
    a, x = _normal(seed, (m, n), (n,))
    kw = {} if depth == 2 else {"schedule": Schedule(buffer_depth=depth)}
    before = lowering.DISPATCH_STATS["pipelined"]
    res = _measure("gemv" if depth == 2 else f"gemv_depth{depth}",
                   f"{m}x{n} @ {n} f32",
                   _dispatch("gemv", interpret=interpret, **kw),
                   registry.get("gemv").ref, (a, x),
                   tol=MXU_TOL[0], why=MXU_TOL[1])
    if depth > 2:
        pipelined = lowering.DISPATCH_STATS["pipelined"] > before
        res.note = f"pipelined={pipelined}"
        res.passed = res.passed and pipelined
    return res


def phase_attention(heads: int = 40, kv_heads: int = 8, s: int = 2048,
                    d: int = 128, *, interpret: bool = False,
                    seed: int = 2) -> Phase:
    """Causal prefill attention, ``jax.vmap``-ped over the query heads.

    Each query head reads its group's K/V head (GQA, ``heads/kv_heads``
    query heads per KV head), expanded before the vmap.
    """
    q, k, v = _normal(seed, (heads, s, d), (kv_heads, s, d), (kv_heads, s, d))
    group = heads // kv_heads

    def per_head(fn):
        def run(q, k, v):
            k, v = jnp.repeat(k, group, 0), jnp.repeat(v, group, 0)
            return jax.vmap(fn)(q, k, v)
        return run

    return _measure(
        "attention",
        f"causal q {heads}x{s}x{d}, kv {kv_heads}x{s}x{d} f32",
        per_head(_dispatch("attention", interpret=interpret, causal=True)),
        per_head(lambda q, k, v: ref.attention_ref(q, k, v, causal=True)),
        (q, k, v), tol=MXU_TOL[0], why=MXU_TOL[1])


def phase_dot(n: int = 64 << 20, *, interpret: bool = False,
              seed: int = 3) -> Phase:
    """A dot product of two f32 vectors."""
    return _measure("reduction", f"dot {n} f32",
                    _dispatch("reduction", interpret=interpret),
                    registry.get("reduction").ref, _uniform_pair(seed, n),
                    tol=SUM_TOL[0], why=SUM_TOL[1])


def phase_stencil1d(n: int = 16 << 20, *, interpret: bool = False,
                    seed: int = 4) -> Phase:
    """The 11-point 1-D star stencil over ``n`` output points."""
    from repro.kernels.stencil import TAPS

    x, w = _normal(seed, (n + TAPS - 1,), (TAPS,))
    return _measure("stencil1d", f"{n} points, {TAPS} taps f32",
                    _dispatch("stencil1d", interpret=interpret),
                    registry.get("stencil1d").ref, (x, w * 0.3),
                    tol=VPU_TOL[0], why=VPU_TOL[1])


def phase_mlp_block(rows: int = 65536, *, interpret: bool = False,
                    seed: int = 5) -> Phase:
    """The fused DAG relu(xW₁+b₁) → ·W₂+b₂ → +H over (rows, 128)."""
    lanes = frontend.LANES
    x, w1, b1, w2, b2 = _normal(seed, (rows, lanes), (lanes, lanes), (lanes,),
                                (lanes, lanes), (lanes,))
    s = lanes ** -0.5
    return _measure("mlp_block", f"x {rows}x{lanes}, W {lanes}x{lanes} f32",
                    _dispatch("mlp_block", interpret=interpret),
                    registry.get("mlp_block").ref,
                    (x, w1 * s, b1 * 0.1, w2 * s, b2 * 0.1),
                    tol=MXU_TOL[0], why=MXU_TOL[1])


def phase_example(name: str, *, interpret: bool = False,
                  seed: int = 6) -> Phase:
    """One registered kernel at its registry ``example`` size, judged by
    ``np.allclose`` semantics with the registry's own ``tol``."""
    entry = registry.get(name)
    args, kw = entry.example(np.random.default_rng(seed))
    idx = [i for i, a in enumerate(args) if hasattr(a, "shape")]

    def bind(fn):
        def run(*arrs):
            full = list(args)
            for i, a in zip(idx, arrs):
                full[i] = a
            return fn(*full, **kw)
        return run

    arrays = [jnp.asarray(args[i]) for i in idx]
    tol = entry.tol
    return _measure(
        name, " ".join("x".join(map(str, a.shape)) or "scalar"
                       for a in arrays) + " (example)",
        bind(lambda *a, **k: registry.dispatch(name, *a, ssr=True,
                                               interpret=interpret, **k)),
        bind(entry.ref), arrays,
        tol=f"rtol={tol['rtol']},atol={tol['atol']}",
        why="the registry's own tolerance, per element",
        check=lambda o, w, _e: (_allclose(o, w, tol["rtol"], tol["atol"]),
                                ""))


# --------------------------------------------------------------------------
# Four-chip phases: the paper's §5.3–5.5 cluster over a ``cores`` mesh
# --------------------------------------------------------------------------


def _cluster_phase(name: str, shapes: str, arrays, *, cores: int,
                   interpret: bool, tol) -> Phase:
    """Run the kernel's ``cluster`` variant over ``cores`` devices; pass
    when it matches ``ref`` and its own ``cores=1`` result, and its
    output shards sit on ``cores`` distinct devices."""
    entry = registry.get(name)
    one = entry.cluster(*arrays, cores=1, interpret=interpret)

    def check(out, want, err):
        err_one = _rel_err(out, one)
        devices = sorted({s.device.id for s in out.addressable_shards})
        return (err <= tol[0] and err_one <= tol[0]
                and len(devices) == cores,
                f"rel_err_vs_cores1={err_one:.3e} shard_devices={devices}")

    return _measure(f"cluster_{name}", f"{shapes}, cores={cores}",
                    lambda *a: entry.cluster(*a, cores=cores,
                                             interpret=interpret),
                    entry.ref, arrays, tol=tol[0], why=tol[1], check=check)


def phase_cluster_dot(n: int = 256 << 20, *, cores: int = 4,
                      interpret: bool = False, seed: int = 7) -> Phase:
    """The cluster dot: each core reduces its slice, one ``psum`` combines."""
    return _cluster_phase("reduction", f"dot {n} f32", _uniform_pair(seed, n),
                          cores=cores, interpret=interpret, tol=SUM_TOL)


def phase_cluster_gemm(m: int = 8192, k: int = 5120, n: int = 17408, *,
                       cores: int = 4, interpret: bool = False,
                       seed: int = 8) -> Phase:
    """The 2-D row×column GEMM split; the contraction stays core-local."""
    a, b = _normal(seed, (m, k), (k, n))
    return _cluster_phase("gemm", f"{m}x{k} @ {k}x{n} f32", (a, b),
                          cores=cores, interpret=interpret, tol=MXU_TOL)


# --------------------------------------------------------------------------
# Driver
# --------------------------------------------------------------------------


def one_chip_phases() -> List[Tuple[str, Callable[[], Phase]]]:
    declared = {e.name for e in registry.entries() if e.mosaic_refusal}
    rest = [n for n in registry.names()
            if n not in MAIN_PATH and n not in declared]
    return ([("gemm", phase_gemm), ("gemv", phase_gemv),
             ("attention", phase_attention), ("reduction", phase_dot),
             ("stencil1d", phase_stencil1d), ("mlp_block", phase_mlp_block),
             ("gemv_depth3", lambda: phase_gemv(depth=3))]
            + [(n, lambda n=n: phase_example(n)) for n in rest])


def four_chip_phases() -> List[Tuple[str, Callable[[], Phase]]]:
    return [("cluster_reduction", phase_cluster_dot),
            ("cluster_gemm", phase_cluster_gemm)]


def _resilience_counters() -> Dict[str, int]:
    return {f"{mod}.{k}": stats[k]
            for mod, stats in (("lowering", lowering.DISPATCH_STATS),
                               ("frontend", frontend.DISPATCH_STATS))
            for k in ("fallbacks", "degraded")}


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: main-path phases; 4: only the cluster split")
    args = ap.parse_args(argv)

    set_vars = [v for v in REFUSED_ENV if os.environ.get(v)]
    if set_vars:
        print(f"chip_smoke: refusing to start with {', '.join(set_vars)} "
              "set: a fallback, an injected fault or a disabled emitter "
              "would hide what the chip does", file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); this smoke runs on the chip only",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2

    print(f"compile cache: {enable_compile_cache()}")
    shutil.rmtree(SCHEDULE_DIR, ignore_errors=True)
    SCHEDULE_DIR.mkdir(parents=True)
    os.environ["REPRO_SCHEDULE_CACHE"] = str(SCHEDULE_DIR)
    lowering.reset_dispatch_stats()
    frontend.reset_dispatch_stats()

    phases = one_chip_phases() if args.chips == 1 else four_chip_phases()
    failed = []
    for name, run in phases:
        try:
            res = run()
        except Exception as e:  # report the phase, keep going, fail at end
            traceback.print_exc()
            print(f"phase {name} ERROR {type(e).__name__}: "
                  f"{str(e).splitlines()[0] if str(e) else ''}", flush=True)
            failed.append(name)
            continue
        print(res.line(), flush=True)
        if not (res.passed and res.custom_call):
            failed.append(name)

    if args.chips == 1:
        for e in registry.entries():
            if e.mosaic_refusal:
                print(f"declared {e.name}: not run on the chip — "
                      f"{e.mosaic_refusal}")
    counters = _resilience_counters()
    print("dispatch counters: "
          + " ".join(f"{k}={v}" for k, v in counters.items())
          + f" lowering.builds={lowering.DISPATCH_STATS['builds']}"
          f" frontend.builds={frontend.DISPATCH_STATS['builds']}"
          f" lowering.pipelined={lowering.DISPATCH_STATS['pipelined']}")
    if any(counters.values()):
        failed.append("resilience-counters")
    print(f"schedule cache: REPRO_SCHEDULE_CACHE="
          f"{SCHEDULE_DIR.relative_to(ROOT)} (emptied at start)")
    if failed:
        print(f"chip_smoke: FAILED {', '.join(failed)}", file=sys.stderr)
        print(f"FAILED: {', '.join(failed)}")
        return 1
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
