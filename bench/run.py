"""Run one benchmark cell on the accelerator this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (a configuration under a traffic mix) is looked up by name in
``BENCHMARK.json``.  The run refuses a host with no TPU, or with fewer
chips than the cell asks for, and the variables that would let a fallback
or an injected fault stand in for the system under test.  It makes weights
and inputs on the device from ``--seed``, warms up the cell's own shapes,
then drives one step after another (closed loop, one caller) for
``--seconds``.  With ``--trace 0`` it reports the cell's end-to-end metrics;
with ``--trace 1`` the profiler records the first seconds of the window and
it reports the per-layer metrics.  Either way it then compares every prompt
the window served with the plain reference.

Standard output ends with one JSON line (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, ``breakdown`` when traced, and
``checks``: each number compared with its limit); standard error ends with
the same comparisons, one a line.  Every cache the run writes stays in the
checkout: compiled programs in ``.jax_cache/`` and the rest in
``.bench_out/``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / ".bench_out"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the checkout and the program, in place of this file's own directory
    # (whose module names would shadow the standard library's)
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if not p or Path(p).resolve() != Path(here)]
    from bench.harness import REFUSED_ENV, Bench, BenchError, check_lines, \
        run_cell, use_checkout_caches

    set_vars = [v for v in REFUSED_ENV if os.environ.get(v)]
    if set_vars:
        print(f"bench: refusing to run with {', '.join(set_vars)} set: a "
              "fallback, an injected fault or a disabled emitter would "
              "stand in for the system under test", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    try:
        bench = Bench(ROOT)
        cell = bench.cell(args.workload)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r}); "
              "this benchmark measures the chip only", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    cache, schedules = use_checkout_caches(ROOT)
    d = devices[0]
    print(f"bench: {cell.name} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace} device={d.platform}/{d.device_kind} "
          f"x{len(devices)} compile_cache={cache.relative_to(ROOT)} "
          f"schedule_cache={schedules.relative_to(ROOT)} (emptied)",
          flush=True)
    try:
        result = run_cell(bench, cell, args.seed, args.seconds,
                          bool(args.trace), out_dir=OUT, t_start=T_START,
                          log=lambda s: print(s, flush=True))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for line in check_lines(result["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
