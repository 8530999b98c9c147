"""Readings that the limits of ``correct`` are set from, on the chip.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--out FILE]

For each seed it sets the cell up as a run does (weights and inputs from
the seed, the cell's own stage and sizes), sends every prompt of the mix
once through the program's step, and reads ``row_err`` against the plain
reference; for each control seed it reads the same number for the two
controls put in the program's place: the reference computed with fp8
contractions (``control``), and the reference with its weights, activations
and KV cache stored in bf16 (``storage``).
One process does all of it, so the program's compiled steps are shared.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def readings(bench, cell, seed: int, *, program: bool, control: bool,
             log=print) -> dict:
    import jax

    from bench import harness
    from bench import inputs as gen

    cfg, traffic = bench.config(cell.config), bench.traffic(cell.traffic)
    ref, stage_mod = bench.reference(cfg), bench.stage(cfg)
    k_w, k_in = jax.random.split(gen.seed_key(seed))
    weights = ref.init_weights(
        cfg, k_w, traffic["phase"],
        stage_mod.weight_shardings(cfg, traffic, cell.chips))
    n = traffic["prompts"]
    out: dict = {"seed": seed}
    if program:
        data = gen.make(cfg, traffic, k_in)
        stage = stage_mod.Stage(cfg, traffic, weights, data, harness.Spans(),
                                chips=cell.chips)
        got = [jax.block_until_ready(stage.step(i)) for i in range(n)]
        del stage, data
    ref_data = gen.make(cfg, traffic, k_in)
    prog, ctrl, stor = [], [], []
    with jax.default_matmul_precision("highest"):
        for i in range(n):
            want = ref.forward(cfg, weights, ref_data, i)
            x = ref_data["x"][i]
            if program:
                prog.append(harness.row_err(got[i], want, x))
            if control:
                low = ref.forward(cfg, weights, ref_data, i, dot=ref.fp8_dot)
                ctrl.append(harness.row_err(low, want, x))
                low = ref.forward(cfg, weights, ref_data, i,
                                  store=ref.bf16_store)
                stor.append(harness.row_err(low, want, x))
    if program:
        out["program_row_err"] = max(prog)
        out["program_row_err_by_prompt"] = prog
    if control:
        out["control_row_err"] = min(ctrl)
        out["control_row_err_by_prompt"] = ctrl
        out["storage_row_err"] = min(stor)
        out["storage_row_err_by_prompt"] = stor
    log(json.dumps(out))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench.harness import Bench, use_checkout_caches

    if jax.devices()[0].platform != "tpu":
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 2
    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    if len(jax.devices()) < cell.chips:
        print(f"calibrate: {cell.name} needs {cell.chips} chips",
              file=sys.stderr)
        return 2
    use_checkout_caches(ROOT)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    rows = [readings(bench, cell, s, program=True, control=s in ctl)
            for s in seeds]
    rows += [readings(bench, cell, s, program=False, control=True)
             for s in sorted(ctl - set(seeds))]
    summary = {
        "workload": cell.name,
        "device": jax.devices()[0].device_kind,
        "program_row_err_max": max(r["program_row_err"] for r in rows
                                   if "program_row_err" in r),
        "control_row_err_min": min((r["control_row_err"] for r in rows
                                    if "control_row_err" in r),
                                   default=None),
        "storage_row_err_min": min((r["storage_row_err"] for r in rows
                                    if "storage_row_err" in r),
                                   default=None),
        "rows": rows,
        "seconds": time.perf_counter() - T_START,
    }
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(summary, indent=1))
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
