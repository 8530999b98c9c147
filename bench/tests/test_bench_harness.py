"""The harness end to end on the CPU at tiny widths, in interpret mode.

A tiny configuration and traffic mixes are added as files in a copy of the
benchmark, with no edit to any file that is there: the harness finds them
by name.  Each mix's step is checked against the plain reference, and a
step broken underneath the harness makes ``correct`` come out false.
"""

import json
import os
import shutil
import subprocess
import sys
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.harness import Bench, run_cell

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 40 + 17

TINY = {"hidden_size": 256, "intermediate_size": 512,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2}
MIXES = {"tp": {"phase": "prefill", "tokens": 128, "prompts": 2},
         "td": {"phase": "decode", "context": 256, "batch": 1,
                "prompts": 3}}


def make_root(tmp: Path, extra_metric: bool = False) -> Path:
    """A checkout holding the benchmark plus tiny cells added as files."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/qwen3-14b.json").read_text())
    cfg.update(TINY, name="tiny")
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    for mix, params in MIXES.items():
        (tmp / f"bench/traffic/{mix}.json").write_text(json.dumps(params))
        (tmp / f"bench/limits/tiny.{mix}.json").write_text(
            json.dumps({"row_err": {"limit": 1e-3}}))
        spec["workloads"].append({"name": f"tiny.{mix}", "config": "tiny",
                                  "traffic": mix, "chips": 1, "why": "test"})
    if extra_metric:
        (tmp / "bench/metrics/throwaway.py").write_text(
            "def read(ctx):\n    return float(ctx.traced_steps > 0)\n")
        spec["per_layer"].append({
            "name": "throwaway", "unit": "count", "better": "higher",
            "source": "host_clock", "layer": "test", "moves": "step_ms",
            "workloads": ["tiny.tp"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root"))


def _run(bench, cell, tmp, trace=False):
    return run_cell(bench, bench.cell(cell), SEED, 0.5, trace,
                    out_dir=tmp / "out", t_start=time.perf_counter(),
                    log=lambda s: None)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_each_mix_matches_its_reference(root, tmp_path, mix):
    res = _run(Bench(root), f"tiny.{mix}", tmp_path)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    # the CPU runs every contraction in float32
    assert res["checks"]["row_err"]["value"] < 1e-4
    assert res["checks"]["prompts_compared"]["value"] == MIXES[mix]["prompts"]
    assert set(res["metrics"]) == {"step_ms", "step_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = make_root(tmp_path / "r", extra_metric=True)
    bench = Bench(root)
    assert bench.config("tiny")["hidden_size"] == 256
    assert bench.traffic("td")["context"] == 256
    res = _run(bench, "tiny.tp", tmp_path, trace=True)
    # the per-layer metrics of a traced run; a CPU trace holds no device
    # programs, so every device-trace reader returns nothing
    assert res["metrics"]["throwaway"]["value"] == 1.0
    assert res["metrics"]["builds_in_window"]["value"] == 0.0
    assert "gemm_roofline" not in res["metrics"]
    assert "step_roofline" not in res["metrics"]
    assert "dispatch_ms" in res["metrics"]
    assert res["device"]["busy_s"] == 0.0


def _faulty(real: types.ModuleType, fault: str) -> types.ModuleType:
    class Stage(real.Stage):
        def step(self, i):
            out = super().step(i)
            x = self.prompts[i]
            if fault == "unchanged":          # the state is returned as is
                return x + 0.0
            if fault == "half":               # half the tokens left out
                t = x.shape[0] // 2
                return jnp.concatenate([out[:t], x[t:]])
            # one token's answer altered where it is produced
            r = (x.shape[0] // 3) if x.ndim == 2 else slice(None)
            return out.at[r].add(0.1 * (out[r] - x[r]))

    return types.SimpleNamespace(**{**vars(real), "Stage": Stage})


@pytest.mark.parametrize("mix,fault", [
    ("tp", "unchanged"), ("tp", "half"), ("tp", "altered"),
    ("td", "unchanged"), ("td", "altered")])
def test_a_broken_step_is_not_correct(root, tmp_path, mix, fault):
    class Broken(Bench):
        def stage(self, cfg):
            return _faulty(super().stage(cfg), fault)

    res = _run(Broken(root), f"tiny.{mix}", tmp_path)
    assert not res["correct"]
    assert res["checks"]["row_err"]["value"] > \
        res["checks"]["row_err"]["limit"]


def test_a_step_that_bypasses_the_compiler_is_not_correct(root, tmp_path):
    from repro.kernels import registry

    class Bypass(Bench):
        def stage(self, cfg):
            real = super().stage(cfg)

            class Stage(real.Stage):
                def _glue(self):
                    super()._glue()
                    self.matmul = lambda a, b: registry.dispatch(
                        "gemm", a, b, ssr=False)
            return types.SimpleNamespace(**{**vars(real), "Stage": Stage})

    res = _run(Bypass(root), "tiny.tp", tmp_path)
    assert not res["correct"]
    chk = res["checks"]["compiler_calls_per_step"]
    assert chk["value"] < chk["limit"]


@pytest.mark.parametrize("cell", ["qwen3-14b.prefill-2k", "yi-6b.prefill-2k",
                                  "yi-6b.decode-b1"])
def test_control_fails_the_cells_limit(root, cell):
    """The reference with fp8 contractions, put in the program's place, at
    tiny widths: it reads above the limit of each cell, which the program
    (float32 here) stays far below."""
    from bench import inputs as gen

    bench = Bench(ROOT)
    limit = bench.limits(cell)["row_err"]["limit"]
    tiny = Bench(root)
    mix = "td" if "decode" in cell else "tp"
    cfg, traffic = tiny.config("tiny"), tiny.traffic(mix)
    ref = tiny.reference(cfg)
    k_w, k_in = jax.random.split(gen.seed_key(SEED))
    weights = ref.init_weights(cfg, k_w, traffic["phase"])
    data = gen.make(cfg, traffic, k_in)
    with jax.default_matmul_precision("highest"):
        for i in range(traffic["prompts"]):
            want = ref.forward(cfg, weights, data, i)
            low = ref.forward(cfg, weights, data, i, dot=ref.fp8_dot)
            assert harness.row_err(low, want, data["x"][i]) > limit


def test_storage_control_fails_the_decode_limit(root):
    """The reference with weights, activations and KV cache stored in bf16,
    at tiny widths: it reads above the decode cell's limit, whose GEMVs keep
    f32 on the chip (the prefill cells' limits do not hold storage)."""
    from bench import inputs as gen

    limit = Bench(ROOT).limits("yi-6b.decode-b1")["row_err"]["limit"]
    tiny = Bench(root)
    cfg, traffic = tiny.config("tiny"), tiny.traffic("td")
    ref = tiny.reference(cfg)
    k_w, k_in = jax.random.split(gen.seed_key(SEED))
    weights = ref.init_weights(cfg, k_w, traffic["phase"])
    data = gen.make(cfg, traffic, k_in)
    with jax.default_matmul_precision("highest"):
        for i in range(traffic["prompts"]):
            want = ref.forward(cfg, weights, data, i)
            low = ref.forward(cfg, weights, data, i, store=ref.bf16_store)
            assert harness.row_err(low, want, data["x"][i]) > limit


def _run_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_run",
                                                  ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_run_refuses_a_cpu(capsys):
    run = _run_module()
    rc = run.main(["--workload", "yi-6b.decode-b1", "--seed", "1",
                   "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no TPU" in out.err


def test_run_refuses_fault_and_fallback_variables(monkeypatch, capsys):
    run = _run_module()
    for var in harness.REFUSED_ENV:
        monkeypatch.setenv(var, "1")
        assert run.main(["--workload", "yi-6b.decode-b1", "--seed", "1",
                         "--seconds", "1"]) != 0
        monkeypatch.delenv(var)
    assert capsys.readouterr().out == ""


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "yi-6b.decode-b1",
         "--seed", "1", "--seconds", "1"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


X4_SCRIPT = r"""
import json, os, sys, time, types
from pathlib import Path
sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
import jax.numpy as jnp
from bench.harness import Bench, run_cell
from bench.tests.test_bench_harness import make_root

root = make_root(Path(sys.argv[2]))
spec = json.loads((root / "BENCHMARK.json").read_text())
cfg = json.loads((root / "bench/configs/tiny.json").read_text())
cfg.update(name="tiny4", split="cluster_2x2", num_attention_heads=8,
           num_key_value_heads=4)
(root / "bench/configs/tiny4.json").write_text(json.dumps(cfg))
(root / "bench/limits/tiny4.tp.json").write_text(
    json.dumps({"row_err": {"limit": 1e-3}}))
spec["workloads"].append({"name": "tiny4.tp", "config": "tiny4",
                          "traffic": "tp", "chips": 4, "why": "test"})
(root / "BENCHMARK.json").write_text(json.dumps(spec))


class NoExchange(Bench):
    # each chip's GEMM output columns reach only itself: the resharding
    # before the next GEMM is left out, the other half of the columns 0
    def stage(self, cfg):
        real = super().stage(cfg)

        class Stage(real.Stage):
            def _glue(self):
                super()._glue()
                mm = self.matmul

                def local(a, b):
                    out = mm(a, b)
                    return out.at[:, out.shape[1] // 2:].set(0.0)
                self.matmul = local
        return types.SimpleNamespace(**{**vars(real), "Stage": Stage})


out = {}
for name, bench in (("clean", Bench(root)), ("no_exchange", NoExchange(root))):
    r = run_cell(bench, bench.cell("tiny4.tp"), 2 ** 40 + 17, 0.5, False,
                 out_dir=root / "out", t_start=time.perf_counter(),
                 log=lambda s: None)
    out[name] = {"correct": r["correct"], "count": r["device"]["count"],
                 "row_err": r["checks"]["row_err"]["value"]}
print(json.dumps(out))
"""


def test_cluster_stage_and_a_missing_exchange(tmp_path):
    """The four-chip stage on four CPU devices: it matches the reference,
    and with the exchange between its split GEMMs left out it does not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", X4_SCRIPT, str(ROOT),
                        str(tmp_path)], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["clean"] == {"correct": True, "count": 4,
                            "row_err": res["clean"]["row_err"]}
    assert res["clean"]["row_err"] < 1e-4
    assert not res["no_exchange"]["correct"]
    assert res["no_exchange"]["row_err"] > 1e-3
