"""The sliding-window stage and its reference on the CPU at tiny widths.

A tiny Danube (head dim 80, window 100 over 520 tokens, so the tokens, the
window and the 104-row tiles divide none of one another) is added as files
in a copy of the benchmark.  Its step matches ``window_gqa.forward``; a
step that ignores the window, or that keeps whole tiles of the band and so
rounds the window up, reads not correct under the cell's own limit, as
does the reference with fp8 contractions or with bf16 storage.
"""

import json
import time
import types
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.harness import Bench, Ctx, run_cell
from bench.tests.test_bench_harness import SEED, make_root

ROOT = Path(__file__).resolve().parents[2]
CELL = "h2o-danube-1.8b.prefill-16k"
TINY = {"hidden_size": 320, "intermediate_size": 512,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "sliding_window": 100}
MIX = {"phase": "prefill", "tokens": 520, "prompts": 2}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("window_root"))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/h2o-danube-1.8b.json")
                     .read_text())
    cfg.update(TINY, name="tinyw")
    (root / "bench/configs/tinyw.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tw.json").write_text(json.dumps(MIX))
    # the new cell's own limit
    (root / "bench/limits/tinyw.tw.json").write_text(
        (ROOT / f"bench/limits/{CELL}.json").read_text())
    spec["workloads"].append({"name": "tinyw.tw", "config": "tinyw",
                              "traffic": "tw", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _run(bench, tmp, trace=False):
    return run_cell(bench, bench.cell("tinyw.tw"), SEED, 0.5, trace,
                    out_dir=tmp / "out", t_start=time.perf_counter(),
                    log=lambda s: None)


def test_stage_matches_the_window_reference(root, tmp_path):
    res = _run(Bench(root), tmp_path)
    assert res["correct"], res["checks"]
    # the CPU runs every contraction in float32
    assert res["checks"]["row_err"]["value"] < 1e-4
    assert res["checks"]["compiler_calls_per_step"]["value"] == 12


def _rounded_window_kernel():
    """The attention kernel with its band, but an element mask that leaves
    the window's edge out: whole tiles of the band are kept."""
    from repro.kernels import attention as A
    from repro.kernels.frontend import NestKernel

    return NestKernel(
        "attention", prepare=A._prepare, nest=A._nest,
        body=lambda st: A._body(st[:4] + (None,) + st[5:]),
        out_dtype=lambda st: st[6])


@pytest.mark.parametrize("fault", ["ignored", "whole_tiles"])
def test_a_stage_that_misses_the_window_is_not_correct(root, tmp_path,
                                                       fault):
    class Broken(Bench):
        def stage(self, cfg):
            real = super().stage(cfg)

            class Stage(real.Stage):
                def _glue(self):
                    super()._glue()
                    scale = 1.0 / np.sqrt(self.s["hd"])
                    if fault == "ignored":
                        from repro.kernels import ops
                        fn = partial(ops.flash_attention, causal=True,
                                     scale=scale, ssr=True)
                    else:
                        fn = partial(_rounded_window_kernel(), causal=True,
                                     window=self.cfg["sliding_window"],
                                     scale=scale)
                    self.attn_prefill = jax.vmap(jax.vmap(
                        fn, in_axes=(0, None, None)))
            return types.SimpleNamespace(**{**vars(real), "Stage": Stage})

    res = _run(Broken(root), tmp_path)
    assert not res["correct"]
    assert res["checks"]["row_err"]["value"] > \
        res["checks"]["row_err"]["limit"]


def _control_errs(root, **control):
    """row_err of the reference run with ``control`` (a lower-precision
    ``dot`` or ``store``) against the exact one, at tiny widths and the
    cell's own depth: storage rounding grows with the layers it crosses."""
    from bench import inputs as gen

    depth = Bench(ROOT).config("h2o-danube-1.8b")["num_hidden_layers"]
    tiny = Bench(root)
    cfg = {**tiny.config("tinyw"), "num_hidden_layers": depth}
    traffic = tiny.traffic("tw")
    ref = tiny.reference(cfg)
    k_w, k_in = jax.random.split(gen.seed_key(SEED))
    weights = ref.init_weights(cfg, k_w, traffic["phase"])
    data = gen.make(cfg, traffic, k_in)
    with jax.default_matmul_precision("highest"):
        return [harness.row_err(ref.forward(cfg, weights, data, i, **control),
                                ref.forward(cfg, weights, data, i),
                                data["x"][i])
                for i in range(traffic["prompts"])]


def test_fp8_control_fails_the_cells_limit(root):
    limit = Bench(ROOT).limits(CELL)["row_err"]["limit"]
    ref = Bench(root).reference({"architecture": "window_gqa"})
    assert min(_control_errs(root, dot=ref.fp8_dot)) > limit


def test_storage_control_fails_the_cells_limit(root):
    """Weights and activations stored in bf16 (the configuration stores
    f32): not correct under the cell's limit."""
    limit = Bench(ROOT).limits(CELL)["row_err"]["limit"]
    ref = Bench(root).reference({"architecture": "window_gqa"})
    assert min(_control_errs(root, store=ref.bf16_store)) > limit


@pytest.mark.parametrize("t,window,block", [(200, 37, 64), (96, 200, 512),
                                            (130, 1, 32)])
def test_blocked_reference_attention_is_the_full_one(root, monkeypatch, t,
                                                     window, block):
    """The reference's query blocks over their bands give what one masked
    (T, T) softmax gives, block and window dividing nothing."""
    ref = Bench(root).reference({"architecture": "window_gqa"})
    monkeypatch.setattr(ref, "BLOCK", block)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal(s), jnp.float32)
               for s in ((t, 4, 16), (t, 2, 16), (t, 2, 16)))
    with jax.default_matmul_precision("highest"):
        got = ref._attend(ref.exact_dot, q, k, v, window)
        i, j = np.arange(t)[:, None], np.arange(t)[None, :]
        keep = (j <= i) & (j > i - window)
        kh, vh = jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1)
        s = jnp.einsum("qhd,khd->hqk", q, kh) / 4.0
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        want = jnp.einsum("hqk,khd->qhd", p, vh).reshape(t, 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_new_cell_files_are_found_by_name():
    bench = Bench(ROOT)
    cell = bench.cell(CELL)
    assert (cell.config, cell.traffic, cell.chips) == \
        ("h2o-danube-1.8b", "prefill-16k", 1)
    cfg = bench.config(cell.config)
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["sliding_window"],
            cfg["num_key_value_heads"]) == (2560, 80, 4096, 8)
    assert bench.reference(cfg).forward and bench.stage(cfg).Stage
    assert bench.traffic(cell.traffic)["tokens"] == 16384
    assert bench.limits(CELL)["row_err"]["limit"] > 0
    per_layer = {m["name"] for m in bench.metrics_for(CELL, "per_layer")}
    assert {"window_attention_roofline", "attention_tiles_share"} <= \
        per_layer
    for cell_name in ("qwen3-14b.prefill-2k", "yi-6b.prefill-2k"):
        assert "attention_tiles_share" in {
            m["name"] for m in bench.metrics_for(cell_name, "per_layer")}


def test_new_metrics_read_the_counters_or_nothing():
    bench = Bench(ROOT)
    ctx = Ctx(bench.cell(CELL), {}, {}, None, [])
    tiles = bench.metric("attention_tiles_share")
    # a program without the counters (the parent) has nothing to read
    assert tiles.read(ctx) is None
    assert bench.metric("window_attention_roofline").read(ctx) is None
    ctx.counters_before = {"lowering.band_tiles": 952,
                           "lowering.rect_tiles": 4096}
    assert tiles.read(ctx) == pytest.approx(23.2421875)


def test_window_work_counts_the_pairs_kept():
    bench = Bench(ROOT)
    cfg = bench.config("h2o-danube-1.8b")
    stage = bench.stage(cfg)
    steps = stage.step_work(cfg, bench.traffic("prefill-16k"))
    attn = [w for span, w in steps if span == "ops.attention"]
    assert len(attn) == cfg["num_hidden_layers"]
    pairs = sum(min(i + 1, 4096) for i in range(16384))
    assert stage.window_pairs(16384, 4096) == pairs == 58_722_304
    assert attn[0].flops == 4 * 80 * pairs * 32
    assert stage.window_pairs(96, 200) == 96 * 97 // 2
