"""The trace reduction, on a small trace recorded on a TPU v5e chip by a
``--trace 1`` run of ``yi-6b.prefill-2k``, and on hand-made intervals."""

import json
from pathlib import Path

import pytest

from bench import trace
from bench.stages.dense_gqa import step_work

DATA = Path(__file__).resolve().parent / "data"
XPLANE = DATA / "yi-6b.prefill-2k.xplane.pb"
RESULT = DATA / "yi-6b.prefill-2k.result.json"
BENCH = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def recorded():
    cfg = json.loads((BENCH / "configs/yi-6b.json").read_text())
    traffic = json.loads((BENCH / "traffic/prefill-2k.json").read_text())
    spans = {s for s, _ in step_work(cfg, traffic)}
    return (trace.reduce_xplane(str(XPLANE), spans),
            json.loads(RESULT.read_text()))


def test_recorded_trace_reads_as_the_run_did(recorded):
    red, result = recorded
    assert len(red.devices) == 1
    assert red.devices[0].name == "/device:TPU:0"
    assert red.busy_s() == pytest.approx(result["device"]["busy_s"],
                                         rel=1e-12)
    assert red.window_s() == pytest.approx(result["device"]["window_s"],
                                           rel=1e-12)
    assert 0 < red.busy_s() <= red.window_s()


def test_recorded_programs_are_named_by_the_spans_that_launched_them(
        recorded):
    red, result = recorded
    dev = red.span_device_s()
    assert {"ops.gemm", "ops.attention", "glue.swiglu"} <= set(dev)
    # the GEMMs take most of a prefill step; nothing of note is unnamed
    assert dev["ops.gemm"] > 0.6 * red.busy_s()
    assert dev.get("other", 0.0) < 0.02 * red.busy_s()
    names = [n for n, _ in red.top_ops()]
    assert names[0].startswith("ops.gemm %pipeline")
    assert result["breakdown"]["device_ops"][0][0] == names[0]
    assert red.collective_exposed_s() is None


def test_idle_gaps_add_up_to_the_idle_time(recorded):
    red, _ = recorded
    gaps = sum(s for _, s in red.idle_gaps(n=100))
    idle = red.window_s() - red.busy_s()
    assert 0.99 * idle <= gaps <= idle + 1e-12


def _dev(ops, modules):
    d = trace.DeviceTrace("/device:TPU:0")
    d.ops = ops
    d.modules = modules
    return d


def test_busy_is_the_union_of_operations():
    d = _dev([(0.0, 2.0, "a"), (1.0, 3.0, "b"), (5.0, 6.0, "c")],
             [(0.0, 3.0, "ops.gemm"), (5.0, 6.0, "glue.x")])
    red = trace.Reduced([d])
    assert red.busy_s() == 4.0
    assert red.window_s() == 6.0
    assert red.idle_gaps() == [["glue.x", 2.0]]
    assert red.span_device_s() == {"ops.gemm": 3.0, "glue.x": 1.0}


def test_exposed_collective_time_is_what_no_compute_covers():
    d = _dev([(0.0, 4.0, "ops.gemm %pipeline.1"),
              (3.0, 6.0, "other %all-gather.2 = f32[8]"),
              (7.0, 8.0, "other %collective-permute.1 = f32[8]")],
             [(0.0, 8.0, "ops.gemm")])
    red = trace.Reduced([d, d])
    assert red.collective_exposed_s() == 3.0     # (4, 6) and (7, 8)


def test_short_op_keeps_name_and_shape():
    hlo = ('%pipeline.1 = f32[2048,7168]{1,0:T(8,128)} custom-call('
           'f32[2048,5120]{1,0:T(8,128)} %arrs_0_.1), custom_call_target='
           '"tpu_custom_call"')
    assert trace.short_op(hlo) == "%pipeline.1 = f32[2048,7168]"


def test_step_roofline_reads_the_device_window(recorded):
    """The whole step's share, from the trace: the traced steps' least time
    over the device window, never the host's clock."""
    from bench.harness import Bench, Cell, Ctx
    from bench.peaks import peaks_for

    red, result = recorded
    cfg = json.loads((BENCH / "configs/yi-6b.json").read_text())
    cfg["num_hidden_layers"] = 4                 # the recorded run's depth
    traffic = json.loads((BENCH / "traffic/prefill-2k.json").read_text())
    peaks = peaks_for(result["device"]["kind"])
    ctx = Ctx(Cell("yi-6b.prefill-2k", "yi-6b", "prefill-2k", 1), cfg,
              traffic, peaks, step_work(cfg, traffic),
              traced_steps=result["attempted"], trace=red)
    value = Bench(BENCH.parent).metric("step_roofline").read(ctx)
    least = sum(w.least_s(peaks) for _, w in ctx.work)
    assert value == pytest.approx(
        100.0 * result["attempted"] * least / red.window_s(), rel=1e-12)
    # a device-bound prefill step: the GEMMs' share bounds it from below
    assert result["metrics"]["gemm_roofline"]["value"] < value < 100.0
    assert Bench(BENCH.parent).metric("step_roofline").read(
        Ctx(ctx.cell, cfg, traffic, peaks, ctx.work, traced_steps=4)) is None
