"""The program's spans on the device trace's clock (``bench/program_trace.py``)
and the four metrics that read them: on hand-made intervals, on the
recorded prefill trace of a program that had no such spans, and on a small
trace recorded on a TPU v5e chip by a ``--trace 1`` run of
``yi-6b.decode-b1`` with ``--seconds 0.5`` (8 steps)."""

import gzip
import json
import shutil
from pathlib import Path

import pytest

from bench import program_trace as pt_mod
from bench import trace
from bench.harness import Bench, Cell, Ctx
from bench.program_trace import Device, ProgramTrace, Span

DATA = Path(__file__).resolve().parent / "data"
BENCH = Path(__file__).resolve().parents[1]
METRICS = ("lookup_ms", "launch_ms", "compiler_idle_ms", "build_s")


def _metric(name):
    return Bench(BENCH.parent).metric(name)


def _ctx(cell, steps, red=None, counters=None):
    c = Cell(cell, cell.split(".")[0], cell.split(".")[1], 1)
    return Ctx(c, {}, {}, None, [], traced_steps=steps, trace=red,
               counters_before=counters or {})


def _trace(spans, devices=()):
    return ProgramTrace(pt_mod.nest([Span(*s) for s in spans]),
                        list(devices))


# -- hand-made intervals ------------------------------------------------


def test_spans_nest_by_thread_and_self_time_leaves_out_launch():
    t = _trace([("ops.gemv", 0, 100, "py"), ("ssr.call", 10, 90, "py"),
                ("ssr.launch", 30, 80, "py"), ("glue.res", 100, 120, "py"),
                ("ssr.launch", 40, 60, "other")])
    by = {(s.name, s.thread): s for s in t.spans}
    call = by["ssr.call", "py"]
    assert t.spans[call.parent].name == "ops.gemv"
    assert t.spans[by["ssr.launch", "py"].parent] is call
    assert by["ssr.launch", "other"].parent is None     # another thread
    assert by["glue.res", "py"].parent is None          # adjacent, not inside
    assert t.self_ns(call, ("ssr.launch", "ssr.build")) == 80 - 50
    assert t.self_ns(call, ()) == 80


def test_offset_recovers_a_known_skew_exactly():
    skew = -1_180_000
    host = [1_000, 50_000, 90_000, 400_000, 800_000]
    lag = [37_000, 0, 260_000, 12_000, 0]
    pairs = [(h + skew + d, h) for h, d in zip(host, lag)]
    assert pt_mod.offset(pairs) == skew
    assert pt_mod.drift(pairs) == 0
    # a clock that runs 5 µs further ahead in the second half
    moved = pairs[:2] + [(d + 5_000, h) for d, h in pairs[2:]]
    assert pt_mod.drift(moved) == 5_000
    assert pt_mod.offset([]) is None and pt_mod.drift(pairs[:1]) is None


def test_idle_gaps_map_onto_the_host_spans_they_fall_in():
    skew = 1_000_000                      # device clock 1 ms ahead
    dev = Device("/device:TPU:0",
                 busy=[(skew + 0, skew + 10), (skew + 40, skew + 50),
                       (skew + 90, skew + 100)],
                 pairs=[(skew + 0, 0), (skew + 45, 40)])
    t = _trace([("ops.gemv", 5, 60, "py"), ("ssr.call", 8, 45, "py"),
                ("ssr.launch", 20, 30, "py"), ("glue.x", 70, 95, "py")],
               [dev])
    assert dev.offset() == skew
    assert dev.idle() == [(skew + 10, skew + 40), (skew + 50, skew + 90)]
    # gap (10, 40) lies inside ssr.call (8, 45); gap (50, 90) does not
    assert t.idle_in("ssr.call") == 30
    assert t.idle_in("ssr.build") is None
    got = t.breakdown()
    assert got == {
        "ssr.launch": {"host_ns": 10, "idle_ns": 10},
        "ssr.call self": {"host_ns": 27, "idle_ns": 20},
        "ops.* outside ssr.call": {"host_ns": 18, "idle_ns": 10},
        "outside every span": {"host_ns": 10, "idle_ns": 10},
        "glue.*": {"host_ns": 25, "idle_ns": 20},
    }
    # every host ns of the window and every idle ns inside it, once
    assert sum(v["host_ns"] for v in got.values()) == 95 - 5
    assert sum(v["idle_ns"] for v in got.values()) == 30 + 40


def test_metrics_read_the_spans_per_traced_step():
    t = _trace([("ops.gemv", 0, 100_000, "py"),
                ("ssr.call", 0, 90_000, "py"),
                ("ssr.launch", 20_000, 80_000, "py"),
                ("ops.gemv", 100_000, 300_000, "py"),
                ("ssr.call", 100_000, 290_000, "py"),
                ("ssr.launch", 150_000, 250_000, "py")],
               [Device("/device:TPU:0", [(0, 10_000), (200_000, 300_000)],
                       [(0, 0)])])
    ctx = _ctx("yi-6b.decode-b1", steps=2, red=object(),
               counters={"frontend.build_ns": 2_500_000_000})
    setattr(ctx, pt_mod._CACHE, t)
    got = {m: _metric(m).read(ctx) for m in METRICS}
    assert got["lookup_ms"] == pytest.approx((30_000 + 90_000) * 1e-6 / 2)
    assert got["launch_ms"] == pytest.approx((60_000 + 100_000) * 1e-6 / 2)
    # the one gap (10 µs, 200 µs) lies in ssr.call but for (90, 100) µs
    assert got["compiler_idle_ms"] == pytest.approx(180_000 * 1e-6 / 2)
    assert got["build_s"] == 2.5


def test_metrics_read_nothing_without_a_trace_or_a_counter():
    ctx = _ctx("yi-6b.decode-b1", steps=4)
    assert all(_metric(m).read(ctx) is None for m in METRICS)


def test_a_program_without_spans_reads_none(tmp_path, monkeypatch):
    """The parent program had no ``ssr.*`` span and no ``build_ns``: its
    recorded prefill trace reads None, and nothing raises."""
    cell = "yi-6b.prefill-2k"
    (tmp_path / cell).mkdir()
    shutil.copy(DATA / f"{cell}.xplane.pb", tmp_path / cell)
    monkeypatch.setattr(pt_mod, "TRACE_DIR", tmp_path)
    ctx = _ctx(cell, steps=4, red=object(),
               counters={"frontend.builds": 5, "frontend.calls": 100})
    assert all(_metric(m).read(ctx) is None for m in METRICS)
    assert pt_mod.of(ctx).named("ssr.call") == []
    # nothing found where the run left no profile
    monkeypatch.setattr(pt_mod, "TRACE_DIR", tmp_path / "none")
    assert pt_mod.of(_ctx(cell, steps=4, red=object())) is None


# -- the recorded decode trace -----------------------------------------

DECODE = "yi-6b.decode-b1"


@pytest.fixture(scope="module")
def decode(tmp_path_factory):
    # kept gzipped (1.8 MB as the profiler wrote it, 0.66 MB stored)
    root = tmp_path_factory.mktemp("trace")
    (root / DECODE).mkdir()
    with gzip.open(DATA / f"{DECODE}.xplane.pb.gz") as src, \
            open(root / DECODE / f"{DECODE}.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    result = json.loads((DATA / f"{DECODE}.result.json").read_text())
    # the run's "counters before:" log line (the result line omits it)
    result["counters_before"] = json.loads(
        (DATA / f"{DECODE}.counters.json").read_text())
    return root, result


def _decode_ctx(decode, monkeypatch):
    root, result = decode
    monkeypatch.setattr(pt_mod, "TRACE_DIR", root)
    red = trace.reduce_xplane(trace.find_xplane(str(root / DECODE)), set())
    return _ctx(DECODE, steps=result["attempted"], red=red,
                counters=result["counters_before"]), result


def test_recorded_decode_metrics_read_as_the_run_did(decode, monkeypatch):
    ctx, result = _decode_ctx(decode, monkeypatch)
    for m in METRICS:
        assert _metric(m).read(ctx) == pytest.approx(
            result["metrics"][m]["value"], rel=1e-9), m


def test_recorded_decode_split_fits_inside_dispatch(decode, monkeypatch):
    ctx, result = _decode_ctx(decode, monkeypatch)
    got = {m: result["metrics"][m]["value"] for m in METRICS}
    dispatch = result["metrics"]["dispatch_ms"]["value"]
    assert 0 < got["lookup_ms"] and 0 < got["launch_ms"]
    assert got["lookup_ms"] + got["launch_ms"] <= dispatch
    dev = result["device"]
    idle_ms = 1e3 * (dev["window_s"] - dev["busy_s"]) / result["attempted"]
    assert 0 < got["compiler_idle_ms"] <= idle_ms
    assert got["build_s"] > 0


def test_recorded_decode_spans_nest_as_the_program_opens_them(
        decode, monkeypatch):
    ctx, result = _decode_ctx(decode, monkeypatch)
    t = pt_mod.of(ctx)
    calls = t.named("ssr.call")
    # 5 compiler calls a layer, one span each, each under its ops.* span
    assert len(calls) == 5 * 16 * result["attempted"]
    assert all(t.spans[c.parent].name.startswith("ops.") for c in calls)
    for c in calls:
        assert [t.spans[k].name for k in c.children] == ["ssr.launch"]
    assert t.named("ssr.build") == []            # warm: no build
    # the profiler leaves the planes apart: the device clock reads behind
    # the host's by far more than a launch takes, and steadily
    (dev,) = t.devices
    assert dev.offset() < -100_000
    assert abs(pt_mod.drift(dev.pairs)) < 50_000
    # every idle gap inside the window lands in one group
    split = t.breakdown()
    assert sum(v["idle_ns"] for v in split.values()) <= sum(
        b - a for a, b in dev.idle())
