"""Work counts from shapes, against values worked by hand."""

import json
from pathlib import Path

import pytest

from bench import work
from bench.peaks import PEAKS, UnknownDevice, peaks_for
from bench.stages.dense_gqa import step_work

BENCH = Path(__file__).resolve().parents[1]


def _load(kind, name):
    return json.loads((BENCH / kind / f"{name}.json").read_text())


@pytest.mark.parametrize("m,k,n,flops,moved", [
    # qwen3-14b prefill: fused QKV, O, gate/up, down
    (2048, 5120, 7168, 150_323_855_360, 4 * (10_485_760 + 36_700_160
                                             + 14_680_064)),
    (2048, 5120, 5120, 107_374_182_400, 4 * (10_485_760 * 2 + 26_214_400)),
    (2048, 5120, 17408, 365_072_220_160, 4 * (10_485_760 + 89_128_960
                                              + 35_651_584)),
    (2048, 17408, 5120, 365_072_220_160, 4 * (35_651_584 + 89_128_960
                                              + 10_485_760)),
    # yi-6b prefill: fused QKV and gate/up
    (2048, 4096, 5120, 85_899_345_920, 4 * (8_388_608 + 20_971_520
                                            + 10_485_760)),
    (2048, 4096, 11008, 184_683_593_728, 4 * (8_388_608 + 45_088_768
                                              + 22_544_384)),
])
def test_gemm_counts(m, k, n, flops, moved):
    w = work.gemm(m, k, n)
    assert (w.family, w.flops, w.bytes) == ("gemm", flops, moved)


@pytest.mark.parametrize("rows,cols,flops,moved", [
    (5120, 4096, 41_943_040, 4 * (20_971_520 + 4096 + 5120)),     # yi QKV
    (4096, 4096, 33_554_432, 4 * (16_777_216 + 4096 + 4096)),     # yi O
    (22016, 4096, 180_355_072, 4 * (90_177_536 + 4096 + 22016)),  # gate·up
    (4096, 11008, 90_177_536, 4 * (45_088_768 + 11008 + 4096)),   # down
])
def test_gemv_counts(rows, cols, flops, moved):
    w = work.gemv(rows, cols)
    assert (w.family, w.flops, w.bytes) == ("gemv", flops, moved)


def test_causal_attention_counts_the_unmasked_half():
    # 40 q heads over 8 KV heads at 2048×128: 2048·2049/2 pairs per head
    w = work.attention(40, 8, 2048, 2048, 128, causal=True)
    assert w.flops == 4 * 128 * (2048 * 2049 // 2) * 40 == 42_970_644_480
    assert w.bytes == 4 * 128 * (2 * 2048 * 40 + 2 * 2048 * 8)
    full = work.attention(40, 8, 2048, 2048, 128, causal=False)
    assert full.flops == 4 * 128 * 2048 * 2048 * 40
    # a query block at the end of a longer key range: row i sees off+i+1
    tail = work.attention(1, 1, 2, 5, 4, causal=True)
    assert tail.flops == 4 * 4 * (4 + 5)


def test_decode_attention_counts():
    # yi-6b: 32 single-token queries over 4 KV heads of a 4096 cache
    w = work.attention(32, 4, 1, 4096, 128, causal=False)
    assert w.flops == 4 * 128 * 4096 * 32 == 67_108_864
    assert w.bytes == 4 * 128 * (2 * 32 + 2 * 4096 * 4)


@pytest.mark.parametrize("config,traffic,calls,ops,flops", [
    ("qwen3-14b", "prefill-2k", 45, 24,
     4 * (150_323_855_360 + 107_374_182_400 + 3 * 365_072_220_160
          + 42_970_644_480)),
    ("yi-6b", "prefill-2k", 177, 96,
     16 * (85_899_345_920 + 2 * 2048 * 4096 * 4096 + 3 * 184_683_593_728
          + 4 * 128 * (2048 * 2049 // 2) * 32)),
    ("yi-6b", "decode-b1", 161, 80,
     16 * (41_943_040 + 33_554_432 + 180_355_072 + 90_177_536
          + 67_108_864)),
])
def test_step_work_of_each_cell(config, traffic, calls, ops, flops):
    steps = step_work(_load("configs", config), _load("traffic", traffic))
    assert len(steps) == calls
    assert sum(1 for s, _ in steps if s.startswith("ops.")) == ops
    assert sum(w.flops for _, w in steps) == flops
    assert all(w.flops == 0 for s, w in steps if s.startswith("glue."))


def test_least_time_takes_the_larger_bound():
    p = peaks_for("TPU v5 lite")
    big = work.gemm(2048, 5120, 17408)          # bound by FLOPs
    assert big.least_s(p) == big.flops / p.flops > big.bytes / p.hbm_bytes
    vec = work.gemv(22016, 4096)                # bound by HBM bytes
    assert vec.least_s(p) == vec.bytes / p.hbm_bytes > vec.flops / p.flops


def test_peaks_refuse_an_unknown_device():
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v4")
    p = peaks_for("TPU v5 lite")
    assert (p.flops, p.hbm_bytes) == (197e12, 819e9)
    assert all("TPU v5e" in v.source for v in PEAKS.values())
