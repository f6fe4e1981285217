"""The benchmark's arithmetic against hand-worked numbers: trace reduction,
roofline and mfu, percentiles."""

import sys

import pytest

from perfbench_fixtures import REPO

DATA = REPO / "tests/perfbench/data"

sys.path.insert(0, str(REPO))

from perfbench import roofline, stats, trace  # noqa: E402

# A small recorded device timeline (ns) and the benchmark's host spans:
# ops overlap at 10-20 and touch at 40; idle 25-30 (inside a decode
# round), 45-60 (its midpoint past the second round) and 90-100.
OPS = [(name, s, e, f"%{name} = f32[8] op()") for name, s, e in (
    ("fusion.1", 0, 20), ("custom-call.1", 10, 25), ("fusion.2", 30, 40),
    ("custom-call.2", 40, 45), ("copy.3", 60, 90))]
OPS[1] = OPS[1][:3] + ('%custom-call.1 = f32[8] custom-call(), kernel_name="lut_matmul"',)
OPS[3] = OPS[3][:3] + ('%custom-call.2 = f32[8] custom-call(), kernel_name="lut_matmul"',)
SPANS = [("window_on", 0, 0), ("window_off", 100, 100),
         ("decode_round", 20, 35), ("decode_round", 40, 50)]


def test_union_merges_overlaps_and_clips():
    assert trace.union([op[1:3] for op in OPS], 5, 95) == [(5, 25), (30, 45), (60, 90)]


def test_busy_and_kernel_time():
    assert trace.busy_ns(OPS, 0, 100) == 25 + 15 + 30
    assert trace.kernel_ns(OPS, "lut_matmul", 0, 100) == 15 + 5
    assert trace.kernel_ns(OPS, "lut_matmul", 0, 42) == 15 + 2


def test_idle_gaps_named_by_the_innermost_span():
    gaps = trace.idle_gaps(OPS, SPANS, 0, 100)
    assert [g[0] for g in gaps] == [trace.OUTSIDE, trace.OUTSIDE, "decode_round"]
    assert [g[1] for g in gaps] == pytest.approx([15e-9, 10e-9, 5e-9])


def test_top_ops_by_device_time():
    top = trace.top_ops(OPS, 0, 100, n=2)
    assert [t[0] for t in top] == ["copy.3", "fusion.1"]
    assert [t[1] for t in top] == pytest.approx([30e-9, 20e-9])


def test_summarize_averages_devices_over_the_window():
    tr = trace.Trace(ops={"/device:TPU:0": OPS, "/device:TPU:1": OPS[:1]}, spans=SPANS)
    s = trace.summarize(tr)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx((70 + 20) / 2 * 1e-9)
    tr.spans.append(("window_on", 50, 50))
    with pytest.raises(ValueError):
        trace.summarize(tr)  # two markers: no one window


def test_gemm_roofline_hand_worked():
    # (8, 1024) @ (1024, 3072): 50,331,648 ops; 6,356,992 bytes in bf16.
    assert roofline.gemm_ops_bytes(8, 1024, 3072) == (50_331_648, 6_356_992)
    # bandwidth-bound on a v5e: 6,356,992 / 819e9 s against 100 us taken
    share = roofline.roofline_share([(8, 1024, 3072)], 1e-4, "TPU v5 lite")
    assert share == pytest.approx(100 * 6_356_992 / 819e9 / 1e-4)
    # compute-bound: (4096, 4096) @ (4096, 4096) in 1 ms
    ops = 2 * 4096 ** 3
    assert roofline.roofline_share([(4096,) * 3], 1e-3, "TPU v5 lite") == pytest.approx(
        100 * ops / 197e12 / 1e-3)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peak("cpu")


CFG = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
       "head_dim": 2, "intermediate_size": 8, "vocab_size": 10, "num_hidden_layers": 3}


def test_request_ops_hand_worked():
    # per layer: q 4*4 + k,v 2*4*2 + o 4*4 + mlp 3*4*8 = 16 + 16 + 16 + 96 = 144
    assert roofline.layer_matmul_params(CFG) == 144
    # prompt 3, 2 tokens out: 4 tokens forwarded, contexts 1+2+3+4 = 10;
    # 3 layers * (2*144*4 + 4*2*2*10) + head 2*4*10*2 = 3 * 1472 + 160
    assert roofline.request_ops(CFG, 3, 2) == 3 * (2 * 144 * 4 + 4 * 2 * 2 * 10) + 160


def test_mfu():
    assert roofline.mfu(197e12, 2.0, 1, "TPU v5 lite") == pytest.approx(50.0)
    assert roofline.mfu(197e12, 1.0, 4, "TPU v5 lite") == pytest.approx(25.0)


def test_percentile_is_linear_interpolation():
    assert stats.percentile([1, 2, 3, 4, 5], 95) == pytest.approx(4.8)
    assert stats.percentile([], 95) is None


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5 lite: three calls of a jitted
    (2048, 2048) bf16 product inside ``bench:decode_round`` spans, 10 ms
    of sleep between them, all inside ``bench:window``."""
    tr = trace.load(DATA)
    assert list(tr.ops) == ["/device:TPU:0"]
    ops = tr.ops["/device:TPU:0"]
    assert [op[0] for op in ops] == ["copy-start", "copy-done", "fusion"] * 3
    assert sorted(n for n, _, _ in tr.spans) == ["decode_round"] * 3 + ["window"]
    (lo, hi), = [(st, en) for n, st, en in tr.spans if n == "window"]
    tr.spans += [("window_on", lo, lo), ("window_off", hi, hi)]
    s = trace.summarize(tr)
    assert s["window_s"] == pytest.approx(0.033996080)
    # the first call's ops fall before the host's window span (the device
    # clock runs ~0.8 ms apart), so two calls' ops count as busy:
    # copy-start, copy-done and the fusion of each, 14 + 3 + 90196 ns and
    # 13 + 2 + 90197 ns
    assert s["busy_s"] == pytest.approx((14 + 3 + 90196 + 13 + 2 + 90197) * 1e-9)
    assert [g[0] for g in s["idle_gaps"][:2]] == ["window", "window"]  # its own span
    assert s["device_ops"][0][0] == "fusion"
