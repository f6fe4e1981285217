"""The program's ``serve:`` spans beside the device operations
(``perfbench.spans``): the clock offset and the idle time each span holds,
on hand-worked intervals and on a trace recorded on one TPU v5 lite."""

import sys

import pytest

from perfbench_fixtures import REPO

sys.path.insert(0, str(REPO))

from perfbench import spans  # noqa: E402

# beside data/, not in it: trace.load of that directory reads its newest-named
# .xplane.pb, which must stay the probe trace
RECORDED = REPO / "tests/perfbench/traces/spans.xplane.pb"

# Two decode rounds on a device clock 5 ns behind the host's.  Host: round
# one's decode 10-40 (prep 10-14, dispatch 14-16, sync 16-36), absorb
# 40-42, an admission 45-60 (sync 50-58), round two's decode 62-90 (sync
# 66-88).  Device (its own clock): round one 13-30, the admission 45-52,
# round two 60-82, each with the host time the runtime enqueued it.
TICKS = [("tick", 8, 43), ("tick", 44, 92)]
SPANS = TICKS + [
    ("decode", 10, 40), ("decode.prep", 10, 14), ("decode.dispatch", 14, 16),
    ("decode.sync", 16, 36), ("absorb", 40, 42), ("admit", 45, 60),
    ("admit.sync", 50, 58), ("decode", 62, 90), ("decode.sync", 66, 88)]
TRACE = spans.ServeTrace(
    ops=[(13, 20), (20, 30), (45, 52), (60, 82)],
    modules=[("jit_decode_greedy", 13, 30, 17), ("jit_admit_step", 45, 52, 47),
             ("jit_decode_greedy", 60, 82, 67)],
    spans=sorted(SPANS, key=lambda s: s[1]))


def test_rounds_pair_each_sync_with_its_program():
    assert spans.rounds(TRACE) == [(16, 36, 13, 30), (66, 88, 60, 82)]
    # a program enqueued outside every decode span, or with no enqueue
    # event, belongs to no round
    stray = spans.ServeTrace(ops=TRACE.ops, modules=[
        ("jit_decode_greedy", 13, 30, 5), ("jit_decode_greedy", 60, 82, None)],
        spans=TRACE.spans)
    assert spans.rounds(stray) == []


def test_offset_is_the_tightest_rounds_margin():
    # margins 36 - 30 = 6 and 88 - 82 = 6, then 5 with a later program end
    assert spans.clock_offset(TRACE) == 6
    late = spans.ServeTrace(ops=TRACE.ops, modules=TRACE.modules[:2]
                            + [("jit_decode_greedy", 60, 83, 67)], spans=TRACE.spans)
    assert spans.clock_offset(late) == 5


def test_idle_time_by_innermost_span():
    # device shifted by 5: busy 18-35, 50-57, 65-87 inside [10, 90]
    att = spans.attribute(TRACE, 5)
    assert att["window_s"] == pytest.approx(80e-9)
    # idle 10-18, 35-50, 57-65, 87-90: 8 + 15 + 8 + 3
    assert att["idle_s"] == pytest.approx(34e-9)
    # outside every child span: 42-45 (tick but 43-44, no span) and 60-62
    assert att["named_s"] == pytest.approx((34 - 3 - 2) * 1e-9)
    assert att["named_share"] == pytest.approx(29 / 34)
    # 10-14 prep, 14-16 dispatch, 16-18 sync; 35-36 sync, 36-40 decode,
    # 40-42 absorb, 42-43 tick, 43-44 none, 44-45 tick, 45-50 admit; 57-58
    # admit.sync, 58-60 admit, 60-62 tick, 62-65 decode; 87-88 sync, 88-90
    # decode
    assert att["by_span"] == pytest.approx({
        "decode.prep": 4e-9, "decode.dispatch": 2e-9, "decode.sync": 4e-9,
        "decode": 9e-9, "absorb": 2e-9, "tick": 4e-9, None: 1e-9, "admit": 7e-9,
        "admit.sync": 1e-9})


def test_a_trace_without_rounds_is_an_error():
    bare = spans.ServeTrace(ops=TRACE.ops, modules=[], spans=TICKS)
    with pytest.raises(ValueError):
        spans.clock_offset(bare)
    with pytest.raises(ValueError):
        spans.attribute(bare, 0)


@pytest.fixture(scope="module")
def recorded():
    return spans.load(RECORDED.parent)


def test_recorded_trace_is_small_and_whole(recorded):
    """``perfbench/record_spans.py`` (eight requests) on one TPU v5 lite: a
    closed-loop queue of 8 requests on 4 slots, qwen3-0.6b's widths at one
    layer."""
    assert RECORDED.stat().st_size < 1_000_000
    names = {n for n, _, _ in recorded.spans}
    assert {"tick", "policy", "admit", "admit.dispatch", "admit.sync", "pool_prefill",
            "pool_prefill.sync", "decode", "decode.prep", "decode.dispatch",
            "decode.sync", "absorb"} <= names
    assert len(spans.rounds(recorded)) == sum(1 for n, _, _ in recorded.spans
                                              if n == "decode.sync") == 21


def test_recorded_clock_offset(recorded):
    """Every round's decode program ends, shifted, before its sync span
    does: the device clock trails the host's by 1.98 ms.  The runtime's
    enqueue events bound it from below: no program starts, shifted, before
    the host enqueued it."""
    off = spans.clock_offset(recorded)
    assert off == pytest.approx(1_981_923)
    assert all(mod_end + off <= sync_end for _, sync_end, _, mod_end in spans.rounds(recorded))
    decode = [m for m in recorded.modules if m[0] == spans.DECODE_MODULE]
    assert all(start + off >= enqueued for _, start, _, enqueued in decode)


def test_recorded_idle_time_lies_inside_named_spans(recorded):
    """Between the first round and the last, 98.5% of the device's idle
    time lies inside a span other than ``tick``; most of it in the decode
    round's own host steps and its sync."""
    att = spans.attribute(recorded, spans.clock_offset(recorded))
    assert att["idle_s"] == pytest.approx(0.060037668)
    assert att["named_share"] >= 0.9
    assert att["named_share"] == pytest.approx(0.98484, abs=1e-5)
    assert list(att["by_span"])[:3] == ["decode.sync", "decode.prep", "decode.dispatch"]
