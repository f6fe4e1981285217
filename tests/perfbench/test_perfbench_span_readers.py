"""The readers of the program's host spans and per-token stamps
(``round_host_ms.open`` / ``.closed``, ``token_gap_p99_ms``) read a finite
value from a window of the tiny cell, in the loop each is listed for, and
nothing from a program that records no spans."""

import math
import sys

import pytest

from perfbench_fixtures import CELL, REPO, make_root

sys.path.insert(0, str(REPO))

from perfbench import harness, spec, traffic  # noqa: E402

READERS = {"open": ["round_host_ms.open", "token_gap_p99_ms"],
           "closed": ["round_host_ms.closed"]}


def _window(root, seed=2 ** 31 + 11, seconds=2.0) -> harness.RunRecord:
    c = harness.prepare(root, CELL, seed)
    offer = traffic.build(c.mix, c.cfg["vocab_size"], seed, seconds)
    harness.warm(c, offer)
    res, window_s = harness.serve(c, offer, harness.requests(c, offer))
    return harness.RunRecord(
        cfg=c.cfg, setup_s=0.0, window_s=window_s,
        tokens_out=int(sum(len(v) for v in res.outputs.values())),
        requests=tuple(res.request_stats), stats=res.stats,
        device_kind="cpu", chips=1)


@pytest.fixture(scope="module")
def windows(tmp_path_factory):
    """One window per loop, made on first use: (Spec, RunRecord)."""
    made = {}

    def get(loop):
        if loop not in made:
            root = make_root(tmp_path_factory.mktemp("bench"), loop=loop)
            made[loop] = spec.Spec(root), _window(root)
        return made[loop]

    return get


PAIRS = [(loop, name) for loop in sorted(READERS) for name in READERS[loop]]


@pytest.mark.parametrize("loop,name", PAIRS)
def test_each_reader_reads_a_finite_value(windows, loop, name):
    s, run = windows(loop)
    value = s.reader(name)(run)
    assert value is not None and math.isfinite(value) and value > 0


@pytest.mark.parametrize("loop", sorted(READERS))
def test_round_host_time_is_the_ticks_less_the_waits(windows, loop):
    s, run = windows(loop)
    st = run.stats
    waited = sum(st.spans[n].total_s for n in ("admit.sync", "pool_prefill.sync",
                                               "decode.sync", "idle") if n in st.spans)
    assert s.reader(READERS[loop][0])(run) == pytest.approx(
        1e3 * (st.spans["tick"].total_s - waited) / st.decode_steps)


@pytest.mark.parametrize("loop,name", PAIRS)
def test_readers_read_nothing_without_spans(windows, loop, name):
    """A program older than the spans (no ``host_s``, no ``token_s``): the
    reader returns None and raises nothing."""
    s, run = windows(loop)

    class Bare:
        decode_steps = run.stats.decode_steps

    class BareRequest:
        tokens_out = 3

    bare = harness.RunRecord(cfg=run.cfg, setup_s=0.0, window_s=1.0, tokens_out=0,
                             requests=(BareRequest(),), stats=Bare(),
                             device_kind="cpu", chips=1)
    assert s.reader(name)(bare) is None
