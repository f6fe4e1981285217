"""The comparison that decides ``correct`` fails what it must, at a toy
size on the CPU: the int8 control of the reference, on each number a cell
file can limit, and a served token altered where the decode step
produces it."""

import sys

import pytest

from perfbench_fixtures import CELL, REPO, WIDE_LIMIT, WIDE_MEAN_LIMIT, make_root

sys.path.insert(0, str(REPO))

from perfbench import harness, traffic, weights  # noqa: E402

SECONDS = 2.0
LIMITS = {"max_logit_gap": WIDE_LIMIT, "mean_logit_gap": WIDE_MEAN_LIMIT}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"), loop="open", wide=True)


@pytest.fixture(scope="module")
def cell(root):
    return harness.prepare(root, CELL, seed=1)


@pytest.fixture(scope="module")
def served(cell):
    """One window per seed, shared by the tests of each number."""
    runs = {}

    def get(seed):
        if seed not in runs:
            cell.sched.params = weights.program_params(cell.cfg, cell.sched.model, seed)
            offer = traffic.build(cell.mix, cell.cfg["vocab_size"], seed, SECONDS)
            harness.warm(cell, offer)
            res, _ = harness.serve(cell, offer, harness.requests(cell, offer))
            runs[seed] = (offer, res.outputs)
        return runs[seed]

    return get


@pytest.mark.parametrize("number", sorted(LIMITS))
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_int8_control_is_not_correct(cell, served, seed, number, monkeypatch):
    """The harness's own decision, with the control in the program's place,
    on a cell file that limits ``number`` alone."""
    monkeypatch.setitem(cell.cell, "check", {"requests": 40, number: LIMITS[number]})
    offer, outputs = served(seed)
    checks, ctl, _ = harness.check(cell, seed, offer, outputs, control=True)
    assert set(checks) == set(ctl) == {number, "bad_requests"}
    assert harness.passes(checks)
    assert not harness.passes(ctl)
    assert ctl[number]["value"] > LIMITS[number]


@pytest.mark.parametrize("number", sorted(LIMITS))
def test_control_run_reads_not_correct(tmp_path, number):
    """A whole run but the look for a chip, with ``--control 1``: the
    result line reads ``correct`` false, with the control's numbers."""
    root = make_root(tmp_path / "bench", loop="open", wide=True,
                     check={number: LIMITS[number]})
    result = harness.run_cell(root, CELL, 7, SECONDS, False, 0.0, control=True)
    assert not result["correct"]
    assert result["checks"][number]["value"] > LIMITS[number]


def test_altered_token_is_not_correct(root, monkeypatch):
    """A whole run but the look for a chip, with the decode step altering
    every fourth round's tokens where it produces them."""
    from repro.serve import strategy

    decode_round = strategy.GreedyDecode.decode_round
    rounds = []

    def altered(self, pool, engine, caches, cur_tok, rows, **kw):
        rr = decode_round(self, pool, engine, caches, cur_tok, rows, **kw)
        rounds.append(1)
        if len(rounds) % 4 == 0:  # every fourth round, every live row
            vocab = pool.model.cfg.vocab_size
            rr.tokens.update({i: [(t + 1) % vocab for t in ts] for i, ts in rr.tokens.items()})
        return rr

    monkeypatch.setattr(strategy.GreedyDecode, "decode_round", altered)
    result = harness.run_cell(root, CELL, 6, SECONDS, False, 0.0)
    assert rounds and not result["correct"]
    assert result["checks"]["bad_requests"]["value"] == 0
    assert result["checks"]["max_logit_gap"]["value"] > WIDE_LIMIT
