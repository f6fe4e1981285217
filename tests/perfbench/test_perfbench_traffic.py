"""The traffic generator: the same seed gives the same requests, and every
seed gets the same work: the same sizes and arrival gaps, in another order
for an open loop and in the mix's order for a closed one."""

import json
import sys
import numpy as np
import pytest

from perfbench_fixtures import REPO

sys.path.insert(0, str(REPO))

from perfbench import traffic  # noqa: E402

MIXES = sorted(p.stem for p in (REPO / "perfbench/traffic").glob("*.json"))
SEEDS = [0, 1, 2 ** 31 + 12345, 2 ** 40 + 3, 2 ** 63 + 5]


def _mix(name):
    return json.loads((REPO / f"perfbench/traffic/{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_requests(name, seed):
    a = traffic.build(_mix(name), 1000, seed, 5.0)
    b = traffic.build(_mix(name), 1000, seed, 5.0)
    assert a.budgets == b.budgets and a.arrivals == b.arrivals
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    """The set of sizes, the set of arrival gaps and the last arrival are
    the mix's; the seed draws the prompt tokens and, open loop, the order."""
    offers = [traffic.build(_mix(name), 1000, s, 5.0) for s in SEEDS]
    work = [sorted(zip((len(p) for p in o.prompts), o.budgets)) for o in offers]
    assert all(w == work[0] for w in work)
    assert len({o.prompts[0].tobytes() for o in offers}) == len(SEEDS)
    if offers[0].arrivals is None:
        assert len({o.budgets for o in offers}) == 1
        return
    gaps = [sorted(np.diff((0.0,) + o.arrivals)) for o in offers]
    assert all(np.allclose(g, gaps[0], rtol=0, atol=1e-9) for g in gaps)
    assert all(abs(o.arrivals[-1] - offers[0].arrivals[-1]) < 1e-9 for o in offers)
    assert len({o.budgets for o in offers}) == len(SEEDS)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_the_mix(name):
    mix = _mix(name)
    o = traffic.build(mix, 1000, 3, 5.0)
    lens = [len(p) for p in o.prompts]
    assert mix["prompt"]["min"] <= min(lens) and max(lens) <= mix["prompt"]["max"] <= mix["bucket"]
    assert mix["output"]["min"] <= min(o.budgets) and max(o.budgets) <= mix["max_new"]
    if o.arrivals is not None:
        assert all(0 <= a < b < 5.0 for a, b in zip(o.arrivals, o.arrivals[1:]))
    else:
        assert len(o.budgets) == int(np.ceil(mix["requests_per_s"] * 5.0))
