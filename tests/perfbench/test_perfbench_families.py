"""A configuration names its family of checks, weights, reference and
operation count (``perfbench/families/<family>.py``, "dense" where it
names none): a family added as new files serves a cell end to end, a
missing one is refused by path, and the dense family keeps its guards and
its numbers."""

import hashlib
import json
import re
import shutil
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench_fixtures import CELL, REPO, make_root, tiny_config, write

sys.path.insert(0, str(REPO))

import jax  # noqa: E402

from perfbench import harness, roofline, spec, weights  # noqa: E402

TOY_FAMILY = REPO / "tests/perfbench/toy_family.py"


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "perfbench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def test_a_family_added_as_files_serves_a_cell(tmp_path):
    root = make_root(tmp_path / "bench", loop="open", wide=True)
    cfg = json.loads((root / "perfbench/configs/tiny.json").read_text())
    write(root / "perfbench/configs/tiny.json", {**cfg, "family": "toy"})
    shutil.copy(TOY_FAMILY, root / "perfbench/families/toy.py")
    after = _digests(root)
    assert all(after[p] == d for p, d in _digests(REPO).items())  # nothing edited

    c = harness.prepare(root, CELL, seed=2 ** 31 + 11)
    assert c.family.__name__ == "perfbench_family_toy"
    toy, dense = (_leaves(f.program_params(c.cfg, c.sched.model, 5))
                  for f in (c.family, c.family.dense))
    same = [np.array_equal(a, b) for a, b in zip(toy, dense)]
    # embedding and final norm are shared by name; every layer leaf is its own
    assert same.count(True) == 2 and len(same) > 2

    result = harness.run_cell(root, CELL, 2 ** 31 + 11, 2.0, False, 0.0)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["checks"]["bad_requests"]["value"] == 0


def test_a_missing_family_is_refused_by_path(tmp_path):
    root = make_root(tmp_path / "bench")
    write(root / "perfbench/configs/tiny.json", {**tiny_config(False), "family": "nosuch"})
    with pytest.raises(FileNotFoundError,
                       match=re.escape(str(root / "perfbench/families/nosuch.py"))):
        harness.prepare(root, CELL, seed=1)


# one departure from what the dense reference implements, per guarded field
_DEPARTURES = {
    "layer_pattern": ("attn_global", "attn_local"), "ffn_activation": "gelu",
    "use_post_norm": True, "embed_scale": True, "num_experts": 8,
    "final_logit_softcap": 30.0, "attn_logit_softcap": 50.0, "use_mrope": True,
    "encoder_layers": 2, "scan_layers": False,
}


@pytest.mark.parametrize("field", sorted(_DEPARTURES))
def test_dense_refuses_what_its_reference_lacks(field):
    dense = spec.Spec(REPO).family(tiny_config(False))
    assert set(_DEPARTURES) == set(dense._PLAIN)
    cfg = tiny_config(False)
    cfg["program"]["overrides"][field] = _DEPARTURES[field]
    with pytest.raises(ValueError, match=f"program {field}=.*the reference implements"):
        dense.program_config(cfg)


@pytest.mark.parametrize("registry", ["qwen3-0.6b", "yi-9b"])
def test_dense_family_reads_what_the_direct_calls_read(registry):
    from repro.models.registry import build_model

    cfg = tiny_config(False, registry)
    dense = spec.Spec(REPO).family(cfg)
    assert "family" not in cfg and dense.__name__ == "perfbench_family_dense"
    model = build_model(dense.program_config(cfg))
    got, want = (_leaves(f(cfg, model, 2 ** 31 + 3))
                 for f in (dense.program_params, weights.program_params))
    assert len(got) == len(want) and all(np.array_equal(a, b) for a, b in zip(got, want))

    served = tuple(SimpleNamespace(prompt_len=p, tokens_out=t)
                   for p, t in [(4, 2), (19, 58), (128, 256)])
    run = harness.RunRecord(cfg=cfg, setup_s=1.0, window_s=2.5, tokens_out=316,
                            requests=served, stats=None, device_kind="TPU v5 lite",
                            chips=1, family=dense)
    ops = sum(roofline.request_ops(cfg, r.prompt_len, r.tokens_out) for r in served)
    assert spec.Spec(REPO).reader("mfu")(run) == roofline.mfu(ops, 2.5, 1, "TPU v5 lite")


def test_family_leaf_ids_start_above_the_shared_names():
    key = weights.base_key(9)
    own = weights.matrix(key, weights.FAMILY_LEAF_BASE, 0, (8, 8), 8)
    assert not np.array_equal(np.asarray(own), np.asarray(weights.matrix(key, "q", 0, (8, 8), 8)))
    with pytest.raises(ValueError, match="start at 100"):
        weights.matrix(key, 5, 0, (8, 8), 8)
