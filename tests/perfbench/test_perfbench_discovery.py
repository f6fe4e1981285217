"""A configuration, a traffic mix, a cell and a per-layer metric added as new
files are found by name, with no edit to a file that was there; and the
command refuses to run off a TPU."""

import json
import os
import subprocess
import sys

import pytest

from perfbench_fixtures import CELL, REPO, make_root

sys.path.insert(0, str(REPO))

from perfbench import harness, spec  # noqa: E402


def _files(root):
    return {p.relative_to(root): p.read_bytes()
            for p in (root / "perfbench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_added_files_are_found_by_name(tmp_path):
    root = make_root(tmp_path / "bench")
    (root / "perfbench/metrics/tiny_admissions.py").write_text(
        "def read(run):\n    return len(run.requests)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "tiny_admissions", "unit": "requests",
                               "better": "higher", "source": "program_counter",
                               "layer": "scheduler", "moves": "tokens_per_s",
                               "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _files(root)
    assert all(after[p] == b for p, b in _files(REPO).items())  # nothing edited

    s = spec.Spec(root)
    assert s.config("tiny")["hidden_size"] == 64
    assert s.traffic("tiny")["loop"] == "open"
    assert s.cell(CELL)["tier"] == "exact"
    assert [m["name"] for m in s.per_layer(CELL)] == ["tiny_admissions"]
    assert [m["name"] for m in s.end_to_end(CELL)] == ["tokens_per_s", "setup_s"]
    assert s.reader("tiny_admissions")(harness.RunRecord(
        cfg={}, setup_s=0, window_s=1, tokens_out=0, requests=(None,) * 7, stats=None,
        device_kind="", chips=1)) == 7


@pytest.mark.parametrize("loop,registry", [("open", "qwen3-0.6b"), ("closed", "yi-9b")])
def test_a_run_of_an_added_cell(tmp_path, loop, registry):
    root = make_root(tmp_path / "bench", loop=loop, registry=registry)
    result = harness.run_cell(root, CELL, 2 ** 31 + 99, 2.0, False, 0.0)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result["metrics"]) == ["tokens_per_s", "setup_s"]
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == {"max_logit_gap", "bad_requests"}


def test_command_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "qwen3-0.6b.exact.alpaca-steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
