"""Structured benchmark harness: registry-driven runner + ``BENCH_*.json``.

The measurement backbone of the repo (docs/benchmarks.md).  Runs any
registered suite (``benchmarks.registry``) and writes one schema-versioned
``BENCH_<suite>.json`` per suite at the repo root: git SHA + environment
fingerprint + the per-row metrics, plus the suite's gating metadata so the
file is self-describing for external diff/plot/gate tooling.

  PYTHONPATH=src python -m benchmarks.harness --list
  PYTHONPATH=src python -m benchmarks.harness --suite engine_matmul --reduced
  PYTHONPATH=src python -m benchmarks.harness --suite all --reduced
  PYTHONPATH=src python -m benchmarks.harness --suite engine_matmul --reduced \
      --compare old/BENCH_engine_matmul.json --threshold 0.25

``--compare`` re-measures, matches rows against the baseline file by the
suite's ``key_fields``, applies the relative ``--threshold`` to every
gated metric, and exits non-zero on any regression — the gate every speed
PR runs against.  ``benchmarks.run`` is a thin CSV-printing shim over the
same registry.
"""

from __future__ import annotations

import argparse
import dataclasses
import datetime
import json
import os
import platform
import subprocess
import sys

if __package__ in (None, ""):  # direct script run: python benchmarks/<mod>.py
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import registry
from repro.runtime.compile_cache import enable_compile_cache

__all__ = [
    "SCHEMA_VERSION",
    "Regression",
    "env_fingerprint",
    "git_sha",
    "run_suite",
    "bench_path",
    "write_doc",
    "load_doc",
    "validate_doc",
    "compare_docs",
    "main",
]

SCHEMA_VERSION = 1
DEFAULT_THRESHOLD = 0.25  # 25% relative tolerance on gated metrics

_TOP_KEYS = {
    "schema_version": int,
    "suite": str,
    "reduced": bool,
    "git_sha": str,
    "created_at": str,
    "env": dict,
    "gating": dict,
    "row_count": int,
    "rows": list,
}
_ENV_KEYS = ("python", "jax", "numpy", "jax_backend", "device_count", "platform")
_GATING_KEYS = ("key_fields", "lower_is_better", "higher_is_better")


def env_fingerprint() -> dict:
    """The environment facts that make two BENCH files comparable."""
    import jax
    import numpy as np

    return {
        "python": platform.python_version(),
        "jax": jax.__version__,
        "numpy": np.__version__,
        "jax_backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "platform": platform.platform(),
        "force_interpret": os.environ.get("REPRO_FORCE_INTERPRET", ""),
    }


def git_sha() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True,
            text=True,
            timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def run_suite(suite: registry.Suite, *, reduced: bool = False) -> dict:
    """Execute one suite and assemble its BENCH document."""
    rows = suite.rows(reduced=reduced)
    return {
        "schema_version": SCHEMA_VERSION,
        "suite": suite.name,
        "reduced": reduced,
        "git_sha": git_sha(),
        "created_at": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "env": env_fingerprint(),
        "gating": suite.gating(),
        "row_count": len(rows),
        "rows": rows,
    }


def bench_path(suite_name: str, out_dir: str = ".") -> str:
    return os.path.join(out_dir, f"BENCH_{suite_name}.json")


def validate_doc(doc: dict) -> None:
    """Raise ``ValueError`` unless ``doc`` is a well-formed BENCH document."""
    if not isinstance(doc, dict):
        raise ValueError(f"BENCH document must be an object, got {type(doc).__name__}")
    for key, typ in _TOP_KEYS.items():
        if key not in doc:
            raise ValueError(f"BENCH document missing key {key!r}")
        if not isinstance(doc[key], typ):
            raise ValueError(
                f"BENCH key {key!r} must be {typ.__name__}, got {type(doc[key]).__name__}"
            )
    if doc["schema_version"] != SCHEMA_VERSION:
        raise ValueError(
            f"unsupported schema_version {doc['schema_version']} (harness speaks {SCHEMA_VERSION})"
        )
    for key in _ENV_KEYS:
        if key not in doc["env"]:
            raise ValueError(f"BENCH env fingerprint missing {key!r}")
    for key in _GATING_KEYS:
        if not isinstance(doc["gating"].get(key), list):
            raise ValueError(f"BENCH gating metadata missing list {key!r}")
    if doc["row_count"] != len(doc["rows"]):
        raise ValueError("BENCH row_count disagrees with len(rows)")
    for i, row in enumerate(doc["rows"]):
        if not isinstance(row, dict) or "table" not in row:
            raise ValueError(f"BENCH row {i} must be an object with a 'table' key")


def write_doc(doc: dict, out_dir: str = ".") -> str:
    validate_doc(doc)
    path = bench_path(doc["suite"], out_dir)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True, default=float)
        f.write("\n")
    return path


def load_doc(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    validate_doc(doc)
    return doc


@dataclasses.dataclass(frozen=True)
class Regression:
    suite: str
    key: tuple
    metric: str
    direction: str  # "lower_is_better" | "higher_is_better"
    baseline: float
    current: float
    rel_change: float  # positive == worse, in the gated direction

    def __str__(self) -> str:
        return (
            f"{self.suite} {dict(zip(self.key[::2], self.key[1::2]))} "
            f"{self.metric}: {self.baseline:.6g} -> {self.current:.6g} "
            f"({100 * self.rel_change:+.1f}% worse, {self.direction})"
        )


def _row_key(row: dict, key_fields) -> tuple:
    out = []
    for k in key_fields:
        out.append(k)
        out.append(str(row.get(k)))
    return tuple(out)


def compare_docs(
    current: dict, baseline: dict, *, threshold: float = DEFAULT_THRESHOLD
) -> list[Regression]:
    """Gated metric comparison; returns the (possibly empty) regression list.

    Rows are matched by the *current* document's ``key_fields``; rows
    absent from the baseline (new modes, new shapes) are not regressions,
    but baseline rows that *disappear* from the current run are — a
    vanished series (e.g. a mode that silently stopped registering its
    Pallas body) must not read as "no regressions".  A gated metric
    regresses when it moves in the bad direction by more than
    ``threshold`` relative to the baseline value.
    """
    validate_doc(current)
    validate_doc(baseline)
    if current["suite"] != baseline["suite"]:
        raise ValueError(
            f"cannot compare suite {current['suite']!r} against {baseline['suite']!r}"
        )
    if current["reduced"] != baseline["reduced"]:
        raise ValueError(
            "cannot compare a reduced run against a full baseline (or vice versa)"
        )
    gating = current["gating"]
    key_fields = gating["key_fields"]
    base_rows = {_row_key(r, key_fields): r for r in baseline["rows"]}
    regressions: list[Regression] = []
    for row in current["rows"]:
        key = _row_key(row, key_fields)
        base = base_rows.get(key)
        if base is None:
            continue
        for direction, metrics in (
            ("lower_is_better", gating["lower_is_better"]),
            ("higher_is_better", gating["higher_is_better"]),
        ):
            for metric in metrics:
                cur_v, base_v = row.get(metric), base.get(metric)
                if not isinstance(cur_v, (int, float)) or not isinstance(base_v, (int, float)):
                    continue
                if base_v == 0:
                    continue  # no relative scale to gate against
                if direction == "lower_is_better":
                    rel = (cur_v - base_v) / abs(base_v)
                else:
                    rel = (base_v - cur_v) / abs(base_v)
                if rel > threshold:
                    regressions.append(
                        Regression(
                            suite=current["suite"],
                            key=key,
                            metric=metric,
                            direction=direction,
                            baseline=float(base_v),
                            current=float(cur_v),
                            rel_change=float(rel),
                        )
                    )
    current_keys = {_row_key(r, key_fields) for r in current["rows"]}
    for key in base_rows:
        if key not in current_keys:
            regressions.append(
                Regression(
                    suite=current["suite"],
                    key=key,
                    metric="row_present",
                    direction="missing_row",
                    baseline=1.0,
                    current=0.0,
                    rel_change=1.0,
                )
            )
    return regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="benchmarks.harness", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--suite", default=None,
                    help="suite name, or 'all' (see --list)")
    ap.add_argument("--reduced", action="store_true",
                    help="CI-smoke shapes/samples (same schema)")
    ap.add_argument("--list", action="store_true", help="list registered suites")
    ap.add_argument("--out-dir", default=".",
                    help="where BENCH_<suite>.json lands (default: cwd)")
    ap.add_argument("--compare", default=None, metavar="BASELINE.json",
                    help="gate this run against a baseline BENCH file; "
                         "exits 1 on regression")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help=f"relative regression tolerance (default {DEFAULT_THRESHOLD})")
    args = ap.parse_args(argv)
    enable_compile_cache()

    suites = registry.discover()
    if args.list or args.suite is None:
        for name in sorted(suites):
            print(f"{name:20s} {suites[name].description}")
        return 0

    if args.suite == "all":
        selected = [suites[n] for n in sorted(suites)]
    else:
        selected = [registry.get_suite(args.suite)]
    if args.compare is not None and len(selected) != 1:
        print("--compare needs exactly one --suite", file=sys.stderr)
        return 2

    failures = 0
    regressions: list[Regression] = []
    for suite in selected:
        print(f"# === {suite.name} ===", flush=True)
        try:
            doc = run_suite(suite, reduced=args.reduced)
            path = write_doc(doc, args.out_dir)
        except Exception as e:  # noqa: BLE001 — report, keep benching
            failures += 1
            print(f"# {suite.name} FAILED: {type(e).__name__}: {e}", flush=True)
            continue
        print(f"# wrote {path} ({doc['row_count']} rows)", flush=True)
        if args.compare is not None:
            try:
                baseline = load_doc(args.compare)
                regressions = compare_docs(doc, baseline, threshold=args.threshold)
            except (OSError, ValueError) as e:
                failures += 1
                print(f"# compare vs {args.compare} FAILED: "
                      f"{type(e).__name__}: {e}", flush=True)
                continue
            for r in regressions:
                print(f"REGRESSION: {r}", flush=True)
            if not regressions:
                print(f"# no regressions vs {args.compare} "
                      f"(threshold {args.threshold:.0%})", flush=True)
    return 1 if (failures or regressions) else 0


if __name__ == "__main__":
    sys.exit(main())
