"""Find a cell's pieces by name: BENCHMARK.json plus one file per piece.

Layout under the benchmark directory (``perfbench/``)::

    configs/<config>.json     model sizes, as run (named by BENCHMARK.json)
    traffic/<traffic>.json    parameters of the one traffic generator
    cells/<workload>.json     tier and correctness limits of one cell
    metrics/<metric>.py       one reader per metric: read(run) -> float | None
    families/<family>.py      what a configuration file's ``family`` names
                              ("dense" where it names none): four functions,
                              program_config(cfg) -> the program's checked
                              ModelConfig; program_params(cfg, model, seed) ->
                              its weights, made on the device; gaps(cfg, seed,
                              prompts, served, length, *, control=False) -> the
                              reference's logit gaps; request_ops(cfg,
                              prompt_len, tokens_out) -> model operations

Adding a cell, a mix, a configuration, a family or a metric adds files
and entries; no existing file changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = "perfbench"
FAMILY_API = ("program_config", "program_params", "gaps", "request_ops")


class Spec:
    """BENCHMARK.json of one checkout, and the lookups into it."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / BENCH_DIR

    # ---------------------------------------------------------- entries
    def workload(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        known = [w["name"] for w in self.bench["workloads"]]
        raise KeyError(f"unknown workload {name!r}; known: {known}")

    def _config_entry(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"unknown config {name!r}")

    def end_to_end(self, workload: str) -> list:
        """End-to-end metrics this cell reports."""
        return [m for m in self.bench["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> list:
        """Per-layer metrics this cell reports: those that list it, and
        those without a list whose end-to-end metric the cell reports."""
        reported = {m["name"] for m in self.end_to_end(workload)}
        out = []
        for m in self.bench["per_layer"]:
            if "workloads" in m:
                if workload in m["workloads"]:
                    out.append(m)
            elif m["moves"] in reported:
                out.append(m)
        return out

    # ------------------------------------------------------------ files
    def config(self, name: str) -> dict:
        return _read_json(self.root / self._config_entry(name)["file"])

    def traffic(self, name: str) -> dict:
        return _read_json(self.dir / "traffic" / f"{name}.json")

    def cell(self, workload: str) -> dict:
        return _read_json(self.dir / "cells" / f"{workload}.json")

    def reader(self, metric: str):
        """The ``read(run)`` function of ``metrics/<metric>.py``."""
        path = self.dir / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise FileNotFoundError(f"metric {metric!r} has no reader at {path}")
        return _module(path, f"perfbench_metric_{metric}").read

    def family(self, cfg: dict):
        """The module of ``families/<family>.py`` for the configuration
        ``cfg``, with the functions of ``FAMILY_API``."""
        name = cfg.get("family", "dense")
        path = self.dir / "families" / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(f"configuration {cfg['name']!r} names family "
                                    f"{name!r}, which has no module at {path}")
        mod = _module(path, f"perfbench_family_{name}")
        missing = [f for f in FAMILY_API if not callable(getattr(mod, f, None))]
        if missing:
            raise AttributeError(f"family module {path} lacks {missing}")
        return mod


def _module(path: Path, name: str):
    """The Python file at ``path``, loaded as a module of its own."""
    mod_spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"missing benchmark data file {path}")
    return json.loads(path.read_text())
