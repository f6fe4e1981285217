"""Run one cell of the on-chip serving benchmark.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout on a machine with the chips the cell asks
for.  The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``); the numbers compared for
``correct`` are also the last lines of standard error.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result.

``--control 1`` is not a benchmark run: it judges the int8 control of
the reference in the program's place, on the run's own requests and
served tokens, and shows that the comparison reads it as not correct.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache() -> None:
    """The program's rule: ``$JAX_COMPILATION_CACHE_DIR`` if set, else the
    fixed, git-ignored ``.jax_cache`` of the checkout.  Every program is
    cached, however quick to compile, so later runs compile nothing."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from perfbench import spec

    chips = spec.Spec(ROOT).workload(args.workload)["chips"]
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        log(f"perfbench: needs {chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s)")
        return 2
    enable_compile_cache()
    from perfbench import harness

    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START, log=log,
                              control=bool(args.control))
    if args.trace and not result["device"]["busy_s"] > 0:
        log("perfbench: the trace shows no operation on the device")
        return 3
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
