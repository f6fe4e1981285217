"""Readings for a cell's correctness limit, many seeds in one process.

    python3 perfbench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1,2,...,12 [--control-seeds 1,2,3] [--out <file>]

For each seed: new weights, one window of the cell's own traffic at its
own load, then the reference over the seeded sample of served requests:
the widest and the mean logit gap of the served tokens (the program's
readings).  On a control seed the same pass also runs the int8 control and
reads the gaps of the tokens it puts first (the control's readings).  A
limit in ``cells/<cell>.json`` is set between the largest program reading
and the smallest control reading of its number.  Not part of a benchmark
run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}

    sys.path.insert(0, str(ROOT))
    import jax

    from perfbench import harness, run, traffic

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    c = harness.prepare(ROOT, args.workload, seeds[0])
    model, rows = c.sched.model, []
    for i, seed in enumerate(seeds):
        if i:
            c.sched.params = c.family.program_params(c.cfg, model, seed)
        offer = traffic.build(c.mix, c.cfg["vocab_size"], seed, args.seconds)
        reqs = harness.requests(c, offer)
        if i == 0:
            harness.warm(c, offer)
        res, window_s = harness.serve(c, offer, reqs)
        outputs = res.outputs
        c.sched.params = None
        del res
        gc.collect()
        checks, ctl, got = harness.check(c, seed, offer, outputs, control=seed in controls,
                                         log=lambda m: print(m, file=sys.stderr))
        row = {"seed": seed, "window_s": window_s, "served": len(outputs),
               "tokens": int(got["served"].size),
               "program_max": float(got["served"].max()),
               "program_mean": float(got["served"].mean()),
               "bad_requests": checks["bad_requests"]["value"]}
        row["correct"] = harness.passes(checks)
        if ctl is not None:
            row["control_max"] = float(got["control"].max())
            row["control_mean"] = float(got["control"].mean())
            row["control_correct"] = harness.passes(ctl)
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"workload": args.workload, "seconds": args.seconds}
    for stat in ("max", "mean"):
        ctl = [r[f"control_{stat}"] for r in rows if f"control_{stat}" in r]
        summary[f"{stat}_logit_gap"] = {
            "lower_reading": max(r[f"program_{stat}"] for r in rows),
            "upper_reading": min(ctl) if ctl else None}
    summary.update(rows=rows, elapsed_s=time.perf_counter() - T_START)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(summary, indent=1))
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
