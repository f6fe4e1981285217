"""Find the knee of an open-loop cell: the highest offered rate at which the
backlog does not grow.  Run once, by hand, on the chip; the cell's mix
then fixes its rate at about four fifths of the knee.

    python3 perfbench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 10,14,18,22 [--out <file>]

One process, one pool: each rate is one window of the mix at that rate.
A rate holds when the mean queue delay of the last quarter of arrivals is
under twice that of the first quarter plus two decode steps: the backlog
does not grow.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    import jax

    from perfbench import harness, run, stats, traffic

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    c = harness.prepare(ROOT, args.workload, args.seed)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(c.mix, rate_rps=rate)
        offer = traffic.build(mix, c.cfg["vocab_size"], args.seed, args.seconds)
        reqs = harness.requests(c, offer)
        if i == 0:
            harness.warm(c, offer)
        res, window_s = harness.serve(c, offer, reqs)
        by_arrival = sorted(res.request_stats, key=lambda r: r.arrival_s)
        q = len(by_arrival) // 4
        first = np.mean([r.queue_delay_s for r in by_arrival[:q]])
        last = np.mean([r.queue_delay_s for r in by_arrival[-q:]])
        step = res.stats.decode_s / max(res.stats.decode_steps, 1)
        row = {
            "rate_rps": rate, "requests": len(reqs), "window_s": window_s,
            "tokens_per_s": sum(len(v) for v in res.outputs.values()) / window_s,
            "ttft_p95_ms": 1e3 * stats.percentile([r.ttft_s for r in by_arrival], 95),
            "queue_first_quarter_ms": 1e3 * first, "queue_last_quarter_ms": 1e3 * last,
            "drain_s": window_s - offer.arrivals[-1],
            "decode_step_ms": 1e3 * step, "slot_occupancy": res.stats.slot_utilization,
        }
        row["holds"] = bool(last < 2 * first + 2 * step)
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
