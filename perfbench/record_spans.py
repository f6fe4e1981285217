"""Record a short profiler trace of the serving loop, with its host spans.

    python3 perfbench/record_spans.py --out tests/perfbench/traces/spans.xplane.pb

On one TPU chip.  Serves a closed-loop queue on a small configuration
(qwen3-0.6b's widths, one layer, four slots) under ``jax.profiler`` and
copies the ``.xplane.pb`` to ``--out``: with the default eight requests,
21 decode rounds with admissions between them, each with its ``serve:``
spans on the host clock and its device operations on the device's.  ``tests/perfbench`` reads the
recorded file (``perfbench.spans``).  Prints the clock offset and the
share of device idle time inside a named span.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--requests", type=int, default=8)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    import numpy as np

    from perfbench import spans
    from repro.configs.registry import get_config
    from repro.models.registry import build_model
    from repro.serve import ContinuousScheduler, Request

    if jax.devices()[0].platform != "tpu":
        print(f"record_spans: needs a TPU; JAX found {jax.devices()[0].platform}",
              file=sys.stderr)
        return 2
    model = build_model(get_config("qwen3-0.6b", num_layers=1))
    params = model.init_params(jax.random.PRNGKey(0))
    sched = ContinuousScheduler(model, params, batch_size=4, prompt_len=32, max_new=12)
    rng = np.random.default_rng(0)
    reqs = [Request(id=i, tokens=rng.integers(0, model.cfg.vocab_size,
                                              int(rng.integers(4, 33))).astype(np.int32),
                    max_new=int(rng.integers(4, 13)))
            for i in range(args.requests)]
    sched.run(reqs)  # compiles every program the traced run drives
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    log_dir = tempfile.mkdtemp(prefix="record-spans-")
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        res = sched.run(reqs, warmup=False)
        jax.profiler.stop_trace()
        src = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, args.out)
        tr = spans.load(Path(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    offset = spans.clock_offset(tr)
    att = spans.attribute(tr, offset)
    print(json.dumps({"decode_steps": res.stats.decode_steps, "rounds": len(spans.rounds(tr)),
                      "bytes": Path(args.out).stat().st_size,
                      "offset_ms": offset * 1e-6, **att}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
