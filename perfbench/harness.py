"""One run of one cell: set up, serve the window, check, read the metrics.

The system under test is ``repro.serve.scheduler.ContinuousScheduler.run``
on the wall clock, one slot pool at the cell's tier, over a model built by
``repro.models.registry.build_model`` from the cell's configuration.  The
benchmark makes the traffic (``perfbench.traffic``) itself, and takes the
program's checked config, the weights, the reference and the operation
count from the configuration's family (``perfbench/families/<family>.py``;
``dense``, over ``perfbench.weights`` and ``perfbench.reference``, where
the file names none).
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Optional

import numpy as np

from perfbench import spec as spec_mod, traffic

# A traced run traces this much of its window, from 40% of the way in: a
# device trace of a whole minute of serving loses events, and reading ten
# seconds of a 30 ms decode step took over five minutes.
TRACE_SECONDS = 2.0


@dataclasses.dataclass
class RunRecord:
    """What a metric reader may read: the run's clocks and counts, the
    program's own per-request stamps and counters, and the trace."""

    cfg: dict
    setup_s: float
    window_s: float
    tokens_out: int
    requests: tuple  # program RequestStats of the requests served
    stats: object  # program ServeStats
    device_kind: str
    chips: int
    trace: Optional[dict] = None  # perfbench.trace.summarize, --trace 1 only
    family: object = None  # the configuration's family module


class _CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    armed (``jax.monitoring`` events)."""

    def __init__(self):
        import jax

        self.armed, self.count = False, 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if self.armed and name == "/jax/compilation_cache/cache_hits":
            self.count += 1

    def _duration(self, name, _secs, **_):
        if self.armed and name == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def _spanned_greedy():
    """Greedy decode with a host span around each round (traced runs)."""
    import jax
    from repro.serve.strategy import GreedyDecode

    class SpannedGreedy(GreedyDecode):
        def decode_round(self, *args, **kwargs):
            with jax.profiler.TraceAnnotation("bench:decode_round"):
                return super().decode_round(*args, **kwargs)

    return SpannedGreedy()


class _Tracer:
    """Traces ``TRACE_SECONDS`` of the window from a thread of its own, with
    host markers where the traced window opens and closes."""

    def __init__(self, seconds: float, log_dir: str):
        self.on = 0.4 * seconds
        self.dir = log_dir
        self.done = threading.Event()
        self.error = None
        self.stop_s = None
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        import jax

        try:
            if self.done.wait(self.on):
                return  # the window closed before the trace would open
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench:window_on"):
                pass
            self.done.wait(TRACE_SECONDS)
            with jax.profiler.TraceAnnotation("bench:window_off"):
                pass
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - t
        except Exception as e:  # re-raised on the main thread by finish()
            self.error = e

    def start(self):
        self.thread.start()

    def finish(self):
        self.done.set()
        self.thread.join()
        if self.error is not None:
            raise self.error


def _sample(outputs: dict, prompts, n: int, seed: int) -> list:
    """Request ids to check: the one with most served tokens (then the
    longest prompt), and ``n - 1`` others drawn from the seed."""
    ids = sorted(outputs)
    longest = max(ids, key=lambda i: (len(outputs[i]), len(prompts[i])))
    rest = [i for i in ids if i != longest]
    rng = np.random.default_rng([seed % 2 ** 64, 0xC4EC])
    picked = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[int(j)] for j in sorted(picked)]


@dataclasses.dataclass
class Cell:
    """A cell's pieces and its slot pool, built once per process."""

    spec: object
    workload: str
    entry: dict
    cfg: dict
    family: object
    mix: dict
    cell: dict
    sched: object
    device: object


def prepare(root: Path, workload: str, seed: int, trace: bool = False) -> Cell:
    """Load the cell by name, build the model and the pool, make the
    weights of ``seed`` on the device."""
    import jax

    sys.path.insert(0, str(Path(root) / "src"))
    from repro.models.registry import build_model
    from repro.serve.scheduler import ContinuousScheduler

    spec = spec_mod.Spec(root)
    entry = spec.workload(workload)
    if entry["chips"] != 1:
        raise NotImplementedError("the harness drives one chip per cell")
    cfg, mix = spec.config(entry["config"]), spec.traffic(entry["traffic"])
    cell, family = spec.cell(workload), spec.family(cfg)
    model = build_model(family.program_config(cfg))
    params = family.program_params(cfg, model, seed)
    jax.block_until_ready(params)
    sched = ContinuousScheduler(
        model, params, batch_size=mix["slots"], prompt_len=mix["bucket"],
        max_new=mix["max_new"], quality=cell["tier"],
        strategy=_spanned_greedy() if trace else None)
    return Cell(spec=spec, workload=workload, entry=entry, cfg=cfg, family=family,
                mix=mix, cell=cell, sched=sched, device=jax.devices()[0])


def requests(c: Cell, offer) -> list:
    from repro.serve.request import Request

    return [Request(id=i, tokens=p, max_new=b)
            for i, (p, b) in enumerate(zip(offer.prompts, offer.budgets))]


def warm(c: Cell, offer) -> None:
    """Short requests through ``run`` that compile exactly the programs the
    window drives: open loop, one request (cache set-up, admission,
    decode); closed loop, one more than the pool holds, as the window's
    full queue starts with the batched prefill of every slot."""
    from repro.serve.request import Request

    n = 1 if offer.arrivals is not None else c.mix["slots"] + 1
    c.sched.run([Request(id=-1 - i, tokens=offer.prompts[i % len(offer.prompts)], max_new=2)
                 for i in range(n)], warmup=False)


def serve(c: Cell, offer, reqs):
    """The measured window: every request of the offer served; returns
    (ServeResult, window seconds)."""
    t0 = time.perf_counter()
    if offer.arrivals is not None:
        res = c.sched.run(reqs, warmup=False, arrivals_s=offer.arrivals, clock="wall")
    else:
        res = c.sched.run(reqs, warmup=False)
    return res, time.perf_counter() - t0


def _limited(c: Cell, gaps, bad: int) -> dict:
    """The numbers a cell's file limits, each beside its limit: the widest
    (``max_logit_gap``) and the mean (``mean_logit_gap``) of the gaps, as
    the file gives them, and the requests served with a wrong count of
    tokens or a token outside the vocabulary."""
    values = {"max_logit_gap": float(gaps.max()), "mean_logit_gap": float(gaps.mean())}
    checks = {name: {"value": value, "limit": c.cell["check"][name]}
              for name, value in values.items() if name in c.cell["check"]}
    if not checks:
        raise ValueError(f"{c.workload}: the cell file gives no gap limit")
    checks["bad_requests"] = {"value": bad, "limit": 0}
    return checks


def passes(checks: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in checks.values())


def check(c: Cell, seed: int, offer, outputs: dict, *, control: bool = False, log=print):
    """The comparison that decides ``correct``: over a seeded sample of the
    requests served, the gaps by which the served tokens lie below the
    reference's best logit.  With ``control``, the int8 control of the
    reference takes the program's place: at each position of the same
    prompts and served tokens, the gap of the token it puts first, held
    to the same limits.  Returns (checks, control checks or None, gaps)."""
    vocab = c.cfg["vocab_size"]
    bad = sum(1 for i, out in outputs.items()
              if len(out) != offer.budgets[i] or out.min() < 0 or out.max() >= vocab)
    ids = _sample(outputs, offer.prompts, c.cell["check"]["requests"], seed)
    t_ref = time.perf_counter()
    got = c.family.gaps(c.cfg, seed, [offer.prompts[i] for i in ids],
                        [outputs[i] for i in ids], c.mix["bucket"] + c.mix["max_new"],
                        control=control)
    log(f"reference over {len(ids)} requests, {len(got['served'])} served tokens "
        f"in {time.perf_counter() - t_ref:.3f} s: max gap {float(got['served'].max())!r}, "
        f"mean gap {float(got['served'].mean())!r}")
    checks = _limited(c, got["served"], bad)
    ctl = _limited(c, got["control"], bad) if control else None
    return checks, ctl, got


def run_cell(root: Path, workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, log=print, control: bool = False) -> dict:
    """One run; returns the result object the command prints last.  With
    ``control`` the int8 control is judged in the program's place: its
    numbers are the ones compared and they decide ``correct``."""
    import jax

    c = prepare(root, workload, seed, trace)
    offer = traffic.build(c.mix, c.cfg["vocab_size"], seed, seconds)
    reqs = requests(c, offer)
    warm(c, offer)
    counter = _CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    tracer = _Tracer(seconds, trace_dir) if trace else None
    setup_s = time.perf_counter() - t_start

    counter.armed = True
    if tracer:
        tracer.start()
    res, window_s = serve(c, offer, reqs)
    if tracer:
        tracer.finish()
    counter.armed = False
    log(f"compilations inside the window: {counter.count}")

    chips = c.entry["chips"]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:chips])
    summary = None
    if trace:
        from perfbench import trace as trace_mod

        t = time.perf_counter()
        summary = trace_mod.summarize(trace_mod.load(Path(trace_dir)))
        log(f"trace: stopped in {tracer.stop_s} s, read in {time.perf_counter() - t:.3f} s")
        shutil.rmtree(trace_dir, ignore_errors=True)
    stats, outputs = res.stats, res.outputs
    served = tuple(res.request_stats)
    tokens_out = int(sum(len(v) for v in outputs.values()))
    log(f"served {len(served)} requests, {tokens_out} tokens in {window_s:.3f} s; "
        f"rejected {stats.rejected}; starved {stats.starved}")
    if offer.arrivals is not None and stats.queue_delay_s:
        log(f"queue delay: mean {np.mean(stats.queue_delay_s):.4f} s, "
            f"max {np.max(stats.queue_delay_s):.4f} s")
    # free the program's state before the reference takes the chip
    c.sched = None
    del res
    gc.collect()

    checks, ctl, _ = check(c, seed, offer, outputs, control=control, log=log)
    if control:
        checks = ctl
    correct = passes(checks)

    run = RunRecord(cfg=c.cfg, setup_s=setup_s, window_s=window_s,
                    tokens_out=tokens_out, requests=served, stats=stats,
                    device_kind=c.device.device_kind, chips=chips, trace=summary,
                    family=c.family)
    wanted = c.spec.per_layer(workload) if trace else c.spec.end_to_end(workload)
    metrics = {}
    for m in wanted:
        value = c.spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = {"platform": c.device.platform, "kind": c.device.device_kind,
           "count": chips, "memory_peak_bytes": int(peak)}
    if trace:
        dev["busy_s"] = summary["busy_s"]
        dev["window_s"] = summary["window_s"]
    failed = checks["bad_requests"]["value"] + stats.starved + stats.rejected
    result = {"correct": correct, "attempted": len(reqs), "failed": failed,
              "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks
    return result
