"""Put the program's host spans beside the device operations of a trace.

The serving loop names its host work with ``serve:<name>`` spans
(``repro.serve.stats.span``), on the profiler's host clock.  A TPU's
operations come on the device's own clock, which runs apart from the
host's by about a millisecond.  This module reads both from one
``.xplane.pb``, estimates that offset from the decode rounds, and says
how much of the device's idle time falls inside each span.

- ``load(log_dir)``: device operations, executions of each jitted program
  (the ``XLA Modules`` line, each with the host time at which the runtime
  enqueued it: the ``DoEnqueueProgram`` event of the same ``run_id``) and
  the ``serve:`` spans.
- ``rounds(tr)``: each greedy decode round's ``decode.sync`` span beside
  its program, the one enqueued inside the round's ``decode`` span.
- ``clock_offset(tr)``: the shift from device to host clock.  A round's
  result reaches the host only once its last device operation has ended,
  so each round's ``decode`` program must end (shifted) before its
  ``serve:decode.sync`` span ends; the offset is the largest shift for
  which this holds in every round, which is the tightest round's margin.
- ``attribute(tr, offset)``: the device idle time between the first
  round's start and the last round's end, in total, inside any span other
  than ``tick``, and by the innermost span around each idle stretch.
"""

from __future__ import annotations

import collections
import dataclasses
from pathlib import Path

from perfbench import trace
from perfbench.trace import union

SERVE_PREFIX = "serve:"
MODULES_LINE = "XLA Modules"
# the host event of the runtime handing a program to the device
ENQUEUE = "DoEnqueueProgram"
# the jitted program of one greedy decode round (TierEngine.decode)
DECODE_MODULE = "jit_decode_greedy"


@dataclasses.dataclass
class ServeTrace:
    """Intervals in nanoseconds, each on its own clock: ``ops`` and
    ``modules`` on the first device's, ``spans`` and enqueue times on the
    host's.  ``ops = [(start, end)]``, ``modules = [(name, start, end,
    enqueued)]`` (name without the ``(hash)``; ``enqueued`` None where the
    trace holds no enqueue event), ``spans = [(name, start, end)]`` (name
    without ``serve:``)."""

    ops: list
    modules: list
    spans: list


def load(log_dir: Path) -> ServeTrace:
    """The first TPU's operations, as ``perfbench.trace.load`` reads them,
    and the programs and ``serve:`` spans of the same ``.xplane.pb`` (the
    newest under ``log_dir``), which that reader leaves out."""
    from jax.profiler import ProfileData

    tr = trace.load(log_dir)
    first = min(tr.ops) if tr.ops else None
    ops = [(s, e) for _, s, e, _ in tr.ops.get(first, ())]
    data = ProfileData.from_file(str(sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]))
    runs, spans, enqueued = [], [], {}
    for plane in data.planes:
        if plane.name == first:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    runs.extend((e.name.split("(", 1)[0], e.start_ns,
                                 e.start_ns + e.duration_ns, _run_id(e)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SERVE_PREFIX):
                        spans.append((e.name[len(SERVE_PREFIX):], e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name == ENQUEUE:
                        run = _run_id(e)
                        enqueued[run] = min(e.start_ns, enqueued.get(run, e.start_ns))
    modules = [(name, s, e, enqueued.get(run)) for name, s, e, run in runs]
    return ServeTrace(ops=ops, modules=sorted(modules, key=lambda m: m[1]),
                      spans=sorted(spans, key=lambda s: s[1]))


def _run_id(event):
    for key, value in event.stats:
        if key == "run_id":
            return int(value)
    return None


def rounds(tr: ServeTrace) -> list:
    """Each greedy decode round as ``(sync_start, sync_end, module_start,
    module_end)``: its ``decode.sync`` span (host clock) and the ``decode``
    program (device clock) enqueued inside the round's ``decode`` span.
    A round with other than one sync (speculative) is left out."""
    decode = [s for s in tr.spans if s[0] == "decode"]
    sync = [s for s in tr.spans if s[0] == "decode.sync"]
    out = []
    for name, ms, me, enq in tr.modules:
        if name != DECODE_MODULE or enq is None:
            continue
        for _, ds, de in decode:
            if ds <= enq <= de:
                inner = [s for s in sync if ds <= s[1] and s[2] <= de]
                if len(inner) == 1:
                    out.append((inner[0][1], inner[0][2], ms, me))
                break
    return out


def clock_offset(tr: ServeTrace) -> float:
    """Nanoseconds to add to a device time to put it on the host clock:
    the largest shift at which every round's program ends before its
    ``decode.sync`` span does."""
    rs = rounds(tr)
    if not rs:
        raise ValueError("the trace holds no decode round with its program")
    return min(sync_end - mod_end for _, sync_end, _, mod_end in rs)


def attribute(tr: ServeTrace, offset: float) -> dict:
    """Device idle time between the first round's ``decode`` span start and
    the last round's end (host clock, device shifted by ``offset``):
    ``idle_s``, ``named_s`` (inside a span other than ``tick``),
    ``named_share``, and ``by_span`` (seconds per innermost span; the idle
    time outside every span under ``None``)."""
    decode = [s for s in tr.spans if s[0] == "decode"]
    if not decode:
        raise ValueError("the trace holds no serve:decode span")
    lo, hi = decode[0][1], decode[-1][2]
    gaps, t = [], lo
    for s, e in union(((s + offset, e + offset) for s, e in tr.ops), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    children = [sp for sp in tr.spans if sp[0] != "tick"]
    named = _overlap(gaps, union(((s, e) for _, s, e in children), lo, hi))
    by_span: dict = collections.defaultdict(float)
    for (s, e), name in _innermost(gaps, tr.spans):
        by_span[name] += (e - s) * 1e-9
    idle = sum(e - s for s, e in gaps)
    return {"window_s": (hi - lo) * 1e-9, "idle_s": idle * 1e-9, "named_s": named * 1e-9,
            "named_share": named / idle if idle else None,
            "by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1]))}


def _overlap(a: list, b: list) -> float:
    """Total length of the intersection of two merged, sorted lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _innermost(gaps: list, spans: list) -> list:
    """``gaps`` cut at every span boundary, each piece with the name of the
    shortest span that holds it (``None`` when none does)."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    out = []
    for gs, ge in gaps:
        inner = [c for c in cuts if gs < c < ge]
        for s, e in zip([gs] + inner, inner + [ge]):
            mid = 0.5 * (s + e)
            around = [sp for sp in spans if sp[1] <= mid <= sp[2]]
            name = min(around, key=lambda sp: sp[2] - sp[1])[0] if around else None
            out.append(((s, e), name))
    return out
