"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
two lists: the device operations (``XLA Ops`` line of each ``/device:TPU``
plane) and the benchmark's own host spans (names starting ``bench:``).
Everything after that is interval arithmetic on plain tuples, so the
reduction is tested on a small recorded trace without a chip.

On a TPU v5 lite the device line's clock runs about a millisecond apart
from the host's (a recorded trace, tests/perfbench/data): negligible for
busy time over a window, but an idle gap of a few milliseconds may take
the label of the span next to it.  A device trace of a minute of serving
showed a 20 s stretch with no operation inside decode rounds, which only
lost events explain; so the harness traces two seconds of the window.
"""

from __future__ import annotations

import collections
import dataclasses
from pathlib import Path

SPAN_PREFIX = "bench:"
OPS_LINE = "XLA Ops"
# markers opening and closing the traced window, and the label of a gap
# that no benchmark span covers
WINDOW_ON, WINDOW_OFF = "window_on", "window_off"
OUTSIDE = "outside decode rounds"


@dataclasses.dataclass
class Trace:
    """Intervals in nanoseconds: ``ops[device] = [(name, start, end,
    text), ...]`` (``name`` the HLO instruction, ``text`` the whole HLO
    line) and ``spans = [(name, start, end), ...]``."""

    ops: dict
    spans: list


def load(log_dir: Path) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(str(files[-1]))
    ops: dict = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (_op_name(e.name), e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], e.start_ns,
                                      e.start_ns + e.duration_ns))
    return Trace(ops=ops, spans=spans)


def _op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def union(intervals, lo: float, hi: float) -> list:
    """Merged, sorted ``(start, end)`` pairs of ``intervals`` clipped to
    ``[lo, hi]``."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_ns(ops, lo: float, hi: float) -> float:
    """Time in ``[lo, hi]`` during which some operation ran."""
    return sum(e - s for s, e in union(((op[1], op[2]) for op in ops), lo, hi))


def kernel_ns(ops, needle: str, lo: float, hi: float) -> float:
    """Summed device time of the operations whose HLO line holds ``needle``
    (a kernel's name), clipped to the window."""
    return sum(max(0.0, min(op[2], hi) - max(op[1], lo)) for op in ops if needle in op[3])


def top_ops(ops, lo: float, hi: float, n: int = 10) -> list:
    """The ``n`` operation names that took most device time: [[name, s], ...]."""
    tot: dict = collections.defaultdict(float)
    for name, s, e, _ in ops:
        tot[name] += max(0.0, min(e, hi) - max(s, lo))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t * 1e-9] for name, t in best]


def idle_gaps(ops, spans, lo: float, hi: float, n: int = 10) -> list:
    """The ``n`` longest stretches of ``[lo, hi]`` with no device operation,
    each named by the innermost benchmark span around its midpoint:
    [[label, s], ...], longest first."""
    gaps, t = [], lo
    for s, e in union(((op[1], op[2]) for op in ops), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (s + e)
        around = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        label = min(around, key=lambda sp: sp[2] - sp[1])[0] if around else OUTSIDE
        out.append([label, (e - s) * 1e-9])
    return out


def window(spans) -> tuple:
    """``(start, end)`` of the traced window: from the ``window_on`` marker
    to the ``window_off`` marker."""
    marks = {}
    for name in (WINDOW_ON, WINDOW_OFF):
        hits = [st for n, st, _ in spans if n == name]
        if len(hits) != 1:
            raise ValueError(f"expected one {name!r} marker in the trace, found {len(hits)}")
        marks[name] = hits[0]
    return marks[WINDOW_ON], marks[WINDOW_OFF]


def summarize(tr: Trace) -> dict:
    """Busy seconds averaged over the devices, the traced window's length,
    every operation (for kernel times) and the breakdown lists."""
    lo, hi = window(tr.spans)
    devices = sorted(tr.ops)
    busy = [busy_ns(tr.ops[d], lo, hi) for d in devices]
    all_ops = [op for d in devices for op in tr.ops[d]]
    return {
        "devices": len(devices),
        "busy_s": (sum(busy) / len(busy) * 1e-9) if busy else 0.0,
        "window_s": (hi - lo) * 1e-9,
        "lo": lo, "hi": hi,
        "ops": all_ops,
        "device_ops": top_ops(all_ops, lo, hi),
        "idle_gaps": idle_gaps(tr.ops[devices[0]], tr.spans, lo, hi) if devices else [],
    }
