"""Quantities that more than one metric reads.  A metric whose cells
report different end-to-end metrics is split by name
(``decode_step_ms.open`` / ``decode_step_ms.closed``); both halves read
the same quantity from here."""

from __future__ import annotations


def decode_step_ms(run):
    """Mean wall time of a decode round, from the program's counters over
    the window (each round ends in the host's read of its tokens)."""
    s = run.stats
    return 1e3 * s.decode_s / s.decode_steps if s.decode_steps else None


def admit_ms(run):
    """Mean wall time of an admission (batch-1 prefill + scatter + first
    token), over the requests seated in the window."""
    return 1e3 * run.stats.prefill_s / len(run.requests) if run.requests else None


def device_idle(run):
    """Percent of the traced window with no operation on the device."""
    if run.trace is None or not run.trace["devices"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
