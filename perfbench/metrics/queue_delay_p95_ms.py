"""Scheduler: 95th percentile over all requests of the wait from arrival
to admission (open loop only)."""

from perfbench.stats import percentile


def read(run):
    p = percentile([r.queue_delay_s for r in run.requests if r.queue_delay_s is not None], 95)
    return None if p is None else 1e3 * p
