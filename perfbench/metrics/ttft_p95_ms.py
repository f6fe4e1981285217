"""95th percentile over all requests of the time to first token, timed
from each request's scheduled arrival (the scheduler's wall-clock stamps,
each after the host has read the token from the device)."""

from perfbench.stats import percentile


def read(run):
    p = percentile([r.ttft_s for r in run.requests], 95)
    return None if p is None else 1e3 * p
