"""95th percentile over all requests of the time per output token after
the first: (latency - ttft) / (tokens - 1)."""

from perfbench.stats import percentile


def read(run):
    p = percentile([(r.latency_s - r.ttft_s) / (r.tokens_out - 1)
                    for r in run.requests if r.tokens_out > 1], 95)
    return None if p is None else 1e3 * p
