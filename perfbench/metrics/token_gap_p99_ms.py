"""Scheduler: 99th percentile over every gap between consecutive tokens of
every request served (``RequestStats.token_s``, the program's per-token
stamps).  A program without stamps reads nothing."""

import numpy as np

from perfbench.stats import percentile


def read(run):
    p = percentile([g for r in run.requests for g in np.diff(getattr(r, "token_s", ()))], 99)
    return None if p is None else 1e3 * p
