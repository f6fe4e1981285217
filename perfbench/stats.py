"""Percentiles, as the program's ``repro.serve.stats.percentile`` takes them
(numpy's linear interpolation), copied so the yardstick stays put."""

from __future__ import annotations

from typing import Optional

import numpy as np


def percentile(values, q: float) -> Optional[float]:
    """``q``-th percentile of ``values``, or ``None`` when there are none."""
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q must be in [0, 100], got {q}")
    vals = list(values)
    if not vals:
        return None
    return float(np.percentile(np.asarray(vals, np.float64), q))
