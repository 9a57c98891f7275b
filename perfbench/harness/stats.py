"""Arithmetic over a whole window: percentiles, rates and means.

``percentile`` is the program's tail arithmetic
(``benchmarks/stats.py``: ``LatencyRecorder.percentiles_ms``, numpy's
linear interpolation), copied so no later change to the program moves the
yardstick.
"""
from __future__ import annotations

import numpy as np


def percentile(values, pct: float) -> float:
    """``pct``-th percentile of every value, linear interpolation."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, dtype=np.float64), pct))


def rate(count: float, seconds: float) -> float:
    """Work per second over the whole window."""
    if seconds <= 0:
        raise ValueError(f"rate over a window of {seconds} s")
    return float(count) / float(seconds)


def mean(values) -> float:
    if len(values) == 0:
        raise ValueError("mean of no values")
    return float(sum(values)) / len(values)
