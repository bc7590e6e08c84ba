"""Metric arithmetic of the benchmark: rates over the whole window,
tails over all requests, quartile spread.  Hand-checked in
tests/benchmark."""

from __future__ import annotations

import math
import statistics
from typing import Dict, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in 0..100) of ALL values: the smallest
    value with at least q% of the sample at or below it.  None for an
    empty sample (a metric with nothing to read is left out)."""
    if not values:
        return None
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[rank - 1]


def rate(total: float, seconds: float) -> float:
    """All the work over all the time of the window."""
    if seconds <= 0:
        raise ValueError("a rate needs a window longer than 0 s")
    return total / seconds


def quartile_spread(values: Sequence[float]) -> float:
    """(Q3 - Q1) / median with Python's statistics.quantiles(n=4): the
    spread the bounds are set from."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def series_summary(counts: Sequence[float]) -> Dict[str, float]:
    """min, max and coefficient of variation of a per-second series."""
    if not counts:
        return {"min": 0.0, "max": 0.0, "cv": 0.0}
    mean = sum(counts) / len(counts)
    var = sum((c - mean) ** 2 for c in counts) / len(counts)
    return {"min": float(min(counts)), "max": float(max(counts)),
            "cv": (math.sqrt(var) / mean) if mean else 0.0}
