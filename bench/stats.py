"""Percentiles for latencies where a request that never got its answer
counts as a miss: it takes the value +inf, so it lands above every
served one and a percentile that reaches it reads +inf."""
from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Nearest-rank p-th percentile (0 < p <= 100) of `values`, which may
    hold +inf for misses."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[k - 1]


def beyond(values, p: float) -> int:
    """How many samples lie beyond the nearest-rank p-th percentile."""
    return len(values) - max(1, math.ceil(p / 100.0 * len(values)))
