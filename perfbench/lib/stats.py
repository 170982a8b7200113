"""Percentile and sub-window arithmetic, kept with the benchmark so that
every PR computes the same number in the same way."""
from __future__ import annotations

import math
from typing import List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) with linear interpolation between the
    two nearest order statistics (numpy's default rule), in plain Python
    so the arithmetic is readable. Raises on an empty sample."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    xs = sorted(values)
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def subwindow_rates(event_times: Sequence[float], weights: Sequence[float],
                    t0: float, seconds: float, part: float) -> List[float]:
    """Rates (weight per second) of the consecutive whole parts of length
    ``part`` that fit into [t0, t0 + seconds). An event at time t counts
    in part floor((t - t0) / part); events outside the whole parts are
    dropped. The last, partial part is never reported."""
    if part <= 0:
        raise ValueError("part length must be positive")
    n_parts = int(math.floor(seconds / part + 1e-9))
    sums = [0.0] * n_parts
    for t, w in zip(event_times, weights):
        i = int(math.floor((t - t0) / part))
        if 0 <= i < n_parts and t >= t0:
            sums[i] += w
    return [s / part for s in sums]
