"""Percentile arithmetic over raw samples (no buckets: the program's
``serving/histogram.py`` uses 2x log buckets, too coarse for a bound of a
few percent)."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional


def percentile(samples: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (rank ``q/100 * (n-1)``), ``None`` for no samples."""
    xs: List[float] = sorted(float(x) for x in samples)
    if not xs:
        return None
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    rank = q / 100.0 * (len(xs) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def median(samples: Iterable[float]) -> Optional[float]:
    return percentile(samples, 50.0)


def highest_supported_percentile(n: int, beyond: int = 10) -> Optional[float]:
    """The highest of p50/p90/p95/p99 that has at least ``beyond`` samples
    beyond it among ``n`` (the choosing-metrics rule for tails)."""
    best = None
    for q in (50, 90, 95, 99):
        if n * (100 - q) >= beyond * 100:
            best = float(q)
    return best
