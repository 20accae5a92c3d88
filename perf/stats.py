"""Order statistics for the benchmark's timings.

A timing is reported as a median and the highest percentile that still
has at least ``MIN_BEYOND`` samples beyond it; a percentile the sample
cannot support is not reported at all (the caller gets ``None`` and
leaves the metric out of the line).
"""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence

MIN_BEYOND = 10


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100), linear interpolation between
    the two nearest order statistics (numpy's default rule)."""
    if not values:
        raise ValueError("percentile of no samples")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"q must be in [0, 100], got {q}")
    ordered = sorted(values)
    rank = (len(ordered) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie strictly above the ``q``-th
    percentile's rank."""
    return n - 1 - math.floor((n - 1) * q / 100.0)


def supported(n: int, q: float) -> bool:
    """Whether ``n`` samples support the ``q``-th percentile: at least
    ``MIN_BEYOND`` of them lie beyond it (q=95 needs some 200 samples)."""
    return n > 0 and samples_beyond(n, q) >= MIN_BEYOND


def supported_percentile(values: Sequence[float], q: float
                         ) -> Optional[float]:
    """``percentile`` where the sample supports it, else None."""
    if not supported(len(values), q):
        return None
    return percentile(values, q)


def median(values: Sequence[float]) -> float:
    return statistics.median(values)


def iqr_share(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median — the spread the bounds in BENCHMARK.json are set from
    (``statistics.quantiles(values, n=4)``, as the driver computes it)."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
