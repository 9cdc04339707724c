"""Percentile and rate arithmetic — one definition for every metric."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (the smallest value with at least q% of
    the sample at or below it); None for an empty sample."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def rate(count: float, seconds: float) -> float:
    """All the work over all the time of the window."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds


def mean(values: Sequence[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None
