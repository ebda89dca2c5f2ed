"""Order statistics over every sample, as the benchmark reports them."""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The `q`-th percentile (0-100) of all `values`, interpolating linearly
    between the two nearest order statistics (numpy's default rule)."""
    if not values:
        raise ValueError("percentile of no samples")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside [0, 100]")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def cv(values: Sequence[float]) -> Optional[float]:
    """Coefficient of variation (population standard deviation over the
    mean); None for fewer than two samples or a zero mean."""
    if len(values) < 2:
        return None
    mean = statistics.fmean(values)
    if mean == 0:
        return None
    return statistics.pstdev(values) / mean
