"""Exact statistics over the benchmark's own samples."""
import math
from typing import Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The exact nearest-rank ``q``-th percentile: the smallest sample with
    at least ``q`` percent of the samples at or below it."""
    if not values:
        raise ValueError("no samples")
    s = sorted(values)
    return s[max(1, math.ceil(q / 100.0 * len(s))) - 1]
