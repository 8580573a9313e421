"""Percentiles, one definition for every metric."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0 < q < 100) by the nearest-rank rule: the
    smallest value with at least q% of the sample at or below it.  An
    infinite value (a request that never produced its token) counts as
    the slowest; an empty sample has no percentile."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(math.ceil(q / 100.0 * len(ordered)), 1)
    return ordered[rank - 1]
