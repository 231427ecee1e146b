"""Exact order statistics of the harness's own timestamps."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank ``p``-th percentile (0 < p <= 100) of ``values``;
    ``math.inf`` entries stand for requests that missed (a failed or
    unfinished request counts as missing every latency limit). None for
    an empty sample."""
    if not values:
        return None
    xs = sorted(values)
    k = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[k - 1]
