"""Small statistics the harness shares: nearest-rank percentiles."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank q-th percentile (0 < q <= 100) of a non-empty list."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])
