"""The order statistic the standard library lacks."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100) of a non-empty list:
    a value that was measured, never an interpolation."""
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]
