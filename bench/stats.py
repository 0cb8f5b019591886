"""Percentiles, computed the same way in every run."""
from __future__ import annotations

import math
from typing import Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The ceil(q * n)-th smallest value (1-based), over all values.

    Exact for any n >= 1: no interpolation, so a tail is always a value
    some request really saw.
    """
    if not values:
        raise ValueError("nearest_rank of no values")
    if not 0.0 < q <= 1.0:
        raise ValueError(f"quantile {q} outside (0, 1]")
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]

