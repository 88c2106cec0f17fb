"""Percentiles as the repo's Histogram takes them (nearest rank), kept here
so that no later change to the program can change how a tail is read."""
from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Nearest-rank percentile, p in (0, 1]: the value at rank ceil(p * n)
    of the sorted values, so at least a share p of them are <= it."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    rank = max(1, min(len(vals), math.ceil(p * len(vals))))
    return vals[rank - 1]
