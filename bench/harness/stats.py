"""Percentiles, kept with the benchmark so that no later change
to the program can change how a number is reduced.

``percentile`` is the linear-interpolation percentile (numpy's default
method), the same arithmetic as the program's ``obs.metrics.percentile``,
copied rather than imported.
"""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile, q in [0, 100]; NaN when empty."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return math.nan
    if len(xs) == 1:
        return xs[0]
    rank = (q / 100.0) * (len(xs) - 1)
    lo = int(math.floor(rank))
    hi = min(lo + 1, len(xs) - 1)
    frac = rank - lo
    return xs[lo] * (1.0 - frac) + xs[hi] * frac
