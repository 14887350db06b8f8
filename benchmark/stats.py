"""The few statistics the benchmark reports, in one place."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0-100) by linear interpolation between the
    sorted values; None of nothing."""
    if not values:
        return None
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def stat(values: Sequence[float], name: str) -> Optional[float]:
    """`p50`, `p95`, ... or `mean`."""
    if not values:
        return None
    if name == "mean":
        return sum(values) / len(values)
    if name.startswith("p"):
        return percentile(values, float(name[1:]))
    raise ValueError(f"unknown statistic {name!r}")
