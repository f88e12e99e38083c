"""Percentiles and spreads, the one arithmetic every metric goes through."""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile over every sample (numpy's default
    method). Raises on an empty sample: a tail of nothing is not 0."""
    vs = sorted(float(v) for v in values)
    if not vs:
        raise ValueError("percentile of an empty sample")
    k = (len(vs) - 1) * q / 100.0
    f, c = math.floor(k), math.ceil(k)
    if f == c or vs[c] == math.inf:
        return vs[c]
    return vs[f] + (vs[c] - vs[f]) * (k - f)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile over the median, as
    ``statistics.quantiles(values, n=4)`` gives the quartiles."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
