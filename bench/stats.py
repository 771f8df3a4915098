"""Order statistics used by the metrics and by the spread of runs."""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in [0, 100]); None on empty input.

    The smallest 1-based rank k with k/n >= q/100, i.e.
    ``k = ceil(q/100 * n)`` (copied from ``repro.cluster.metrics``; here
    an empty sample gives None, so that no metric reads 0).
    """
    if not xs:
        return None
    s = sorted(xs)
    k = min(len(s) - 1, max(0, math.ceil(q / 100.0 * len(s)) - 1))
    return s[k]


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile over the median,
    with Python's ``statistics.quantiles(values, n=4)``."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
