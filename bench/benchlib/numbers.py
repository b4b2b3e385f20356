"""The arithmetic of the end-to-end metrics and of their spread.

* A tail is a nearest-rank percentile over every message due in the
  window; a message that never completed counts as infinitely late.
* A rate is the messages completed inside the window over the window's
  length.
* A spread is the distance between the first and third quartiles, as
  ``statistics.quantiles(values, n=4)`` gives them, over the median.
"""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (``0 < q <= 100``): the smallest
    value with at least ``q`` percent of the values at or below it."""
    if not values:
        raise ValueError("percentile of no values")
    if not 0.0 < q <= 100.0:
        raise ValueError(f"q must be in (0, 100], got {q}")
    ordered = sorted(values)
    rank = math.ceil(q / 100.0 * len(ordered))
    return ordered[max(rank, 1) - 1]


def window_rate(done_at: Sequence[Optional[float]], close: float,
                seconds: float) -> float:
    """Messages completed by ``close`` over the window's ``seconds``;
    ``None`` marks a message that never completed."""
    return sum(1 for t in done_at if t is not None and t <= close) / seconds


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
