"""Percentile, rate and spread arithmetic on plain lists of numbers."""
from __future__ import annotations

import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100), linear between closest ranks (the
    same rule as numpy's default); None for no values."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def mean(values: Sequence[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None


def count_in(times: Sequence[float], t0: float, t1: float) -> int:
    return sum(1 for t in times if t0 <= t < t1)


def rate(times: Sequence[float], t0: float, t1: float) -> float:
    """Events per second of the window [t0, t1)."""
    return count_in(times, t0, t1) / (t1 - t0)


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median, as a share: the spread the
    benchmark's bounds are set from (``statistics.quantiles``, n=4)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def union_length(intervals: Sequence[tuple]) -> float:
    """Total length covered by a set of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total
