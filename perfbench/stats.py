"""Small order statistics used by the harness and the metric readers."""

from __future__ import annotations

from typing import Iterable, List, Sequence, Tuple


def percentile(values: Iterable[float], p: float) -> float:
    """Linear-interpolated percentile `p` in [0, 100] of `values`."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    if len(v) == 1:
        return float(v[0])
    k = (len(v) - 1) * p / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (k - lo))


def mean(values: Sequence[float]) -> float:
    if not values:
        raise ValueError("mean of no values")
    return float(sum(values) / len(values))


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length covered by possibly overlapping [start, end) spans."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[Tuple[float, float]], start: float,
         end: float) -> List[Tuple[float, float]]:
    """The parts of [start, end) that no interval covers."""
    out, cur = [], start
    for s, e in sorted(intervals):
        if e <= cur:
            continue
        if s >= end:
            break
        if s > cur:
            out.append((cur, min(s, end)))
        cur = max(cur, e)
        if cur >= end:
            break
    if cur < end:
        out.append((cur, end))
    return out
