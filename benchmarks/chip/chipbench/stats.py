"""Small statistics the metric readers share."""
from __future__ import annotations

import math
from typing import Iterable, Sequence


def nearest_rank(values: Sequence[float], pct: float) -> float:
    """The ``pct``-th percentile by nearest rank: the smallest sample with
    at least ``pct`` % of the samples at or below it."""
    if not values:
        raise ValueError("no samples")
    xs = sorted(values)
    rank = max(1, math.ceil(pct / 100.0 * len(xs)))
    return xs[rank - 1]


def merge(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, disjoint union of closed intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[tuple[float, float]]) -> float:
    return sum(b - a for a, b in merge(intervals))


def clip(intervals: Iterable[tuple[float, float]],
         windows: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """The parts of ``intervals`` that lie inside ``windows``."""
    wins = merge(windows)
    out = []
    for a, b in merge(intervals):
        for lo, hi in wins:
            x, y = max(a, lo), min(b, hi)
            if y > x:
                out.append((x, y))
    return out


def gaps(intervals: Iterable[tuple[float, float]],
         windows: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """The parts of ``windows`` that no interval covers."""
    busy = merge(intervals)
    out = []
    for lo, hi in merge(windows):
        t = lo
        for a, b in busy:
            if b <= t or a >= hi:
                continue
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if t < hi:
            out.append((t, hi))
    return out
