"""Arithmetic over a measured window: every rate over all the work and
all the time of the window, every tail over all its requests."""

from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Nearest-rank ``p``-th percentile (0 < p <= 100) of all values."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = max(1, math.ceil(p / 100.0 * len(xs)))
    return float(xs[k - 1])


def rate(count: float, t0: float, t1: float) -> float:
    if t1 <= t0:
        raise ValueError(f"empty window [{t0}, {t1}]")
    return count / (t1 - t0)


def due_in(due, t0: float, t1: float) -> list[int]:
    """Indices of requests due in [t0, t1)."""
    return [i for i, d in enumerate(due) if t0 <= d < t1]


def tokens_in(stamps, t0: float, t1: float) -> int:
    """Tokens whose host arrival time lies in [t0, t1]."""
    return sum(1 for s in stamps if t0 <= s <= t1)


def ttft(due: float, stamps) -> float:
    return stamps[0] - due


def gaps(stamps) -> list[float]:
    return [b - a for a, b in zip(stamps, stamps[1:])]
