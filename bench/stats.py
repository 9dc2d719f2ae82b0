"""Small reductions the metric readers share."""
from __future__ import annotations

import numpy as np


def p50(xs) -> float | None:
    """Median, None when empty."""
    xs = [x for x in xs if x is not None]
    return float(np.median(xs)) if xs else None


def mean(xs) -> float | None:
    xs = [x for x in xs if x is not None]
    return float(np.mean(xs)) if xs else None


def within(spans, t0: float, t1: float):
    """Records ``(start, end, ...)`` that lie wholly inside [t0, t1]."""
    return [s for s in spans if s[0] >= t0 and s[1] <= t1]


def prorated(spans, t0: float, t1: float) -> float:
    """Sum of each ``(start, end, amount)`` weighted by the share of its
    interval inside [t0, t1]."""
    total = 0.0
    for a, b, n in spans:
        if b <= t0 or a >= t1 or not n:
            continue
        if b <= a:
            total += n
            continue
        total += n * (min(b, t1) - max(a, t0)) / (b - a)
    return total


def traced(rec: dict, key: str):
    """Records of ``rec[key]`` inside the traced window."""
    t0, t1 = rec["trace_window"]
    return within(rec[key], t0, t1)
