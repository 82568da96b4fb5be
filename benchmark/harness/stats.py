"""Percentile arithmetic, kept with the benchmark."""

from __future__ import annotations

import math


def percentile(values: list, q: float) -> float:
    """Linear-interpolated percentile, q in [0, 1] (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def supported_q(n: int, q: float) -> float:
    """The highest percentile <= q that has ten samples beyond it: a
    p95 needs 200 samples; from fewer, the tail reported is
    1 - 10/n (never under the median)."""
    if n <= 0:
        raise ValueError("no samples")
    return max(0.5, min(q, 1.0 - 10.0 / n))


def tail(values: list, q: float) -> tuple:
    """(value, the percentile actually reported)."""
    q_eff = supported_q(len(values), q)
    return percentile(values, q_eff), q_eff
