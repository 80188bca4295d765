"""Percentile and token-gap arithmetic (the percentile is the one
``benchmarks/loadgen.py`` uses: linear interpolation between order
statistics, as numpy's default)."""

from __future__ import annotations


def percentile(values: list[float], q: float) -> float:
    """The ``q``-th percentile (0..100) of ``values``; raises on none."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def token_gaps(arrivals: list[tuple[float, int]]) -> list[float]:
    """Gaps between successive output tokens of one request, from its
    stream's deltas ``(time, tokens in the delta)``. The first delta's first
    token has no gap (that is time to first token). A later delta of k
    tokens is one gap of its interval and k-1 gaps of 0: the tokens came
    together."""
    gaps: list[float] = []
    for i, (t, k) in enumerate(arrivals):
        if k <= 0:
            continue
        if i > 0:
            gaps.append(t - arrivals[i - 1][0])
        gaps.extend([0.0] * (k - 1))
    return gaps
