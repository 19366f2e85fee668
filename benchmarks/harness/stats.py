"""Quantiles, the sample-count rule and the spread the bounds are set from."""

from __future__ import annotations

import math
import statistics


def quantile(values, q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("quantile of no values")
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def samples_beyond(n: int, q: float) -> int:
    """How many of n samples lie beyond the q-quantile."""
    return int(math.floor(n * (1.0 - q) + 1e-9))


def highest_supported_quantile(n: int, candidates=(0.99, 0.95, 0.9, 0.75),
                               beyond: int = 10) -> float | None:
    """The highest percentile that keeps `beyond` samples beyond it
    (choosing-metrics section 1)."""
    for q in candidates:
        if samples_beyond(n, q) >= beyond:
            return q
    return None


def iqr_share(values) -> float:
    """Distance between the first and third quartile, as Python's
    `statistics.quantiles(values, n=4)` gives them, over the median:
    the spread the bounds in BENCHMARK.json are set from."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def summary(values) -> dict:
    xs = list(values)
    if not xs:
        return {"n": 0}
    return {"n": len(xs), "p50": quantile(xs, 0.5), "p90": quantile(xs, 0.9),
            "max": max(xs)}
