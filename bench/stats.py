"""Exact order statistics over raw samples.

Tails are taken from every sample of a window, never from bucket edges or
from medians of chunks.  ``percentile`` is nearest-rank (it returns an
observed value), the same rule as ``repro.obs.metrics.exact_percentiles``,
copied here so that the yardstick does not move with the program.
"""
from __future__ import annotations

import math
import statistics


def percentile(samples, p: float) -> float | None:
    """Nearest-rank ``p``-th percentile of ``samples``; None when empty."""
    xs = sorted(float(x) for x in samples)
    if not xs:
        return None
    rank = max(int(math.ceil(p / 100.0 * len(xs))), 1)
    return xs[rank - 1]


def summary(samples) -> dict:
    """n, p50, p95, p99 and max of ``samples`` (None values when empty)."""
    xs = list(samples)
    return {"n": len(xs), "p50": percentile(xs, 50),
            "p95": percentile(xs, 95), "p99": percentile(xs, 99),
            "max": max(xs) if xs else None}


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / med
