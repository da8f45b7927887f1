"""Percentile arithmetic of the benchmark (part of the yardstick).

A percentile is the nearest-rank one over ALL operations due in the
window: an operation that failed (refused, errored, or not converged by
its deadline) has no latency and counts as beyond every percentile, at
``beyond_ms``. Nothing here is clipped, trimmed or averaged.
"""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of ``values``."""
    if not values:
        raise ValueError("percentile of no samples")
    if not 0 < q <= 100:
        raise ValueError(f"q={q} outside (0, 100]")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def percentile_with_failed(values: list[float], n_failed: int, q: float,
                           beyond_ms: float) -> float:
    """The ``q``-th percentile where ``n_failed`` more operations count
    as slower than every measured one (reported as ``beyond_ms``)."""
    n = len(values) + n_failed
    if n == 0:
        raise ValueError("percentile of no operations")
    rank = max(1, math.ceil(q / 100.0 * n))
    if rank > len(values):
        return float(beyond_ms)
    return sorted(values)[rank - 1]


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the ``q``-th percentile."""
    return n - max(1, math.ceil(q / 100.0 * n)) if n else 0
