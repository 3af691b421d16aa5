"""Exact percentiles over a list of samples (no buckets).

``percentile(values, q)`` is the nearest-rank percentile: the smallest
sample such that at least q% of the samples are <= it. A missing sample
(a failed or shed request) is passed as ``math.inf`` and sorts last, so a
run in which more than (100-q)% of requests failed reports ``inf``."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no samples")
    if not 0 < q <= 100:
        raise ValueError(f"q must be in (0, 100], got {q}")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return float(vals[rank - 1])


def samples_beyond(n: int, q: float) -> int:
    """How many samples lie strictly beyond the q-th percentile's rank."""
    return n - max(1, math.ceil(q / 100.0 * n))
