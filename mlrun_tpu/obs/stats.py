"""Tiny shared statistics helpers for the observability layer.

One canonical nearest-rank percentile for everything that quotes p50/p95
(the engine latency rings, ``serving/llm_batch._percentile``): two
hand-rolled index formulas had drifted apart — ``int(n * q)`` picks the
order statistic ONE RANK HIGH of the nearest-rank definition whenever
``q * n`` is an integer (p95 of 100 samples must be the 95th smallest,
``ceil(0.95 * 100) = 95`` → index 94, not index 95). Stdlib only, same
bottom-layer rule as the rest of ``obs/``.
"""

from __future__ import annotations

import math


def nearest_rank(sorted_samples, q: float) -> float:
    """Nearest-rank percentile: the ``ceil(q * n)``-th order statistic of
    an already-sorted, non-empty sample sequence (0 < q <= 1)."""
    n = len(sorted_samples)
    if n == 0:
        raise ValueError("nearest_rank needs at least one sample")
    idx = max(0, math.ceil(q * n) - 1)
    return sorted_samples[min(idx, n - 1)]
