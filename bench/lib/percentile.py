"""Percentiles of the raw samples.

The program's HdrRecorder (runtime/metrics.py) buckets samples log-linearly
because a million-client run cannot keep them; a benchmark run has at most
a few tens of thousands, keeps every one and reads the exact order
statistic: the smallest sample with at least q of the samples at or below
it (nearest rank, the rule HdrRecorder.percentile applies to its buckets).
"""
from __future__ import annotations

import math

import numpy as np


def _rank(q: float, n: int) -> int:
    # q * n in floating point can land a hair above a whole number.
    return min(n, max(1, math.ceil(q * n - 1e-9)))


def percentile(samples: np.ndarray, q: float) -> float:
    """Nearest-rank quantile `q` in (0, 1] of `samples`; NaN if empty."""
    n = len(samples)
    if n == 0:
        return float("nan")
    rank = _rank(q, n)
    return float(np.partition(np.asarray(samples), rank - 1)[rank - 1])


def beyond(samples: np.ndarray, q: float) -> int:
    """How many samples lie beyond the nearest-rank quantile."""
    n = len(samples)
    return n - _rank(q, n) if n else 0
