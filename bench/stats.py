"""The benchmark's arithmetic: tails, rates and recall.

A tail is taken over every request, never over medians of chunks, and a
request that failed or never came counts as missing it (its latency is
infinite).  Percentiles are nearest-rank: the value at rank ``ceil(q * n)``
of the sorted sample, so a tail is always a latency some request had.
"""
from __future__ import annotations

import math

import numpy as np


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of ``values``."""
    v = np.sort(np.asarray(values, np.float64).ravel())
    if v.size == 0:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * v.size))
    return float(v[rank - 1])


def latencies_ms(completed_at, due_at, failed) -> np.ndarray:
    """Completion minus scheduled send, in ms; a failed request is +inf."""
    lat = (np.asarray(completed_at, np.float64)
           - np.asarray(due_at, np.float64)) * 1e3
    return np.where(np.asarray(failed, bool), np.inf, lat)


def rate(count: int, seconds: float) -> float:
    """Work completed per second of the window."""
    if seconds <= 0:
        raise ValueError(f"rate over a window of {seconds} s")
    return count / seconds


def recall_at_k(found, truth, k: int) -> np.ndarray:
    """Per-row share of the true ``k`` nearest found among the first ``k``
    answers (ids < 0 are empty slots and never count)."""
    found = np.asarray(found)[:, :k]
    truth = np.asarray(truth)[:, :k]
    hits = (found[:, :, None] == truth[:, None, :]) & (found[:, :, None] >= 0)
    return hits.any(axis=2).sum(axis=1) / float(k)


def in_span(times, span) -> np.ndarray:
    """Mask of finite ``times`` inside ``span`` = (start, end); with no
    span, every finite time."""
    t = np.asarray(times, np.float64)
    ok = np.isfinite(t)
    if span is not None:
        ok &= (t >= span[0]) & (t <= span[1])
    return ok
