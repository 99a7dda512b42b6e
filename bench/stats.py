"""Arithmetic shared by the metric readers and the comparison."""

from __future__ import annotations

import numpy as np


def recall(served: np.ndarray, truth: np.ndarray) -> float:
    """Mean |served ∩ truth| / k over rows (recall@k), (Q, k) each."""
    served = np.asarray(served)
    truth = np.asarray(truth)
    if served.shape != truth.shape:
        raise ValueError(f"shapes differ: {served.shape} {truth.shape}")
    hits = (served[:, :, None] == truth[:, None, :]).any(-1).sum()
    return float(hits) / truth.size


def union_seconds(starts_ns, ends_ns) -> float:
    """Length of the union of [start, end) intervals, in seconds."""
    s = np.asarray(starts_ns, dtype=np.float64)
    e = np.asarray(ends_ns, dtype=np.float64)
    if s.size == 0:
        return 0.0
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    # a new run starts where an interval begins after everything before
    new = np.empty(s.size, dtype=bool)
    new[0] = True
    new[1:] = s[1:] > reach[:-1]
    run_start = s[new]
    run_end = np.maximum.reduceat(e, np.flatnonzero(new))
    return float((run_end - run_start).sum()) / 1e9


def quartile_spread(values) -> float:
    """(Q3 - Q1) / median, with Python's ``statistics.quantiles``."""
    import statistics

    q1, med, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / med
