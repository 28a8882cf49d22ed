"""Order statistics the end-to-end and per-layer metrics share.

``percentile`` is the linear-interpolation percentile of the program's
``metrics/logger.percentile``, kept here so that no later change to the
program can change how a tail is read.
"""
from __future__ import annotations

import statistics

import numpy as np


def percentile(values, q: float) -> float:
    """Linear-interpolation percentile, q in [0, 100]; NaN when empty."""
    arr = np.asarray(list(values), np.float64).reshape(-1)
    if arr.size == 0:
        return float("nan")
    return float(np.percentile(arr, q))


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles``, the exclusive method)."""
    q1, q2, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / q2
