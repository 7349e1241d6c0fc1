"""Percentiles and spreads, one definition for every reader."""
from __future__ import annotations

import statistics

import numpy as np


def percentile(values, q: float) -> float | None:
    """The q-th percentile (linear interpolation); None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles``, exclusive method)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
