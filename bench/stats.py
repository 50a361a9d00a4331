"""Percentiles and spreads, defined once."""
from __future__ import annotations

import statistics

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile, linear between order statistics (NumPy's
    default), of a non-empty sample."""
    values = np.asarray(values, float)
    if not len(values):
        raise ValueError("percentile of an empty sample")
    return float(np.percentile(values, q))


def spread(values) -> float:
    """Interquartile distance as a share of the median, with the quartiles
    of ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / med
