"""End-to-end arithmetic: percentiles over all requests and rates over
all window time.

``percentile`` is the linear interpolation of the program's
``serving/metrics.percentile_summary`` (numpy's default), copied here so
that a change to the program cannot move the yardstick.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    arr = np.asarray(list(values), dtype=np.float64)
    if arr.size == 0:
        raise ValueError("percentile of an empty sample")
    return float(np.percentile(arr, q))


def rate(work: float, seconds: float) -> float:
    """All the work of the window over all of its time."""
    if seconds <= 0:
        raise ValueError("a window has positive length")
    return work / seconds
