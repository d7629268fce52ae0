"""Small arithmetic the metric readers share."""
from __future__ import annotations

import math

import numpy as np

__all__ = ["percentile", "idle_share"]


def percentile(values, q: float):
    """The q-th percentile (0-100, linear between order statistics) of
    ``values``.  A missing value (NaN) counts as infinitely late; a
    percentile that is not finite is None."""
    v = np.asarray(values, np.float64)
    if not len(v):
        return None
    x = float(np.percentile(np.where(np.isnan(v), np.inf, v), q))
    return x if math.isfinite(x) else None


def idle_share(record):
    tr = record.get("trace")
    if not tr:
        return None
    chips = tr["chips"].values()
    busy = sum(c["busy_s"] for c in chips) / len(chips)
    return 1.0 - busy / tr["window_s"]
