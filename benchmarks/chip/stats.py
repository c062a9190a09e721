"""Q-error and percentile arithmetic.

``qerror`` is copied from ``benchmarks/common.py``
(paper §6.1: ``max(est/true, true/est)`` with both clamped to at least 1),
vectorised.
"""
from __future__ import annotations

import numpy as np


def qerror(est, true) -> np.ndarray:
    e = np.maximum(np.asarray(est, np.float64), 1.0)
    c = np.maximum(np.asarray(true, np.float64), 1.0)
    return np.maximum(e / c, c / e)


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (numpy's linear rule); None when empty."""
    a = np.asarray(values, np.float64)
    return float(np.percentile(a, q)) if a.size else None
