"""Poisson arrivals: exponential gaps of mean 1/rate, from a fixed
quantile grid of ``block`` gaps that the seed permutes block by block,
so every seed offers the same gaps in another order."""
from __future__ import annotations

import numpy as np

from harness import traffic


def due_times(params: dict, rate: float, horizon_s: float, block: int,
              rng: np.random.Generator) -> np.ndarray:
    """Due times in seconds, up to ``horizon_s``."""
    gaps = -np.log1p(-traffic.quantiles(block)) / rate
    due, t = [], 0.0
    while t <= horizon_s:
        due.append(t + np.cumsum(gaps[rng.permutation(block)]))
        t = due[-1][-1]
    due = np.concatenate(due)
    return due[due <= horizon_s]
