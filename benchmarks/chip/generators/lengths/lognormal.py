"""Lognormal lengths in words: ``median``, ``sigma``, clipped to
``min``..``max``."""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def words(params: dict, u: np.ndarray) -> np.ndarray:
    """The lengths at probabilities ``u``."""
    z = np.array([NormalDist().inv_cdf(p) for p in u])
    w = np.exp(math.log(params["median"]) + params["sigma"] * z)
    return np.clip(np.round(w), params["min"], params["max"]).astype(np.int64)
