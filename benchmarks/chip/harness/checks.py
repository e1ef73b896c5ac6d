"""The comparisons that decide ``correct``."""
from __future__ import annotations

import numpy as np


def mismatches(got, want) -> int:
    """How many integers of ``got`` differ from ``want`` (a shape that
    differs counts every element)."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return int(max(got.size, want.size))
    return int(np.count_nonzero(got != want))


def logit_rel_gap(got, want_v, scale) -> float:
    """Largest |logit - v_ref * scale|, over max(|v_ref * scale|, scale):
    the program's float logits against the reference's integer readout V
    in the benchmark's units."""
    want = np.asarray(want_v, np.float64) * float(scale)
    got = np.asarray(got, np.float64).reshape(want.shape)
    den = np.maximum(np.abs(want), float(scale))
    return float(np.max(np.abs(got - want) / den)) if want.size else 0.0
