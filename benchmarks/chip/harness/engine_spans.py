"""The serving engine's phase spans in a traced window.

`SNNServeEngine.step` marks each call with a ``snn_engine.step`` span and
each phase inside it with its own span (``serve/snn_engine.py``). The
six phases below split the step span with nothing counted twice: the
retire children ``fetch``, ``account`` and ``evict`` lie inside
``retire``, of which only ``fetch`` is split off, and ``step_self`` is
the step less every phase span directly inside it.
"""
from __future__ import annotations

from collections import defaultdict

import numpy as np

STEP = "snn_engine.step"
#: top-level phases of a step: the spans that lie directly inside it
TOP = ("snn_engine.admit", "snn_engine.stage", "snn_engine.stage_next",
       "snn_engine.dispatch", "snn_engine.retire")


def phase_ms(trace) -> dict | None:
    """Per-tick mean milliseconds of each phase (``admit``, ``stage``,
    ``dispatch``, ``fetch``, ``retire``, ``step_self``) over the engine
    step spans of ``trace`` (a `Reduced`); phase spans outside every
    recorded step are left out. None when the trace holds no step span,
    as with a program that has no engine spans."""
    if trace is None:
        return None
    steps = np.asarray(sorted((s, e) for n, s, e in trace.host if n == STEP),
                       float).reshape(-1, 2)
    if not len(steps):
        return None
    total = defaultdict(float)
    for name, s, e in trace.host:
        if not name.startswith("snn_engine.") or name == STEP:
            continue
        k = np.searchsorted(steps[:, 0], s, side="right") - 1
        if k >= 0 and e <= steps[k, 1]:
            total[name] += e - s
    ms = 1e3 / len(steps)
    return {
        "admit": total["snn_engine.admit"] * ms,
        "stage": (total["snn_engine.stage"]
                  + total["snn_engine.stage_next"]) * ms,
        "dispatch": total["snn_engine.dispatch"] * ms,
        "fetch": total["snn_engine.fetch"] * ms,
        "retire": (total["snn_engine.retire"]
                   - total["snn_engine.fetch"]) * ms,
        "step_self": (float((steps[:, 1] - steps[:, 0]).sum())
                      - sum(total[n] for n in TOP)) * ms,
    }
