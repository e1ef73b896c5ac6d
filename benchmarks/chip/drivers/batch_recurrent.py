"""Offline batch scoring of a recurrent configuration: the batch driver
(`drivers/batch.py`) unchanged, its timing, warm-up, reservoir and checks
included, with the recurrent configuration's program configuration,
weights and plain reference (`harness/recurrent.py`) in place of the
feed-forward ones. A program that has no recurrent layers refuses the
configuration at once, before any traffic is made.
"""
from __future__ import annotations

import importlib.util

from harness import recurrent
from harness.spec import ROOT


def _batch_driver():
    """A private instance of ``drivers/batch.py`` whose configuration,
    weights and reference names are the recurrent ones; the batch
    driver's own module, which the feed-forward cells run, is untouched."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_drivers_batch_for_recurrent", ROOT / "drivers" / "batch.py")
    batch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(batch)
    batch.model_config = recurrent.model_config
    batch.weights = batch.reference = recurrent
    return batch


def run(r):
    return _batch_driver().run(r)
