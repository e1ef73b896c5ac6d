"""What the traffic generators share. A mix is a data file
(``traffic/<mix>.json``); its ``kind`` names the generator that reads it,
``generators/<kind>.py``, found by that name:

* ``open_loop``: requests with wall-clock due times (``schedule``);
* ``batch``: a few distinct full batches made on the device
  (``batches``).

A new arrival process, length distribution or input type is a new file
under ``generators/arrivals``, ``generators/lengths`` or
``generators/inputs``, named in the mix.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from harness import spec
from harness.weights import nominal_threshold

#: event current / nominal encoder threshold. The margin (~250 float32
#: ulps) covers ulp-level differences in how softplus is evaluated; the
#: residual it leaves (1.5e-5 of a threshold per event) stays far below a
#: threshold for any stream under ~60,000 events
EVENT_MARGIN = 1.0 + 2.0 ** -16


def event_current(config: dict) -> np.float32:
    return np.float32(nominal_threshold(config) * EVENT_MARGIN)


def frame_shape(config: dict) -> tuple:
    """One frame of one lane: the image for a conv-encoder network, the
    encoder's input currents otherwise."""
    if config["conv_spec"]:
        return tuple(config["in_shape"])
    return (config["layer_sizes"][0],)


def quantiles(n: int) -> np.ndarray:
    """``n`` evenly spaced probabilities, the grid every seed shares."""
    return (np.arange(n) + 0.5) / n


@dataclass
class Schedule:
    due_s: np.ndarray         # (N,) seconds after the schedule starts
    frames: np.ndarray        # (N,) frames per request
    offsets: np.ndarray       # (N,) start of each request in the pool
    pool: np.ndarray          # (P, *frame_shape) float32 frames


def generator(mix: dict):
    """The generator module of the mix's ``kind``."""
    return spec.load_module("generators", mix["kind"])


def part(group: str, params: dict, key: str):
    """The module ``generators/<group>/<params[key]>.py``."""
    return spec.load_module(f"generators/{group}", params[key])
