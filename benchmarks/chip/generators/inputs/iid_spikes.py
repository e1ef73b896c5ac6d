"""Encoder-exact iid input spikes at the mix's ``sparsity``: an event's
current is just above the encoder threshold, so the float encoder emits
exactly the offered raster."""
from __future__ import annotations

import numpy as np

from harness import traffic


def pool(params: dict, config: dict, n_frames: int,
         rng: np.random.Generator) -> np.ndarray:
    """``n_frames`` frames of encoder currents, float32."""
    shape = (n_frames, *traffic.frame_shape(config))
    raster = rng.random(shape, np.float32) >= params["sparsity"]
    return raster.astype(np.float32) * traffic.event_current(config)
