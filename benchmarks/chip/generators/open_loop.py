"""Open-loop requests with wall-clock due times.

Arrivals (``generators/arrivals/<process>.py``), lengths in words of
``timesteps`` frames (``generators/lengths/<dist>.py``) and the input
frames (``generators/inputs/<kind>.py``) are each named in the mix.
Lengths come from a fixed quantile grid, ``block`` requests at a time,
and the seed only permutes each block, so every seed offers the same
sizes in another order. A request's frames are cut from one seeded pool
of ``pool_frames`` frames at a seeded offset.
"""
from __future__ import annotations

import numpy as np

from harness import traffic


def schedule(mix: dict, config: dict, rate: float, horizon_s: float,
             seed: int) -> traffic.Schedule:
    """Requests due over ``horizon_s`` seconds at ``rate`` per second."""
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    arrivals = traffic.part("arrivals", mix["arrivals"], "process")
    due = arrivals.due_times(mix["arrivals"], rate, horizon_s, mix["block"],
                             rng)
    n = mix["block"]
    grid = traffic.part("lengths", mix["words"], "dist").words(
        mix["words"], traffic.quantiles(n)) * config["timesteps"]
    blocks = -(-len(due) // n)
    frames = np.concatenate([grid[rng.permutation(n)]
                             for _ in range(blocks)])[:len(due)]
    pool = traffic.part("inputs", mix["input"], "kind").pool(
        mix["input"], config, mix["pool_frames"], rng)
    offsets = rng.integers(0, mix["pool_frames"] - frames + 1)
    return traffic.Schedule(due_s=due, frames=frames, offsets=offsets,
                            pool=pool)
