#!/usr/bin/env python3
"""Rehearse every cell on the CPU at tiny sizes, Pallas in interpret mode:

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [--seconds 1]

Each cell runs once with ``--trace 0`` and once with ``--trace 1`` through
the same drivers, readers and result line as ``run.py``, with the
traffic and lane pool shrunk (widths untouched) and the chip check
skipped; the per-layer readers are handed the v5e's peaks. It prints
each result line; a number from here is a CPU number and is never a
device metric.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
from harness import spec  # noqa: E402

#: per driver: (traffic overrides, settings overrides)
TINY = {
    "serve": ({"words": {"dist": "lognormal", "median": 2, "sigma": 0.75,
                         "min": 1, "max": 4},
               "block": 64, "pool_frames": 4096},
              {"batch_slots": 8, "rate_rps": 4.0, "prefill_s": 0.5,
               "trace_s": 0.5, "drain_limit_s": 20.0}),
    "batch": ({"batch": 8, "words": 2}, {"trace_calls": 1}),
}


def tiny(cell: spec.Cell) -> spec.Cell:
    """``cell`` with its traffic and lane pool shrunk for the CPU."""
    traffic, settings = TINY[cell.settings["driver"]]
    return dataclasses.replace(cell, traffic={**cell.traffic, **traffic},
                               settings={**cell.settings, **settings})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=2 ** 33 + 5)
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    import jax
    bench = json.loads(spec.BENCHMARK.read_text())
    names = args.workload or [w["name"] for w in bench["workloads"]]
    for name in names:
        cell = tiny(spec.load_cell(name, bench))
        for trace in (False, True):
            line = run.run_cell(cell, args.seed, args.seconds, trace,
                                jax.devices()[:1], {"interpret": True},
                                peaks_kind="TPU v5 lite")
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
