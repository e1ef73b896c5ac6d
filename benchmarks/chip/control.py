#!/usr/bin/env python3
"""The control of a cell's comparison: the reference put in the
program's place, computed one weight bit below the configuration, read
through the same checks and limits as a run:

    python3 benchmarks/chip/control.py --workload imdb-batch --seeds 1,2,3 \
        --seconds 51

Batch cells: the control scores every distinct batch of the mix. Serve
cells: the control scores every request a run of ``--seconds`` would
see, due from the schedule's start to the window's close, at the cell's
rate. Prints one JSON line per seed with each check's reading; the
control has to fail at least one. The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from harness import reference, spec, traffic, weights  # noqa: E402


def control_checks(cell: spec.Cell, seed: int, seconds: float) -> list:
    """The checks of ``cell`` with the control in the program's place."""
    _, truth = weights.make(cell.config, seed)
    w_bits = cell.config["w_bits"]
    low = reference.lower_precision(truth, w_bits - 1, w_bits)
    v_bits, limits = cell.config["v_bits"], cell.settings["limits"]
    scale = float(truth.layers[-1].scale)
    low_scale = float(low.layers[-1].scale)
    if cell.settings["driver"] == "batch":
        batch = spec.load_module("drivers", "batch")
        xs_list = traffic.generator(cell.traffic).batches(
            cell.traffic, cell.config, seed)
        want = [reference.run_batch(truth, xs, v_bits) for xs in xs_list]
        got = [reference.run_batch(low, xs, v_bits) for xs in xs_list]
        kept = list(enumerate(got))
        logits = [(i, np.asarray(g[-1], np.float32) * np.float32(low_scale))
                  for i, g in kept]
        return batch.compare(kept, logits, want, scale, limits)
    serve = spec.load_module("drivers", "serve")
    st = cell.settings
    sched = traffic.generator(cell.traffic).schedule(
        cell.traffic, cell.config, st["rate_rps"], st["prefill_s"] + seconds,
        seed)
    pool = jax.numpy.asarray(sched.pool)
    want = reference.run_streams(truth, pool, sched.offsets, sched.frames,
                                 v_bits)[-1]
    got = np.asarray(reference.run_streams(
        low, pool, sched.offsets, sched.frames, v_bits)[-1])
    return serve.compare(got, got.astype(np.float32) * np.float32(low_scale),
                         np.asarray(want), scale, 0, limits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        found = control_checks(cell, seed, args.seconds)
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "w_bits": cell.config["w_bits"] - 1,
            "correct": all(c.ok for c in found),
            "checks": {c.name: {"value": c.value, "limit": c.limit}
                       for c in found},
            "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
