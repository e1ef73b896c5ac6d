#!/usr/bin/env python3
"""The controls of a recurrent batch cell's comparison (``ssc-batch``):
the plain reference put in the program's place with a known fault, read
through the same checks and limits as a run:

    python3 benchmarks/chip/control_ssc.py --workload ssc-batch \
        --seeds 1,2,3

* ``w5``: the same network one weight bit below the configuration;
* ``no_w_rec``: the same network with its W_rec rows dropped.

Both score every distinct batch of the mix and have to fail at least one
check. Prints one JSON line per (seed, control), then one with the
reference's hidden-layer spike densities on the mix's first batch; the
benchmark's own runs never run it.
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

from harness import recurrent, reference, spec, traffic  # noqa: E402


def controls(truth, w_bits: int) -> dict:
    return {"w5": reference.lower_precision(truth, w_bits - 1, w_bits),
            "no_w_rec": recurrent.drop_w_rec(truth)}


def control_checks(cell: spec.Cell, seed: int) -> dict:
    """Each control's checks of ``cell``, by control name."""
    _, truth = recurrent.make(cell.config, seed)
    v_bits, limits = cell.config["v_bits"], cell.settings["limits"]
    batch = spec.load_module("drivers", "batch")
    xs_list = traffic.generator(cell.traffic).batches(
        cell.traffic, cell.config, seed)
    want = [recurrent.run_batch(truth, xs, v_bits) for xs in xs_list]
    found = {}
    for name, fault in controls(truth, cell.config["w_bits"]).items():
        got = [recurrent.run_batch(fault, xs, v_bits) for xs in xs_list]
        kept = list(enumerate(got))
        scale = np.float32(fault.layers[-1].scale)
        logits = [(i, np.asarray(g[-1], np.float32) * scale)
                  for i, g in kept]
        found[name] = batch.compare(kept, logits, want,
                                    float(truth.layers[-1].scale), limits)
    return found


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="ssc-batch")
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        for name, found in control_checks(cell, seed).items():
            print(json.dumps({
                "workload": cell.name, "seed": seed, "control": name,
                "correct": all(c.ok for c in found),
                "checks": {c.name: {"value": c.value, "limit": c.limit}
                           for c in found},
                "device": jax.devices()[0].device_kind}), flush=True)
        _, truth = recurrent.make(cell.config, seed)
        xs = traffic.generator(cell.traffic).batches(
            cell.traffic, cell.config, seed)[0]
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "hidden_densities": recurrent.hidden_densities(
                truth, xs, cell.config["v_bits"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
