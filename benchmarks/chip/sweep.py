#!/usr/bin/env python3
"""Sweep a serve cell's offered rate on the chip, to find the highest
rate it sustains:

    python3 benchmarks/chip/sweep.py --workload imdb-serve \
        --rates 20,40,60 --seconds 15 [--prefill 10] [--seed 3]

One process, one program; a fresh engine and schedule per rate, through
the same phases as a run (prefill, window, drain). A rate is sustained
when every request due in the window finishes within the drain limit and
the queue at the window's close is no longer than at its open. Prints
one JSON line per rate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import numpy as np  # noqa: E402

from harness import device, spec, traffic, weights  # noqa: E402
from harness.outcome import (CompileCounter, GcWatch, StallWatch,  # noqa: E402
                             settle)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="imdb-serve")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--prefill", type=float, default=None)
    ap.add_argument("--seed", type=int, default=3)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devices = device.require_chips(cell.chips)
    from repro.core import pipeline
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    serve = spec.load_module("drivers", "serve")
    st = cell.settings
    prefill = st["prefill_s"] if args.prefill is None else args.prefill
    params, _ = weights.make(cell.config, args.seed)
    program = pipeline.compile_network(spec.model_config(cell.config),
                                       params, domain="int")
    counter = CompileCounter()
    for rate in (float(r) for r in args.rates.split(",")):
        sched = traffic.generator(cell.traffic).schedule(
            cell.traffic, cell.config, rate,
            prefill + args.seconds + st["drain_limit_s"], args.seed)
        eng = serve.make_engine(program, st, {})
        serve.warm_up(eng, sched.pool)
        settle()
        out = serve.phases(eng, sched, prefill_s=prefill,
                           seconds=args.seconds, trace_s=0.0,
                           drain_limit_s=st["drain_limit_s"],
                           counter=counter, gcw=GcWatch(),
                           stalls=StallWatch())
        p, idx = out["pump"], out["in_window"]
        lat = (p.finish_s[idx] - sched.due_s[idx]) * 1e3
        done = lat[~np.isnan(lat)]
        steps = out["steps"]
        q0, q1 = out["queue"]
        unfinished = int(np.isnan(lat).sum())
        print(json.dumps({
            "rate_rps": rate,
            "offered_frames_per_s": float(rate * sched.frames.mean()),
            "frames_per_s": out["frames"] / (out["w1"] - out["w0"]),
            "p50_ms": float(np.percentile(done, 50)) if len(done) else None,
            "p95_ms": float(np.percentile(done, 95)) if len(done) else None,
            "queue_open_close": [q0, q1], "unfinished": unfinished,
            "due_in_window": len(idx),
            "tick_ms": float((steps[:, 1] - steps[:, 0]).mean() * 1e3),
            "mean_lanes_busy": float(out["frames"] / len(steps)),
            "sustained": unfinished == 0 and q1 <= q0,
            "device": device.describe(devices)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
