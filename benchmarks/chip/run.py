#!/usr/bin/env python3
"""Run one benchmark cell once on the chip it is started on.

    python3 benchmarks/chip/run.py --workload imdb-serve --seed 7 \
        --seconds 20 --trace 0

The cell, its configuration, traffic mix, settings, driver and metric
readers are found by the names in ``BENCHMARK.json``. With ``--trace 0``
the result carries the cell's end-to-end metrics; with ``--trace 1`` its
per-layer metrics, read from a profiler trace of a short sub-window and
from the driver's counters. Every run compares what the timed path
produced with the plain reference and prints each compared number beside
its limit, last on stderr and last in the result line.

Without an accelerator, or with fewer chips than the cell asks for, it
exits with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from harness import device, spec  # noqa: E402
from harness.outcome import Outcome, Readings  # noqa: E402

EXIT_NO_CHIP = 3


@dataclass
class RunArgs:
    """What a driver gets: the cell, the run's arguments, and how to
    reach the devices."""
    cell: spec.Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_start: float
    kernel_kw: dict = field(default_factory=dict)

    def memory_peak(self) -> int:
        return device.memory_peak(self.devices)


def per_layer(cell: spec.Cell, out: Outcome, kind: str) -> dict:
    """Each per-layer metric of the cell that its reader finds."""
    readings = Readings(counters=out.counters, trace=out.reduced,
                        config=cell.config, peaks=device.peaks(kind))
    found = {}
    for m in cell.per_layer:
        value = spec.load_module("metrics", m["name"]).read(readings)
        if value is not None:
            found[m["name"]] = {"value": value, "unit": m["unit"]}
    return found


def result(cell: spec.Cell, out: Outcome, trace: bool, dev: dict,
           peaks_kind: str | None = None) -> dict:
    if trace:
        metrics = per_layer(cell, out, peaks_kind or dev["kind"])
    else:
        metrics = {m["name"]: {"value": out.end_to_end[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    correct = all(c.ok for c in out.checks) and all(
        math.isfinite(v["value"]) for v in metrics.values())
    line = {"correct": correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics,
            "device": dict(dev, memory_peak_bytes=out.memory_peak_bytes)}
    if trace and out.reduced is not None:
        line["device"].update(busy_s=out.reduced.busy_s,
                              window_s=out.reduced.window_s)
        line["breakdown"] = {"device_ops": out.reduced.top_ops(),
                             "idle_gaps": out.reduced.idle_gaps()}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devices: list, kernel_kw: dict | None = None,
             t_start: float = T_START, peaks_kind: str | None = None) -> dict:
    """Drive one cell and build its result line. ``kernel_kw`` and
    ``peaks_kind`` are for rehearsals off the chip: backend arguments
    (interpret mode) and the peaks table row to read."""
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    driver = spec.load_module("drivers", cell.settings["driver"])
    out = driver.run(RunArgs(cell=cell, seed=seed, seconds=seconds,
                             trace=trace, devices=devices, t_start=t_start,
                             kernel_kw=dict(kernel_kw or {})))
    for note in out.notes:
        print(note, file=sys.stderr)
    line = result(cell, out, trace, device.describe(devices), peaks_kind)
    for c in out.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        devices = device.require_chips(cell.chips)
    except device.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
