# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper figure/table plus the roofline
report derived from the dry-run artifacts.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json PATH]

``--quick`` runs the fast smoke subset (analytic tables + a reduced
sparsity-gating sweep) — the per-PR CI perf-trajectory probe. ``--json``
additionally writes the emitted rows as a JSON artifact (default
BENCH_quick.json / BENCH_full.json when the flag is given bare).
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys
import time


def _run_mod(mod, quick: bool):
    if quick and "quick" in inspect.signature(mod.run).parameters:
        return mod.run(quick=True)
    return mod.run()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fast smoke subset (CI perf trajectory)")
    ap.add_argument("--json", nargs="?", const="", default=None,
                    help="write rows to a JSON artifact (optional path)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (analysis_check, fig6_neuron_energy, fig9_accuracy,
                            fig9_efficiency, fig11_sparsity_edp,
                            pipeline_fusion, roofline, serve_snn,
                            sparsity_gating, table1_comparison)
    print("name,us_per_call,derived")
    t0 = time.time()
    if args.quick:
        mods = [("fig6", fig6_neuron_energy), ("table1", table1_comparison),
                ("fig9_eff", fig9_efficiency), ("gating", sparsity_gating),
                ("serve_snn", serve_snn), ("analysis", analysis_check)]
    else:
        mods = [("fig6", fig6_neuron_energy), ("fig9_eff", fig9_efficiency),
                ("fig9_acc", fig9_accuracy), ("fig11", fig11_sparsity_edp),
                ("gating", sparsity_gating), ("serve_snn", serve_snn),
                ("fusion", pipeline_fusion), ("table1", table1_comparison),
                ("roofline", roofline), ("analysis", analysis_check)]
    failures, rows = 0, []
    for name, mod in mods:
        try:
            rows += _run_mod(mod, args.quick) or []
        except Exception as e:  # noqa: BLE001
            failures += 1
            row = f"{name}_FAILED,0,{e!r}"
            rows.append(row)
            print(row)
    elapsed = time.time() - t0
    print(f"# total {elapsed:.0f}s, failures={failures}")
    if args.json is not None:
        path = args.json or ("BENCH_quick.json" if args.quick
                             else "BENCH_full.json")
        payload = {"mode": "quick" if args.quick else "full",
                   "elapsed_s": round(elapsed, 1), "failures": failures,
                   "rows": [dict(zip(("name", "us_per_call", "derived"),
                                     r.split(",", 2))) for r in rows]}
        with open(path, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {path}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
