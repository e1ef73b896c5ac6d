"""Benchmark regression gate: compare a fresh BENCH_quick.json against the
committed baseline and fail CI when the numbers that must not regress do.

    python tools/bench_gate.py BENCH_quick.json benchmarks/baseline_quick.json
    python tools/bench_gate.py BENCH_quick.json benchmarks/baseline_quick.json \
        --write-baseline     # intentional change: adopt current as baseline

Policy (what fails vs what only reports):

  * FAIL — a row present in the baseline is missing from the current run
    (benchmark coverage regressed), or any ``*_FAILED`` row is present.
  * FAIL — a skipped-work fraction dropped more than ``--abs-tol`` below
    its baseline: the event-gating keys (``skipped_tiles``,
    ``fc_skipped_tiles``, ``conv_skipped_tiles``, ``tile``, ``block<G>``,
    ``events``, ``skipped_rows``, ``pallas_events``) are the executed
    sparsity win this repo exists to keep;
    on the jax pin that generated the baseline they are deterministic
    (seeded rasters, seeded training), so a drop means gating got
    coarser or stopped firing. Gains are fine. Seeded weights and rows
    derived from float training are NOT bit-stable across jax versions:
    regenerate the baseline when CI's jax pin moves.
  * FAIL — an instruction count (``instr``) drifted more than
    ``--rel-tol-instr`` in either direction, or a calibrated energy-model
    number (``energy``, ``E/op``, ``E/inference``, ``TOPS/W``,
    ``GOPS/mm2``, ``ours/theirs``, ``err``) drifted more than
    ``--rel-tol``: both are exact functions of the executed program and
    the paper's calibration, not of machine load.
  * FAIL — a traced cost-model number (``macs``, ``hbm_bytes``,
    the benchmarks/analysis_check.py rows) changed AT ALL: these are
    counted off the compiled jaxpr, so any drift is a real change to the
    dispatched computation — zero tolerance, no knob.
  * REPORT-ONLY — wall-clock (``us_per_call``, ``dense_us``, ``speedup``):
    CI CPUs are noisy and interpret-mode timing is not the target signal.
    Workload statistics (sparsities, frequencies, frame counts) and rows
    new in the current run are also report-only; regenerating the baseline
    adopts them.

Values parse from ``key=value`` tokens in the derived column; units
(``pJ``, ``nJ``, ``%``, ``x``, ``MHz``...) are stripped, ``a/b``
slash-lists compare elementwise.
"""
from __future__ import annotations

import argparse
import json
import re
import shutil
import sys

# keys whose drop below baseline - abs_tol fails the gate (prefix match for
# block2/block4/block8). skipped_rows is the serving engines' pooled
# per-slot row-skip fraction (benchmarks/serve_snn.py) — deterministic on
# the pin for the same reason the gating rows are (seeded rasters).
# pallas_events is the device event-list kernel's EXECUTED skip fraction
# (its own per-row counters, sparsity_gating granularity rows + the
# serve_snn device ledger) — a drop means the compaction path stopped
# skipping work it used to skip.
SKIP_FRACTION_KEYS = ("skipped_tiles", "fc_skipped_tiles",
                      "conv_skipped_tiles", "tile", "events",
                      "skipped_rows", "pallas_events")
SKIP_FRACTION_PREFIXES = ("block",)
# keys gated two-sided at rel_tol_instr / rel_tol. The measured_* /
# *_vs_dense spellings are the fig11 row keys — exact names, because
# compare() matches keys exactly
INSTR_KEYS = ("instr",)
CALIBRATED_KEYS = ("energy", "E/op", "E/inference", "EDP", "measured_EDP",
                   "TOPS/W", "GOPS/mm2", "ours/theirs", "err", "reduction",
                   "measured_reduction", "reduction_vs_dense")
# keys gated EXACTLY (zero tolerance): the trace cost model counts these
# off the compiled jaxpr (analysis.check_trace), so any change is a real
# change to the dispatched computation, never noise
TRACE_KEYS = ("macs", "hbm_bytes")

_NUM = re.compile(r"^[-+]?\d+(\.\d*)?([eE][-+]?\d+)?")


def _parse_value(tok: str):
    """'1.80pJ' -> 1.80, '0.040/0.020' -> [0.04, 0.02], else None."""
    if "/" in tok and not tok.replace(".", "").replace("/", "").isalpha():
        parts = [_parse_value(p) for p in tok.split("/")]
        if all(isinstance(p, float) for p in parts):
            return parts
    m = _NUM.match(tok)
    if m and m.group(0) not in ("", "-", "+"):
        rest = tok[m.end():]
        if rest == "" or rest.isalpha() or rest in ("%",):
            return float(m.group(0))
    return None


def parse_row(derived: str) -> dict:
    """key=value tokens of one derived column -> {key: float | [float]}."""
    out = {}
    for tok in derived.split():
        if "=" not in tok:
            continue
        key, _, val = tok.partition("=")
        parsed = _parse_value(val)
        if parsed is not None:
            out[key] = parsed
    return out


def _is_skip_key(key: str) -> bool:
    return key in SKIP_FRACTION_KEYS or any(
        key.startswith(p) and key[len(p):].isdigit()
        for p in SKIP_FRACTION_PREFIXES)


def _pairs(cur, base):
    """Element pairs of two parsed values; None when their shapes disagree
    (a slash-list losing elements is itself a regression, not a pass)."""
    cur = cur if isinstance(cur, list) else [cur]
    base = base if isinstance(base, list) else [base]
    if len(cur) != len(base):
        return None
    return zip(cur, base)


def compare(current: dict, baseline: dict, *, abs_tol: float = 0.05,
            rel_tol_instr: float = 0.02, rel_tol: float = 0.05
            ) -> tuple[list, list]:
    """Gate the current payload against the baseline. Returns
    (failures, notes) — both lists of human-readable strings; a non-empty
    failures list means the gate rejects the run."""
    failures, notes = [], []
    cur_rows = {r["name"]: r for r in current["rows"]}
    base_rows = {r["name"]: r for r in baseline["rows"]}
    for name in cur_rows:
        if name.endswith("_FAILED"):
            failures.append(f"{name}: benchmark crashed: "
                            f"{cur_rows[name]['derived']}")
    for name, brow in base_rows.items():
        if name.endswith("_FAILED"):
            continue                   # a broken baseline row gates nothing
        if name not in cur_rows:
            failures.append(f"{name}: row missing from current run "
                            "(benchmark coverage regressed)")
            continue
        cvals = parse_row(cur_rows[name]["derived"])
        bvals = parse_row(brow["derived"])
        for key, bval in bvals.items():
            if key not in cvals:
                failures.append(f"{name}: key {key!r} missing from current "
                                "derived column")
                continue
            cval = cvals[key]
            pairs = _pairs(cval, bval)
            if pairs is None:
                failures.append(
                    f"{name}: {key} value count changed vs baseline "
                    f"({cval} vs {bval}) — a benchmark stopped reporting "
                    "part of its sweep")
                continue
            for ci, bi in pairs:
                if _is_skip_key(key):
                    if ci < bi - abs_tol:
                        failures.append(
                            f"{name}: skipped-work fraction {key}={ci:.3f} "
                            f"dropped below baseline {bi:.3f} - {abs_tol}")
                    elif ci > bi + abs_tol:
                        notes.append(f"{name}: {key} improved "
                                     f"{bi:.3f} -> {ci:.3f}")
                elif key in TRACE_KEYS:
                    if ci != bi:
                        failures.append(
                            f"{name}: traced {key}={ci:g} != baseline "
                            f"{bi:g} — the compiled dispatch changed "
                            "(zero-tolerance key)")
                elif key in INSTR_KEYS or key in CALIBRATED_KEYS:
                    tol = rel_tol_instr if key in INSTR_KEYS else rel_tol
                    # true relative drift — no absolute floor, EDP rows
                    # live at 1e-20 J*s and would swamp any epsilon
                    drift = (abs(ci - bi) / abs(bi) if bi != 0
                             else float(ci != 0))
                    if drift > tol:
                        failures.append(
                            f"{name}: {key}={ci:g} drifted from baseline "
                            f"{bi:g} (> {tol:.0%} rel)")
                # anything else (wall-clock, workload stats): report-only
    for name in cur_rows:
        if name not in base_rows and not name.endswith("_FAILED"):
            notes.append(f"{name}: new row (not in baseline; regenerate "
                         "the baseline to gate it)")
    return failures, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("current", help="fresh BENCH_quick.json")
    ap.add_argument("baseline", help="committed benchmarks/baseline_quick.json")
    ap.add_argument("--abs-tol", type=float, default=0.05,
                    help="allowed drop of a skipped-work fraction")
    ap.add_argument("--rel-tol-instr", type=float, default=0.02,
                    help="allowed relative drift of instruction counts")
    ap.add_argument("--rel-tol", type=float, default=0.05,
                    help="allowed relative drift of calibrated energy rows")
    ap.add_argument("--write-baseline", action="store_true",
                    help="adopt the current run as the new baseline")
    args = ap.parse_args(argv)

    with open(args.current) as f:
        current = json.load(f)
    if args.write_baseline:
        # a payload with crashed benchmarks must never become the baseline:
        # compare() skips *_FAILED baseline rows, so adopting one would
        # silently and permanently drop those rows from gate coverage
        broken = [r["name"] for r in current["rows"]
                  if r["name"].endswith("_FAILED")]
        if current.get("failures", 0) or broken:
            print(f"bench_gate: refusing --write-baseline: current run has "
                  f"failures={current.get('failures', 0)} "
                  f"crashed rows={broken}")
            return 1
        shutil.copyfile(args.current, args.baseline)
        print(f"bench_gate: wrote {args.baseline} from {args.current}")
        return 0
    with open(args.baseline) as f:
        baseline = json.load(f)
    failures, notes = compare(current, baseline, abs_tol=args.abs_tol,
                              rel_tol_instr=args.rel_tol_instr,
                              rel_tol=args.rel_tol)
    for n in notes:
        print(f"bench_gate note: {n}")
    for f_ in failures:
        print(f"bench_gate FAIL: {f_}")
    if failures:
        print(f"bench_gate: {len(failures)} regression(s) vs {args.baseline}")
        return 1
    print(f"bench_gate: OK ({len(baseline['rows'])} baseline rows held)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
