"""Streaming SNN serving: throughput AND latency of the continuous-batching
engine over persistent V_MEM slots.

Three row families:

  * ``serve_snn_s*`` — the classic tick-by-tick drain (K=1, one page)
    swept over offered input sparsity, plus the device event-list backend
    serving the same workload (its kernel-counter ledger rides along as
    the gated ``pallas_events`` fraction);
  * ``serve_snn_mega_*`` — the same workload served at scale: K-frame
    megasteps (one device dispatch advances every lane K ticks) over a
    paged V-slot pool with double-buffered frame upload. Reports the
    sustained frames/s speedup over the committed ``serve_snn_s85``
    baseline figure (report-only: wall-clock) — the engine outputs are
    bit-identical to the K=1 drain, so ``skipped_rows``/``instr`` are
    gated against the same values;
  * ``serve_snn_poisson_*`` — offered-load serving: seeded Poisson
    arrivals on the engine's frame clock, reporting p50/p99 per-request
    latency (queueing + service, in frame ticks scaled by the measured
    tick rate). Lanes never interact, so the gated ``skipped_rows`` /
    ``instr`` values are schedule-independent.

Gated keys (tools/bench_gate.py): ``skipped_rows`` (pooled per-slot
skipped-work fraction; silent (frame, input-row) pairs over all gate
sites), ``pallas_events`` (device ledger fraction), ``instr`` (pooled
executed instruction cycles, two-sided). Deterministic: request rasters
and arrival schedules are seeded and the encoder reproduces the rasters
exactly (currents scaled by the encoder threshold). Wall-clock
(``frames_per_s``/``words_per_s``/``p50_ms``/``p99_ms``/``speedup``) is
report-only — CI CPUs are noisy; the TPU target is where the fused
kernel's latency matters.
"""
from __future__ import annotations

import json
import pathlib
import re
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.configs.impulse_snn import get_snn_config
from repro.core import pipeline, snn
from repro.launch.serve_snn import make_requests
from repro.serve import SNNServeEngine

SWEEP = (0.5, 0.85)


def _serve_row(program, cfg, sparsity: float, *, n_requests: int,
               n_words: int, slots: int, seed: int = 0,
               backend: str = "int_ref", step_kw: dict = None,
               key: str = None, pages: int = 1, megastep: int = 1,
               double_buffer: bool = False, poisson_gap: float = None,
               latency: bool = False, speedup_vs: float = None,
               metrics: dict = None) -> str:
    def build():
        return SNNServeEngine(program, batch_slots=slots, backend=backend,
                              step_kw=({"use_sparse": True}
                                       if step_kw is None else step_kw),
                              pages=pages, megastep=megastep,
                              double_buffer=double_buffer)
    # warmup drain on a throwaway engine: every dispatch shape this config
    # uses gets compiled outside the timed region (jit caches are global),
    # so rows measure steady-state serving, not first-call compilation
    warm = build()
    for req in make_requests(program, 1, 1, cfg.timesteps, sparsity, seed):
        warm.submit(req)
    warm.run_until_drained(max_ticks=100_000)
    eng = build()
    for req in make_requests(program, n_requests, n_words, cfg.timesteps,
                             sparsity, seed, poisson_gap=poisson_gap):
        eng.submit(req)
    t0 = time.perf_counter()
    done = eng.run_until_drained(max_ticks=100_000)
    dt = time.perf_counter() - t0
    frames = sum(r.ticks for r in done)
    fps = frames / dt
    rep = eng.aggregate_report()
    counts = rep.instruction_counts()
    tag = f"{int(round(sparsity * 100)):02d}"
    extra = ""
    if megastep > 1 or pages > 1:
        extra += f"K={megastep} pages={pages} "
    if latency:
        # per-request latency on the frame clock (arrival -> finish tick),
        # scaled by the measured wall time per clock tick — report-only
        lats = np.array([r.latency_ticks for r in done
                         if r.latency_ticks is not None], np.float64)
        ms_per_tick = dt / max(eng.clock, 1) * 1e3
        extra += (f"p50_ms={np.percentile(lats, 50) * ms_per_tick:.2f} "
                  f"p99_ms={np.percentile(lats, 99) * ms_per_tick:.2f} ")
    if speedup_vs:
        extra += f"speedup={fps / speedup_vs:.1f}x "
    if eng.device_row_events is not None:
        # the kernel's own executed-skip ledger — closes against the
        # per-slot raster accounting at any occupancy now that vacated
        # lanes are re-seeded with zero state — gated like the
        # granularity rows
        extra += f"pallas_events={eng.device_skipped_row_fraction():.3f} "
    row = emit(
        key or f"serve_snn_s{tag}", dt / max(eng.ticks, 1) * 1e6,
        f"frames_per_s={fps:.1f} "
        f"words_per_s={frames / cfg.timesteps / dt:.1f} "
        f"skipped_rows={rep.skipped_row_fraction:.3f} {extra}"
        f"instr={counts.total} offered={sparsity:.2f} reqs={len(done)}")
    if metrics is not None:
        metrics[key or f"serve_snn_s{tag}"] = fps
    return row


def _committed_fps(name: str) -> float:
    """frames_per_s of a row in the committed quick baseline, if present —
    the megastep speedup is quoted against the committed ``serve_snn_s85``
    figure (the acceptance bar), not the same-run K=1 row, which itself
    benefits from the shared jitted dispatch."""
    path = pathlib.Path(__file__).parent / "baseline_quick.json"
    try:
        rows = json.loads(path.read_text())["rows"]
    except (OSError, ValueError, KeyError):
        return None
    for r in rows:
        if r["name"] == name:
            m = re.search(r"frames_per_s=([\d.]+)", r.get("derived", ""))
            if m:
                return float(m.group(1))
    return None


def run(quick: bool = False):
    cfg = get_snn_config("impulse-imdb")
    params = snn.init_fc_snn(jax.random.PRNGKey(0), cfg)
    program = pipeline.compile_network(cfg, params, domain="int")
    n_requests, n_words, slots = (4, 2, 2) if quick else (12, 6, 4)
    metrics = {}
    rows = [_serve_row(program, cfg, s, n_requests=n_requests,
                       n_words=n_words, slots=slots, metrics=metrics)
            for s in SWEEP]
    # the device event-list backend serving the same 0.85 workload: the
    # engine's kernel-counter ledger rides along as the gated
    # ``pallas_events`` fraction (interpret mode; wall-clock is TPU-only)
    rows.append(_serve_row(
        program, cfg, 0.85, n_requests=n_requests, n_words=n_words,
        slots=slots, backend="pallas_events",
        step_kw={"interpret": True, "block_b": slots},
        key="serve_snn_events_s85"))
    # megastep serving at scale: same workload, K=8 frames per dispatch
    # over a 2-page pool with double-buffered upload — bit-identical
    # outputs, so skipped_rows/instr gate against the K=1 values; the
    # frames/s speedup over the committed serve_snn_s85 figure is the
    # tentpole number
    rows.append(_serve_row(
        program, cfg, 0.85, n_requests=n_requests, n_words=n_words,
        slots=slots, pages=2, megastep=8, double_buffer=True, latency=True,
        speedup_vs=_committed_fps("serve_snn_s85") or
        metrics["serve_snn_s85"], key="serve_snn_mega_s85"))
    rows.append(_serve_row(
        program, cfg, 0.85, n_requests=n_requests, n_words=n_words,
        slots=slots, backend="pallas_events", pages=2, megastep=4,
        step_kw={"interpret": True, "block_b": slots},
        key="serve_snn_mega_events_s85"))
    # offered-load latency: seeded Poisson arrivals at roughly half the
    # engine's lane capacity — p50/p99 are the serving latency numbers
    gap = float(cfg.timesteps * n_words) / (2 * slots)
    rows.append(_serve_row(
        program, cfg, 0.85, n_requests=n_requests, n_words=n_words,
        slots=slots, pages=2, megastep=8, double_buffer=True,
        poisson_gap=gap, latency=True, key="serve_snn_poisson_s85"))
    return rows


if __name__ == "__main__":
    run()
