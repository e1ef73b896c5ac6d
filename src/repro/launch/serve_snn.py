"""Streaming SNN serving launcher: word streams through the V_MEM-slot
continuous-batching engine (`serve.SNNServeEngine`).

    PYTHONPATH=src python -m repro.launch.serve_snn --requests 8 \
        --slots 4 --sparsity 0.85 --backend int_ref

Each request is a synthetic word stream for the IMDB-geometry network:
a seeded spike raster at the offered sparsity, scaled by the encoder
threshold so the off-macro encoder reproduces it exactly (the same trick
benchmarks/serve_snn.py uses — offered sparsity is then exact, not
approximate). The engine streams all requests through fixed decode slots
whose per-slot state is the membrane-potential tree, and reports
throughput (frames/s and words/s), the skipped-work fraction from the
pooled per-slot event accounting, and the measured-EDP figure it implies.

``--stop-threshold`` enables the readout-confidence early exit;
``--megastep K`` advances every lane K frames per device dispatch,
``--pages N`` grows the V-slot pool to N pages of ``--slots`` lanes,
``--double-buffer`` stages the next frame block while one computes, and
``--poisson-gap G`` draws seeded Poisson arrivals (mean gap G frame
ticks) for the admission-control path; ``--quick`` shrinks everything
for the CI serving smoke step.

``--mesh DATA,MODEL`` serves over a `jax.sharding.Mesh`: lanes partition
over the data axis, row-tiled macro fan-in over the model axis, and the
outputs stay bit-identical to the single-device drain (docs/serving.md
§Mesh). The devices must exist before jax initialises — on CPU launch
with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

The Pallas backends compile for the device JAX runs on; ``--interpret``
runs them in the Pallas interpreter instead, to rehearse on a CPU.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.impulse_snn import get_snn_config
from repro.core import energy, pipeline, snn
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import SNNRequest, SNNServeEngine


def encoder_exact_frames(program, raster: np.ndarray) -> np.ndarray:
    """Input currents that make the float encoder emit ``raster`` exactly:
    x = threshold * raster drives V to exactly threshold on event ticks
    (fires, resets/subtracts back to rest) and leaves it unchanged on
    silent ones — so the offered raster IS the encoder output raster."""
    th = float(np.asarray(program.layers[0].threshold))
    return raster.astype(np.float32) * th


def make_requests(program, n_requests: int, n_words: int, timesteps: int,
                  sparsity: float, seed: int, stop_threshold=None,
                  poisson_gap=None) -> list:
    """Seeded synthetic word-stream requests. ``poisson_gap`` (mean
    inter-arrival gap in frame ticks) stamps each request with a Poisson
    ``arrival_tick`` — seeded exponential gaps, sorted by construction —
    so the engine's admission control sees an offered-load process instead
    of a batch arrival."""
    rng = np.random.default_rng(seed)
    d = program.layers[0].n_in
    reqs = []
    arrival = 0.0
    for rid in range(n_requests):
        t_total = n_words * timesteps
        raster = (rng.random((t_total, d)) > sparsity).astype(np.int8)
        req = SNNRequest(
            rid=rid, frames=encoder_exact_frames(program, raster),
            stop_threshold=stop_threshold)
        if poisson_gap:
            arrival += rng.exponential(poisson_gap)
            req.arrival_tick = int(arrival)
        reqs.append(req)
    return reqs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="impulse-imdb")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--words", type=int, default=6)
    ap.add_argument("--sparsity", type=float, default=0.85)
    ap.add_argument("--backend", default="int_ref",
                    choices=list(pipeline.STREAM_BACKENDS))
    ap.add_argument("--stop-threshold", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--megastep", type=int, default=1,
                    help="frames advanced per device dispatch (K)")
    ap.add_argument("--pages", type=int, default=1,
                    help="V-slot pool pages of --slots lanes each")
    ap.add_argument("--double-buffer", action="store_true",
                    help="stage the next frame block while this one computes")
    ap.add_argument("--poisson-gap", type=float, default=None,
                    help="mean inter-arrival gap in frame ticks (Poisson "
                         "admission; default: all requests arrive at once)")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="serve on a (data, model) device mesh, e.g. 2,2 "
                         "(needs DATA*MODEL devices; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count first)")
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes (CI serving smoke)")
    ap.add_argument("--interpret", action="store_true",
                    help="run Pallas backends in the interpreter (CPU "
                         "rehearsal)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh
        n_data, n_model = (int(v) for v in args.mesh.split(","))
        need = n_data * n_model
        if len(jax.devices()) < need:
            raise SystemExit(
                f"--mesh {args.mesh} needs {need} devices but jax sees "
                f"{len(jax.devices())}; on CPU relaunch with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={need}")
        mesh = make_mesh((n_data, n_model), ("data", "model"))

    cfg = get_snn_config(args.arch)
    if args.quick:
        args.requests, args.words, args.slots = 3, 2, 2
    params = snn.init_fc_snn(jax.random.PRNGKey(args.seed), cfg)
    program = pipeline.compile_network(cfg, params, domain="int")
    eng = SNNServeEngine(program, batch_slots=args.slots,
                         backend=args.backend,
                         step_kw=({"interpret": True}
                                  if args.interpret
                                  and args.backend.startswith("pallas")
                                  else {}),
                         pages=args.pages, megastep=args.megastep,
                         double_buffer=args.double_buffer, mesh=mesh)
    for req in make_requests(program, args.requests, args.words,
                             cfg.timesteps, args.sparsity, args.seed,
                             args.stop_threshold,
                             poisson_gap=args.poisson_gap):
        eng.submit(req)
    t0 = time.perf_counter()
    done = eng.run_until_drained()
    dt = time.perf_counter() - t0
    frames = sum(r.ticks for r in done)
    rep = eng.aggregate_report()
    dev = jax.devices()[0]
    print(f"served {len(done)} requests, {frames} frames in {dt:.2f}s "
          f"({frames / dt:.1f} frames/s, "
          f"{frames / cfg.timesteps / dt:.1f} words/s on {dev.platform} "
          f"{dev.device_kind}"
          f"{' (interpret)' if args.interpret else ''}; "
          f"K={args.megastep}, {args.pages} page(s) x {args.slots} lanes"
          + (f", mesh data={args.mesh.split(',')[0]} "
             f"model={args.mesh.split(',')[1]}" if args.mesh else "") + ")")
    lats = [r.latency_ticks for r in done if r.latency_ticks is not None]
    if lats:
        print(f"latency (frame ticks, arrival->finish): "
              f"p50={np.percentile(lats, 50):.0f} "
              f"p99={np.percentile(lats, 99):.0f} "
              f"over clock {eng.clock}")
    print(f"offered sparsity {args.sparsity:.2f} -> skipped-row fraction "
          f"{rep.skipped_row_fraction:.3f}, instr={rep.instruction_counts().total}, "
          f"measured EDP {energy.measured_edp(rep.instruction_counts()):.3e} J*s")
    for r in sorted(done, key=lambda r: r.rid)[:4]:
        print(f"  req {r.rid}: {r.ticks} ticks, logits {np.round(r.logits, 3)}")
    return done


if __name__ == "__main__":
    main()
