#!/usr/bin/env python3
"""Bring-up smoke run of the IMPULSE serving path on a TPU.

    python chip_smoke.py              # one chip: batch and serving phases
    python chip_smoke.py --arch rsnn-ssc   # the same phases for one config
    python chip_smoke.py --chips 4    # four chips: the mesh serving phase only

Drives `compile_network` -> the integer Pallas backends -> `SNNServeEngine`
at the published widths of both configs in `configs/impulse_snn.py`, with
weights drawn from ``--seed``, and checks every result bit for bit against
the `int_ref` word-level reference run in the same process:

* batch: `run_network` on impulse-imdb (B=64) and impulse-mnist (B=16) in
  8-row batch tiles, on `pallas`, `pallas_sparse` (gate granularity 1 and
  8) and `pallas_events`; rasters, final V and logits must equal int_ref's.
* serving: 16 seeded word-stream requests at offered sparsity 0.85 with
  Poisson arrivals, drained by an impulse-imdb engine of 2 pages x 8 lanes
  at megastep K=8 on each Pallas backend; per-request logits must equal
  those of an int_ref engine's drain.
* ``--arch NAME`` runs the batch and serving phases for that config alone
  (rsnn-ssc: its two recurrent layers, 700 -> 400r -> 400r -> 35, over
  50-frame streams at input sparsity 0.95). Backends that do not execute
  recurrent layers must refuse it by name (``match=refused``); its
  serving drain is also checked against one batch `run_network` of every
  request on `int_ref`.
* mesh (``--chips 4``): the serving requests drained on (4,1) and (2,2)
  meshes — lanes over "data", macro row tiles over "model" with the
  integer-psum AccV2V — must equal the single-device drain.

Each phase prints one line with the seconds it took, compilation included:
smoke timing, not a benchmark. Any mismatch or error exits non-zero, as
does a run where JAX finds no TPU. The last line of stdout is a JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.analysis import ContractError  # noqa: E402
from repro.configs.impulse_snn import SNN_CONFIGS, get_snn_config  # noqa: E402
from repro.core import pipeline, snn  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve_snn import make_requests  # noqa: E402
from repro.serve import SNNServeEngine  # noqa: E402

#: dispatch arguments of every Pallas call: 8-row batch tiles, so the batch
#: grids hold several tiles
KERNEL_KW = {"block_b": 8}
BATCH_CASES = (("impulse-imdb", 64), ("impulse-mnist", 16))
PALLAS_CASES = (("pallas", {}),
                ("pallas_sparse", {"gate_granularity": 1}),
                ("pallas_sparse", {"gate_granularity": 8}),
                ("pallas_events", {}))
SERVE_BACKENDS = ("pallas", "pallas_sparse", "pallas_events")
MESH_SHAPES = ((4, 1), (2, 2))
N_WORDS = 6                 # words per stream (imdb) / batch presentation
N_REQUESTS = 16
LANES, PAGES, MEGASTEP = 8, 2, 8
SPARSITY = 0.85
#: (words per stream, sparsity) where a config differs from the above:
#: rsnn-ssc presents one frame per word, so its streams are 50 frames
STREAMS = {"rsnn-ssc": (50, 0.95)}
POISSON_GAP = 4.0           # mean inter-arrival gap, frame ticks


class Mismatch(Exception):
    """A device result differs from the reference it is checked against."""


def _check_equal(what: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise Mismatch(f"{what}: shape {got.shape} != {want.shape}")
    if not np.array_equal(got, want):
        raise Mismatch(f"{what}: {int(np.sum(got != want))} of {got.size} "
                       "elements differ")


def _report(phase: str, model: str, backend: str, t0: float,
            match: str = "True", **extra) -> None:
    """One result line; a mismatch raises before it is printed."""
    fields = "".join(f" {k}={v}" for k, v in extra.items())
    print(f"phase={phase} model={model} backend={backend}{fields} "
          f"match={match} smoke_s={time.perf_counter() - t0:.3f} "
          "(smoke timing incl. compile, not a benchmark)", flush=True)


def _program(arch: str, seed: int):
    cfg = get_snn_config(arch)
    init = snn.init_lenet_snn if cfg.conv_spec else snn.init_fc_snn
    params = init(jax.random.PRNGKey(seed), cfg)
    return cfg, pipeline.compile_network(cfg, params, domain="int")


def _batch_inputs(cfg, batch: int, seed: int) -> jax.Array:
    """Seeded input currents: static images for the conv stack, word
    streams for the fc stack."""
    rng = np.random.default_rng(seed)
    if cfg.conv_spec:
        x = rng.standard_normal((batch, *cfg.in_shape)).astype(np.float32)
        return pipeline.present_static(jnp.asarray(2.0 * x), cfg.timesteps)
    words = STREAMS.get(cfg.arch_id, (N_WORDS,))[0]
    x = rng.standard_normal((batch, words, cfg.layer_sizes[0]))
    return pipeline.present_words(jnp.asarray(x.astype(np.float32)),
                                  cfg.timesteps)


def batch_phase(arch: str, batch: int, seed: int) -> None:
    cfg, program = _program(arch, seed)
    xs = _batch_inputs(cfg, batch, seed)
    t0 = time.perf_counter()
    ref = pipeline.run_network(program, xs, "int_ref")
    jax.block_until_ready(ref.logits)
    _report("batch", arch, "int_ref", t0, match="reference", B=batch)
    for backend, kw in PALLAS_CASES:
        label = backend + "".join(f"[G={g}]" for g in kw.values())
        t0 = time.perf_counter()
        if _refused(program, backend, lambda: pipeline.run_network(
                program, xs, backend, **KERNEL_KW, **kw)):
            _report("batch", arch, label, t0, match="refused", B=batch)
            continue
        res = pipeline.run_network(program, xs, backend, **KERNEL_KW, **kw)
        where = f"batch {arch} {label}"
        if len(res.rasters) != len(ref.rasters):
            raise Mismatch(f"{where}: {len(res.rasters)} rasters, want "
                           f"{len(ref.rasters)}")
        for i, (a, b) in enumerate(zip(res.rasters, ref.rasters)):
            _check_equal(f"{where} raster {i}", a, b)
        for i, (a, b) in enumerate(zip(res.v_final[1:], ref.v_final[1:])):
            _check_equal(f"{where} V {i}", a, b)
        _check_equal(f"{where} logits", res.logits, ref.logits)
        _report("batch", arch, label, t0, B=batch,
                block_b=KERNEL_KW["block_b"])


def _refused(program, backend: str, call) -> bool:
    """True when ``program`` has recurrent layers and ``call`` refuses
    them by name, as a backend without recurrent execution must; a
    feed-forward program is never refused."""
    if not program.recurrent_layers:
        return False
    try:
        call()
    except ContractError as e:
        if "recurrent" in str(e):
            return True
        raise
    return False


def _requests(program, cfg, seed: int) -> list:
    words, sparsity = STREAMS.get(cfg.arch_id, (N_WORDS, SPARSITY))
    return make_requests(program, N_REQUESTS, words, cfg.timesteps,
                         sparsity, seed, poisson_gap=POISSON_GAP)


def _drain(program, cfg, backend: str, seed: int, mesh=None) -> dict:
    """Serve the seeded request set to completion; logits by request id."""
    eng = SNNServeEngine(program, batch_slots=LANES, backend=backend,
                         step_kw={} if backend == "int_ref" else KERNEL_KW,
                         pages=PAGES, megastep=MEGASTEP, mesh=mesh)
    for req in _requests(program, cfg, seed):
        eng.submit(req)
    done = eng.run_until_drained()
    if len(done) != N_REQUESTS:
        raise Mismatch(f"{backend} drain finished {len(done)} of "
                       f"{N_REQUESTS} requests")
    return {r.rid: r.logits for r in done}


def _check_drain(where: str, got: dict, want: dict) -> None:
    if sorted(got) != sorted(want):
        raise Mismatch(f"{where}: request ids {sorted(got)} != "
                       f"{sorted(want)}")
    for rid in want:
        _check_equal(f"{where} request {rid} logits", got[rid], want[rid])


def serve_phase(seed: int, arch: str = "impulse-imdb") -> None:
    cfg, program = _program(arch, seed)
    shape = dict(lanes=LANES, pages=PAGES, K=MEGASTEP, requests=N_REQUESTS)
    t0 = time.perf_counter()
    ref = _drain(program, cfg, "int_ref", seed)
    if program.recurrent_layers:
        # every request from rest in one batch run: the drain's admissions,
        # evictions and carried rasters must not show in any result
        reqs = _requests(program, cfg, seed)
        batch = pipeline.run_network(
            program, jnp.stack([jnp.asarray(r.frames) for r in reqs], 1),
            "int_ref")
        _check_drain("serve int_ref vs batch", ref,
                     {r.rid: np.asarray(batch.logits)[i]
                      for i, r in enumerate(reqs)})
    _report("serve", arch, "int_ref", t0, match="reference", **shape)
    for backend in SERVE_BACKENDS:
        t0 = time.perf_counter()
        if _refused(program, backend, lambda: SNNServeEngine(
                program, batch_slots=LANES, backend=backend)):
            _report("serve", arch, backend, t0, match="refused", **shape)
            continue
        _check_drain(f"serve {backend}", _drain(program, cfg, backend, seed),
                     ref)
        _report("serve", arch, backend, t0, **shape)


def mesh_phase(seed: int) -> None:
    arch = "impulse-imdb"
    cfg, program = _program(arch, seed)
    for backend in SERVE_BACKENDS:
        t0 = time.perf_counter()
        single = _drain(program, cfg, backend, seed)
        _report("mesh", arch, backend, t0, match="reference", mesh="single")
        for shape in MESH_SHAPES:
            t0 = time.perf_counter()
            mesh = make_mesh(shape, ("data", "model"))
            _check_drain(f"mesh {shape} {backend}",
                         _drain(program, cfg, backend, seed, mesh=mesh),
                         single)
            _report("mesh", arch, backend, t0,
                    mesh=f"data{shape[0]}xmodel{shape[1]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="IMPULSE serving-path smoke run on a TPU")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh serving phase on 4 chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, inputs and requests")
    ap.add_argument("--arch", choices=sorted(SNN_CONFIGS),
                    help="run the batch and serving phases for this "
                         "config alone")
    args = ap.parse_args(argv)
    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform} ({dev.device_kind})")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{len(devices)} device(s)")
    if args.chips == 4:
        mesh_phase(args.seed)
    elif args.arch:
        batch_phase(args.arch, dict(BATCH_CASES).get(args.arch, 64),
                    args.seed)
        serve_phase(args.seed, args.arch)
    else:
        for arch, batch in BATCH_CASES:
            batch_phase(arch, batch, args.seed)
        serve_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
