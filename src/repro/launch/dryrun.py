import os
# opt level 0: ~35x faster XLA:CPU compiles with verified-identical
# cost/memory analysis on a reference cell (EXPERIMENTS.md §Methodology);
# SPMD partitioning (the thing being proven) runs at every opt level.
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_backend_optimization_level=0"
                           " --xla_force_host_platform_device_count=512").strip()

__doc__ = """Multi-pod dry-run: .lower().compile() every (architecture x
input-shape x mesh) cell and extract the roofline terms from the compiled
artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Per cell this writes artifacts/dryrun/<mesh>/<arch>__<shape>.json with:
  flops/device, bytes-accessed/device, per-collective byte totals,
  memory analysis (argument/output/temp bytes per device), roofline terms
  (compute/memory/collective seconds), MODEL_FLOPS and the useful-compute
  ratio. EXPERIMENTS.md §Dry-run/§Roofline are generated from these files.

NOTE: the XLA_FLAGS assignment above MUST stay the first statement — jax
locks the device count at first init. Smoke tests and benchmarks never import
this module, so they keep seeing 1 device.
"""

import argparse
import dataclasses
import json
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (ASSIGNED_ARCHS, ModelConfig, ParallelConfig,
                                RunConfig, SHAPES, ShapeConfig, get_config)
from repro.dist import sharding as shd
from repro.launch.mesh import (HBM_BW, HBM_BYTES, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.models import io_spec, lm
from repro.optim import make_optimizer
from repro.train.train_state import TrainState

ART_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# ---------------------------------------------------------------------------
# Per-cell parallel policy (the hillclimb edits THIS table; defaults first)
# ---------------------------------------------------------------------------

DEFAULT_TRAIN = dict(remat="block", fsdp=True, scan_layers=True,
                     vocab_chunking=4, microbatches=1)
DEFAULT_SERVE = dict(remat="none", fsdp=False, scan_layers=True,
                     vocab_chunking=1, microbatches=1)

OVERRIDES: dict[tuple[str, str], dict] = {
    # llama4-maverick: 400B params -> factored optimizer, more loss chunks
    ("llama4-maverick-400b-a17b", "train_4k"): dict(optimizer="adafactor",
                                                    vocab_chunking=8),
    ("starcoder2-15b", "train_4k"): dict(vocab_chunking=4),
}

# Hillclimb variants (§Perf): selected by --tag; each entry overrides the
# baseline ParallelConfig / optimizer for one (arch, shape). The iteration
# log lives in EXPERIMENTS.md §Perf.
HILLCLIMB: dict[tuple[str, str, str], dict] = {
    # --- jamba train_4k (worst memory blowup; paper-representative SSM) ---
    # p1: shard the SSM scan tensors + remat chunk bodies
    ("jamba-v0.1-52b", "train_4k", "p1"): dict(state_constraints=True),
    # p2: + gather-only dispatch on its 16-expert MoE + blocked attention
    ("jamba-v0.1-52b", "train_4k", "p2"): dict(state_constraints=True,
                                               moe_gather_dispatch=True,
                                               attn_q_chunk=1024),
    # p3: + microbatching to halve live activations
    ("jamba-v0.1-52b", "train_4k", "p3"): dict(state_constraints=True,
                                               moe_gather_dispatch=True,
                                               attn_q_chunk=1024,
                                               microbatches=2),
    # --- llama4 train_4k (most collective-bound) ---
    ("llama4-maverick-400b-a17b", "train_4k", "p1"): dict(
        optimizer="adafactor", vocab_chunking=8, moe_constraints=True),
    ("llama4-maverick-400b-a17b", "train_4k", "p2"): dict(
        optimizer="adafactor", vocab_chunking=8, moe_gather_dispatch=True),
    ("llama4-maverick-400b-a17b", "train_4k", "p3"): dict(
        optimizer="adafactor", vocab_chunking=8, moe_gather_dispatch=True,
        attn_q_chunk=1024, microbatches=2),
    # --- deepseek train_4k (worst roofline fraction) ---
    ("deepseek-v2-lite-16b", "train_4k", "p1"): dict(moe_constraints=True),
    ("deepseek-v2-lite-16b", "train_4k", "p2"): dict(moe_gather_dispatch=True),
    ("deepseek-v2-lite-16b", "train_4k", "p3"): dict(moe_gather_dispatch=True,
                                                     attn_q_chunk=1024,
                                                     microbatches=2),
    ("deepseek-v2-lite-16b", "train_4k", "p4"): dict(moe_gather_dispatch=True,
                                                     microbatches=4),
    ("llama4-maverick-400b-a17b", "train_4k", "p4"): dict(
        optimizer="adafactor", vocab_chunking=8, moe_gather_dispatch=True,
        microbatches=4),
    ("jamba-v0.1-52b", "train_4k", "p4"): dict(state_constraints=True,
                                               moe_gather_dispatch=True,
                                               microbatches=4),
    # --- rwkv long_500k (paper's fused-state serving path) ---
    # p1: 2D tensor parallelism for decode (weights sharded over data x model)
    ("rwkv6-7b", "long_500k", "p1"): dict(fsdp=True),
    # --- bonus: blocked attention on the worst prefill cells ---
    ("whisper-large-v3", "prefill_32k", "p1"): dict(attn_q_chunk=2048),
    ("llama3-8b", "prefill_32k", "p1"): dict(attn_q_chunk=2048),
    ("phi3-medium-14b", "prefill_32k", "p1"): dict(attn_q_chunk=2048),
}

# long_500k applicability (DESIGN.md §4): sub-quadratic archs only
LONG_OK = {"rwkv6-7b", "jamba-v0.1-52b"}


def cell_list(archs, shapes) -> list[tuple[str, str, str | None]]:
    cells = []
    for a in archs:
        for s in shapes:
            skip = None
            if s == "long_500k" and a not in LONG_OK:
                skip = "full-attention arch: 500k dense decode skipped per assignment"
            cells.append((a, s, skip))
    return cells


def make_run(arch: str, shape_name: str, tag: str = "") -> RunConfig:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    base = dict(DEFAULT_TRAIN if shape.kind == "train" else DEFAULT_SERVE)
    ov = dict(OVERRIDES.get((arch, shape_name), {}))
    if tag:
        ov.update(HILLCLIMB.get((arch, shape_name, tag), {}))
    optimizer = ov.pop("optimizer", "adamw")
    base.update(ov)
    return RunConfig(model=cfg, shape=shape, parallel=ParallelConfig(**base),
                     optimizer=optimizer)


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)")
_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")


def _type_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict[str, int]:
    """Sum operand bytes per collective op kind from post-SPMD HLO."""
    # symbol table: instruction name -> result bytes
    sym: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            sym[m.group(1)] = _type_bytes(m.group(2))
    out: dict[str, int] = {k: 0 for k in _COLL_OPS}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        op = m.group(3)
        base = None
        for k in _COLL_OPS:
            if op == k or op == k + "-start":
                base = k
                break
        if base is None:
            continue
        # operand list: first (...) after the opcode
        rest = line[m.end():]
        paren = rest.find("(")
        if paren < 0:
            continue
        depth, j = 0, paren
        for j in range(paren, len(rest)):
            if rest[j] == "(":
                depth += 1
            elif rest[j] == ")":
                depth -= 1
                if depth == 0:
                    break
        operands = rest[paren + 1:j]
        bytes_ = 0
        for name in re.findall(r"%?([\w.\-]+)", operands):
            if name in sym:
                bytes_ += sym[name]
        if bytes_ == 0:                          # fallback: result size
            bytes_ = _type_bytes(m.group(2))
        out[base] += bytes_
    return out


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def build_train(run: RunConfig, mesh):
    cfg, parallel = run.model, run.parallel
    opt = make_optimizer(run.optimizer, run.learning_rate, run.weight_decay)
    from repro.train.train_state import make_train_step
    step_fn = make_train_step(run, opt)

    pshapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    oshapes = jax.eval_shape(opt.init, pshapes)
    state_shapes = TrainState(pshapes, oshapes,
                              jax.ShapeDtypeStruct((), jnp.int32))
    pspecs = shd.param_specs(pshapes, mesh, parallel)
    ospecs = shd.param_specs(oshapes, mesh, parallel)
    state_specs = TrainState(pspecs, ospecs, shd.replicated(mesh))
    batch = io_spec.train_batch_spec(cfg, run.shape)
    bspecs = shd.batch_specs(batch, mesh, parallel)
    metric_specs = {"loss": shd.replicated(mesh), "grad_norm": shd.replicated(mesh),
                    "step": shd.replicated(mesh)}
    fn = jax.jit(step_fn,
                 in_shardings=(state_specs, bspecs),
                 out_shardings=(state_specs, metric_specs),
                 donate_argnums=(0,))
    return fn, (state_shapes, batch)


def build_prefill(run: RunConfig, mesh):
    cfg, parallel = run.model, run.parallel
    pshapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    pspecs = shd.param_specs(pshapes, mesh, parallel)
    batch = io_spec.prefill_batch_spec(cfg, run.shape)
    bspecs = shd.batch_specs(batch, mesh, parallel)
    S = run.shape.seq_len

    def fn(params, b):
        return lm.prefill(params, b, cfg, S, parallel)

    cache_shapes = jax.eval_shape(
        lambda: lm.init_cache(cfg, run.shape.global_batch, S,
                              enc_len=(S if cfg.is_encoder_decoder else 0)))
    cspecs = shd.cache_specs(cache_shapes, mesh, parallel, cfg)
    out_specs = (shd.logits_spec(
        mesh, (run.shape.global_batch, cfg.vocab_size)), cspecs)
    jfn = jax.jit(fn, in_shardings=(pspecs, bspecs), out_shardings=out_specs)
    return jfn, (pshapes, batch)


def build_decode(run: RunConfig, mesh):
    cfg, parallel = run.model, run.parallel
    pshapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    pspecs = shd.param_specs(pshapes, mesh, parallel)
    tokens, cache_shapes = io_spec.decode_spec(cfg, run.shape)
    cspecs = shd.cache_specs(cache_shapes, mesh, parallel, cfg)
    tspec = shd.batch_specs(tokens, mesh, parallel)

    def fn(params, t, cache):
        return lm.decode_step(params, t, cache, cfg, parallel)

    jfn = jax.jit(fn, in_shardings=(pspecs, tspec, cspecs),
                  out_shardings=(shd.logits_spec(
                      mesh, (run.shape.global_batch, cfg.vocab_size)), cspecs),
                  donate_argnums=(2,))
    return jfn, (pshapes, tokens, cache_shapes)


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch                     # decode: per token


def _compile_cell(run: RunConfig, mesh):
    builders = {"train": build_train, "prefill": build_prefill,
                "decode": build_decode}
    build = builders[run.shape.kind]
    with mesh:
        with shd.activation_rules(mesh, run.parallel):
            fn, abstract_args = build(run, mesh)
            lowered = fn.lower(*abstract_args)
        compiled = lowered.compile()
        return compiled


def _measure(compiled) -> dict:
    ca = compiled.cost_analysis() or {}
    coll = parse_collectives(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll}


def _reduced_run(run: RunConfig, n: int) -> RunConfig:
    """Depth-n variant (n super-blocks / encoder layers) with time scans
    unrolled, for the linear-in-depth cost extrapolation (XLA cost analysis
    counts while-loop bodies once; see EXPERIMENTS.md §Dry-run methodology).

    For attention-free rwkv every cost component is exactly linear in T at
    fixed wkv chunk, so the accounting compiles run at T<=4096 and scale by
    T/T' — this bounds the number of unrolled wkv chunk bodies at 64."""
    from repro.models.lm import n_prelude, super_period
    cfg = run.model
    kw: dict = {"n_layers": n_prelude(cfg) + super_period(cfg) * n}
    if cfg.is_encoder_decoder:
        kw["n_encoder_layers"] = n
    cfg2 = dataclasses.replace(cfg, **kw)
    # scan_layers=False: depth must change the HLO, not just a trip count
    par2 = dataclasses.replace(run.parallel, unroll_time_scans=True,
                               scan_layers=False)
    shape = run.shape
    if cfg.rwkv is not None and shape.kind != "decode" and shape.seq_len > 4096:
        shape = dataclasses.replace(shape, seq_len=4096)
    return dataclasses.replace(run, model=cfg2, parallel=par2, shape=shape)


def extrapolated_costs(run: RunConfig, mesh) -> dict:
    """costs(N) = v1 + (N-1) * (v2 - v1), measured at depth 1 and 2."""
    from repro.models.lm import n_super
    full_n = n_super(run.model)
    r1 = _reduced_run(run, 1)
    v1 = _measure(_compile_cell(r1, mesh))
    if full_n == 1:
        v = v1
    else:
        v2 = _measure(_compile_cell(_reduced_run(run, 2), mesh))
        scale = full_n - 1

        def ext(a, b):
            return a + scale * (b - a)

        coll = {k: max(0.0, ext(v1["coll"][k], v2["coll"][k]))
                for k in v1["coll"]}
        # clamp: extrapolation noise on micro-scale cells can go negative
        v = {"flops": max(ext(v1["flops"], v2["flops"]), 0.0),
             "bytes": max(ext(v1["bytes"], v2["bytes"]), 0.0), "coll": coll}
    mult = run.parallel.microbatches if run.parallel.microbatches > 1 else 1
    mult *= run.shape.seq_len / r1.shape.seq_len      # rwkv T-scaling (==1 else)
    if mult != 1:
        v = {"flops": v["flops"] * mult, "bytes": v["bytes"] * mult,
             "coll": {k: c * mult for k, c in v["coll"].items()}}
    return v


def run_cell(arch: str, shape_name: str, mesh_kind: str, tag: str = "") -> dict:
    run = make_run(arch, shape_name, tag)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    compiled = _compile_cell(run, mesh)                      # the PROOF compile
    t_compile = time.time() - t0
    t_lower = 0.0
    ma = compiled.memory_analysis()
    raw = _measure(compiled)
    # roofline costs from depth-extrapolation (correct while-loop accounting)
    costs = extrapolated_costs(run, mesh)
    coll = costs["coll"]
    coll_bytes = float(sum(coll.values()))
    flops_dev = costs["flops"]
    bytes_dev = costs["bytes"]
    terms = {
        "compute_s": flops_dev / PEAK_FLOPS_BF16,
        "memory_s": bytes_dev / HBM_BW,
        "collective_s": coll_bytes / ICI_BW,
    }
    dominant = max(terms, key=terms.get)
    mf = model_flops(run.model, run.shape)
    hlo_global = flops_dev * n_chips
    mem = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
    }
    peak = mem["argument_bytes"] + mem["temp_bytes"] + mem["output_bytes"] \
        - mem["alias_bytes"]
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "chips": n_chips,
        "kind": run.shape.kind,
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_bytes,
        "collectives": coll,
        "raw_rolled_costs": raw,
        "memory": mem,
        "peak_bytes_per_device": int(peak),
        "fits_16GiB": bool(peak <= HBM_BYTES),
        "roofline_terms_s": terms,
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_global": hlo_global,
        "useful_ratio": mf / hlo_global if hlo_global else 0.0,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "parallel": dataclasses.asdict(run.parallel),
        "optimizer": run.optimizer,
    }
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(ART_DIR))
    ap.add_argument("--tag", default="", help="suffix for artifact files (perf iterations)")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells whose artifact already exists")
    args = ap.parse_args(argv)

    archs = ASSIGNED_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for mesh_kind in meshes:
        outdir = Path(args.out) / mesh_kind
        outdir.mkdir(parents=True, exist_ok=True)
        for arch, shape, skip in cell_list(archs, shapes):
            tag = f"__{args.tag}" if args.tag else ""
            fp = outdir / f"{arch}__{shape}{tag}.json"
            if args.resume and fp.exists():
                print(f"[skip] {mesh_kind} {arch} {shape}: artifact exists")
                continue
            if skip:
                fp.write_text(json.dumps(
                    {"arch": arch, "shape": shape, "mesh": mesh_kind,
                     "skipped": skip}, indent=1))
                print(f"[skip] {mesh_kind} {arch} {shape}: {skip}")
                continue
            try:
                res = run_cell(arch, shape, mesh_kind, args.tag)
                fp.write_text(json.dumps(res, indent=1))
                t = res["roofline_terms_s"]
                print(f"[ok]   {mesh_kind} {arch} {shape}: dominant={res['dominant']}"
                      f" compute={t['compute_s']:.3e}s memory={t['memory_s']:.3e}s"
                      f" coll={t['collective_s']:.3e}s peak={res['peak_bytes_per_device']/2**30:.2f}GiB"
                      f" fits={res['fits_16GiB']} (compile {res['compile_s']}s)")
            except Exception as e:  # noqa: BLE001 — a failing cell is a bug to fix
                failures.append((mesh_kind, arch, shape, repr(e)))
                print(f"[FAIL] {mesh_kind} {arch} {shape}: {e!r}"[:500])
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f[0], f[1], f[2], f[3][:200])
        sys.exit(1)
    print("\nall requested cells compiled.")


if __name__ == "__main__":
    main()
