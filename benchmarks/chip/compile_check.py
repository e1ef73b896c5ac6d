#!/usr/bin/env python3
"""Compile every cell's timed path at its real shapes for a described
TPU v5e, without a chip:

    JAX_PLATFORMS=cpu python3 benchmarks/chip/compile_check.py

For a batch cell that is the jitted ``run_network`` call at the mix's
batch and length; for a serve cell, the engine's one-tick dispatch over
the full lane page. Prints, per cell, whether Mosaic kernels are in the
program and the compiler's memory analysis. Nothing runs.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from harness import spec, weights  # noqa: E402


def _program(cell):
    from repro.core import pipeline
    params, _ = weights.make(cell.config, 0)
    return pipeline.compile_network(spec.model_config(cell.config), params,
                                    domain="int")


def batch_lowered(cell, sharding):
    from repro.core import pipeline
    program = _program(cell)
    mix, cfg = cell.traffic, cell.config
    t = cfg["timesteps"]
    if cfg["conv_spec"]:
        shape = (t, mix["batch"], *cfg["in_shape"])
    else:
        shape = (mix["words"] * t, mix["batch"], cfg["layer_sizes"][0])

    def score(xs):
        res = pipeline.run_network(program, xs, cell.settings["backend"],
                                   emit_rasters=False)
        return res.logits, tuple(res.v_final[1:])

    return jax.jit(score).lower(
        jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding))


def serve_lowered(cell, sharding):
    from repro.serve.snn_engine import _jit_megastep
    from repro.core import pipeline
    program = _program(cell)
    st = cell.settings
    lanes = st["batch_slots"]
    fn = _jit_megastep(program, st["backend"], {}, st["track_events"])
    state = jax.eval_shape(lambda: pipeline.init_stream_state(
        program, lanes, st["backend"]))

    def place(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
    state = jax.tree_util.tree_map(place, state)
    block = place(jax.ShapeDtypeStruct(
        (1, lanes, cell.config["layer_sizes"][0]), jnp.float32))
    counts = place(jax.ShapeDtypeStruct((lanes,), jnp.int32))
    return fn.lower(state, block, counts)


def main() -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    bench = json.loads(spec.BENCHMARK.read_text())
    ok = True
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], bench)
        lower = serve_lowered if cell.settings["driver"] == "serve" \
            else batch_lowered
        try:
            compiled = lower(cell, one).compile()
        except Exception as e:          # report every cell, then fail
            ok = False
            print(f"{cell.name}: REFUSED: {type(e).__name__}: {e}"[:2000])
            continue
        text = compiled.as_text()
        mem = compiled.memory_analysis()
        print(f"{cell.name}: compiled; tpu_custom_call x"
              f"{text.count('tpu_custom_call')}; "
              f"args {mem.argument_size_in_bytes} B, "
              f"outputs {mem.output_size_in_bytes} B, "
              f"temps {mem.temp_size_in_bytes} B")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
