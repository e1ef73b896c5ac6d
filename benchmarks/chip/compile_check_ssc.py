#!/usr/bin/env python3
"""Compile a recurrent batch cell's timed path at its real shapes for a
described TPU v5e, without a chip:

    JAX_PLATFORMS=cpu python3 benchmarks/chip/compile_check_ssc.py

As ``compile_check.py`` does for the feed-forward cells, with the
program built by `harness.recurrent` (whose weights carry the W_rec
rows): the jitted ``run_network`` call at the mix's batch and length.
Prints whether Mosaic kernels are in the program and the compiler's
memory analysis. Nothing runs.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from harness import recurrent, spec  # noqa: E402


def lowered(cell, sharding):
    from repro.core import pipeline
    params, _ = recurrent.make(cell.config, 0)
    program = pipeline.compile_network(recurrent.model_config(cell.config),
                                       params, domain="int")
    mix, cfg = cell.traffic, cell.config
    shape = (mix["words"] * cfg["timesteps"], mix["batch"],
             cfg["layer_sizes"][0])

    def score(xs):
        res = pipeline.run_network(program, xs, cell.settings["backend"],
                                   emit_rasters=False)
        return res.logits, tuple(res.v_final[1:])

    return jax.jit(score).lower(
        jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding))


def main(argv=None) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    names = (argv if argv is not None else sys.argv[1:]) or ["ssc-batch"]
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    for name in names:
        cell = spec.load_cell(name)
        compiled = lowered(cell, one).compile()
        text = compiled.as_text()
        mem = compiled.memory_analysis()
        print(f"{cell.name}: compiled; tpu_custom_call x"
              f"{text.count('tpu_custom_call')}; "
              f"args {mem.argument_size_in_bytes} B, "
              f"outputs {mem.output_size_in_bytes} B, "
              f"temps {mem.temp_size_in_bytes} B")
    return 0


if __name__ == "__main__":
    sys.exit(main())
