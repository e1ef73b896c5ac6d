"""Compile rehearsals of the fused kernel for a TPU v5e that is described,
not attached: the TPU compiler refuses what interpret mode accepts
(unaligned blocks, primitives Mosaic cannot lower), so every Pallas kernel
mode is compiled here at impulse-imdb widths (100 -> 128 -> 128 -> 1,
T=10, int8) with B=64 in 8-row tiles — a grid of 8 batch tiles.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_snn_net.ops import fused_snn_net

WIDTHS = (100, 128, 128, 1)
T, BATCH, BLOCK_B = 10, 64, 8

MODES = {
    "dense": {},
    "sparse_g1": {"use_sparse": True, "gate_granularity": 1},
    "sparse_g8": {"use_sparse": True, "gate_granularity": 8},
    "events": {"use_events": True},
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, mode: dict, v_init: bool):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    spikes = sds((T, BATCH, WIDTHS[0]), jnp.int8)
    ws = [sds((WIDTHS[i], WIDTHS[i + 1]), jnp.int8)
          for i in range(len(WIDTHS) - 1)]
    kw = dict(thresholds=(8, 8), leaks=(1, 1), block_b=BLOCK_B, **mode)
    if v_init:
        vs = [sds((BATCH, w), jnp.int32) for w in WIDTHS[1:]]
        fn = jax.jit(lambda s, w, v: fused_snn_net(s, w, v_init=v, **kw))
        return fn.lower(spikes, ws, vs).compile()
    fn = jax.jit(lambda s, w: fused_snn_net(s, w, **kw))
    return fn.lower(spikes, ws).compile()


@pytest.mark.parametrize("v_init", [False, True], ids=["batch", "v_init"])
@pytest.mark.parametrize("mode", list(MODES))
def test_kernel_compiles_for_v5e(one_chip, mode, v_init):
    """Every kernel mode, from scratch and from carried V (the streaming
    step and megastep entry), compiles to a Mosaic custom call."""
    compiled = _compile(one_chip, MODES[mode], v_init)
    assert "tpu_custom_call" in compiled.as_text()
