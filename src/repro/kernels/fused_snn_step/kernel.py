"""Pallas TPU kernel: fused SNN timestep loop with VMEM-resident V_MEM.

This is the TPU-native realization of IMPULSE's fused W_MEM/V_MEM array:
the membrane-potential tile lives in VMEM (registers of the array, in macro
terms) across the ENTIRE timestep loop; weights are loaded HBM->VMEM once per
(batch, neuron) tile; the accumulate (AccW2V), leak (AccV2V), threshold
compare (SpikeCheck) and reset (ResetV) all execute in-kernel with no HBM
round-trip for V. HBM traffic for V: O(B*N) total instead of O(T*B*N).

Tiling: the macro's 128-row fan-in aligns with the MXU's 128-lane contraction;
spike activations are int8 {0,1} so the accumulate is an int8 x int8 -> int32
MXU matmul (the whole-row parallelism of the bitline adders).

Grid: (B // block_b, N_out // block_n); T is an in-kernel fori_loop so V never
leaves VMEM (grid dims would evict it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quant import clamp_v, spike_compare

NEURON_IDS = {"if": 0, "lif": 1, "rmp": 2}


def _snn_kernel(spikes_ref, w_ref, params_ref, out_ref, v_ref, *,
                neuron: str, clamp_mode: str, timesteps: int):
    """spikes_ref: (T, Bt, Nin) int8; w_ref: (Nin, Nt) int8;
    params_ref: (3,) int32 [threshold, leak, reset] (SMEM-like small operand);
    out_ref: (T, Bt, Nt) int8; v_ref: (Bt, Nt) int32 (final V, also the
    VMEM-resident accumulator via the carry)."""
    w = w_ref[...]
    threshold = params_ref[0]
    leak = params_ref[1]
    reset = params_ref[2]

    def body(t, v):
        s_in = spikes_ref[t]                                  # (Bt, Nin) int8
        # AccW2V: event-gated row accumulate == binary matmul on the MXU
        acc = jax.lax.dot_general(
            s_in, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        v = clamp_v(v + acc, clamp_mode)
        if neuron == "lif":                                   # AccV2V(-leak)
            v = clamp_v(v - leak, clamp_mode)
        fired = spike_compare(v, threshold, clamp_mode)       # SpikeCheck
        if neuron == "rmp":                                   # AccV2V(-th), gated
            v = clamp_v(jnp.where(fired, v - threshold, v), clamp_mode)
        else:                                                 # ResetV
            v = jnp.where(fired, reset, v)
        out_ref[pl.ds(t, 1)] = fired.astype(jnp.int8)[None]
        return v

    v0 = jnp.zeros(v_ref.shape, jnp.int32)
    v_ref[...] = jax.lax.fori_loop(0, timesteps, body, v0)


def fused_snn_pallas(spikes: jax.Array, wq: jax.Array, params: jax.Array, *,
                     neuron: str, clamp_mode: str, block_b: int, block_n: int,
                     interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Dispatch the Pallas kernel. Shapes must be pre-padded:
    spikes (T, B, N_in) int8 with N_in % 128 == 0, B % block_b == 0;
    wq (N_in, N_out) int8 with N_out % block_n == 0; params (3,) int32."""
    T, B, N_in = spikes.shape
    N_out = wq.shape[1]
    grid = (B // block_b, N_out // block_n)
    kernel = functools.partial(_snn_kernel, neuron=neuron,
                               clamp_mode=clamp_mode, timesteps=T)
    out_spikes, v_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((T, block_b, N_in), lambda i, j: (0, i, 0)),
            pl.BlockSpec((N_in, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((3,), lambda i, j: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((T, block_b, block_n), lambda i, j: (0, i, j)),
            pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, N_out), jnp.int8),
            jax.ShapeDtypeStruct((B, N_out), jnp.int32),
        ],
        interpret=interpret,
    )(spikes, wq, params)
    return out_spikes, v_final
