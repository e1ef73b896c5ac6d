"""IMPULSE in-memory instruction set — functional (integer) semantics.

This layer defines the four macro instructions at *word level* (int32 math,
11-bit clamped), vectorizable over batch. It is the contract between:

  * macro.py  -- the bit-accurate column/bitline model (validated to match
                 this layer instruction-for-instruction), and
  * snn.py / kernels/fused_snn_step -- the training & TPU fast paths
                 (validated to match this layer end-to-end).

Macro geometry (the fabricated 65nm instance):
  W_MEM: 128 rows x 12 six-bit signed weights  (one row per input neuron)
  V_MEM: 32 rows x 6 twelve-bit slots; a neuron set (12 neurons) spans 2
         staggered rows (odd-parity slots + even-parity slots). 6 constant
         rows (threshold/reset/leak, odd+even each) leave 13 neuron sets.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import clamp_v, spike_compare

MACRO_IN = 128          # input rows
MACRO_OUT = 12          # weights (output neurons) per row
V_ROWS = 32
V_SLOTS_PER_ROW = 6
N_CONST_ROWS = 6        # threshold_o/e, reset_o/e, leak_o/e
N_NEURON_SETS = (V_ROWS - N_CONST_ROWS) // 2    # 13


class InstrCount(NamedTuple):
    """Executed-cycle counts per instruction type (energy model input)."""
    acc_w2v: int = 0
    acc_v2v: int = 0
    spike_check: int = 0
    reset_v: int = 0

    def __add__(self, o: "InstrCount") -> "InstrCount":
        return InstrCount(*(a + b for a, b in zip(self, o)))

    @property
    def total(self) -> int:
        return sum(self)


@dataclass
class MacroState:
    """Logical state of one macro (word-level)."""
    wmem: jax.Array                       # (128, 12) int8 in [-31, 31]
    vmem: jax.Array                       # (N_SETS, 12) int32, 11-bit clamped
    threshold: jax.Array                  # (12,) int32 (stored negated on-chip)
    reset: jax.Array                      # (12,) int32
    leak: jax.Array                       # (12,) int32 (stored negated on-chip)
    spike_buf: jax.Array                  # (N_SETS, 12) bool
    clamp_mode: str = "saturate"


def make_state(wq: np.ndarray, threshold: int, reset: int = 0, leak: int = 0,
               clamp_mode: str = "saturate") -> MacroState:
    if wq.shape != (MACRO_IN, MACRO_OUT):
        raise ValueError(f"macro weight tile must be "
                         f"{(MACRO_IN, MACRO_OUT)}, got {wq.shape}")
    return MacroState(
        wmem=jnp.asarray(wq, jnp.int8),
        vmem=jnp.zeros((N_NEURON_SETS, MACRO_OUT), jnp.int32),
        threshold=jnp.full((MACRO_OUT,), threshold, jnp.int32),
        reset=jnp.full((MACRO_OUT,), reset, jnp.int32),
        leak=jnp.full((MACRO_OUT,), leak, jnp.int32),
        spike_buf=jnp.zeros((N_NEURON_SETS, MACRO_OUT), bool),
        clamp_mode=clamp_mode,
    )


# ---------------------------------------------------------------------------
# Instructions. ``cycle``: 0 = odd (even-indexed weight groups), 1 = even.
# Each call models ONE executed macro cycle.
# ---------------------------------------------------------------------------

def _parity_mask(cycle: int) -> np.ndarray:
    m = np.zeros(MACRO_OUT, bool)
    m[cycle::2] = True
    return m


def acc_w2v(st: MacroState, set_idx: int, in_row, cycle: int) -> MacroState:
    """V[set, parity] += W[in_row, parity]  (triple-row decode: RWLo/e + V RWL + WWL)."""
    mask = jnp.asarray(_parity_mask(cycle))
    w = st.wmem[in_row].astype(jnp.int32)
    v = st.vmem[set_idx]
    v = jnp.where(mask, clamp_v(v + w, st.clamp_mode), v)
    return replace(st, vmem=st.vmem.at[set_idx].set(v))


def acc_v2v(st: MacroState, set_idx: int, add: jax.Array, cycle: int,
            conditional: bool = False) -> MacroState:
    """V[set, parity] += add[parity]; optionally gated by the spike buffers
    (conditional write drivers), e.g. RMP soft reset."""
    mask = jnp.asarray(_parity_mask(cycle))
    if conditional:
        mask = mask & st.spike_buf[set_idx]
    v = st.vmem[set_idx]
    v = jnp.where(mask, clamp_v(v + add.astype(jnp.int32), st.clamp_mode), v)
    return replace(st, vmem=st.vmem.at[set_idx].set(v))


def spike_check(st: MacroState, set_idx: int, cycle: int) -> MacroState:
    """Compare V against threshold (adder-as-comparator; MSB carry-out).
    Latches spike buffers for the parity's neurons. Read-only on V. In
    ``wrap`` clamp mode the comparison itself wraps (quant.spike_compare):
    the silicon evaluates v + (-th) on the 11-bit adder."""
    mask = jnp.asarray(_parity_mask(cycle))
    fired = spike_compare(st.vmem[set_idx], st.threshold, st.clamp_mode)
    buf = jnp.where(mask, fired, st.spike_buf[set_idx])
    return replace(st, spike_buf=st.spike_buf.at[set_idx].set(buf))


def reset_v(st: MacroState, set_idx: int, cycle: int) -> MacroState:
    """Conditionally (per spike buffer) rewrite V from the reset row. The BLFA
    is bypassed; SINV -> CWD direct transfer."""
    mask = jnp.asarray(_parity_mask(cycle)) & st.spike_buf[set_idx]
    v = jnp.where(mask, st.reset, st.vmem[set_idx])
    return replace(st, vmem=st.vmem.at[set_idx].set(v))


# ---------------------------------------------------------------------------
# Neuron-update sequences (Fig. 6) and the per-timestep program.
# ---------------------------------------------------------------------------

def neuron_update(st: MacroState, set_idx: int, neuron: str) -> tuple[MacroState, jax.Array, InstrCount]:
    """End-of-timestep neuron update for both parities. Returns spikes (12,)."""
    cnt = InstrCount()
    if neuron == "lif":
        for c in (0, 1):
            st = acc_v2v(st, set_idx, -st.leak, c)
        cnt += InstrCount(acc_v2v=2)
    for c in (0, 1):
        st = spike_check(st, set_idx, c)
    cnt += InstrCount(spike_check=2)
    if neuron == "rmp":                            # soft reset: AccV2V(-th), gated
        for c in (0, 1):
            st = acc_v2v(st, set_idx, -st.threshold, c, conditional=True)
        cnt += InstrCount(acc_v2v=2)
    elif neuron in ("if", "lif"):
        for c in (0, 1):
            st = reset_v(st, set_idx, c)
        cnt += InstrCount(reset_v=2)
    else:
        raise ValueError(neuron)
    return st, st.spike_buf[set_idx], cnt


def timestep(st: MacroState, set_idx: int, in_spikes, neuron: str
             ) -> tuple[MacroState, jax.Array, InstrCount]:
    """One SNN timestep on one macro: event-driven AccW2V per spiking input
    row (odd+even cycles), then the neuron-update sequence.

    ``in_spikes``: (128,) bool host array — the *event list*; only spiking rows
    issue instructions (this is the sparsity → energy mechanism, Fig. 11).
    """
    in_spikes = np.asarray(in_spikes).astype(bool)
    rows = np.nonzero(in_spikes)[0]
    for r in rows:
        st = acc_w2v(st, set_idx, int(r), cycle=0)
        st = acc_w2v(st, set_idx, int(r), cycle=1)
    cnt = InstrCount(acc_w2v=2 * len(rows))
    st, spikes, c2 = neuron_update(st, set_idx, neuron)
    return st, spikes, cnt + c2


# ---------------------------------------------------------------------------
# Vectorized reference of the same semantics (jit-able; used as the oracle
# for snn.py / the Pallas kernel). Processes a whole layer tile at once.
# ---------------------------------------------------------------------------

def neuron_dynamics_int(v: jax.Array, *, neuron: str, threshold: jax.Array,
                        leak: jax.Array, reset: jax.Array,
                        clamp_mode: str = "saturate"
                        ) -> tuple[jax.Array, jax.Array]:
    """The post-accumulation half of a timestep: leak / SpikeCheck / reset on
    an already-accumulated (and clamped) V. Split out from
    `layer_timestep_int` so event-gated executors can skip the AccW2V matmul
    for all-silent inputs while still running the neuron update every
    timestep (LIF leaks and RMP can re-fire with zero input — the update
    sequence is unconditional on silicon too, Fig. 6)."""
    if neuron == "lif":
        v = clamp_v(v - leak, clamp_mode)
    s = spike_compare(v, threshold, clamp_mode)
    if neuron == "rmp":
        v = clamp_v(jnp.where(s, v - threshold, v), clamp_mode)
    else:
        v = jnp.where(s, reset, v)
    return v, s.astype(jnp.int32)


def layer_timestep_int(v: jax.Array, wq: jax.Array, in_spikes: jax.Array, *,
                       neuron: str, threshold: jax.Array, leak: jax.Array,
                       reset: jax.Array, clamp_mode: str = "saturate",
                       recurrent_spikes: jax.Array = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Batched integer timestep: v (..., n_out) int32, wq (n_in, n_out) int8,
    in_spikes (..., n_in) {0,1}. Mathematically == issuing `timestep` per macro
    tile (tested). Returns (v', out_spikes).

    ``recurrent_spikes`` (..., n_out) {0,1} — the layer's own spikes of the
    previous tick — makes it a recurrent layer: ``wq`` is then the
    (n_in + n_out, n_out) block [W_in; W_rec], and one AccW2V pass covers
    the input rows and the n_out recurrent rows after them."""
    if recurrent_spikes is not None:
        if wq.shape[0] != in_spikes.shape[-1] + recurrent_spikes.shape[-1]:
            raise ValueError(
                f"recurrent layer: weight block has {wq.shape[0]} rows, "
                f"want {in_spikes.shape[-1]} input + "
                f"{recurrent_spikes.shape[-1]} recurrent rows")
        in_spikes = jnp.concatenate(
            [in_spikes.astype(jnp.int32), recurrent_spikes.astype(jnp.int32)],
            axis=-1)
    acc = jnp.matmul(in_spikes.astype(jnp.int32), wq.astype(jnp.int32))
    v = clamp_v(v + acc, clamp_mode)
    return neuron_dynamics_int(v, neuron=neuron, threshold=threshold,
                               leak=leak, reset=reset, clamp_mode=clamp_mode)


def conv_layer_timestep_int(v: jax.Array, wq: jax.Array, in_spikes: jax.Array,
                            *, stride: int, neuron: str, threshold: jax.Array,
                            leak: jax.Array, reset: jax.Array,
                            clamp_mode: str = "saturate"
                            ) -> tuple[jax.Array, jax.Array]:
    """Batched integer conv timestep — the word-level semantics of one conv
    layer on the macro grid. v (B, H_out, W_out, c_out) int32; wq the HWIO
    int8 kernel (k, k, c_in, c_out); in_spikes (B, H, W, c_in) {0,1}.

    Lowered via im2col over the 128-row fan-in rule (mapping.im2col): every
    output position is an independent frame whose k*k*c_in patch vector
    drives `layer_timestep_int` on the packed (k*k*c_in, c_out) weight block
    — each position re-uses the same macro grid (mapping.conv_tiling), with
    its own V_MEM neuron set. Returns (v', out_spikes), both
    (B, H_out, W_out, c_out)."""
    from repro.core import mapping
    patches = mapping.im2col(in_spikes, wq.shape[0], stride)
    return layer_timestep_int(v, mapping.pack_conv_weights(wq), patches,
                              neuron=neuron, threshold=threshold, leak=leak,
                              reset=reset, clamp_mode=clamp_mode)


def count_layer_instructions_from_events(total_events: int, batch_t: int,
                                         n_in: int, n_out: int, neuron: str
                                         ) -> InstrCount:
    """Instruction cycles for a (n_in -> n_out) FC layer given only the
    aggregate event statistics: ``total_events`` input spikes over
    ``batch_t`` (timestep, example) frames. This is the raster-free entry
    point used by `pipeline.SparsityReport` (occupancy summaries carry the
    same information the counter needs); `count_layer_instructions`
    delegates here, so both paths agree by construction. Includes
    multi-macro tiling (mapping.py geometry: row tiles add AccV2V
    partial-sum reductions).
    """
    from repro.core import mapping
    tiles = mapping.fc_tiling(n_in, n_out)
    # AccW2V: each event hits every column tile, odd+even cycles
    n_acc_w = 2 * int(total_events) * tiles.col_tiles
    # partial-sum reduction: (row_tiles-1) AccV2V per set per parity per timestep
    n_red = 2 * (tiles.row_tiles - 1) * tiles.col_tiles * batch_t
    cnt = InstrCount(acc_w2v=n_acc_w, acc_v2v=n_red)
    # neuron update on the reduced set ("none" = accumulate-only readout layer)
    per_update = {"if": InstrCount(spike_check=2, reset_v=2),
                  "lif": InstrCount(acc_v2v=2, spike_check=2, reset_v=2),
                  "rmp": InstrCount(spike_check=2, acc_v2v=2),
                  "none": InstrCount()}[neuron]
    upd = InstrCount(*(x * tiles.col_tiles * batch_t for x in per_update))
    return cnt + upd


def count_skipped_instructions_from_events(total_events: int, batch_t: int,
                                           n_in: int, n_out: int
                                           ) -> InstrCount:
    """Instruction cycles event-driven execution *never issues* for a
    (n_in -> n_out) FC layer: every silent (frame, input-row) pair would
    have cost 2 AccW2V cycles per column tile on a dense scan. This is the
    row-granular skip model of Fig. 11b — the complement of
    `count_layer_instructions_from_events`, so

        executed + skipped == the dense tally at sparsity 0

    holds exactly (neuron-update and AccV2V-reduction cycles are
    unconditional and appear only on the executed side)."""
    from repro.core import mapping
    silent = batch_t * n_in - int(total_events)
    if silent < 0:
        raise ValueError(f"event count {total_events} exceeds the "
                         f"{batch_t * n_in} (frame, row) sites of a "
                         f"{n_in}->{n_out} layer over {batch_t} frames")
    tiles = mapping.fc_tiling(n_in, n_out)
    return InstrCount(acc_w2v=2 * silent * tiles.col_tiles)


def count_layer_instructions(spike_raster: np.ndarray, n_in: int, n_out: int,
                             neuron: str) -> InstrCount:
    """Instruction cycles to run a (n_in -> n_out) FC layer for a spike raster
    of shape (T, ..., n_in). See `count_layer_instructions_from_events`.
    """
    spikes_per_t = np.asarray(spike_raster).reshape(spike_raster.shape[0], -1, n_in)
    total_events = int(spikes_per_t.sum())
    batch_t = spikes_per_t.shape[0] * spikes_per_t.shape[1]
    return count_layer_instructions_from_events(total_events, batch_t,
                                                n_in, n_out, neuron)
