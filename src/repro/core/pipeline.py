"""Compiled network-level SNN programs with pluggable execution backends.

IMPULSE's architectural claim is *fusion*: W_MEM and V_MEM share one array so
the membrane state never crosses a memory boundary. Before this module, that
fusion was only realized per layer, and the network loop around it was
re-implemented four times (float training, integer ISA, per-layer Pallas,
bit-level macro). `compile_network` lifts the network itself into a first-
class object — an `SNNProgram` describing the full stack (encoder -> on-
macro convs (im2col-lowered, mapping.py) -> spiking FCs -> accumulate
readout, thresholds/leaks/scales, multi-macro tiling) — executed by a
registry of backends that are tested to agree bit-for-bit:

  float    — QAT training semantics (surrogate gradients, fake-quant
             weights). For integer-domain programs it executes the *same*
             integer program in f32 (exact: all values < 2^24), which is the
             equivalence bridge between training and deployment.
  int_ref  — word-level ISA semantics (isa.layer_timestep_int scanned over
             the network), the functional contract of the silicon.
  pallas   — the network-level fused TPU kernel (kernels/fused_snn_net):
             every layer's V tile lives in VMEM scratch across the entire
             timestep loop and inter-layer spikes never touch HBM — the
             network-scale analogue of the macro's fused array.
  bitmacro — the bit-accurate column/bitline model (silicon oracle; wrap
             arithmetic only, as on silicon; fan-in > 128 layers split over
             row-tiled macros whose partial sums reduce with word-level
             AccV2V cycles, conv layers lower via im2col, and frames beyond
             13 neuron sets claim extra macro banks).

Instruction counting is a *program-level pass* (`count_network_instructions`)
over the spike rasters, so every backend reports identical energy-model
inputs by construction.

See DESIGN.md §3 for the pipeline/backends diagram and the VMEM-residency
argument.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.impulse_snn import SNNModelConfig
from repro.core import isa, mapping
from repro.core.neuron import NeuronState, neuron_step
from repro.core.quant import (clamp_v, fake_quant_w, quantize_neuron_const,
                              quantize_w, spike_compare)

# ---------------------------------------------------------------------------
# Program representation
# ---------------------------------------------------------------------------

# Layer kinds:
#   encoder — off-macro neuron layer over raw input current (identity weight)
#   conv    — conv transform + neuron dynamics. The FIRST conv of a stack is
#             the off-macro spike encoder (float weights, like the paper's
#             input layer); later convs are on-macro in the int domain
#             (scale set, int8 HWIO kernel, im2col-lowered — mapping.py)
#   fc      — spiking FC layer (on-macro)
#   readout — accumulate-only FC (prediction = final V_MEM)
LAYER_KINDS = ("encoder", "conv", "fc", "readout")


@dataclass(frozen=True)
class LayerSpec:
    kind: str
    n_in: int
    n_out: int
    w: Any = None                 # float weights | int8 wq (program.domain)
    threshold: Any = None         # float | int on the layer's fixed-point grid
    leak: Any = None
    scale: Any = None             # int domain: float <-> grid scale
    stride: int = 1               # conv only
    quantize: bool = True         # float domain: fake-quant this layer's w
    state_shape: tuple = ()       # per-example V shape (set at compile)
    recurrent: bool = False       # fc only: w is [W_in; W_rec], the layer's
                                  # previous-tick spikes drive the W_rec rows

    @property
    def fan_in(self) -> int:
        """W_MEM rows of the layer: n_in, plus n_out recurrent rows."""
        return self.n_in + self.n_out if self.recurrent else self.n_in

    @property
    def tiling(self) -> mapping.FCTiling:
        """This layer's macro-grid tiling (row/col tile counts for the
        fan_in x n_out weight block — `mapping.fc_tiling`)."""
        return mapping.fc_tiling(self.fan_in, self.n_out)


@dataclass(frozen=True)
class SNNProgram:
    cfg: Optional[SNNModelConfig]
    domain: str                   # "float" (QAT training) | "int" (deployed)
    neuron: str                   # if | lif | rmp
    timesteps: int                # presentation steps per input frame
    layers: tuple                 # tuple[LayerSpec, ...]
    clamp_mode: str = "saturate"  # int domain V_MEM policy (see quant.clamp_v)
    quantize: bool = True         # float domain: QAT fake-quant on

    @property
    def fc_stack(self) -> tuple:
        """The FC part of the on-macro stack: spiking FCs + readout."""
        return tuple(ly for ly in self.layers if ly.kind in ("fc", "readout"))

    @property
    def int_conv_stack(self) -> tuple:
        """On-macro conv layers (int domain only: quantized, scale set).
        The first conv of a stack is the off-macro encoder and never
        appears here."""
        return tuple(ly for ly in self.layers
                     if ly.kind == "conv" and ly.scale is not None)

    @property
    def macro_stack(self) -> tuple:
        """Everything that executes on macros: on-macro convs (im2col-
        lowered), spiking FCs, readout — the layers instruction counting
        and the integer backends iterate over."""
        return self.int_conv_stack + self.fc_stack

    @property
    def recurrent_layers(self) -> tuple:
        """Indices into ``layers`` of the recurrent FC layers, in order —
        the layers whose last spike raster is carried state beside V."""
        return tuple(i for i, ly in enumerate(self.layers) if ly.recurrent)

    @property
    def neuron_layers(self) -> tuple:
        """Layers with membrane dynamics that emit spikes."""
        return tuple(ly for ly in self.layers if ly.kind != "readout")

    def logits(self, v_out: jax.Array) -> jax.Array:
        """Readout V ``v_out`` (..., n_out) -> float logits of the same
        shape (undo the last layer's weight scale)."""
        if self.domain == "int":
            return v_out.astype(jnp.float32) * self.layers[-1].scale
        return v_out

    # -- streaming execution (DESIGN.md §3 "Streaming execution & serving")
    def init_state(self, batch: int, backend: str = "float") -> "StreamState":
        """Fresh per-layer membrane state for ``batch`` streams."""
        return init_stream_state(self, batch, backend)

    def step(self, state: "StreamState", frame: jax.Array,
             backend: str = "float", **kw
             ) -> "tuple[StreamState, StreamOut]":
        """Advance every stream one tick on a (B, ...) current frame."""
        return stream_step(self, state, frame, backend, **kw)

    def megastep(self, state: "StreamState", frames: jax.Array,
                 backend: str = "float", **kw
                 ) -> "tuple[StreamState, MegastepOut]":
        """Advance every stream of ``state`` K ticks on a (K, B, ...)
        ``frames`` block in one ``backend`` dispatch; ``kw`` passes
        through to `stream_megastep` (active / emit_rasters / mesh /
        kernel knobs)."""
        return stream_megastep(self, state, frames, backend, **kw)


@dataclass
class NetResult:
    """What one backend run produces. ``rasters[i]`` is the *input* spike
    raster of macro-stack layer i (so rasters[0] is the encoder output) —
    (T_total, B, n) flat for FC layers, (T_total, B, H, W, C) spike maps
    feeding conv layers; ``v_final`` lists final V per layer, readout
    last."""
    v_out: jax.Array
    logits: jax.Array
    v_final: list
    rasters: Optional[list] = None
    aux: dict = field(default_factory=dict)


# ---------------------------------------------------------------------------
# Compilation
# ---------------------------------------------------------------------------

def conv2d(x: jax.Array, w: jax.Array, stride: int) -> jax.Array:
    """SAME-padded 2-D convolution of NHWC ``x`` with HWIO kernel ``w``
    at ``stride`` — the one conv primitive every domain lowers through."""
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _conv_state_shapes(cfg: SNNModelConfig, convs: list) -> list:
    x = jnp.zeros((1, *cfg.in_shape))
    shapes = []
    for c, (_, _, stride) in zip(convs, cfg.conv_spec):
        x = jax.eval_shape(lambda a, w, s=stride: conv2d(a, w, s), x, c["w"])
        shapes.append(tuple(x.shape[1:]))
        x = jnp.zeros(x.shape, x.dtype)
    return shapes


def compile_network(cfg: SNNModelConfig, params: dict, *, domain: str = "float",
                    clamp_mode: str = "saturate", quantize: bool = True,
                    validate: bool = True) -> SNNProgram:
    """Lower (cfg, params) to an executable network program.

    ``domain="float"`` keeps the trainable parameterization (softplus'd
    thresholds/leaks, fake-quant weights) — differentiable, used for QAT.
    ``domain="int"`` quantizes every on-macro layer onto its 6b/11b grid
    (the deployed macro program); the encoder — the first FC *or conv*
    layer — stays float (off-macro input layer, as in the paper). On-macro
    convs keep their HWIO int8 kernel plus the im2col fan-in geometry
    (n_in = k*k*c_in — the 128-row rule, mapping.conv_tiling).

    Neuron constants quantize through `quant.quantize_neuron_const`, which
    folds them into the 11-bit V word under the program's clamp mode — a
    wrap-mode constant that rounds outside [V_MIN, V_MAX] wraps exactly as
    the datapath would read it, instead of clipping to a value no V op
    ever computes against.

    ``validate`` (default on) runs the static analyzer over the compiled
    program before returning it: `repro.analysis.check_program` proves the
    per-layer value ranges (no int32 accumulator overflow at the
    program's timestep horizon) and `check_kernel_contracts` verifies the
    fused-kernel dispatch geometry — a mis-configured program is rejected
    with a named `AnalysisError` at compile time, not mid-dispatch.
    """
    th = jax.nn.softplus(params["threshold"]) + 1e-3
    lk = jax.nn.softplus(params["leak"]) * 0.1
    layers: list[LayerSpec] = []
    k = 0                                         # neuron-layer index into th/lk

    convs = params.get("convs", [])
    if convs:
        shapes = _conv_state_shapes(cfg, convs)
        c_in = cfg.in_shape[-1]
        for i, (c, shape) in enumerate(zip(convs, shapes)):
            kh, kw = c["w"].shape[:2]
            if domain == "int" and i > 0:         # on-macro conv
                wq, scale = quantize_w(c["w"])
                layers.append(LayerSpec(
                    kind="conv", n_in=kh * kw * c_in, n_out=shape[-1],
                    w=wq,
                    threshold=jnp.int32(quantize_neuron_const(
                        float(th[k]), scale, clamp_mode)),
                    leak=jnp.int32(quantize_neuron_const(
                        float(lk[k]), scale, clamp_mode)),
                    scale=float(scale), stride=cfg.conv_spec[i][2],
                    quantize=False, state_shape=shape))
            else:                                 # float / encoder conv
                layers.append(LayerSpec(
                    kind="conv", n_in=kh * kw * c_in,
                    n_out=shape[-1], w=c["w"], threshold=th[k], leak=lk[k],
                    stride=cfg.conv_spec[i][2], quantize=(i > 0),
                    state_shape=shape))
            c_in = shape[-1]
            k += 1
    else:
        # word/current encoder: identity weight, neuron dynamics
        d_in = cfg.layer_sizes[0]
        layers.append(LayerSpec(kind="encoder", n_in=d_in, n_out=d_in,
                                threshold=th[k], leak=lk[k],
                                state_shape=(d_in,)))
        k += 1

    sizes = cfg.layer_sizes
    fc_ws = params["layers"]
    for j, (n_in, n_out) in enumerate(zip(sizes[:-1], sizes[1:])):
        is_readout = j == len(fc_ws) - 1
        rec = not is_readout and cfg.is_recurrent(j)
        w = fc_ws[j]["w"]
        if w.shape[0] != (n_in + n_out if rec else n_in):
            raise ValueError(
                f"fc layer {j} ({n_in}x{n_out}, "
                f"{'recurrent' if rec else 'feed-forward'}) needs "
                f"{n_in + n_out if rec else n_in} weight rows, got "
                f"{w.shape[0]}; a recurrent layer's block is [W_in; W_rec]")
        if domain == "int":
            # a recurrent block [W_in; W_rec] quantizes on ONE 6-bit grid:
            # the W_rec rows are further W_MEM rows of the same macro
            wq, scale = quantize_w(w)
            th_i = None if is_readout else jnp.int32(
                quantize_neuron_const(float(th[k]), scale, clamp_mode))
            lk_i = None if is_readout else jnp.int32(
                quantize_neuron_const(float(lk[k]), scale, clamp_mode))
            layers.append(LayerSpec(
                kind="readout" if is_readout else "fc", n_in=n_in, n_out=n_out,
                w=wq, threshold=th_i, leak=lk_i, scale=float(scale),
                state_shape=(n_out,), recurrent=rec))
        else:
            layers.append(LayerSpec(
                kind="readout" if is_readout else "fc", n_in=n_in, n_out=n_out,
                w=w, threshold=None if is_readout else th[k],
                leak=None if is_readout else lk[k], state_shape=(n_out,),
                recurrent=rec))
        if not is_readout:
            k += 1

    program = SNNProgram(cfg=cfg, domain=domain, neuron=cfg.spiking.neuron,
                         timesteps=cfg.timesteps, layers=tuple(layers),
                         clamp_mode=clamp_mode, quantize=quantize)
    if validate:
        # lazy import: analysis consumes programs, pipeline produces them —
        # the compile-time hook must not create an import cycle
        from repro.analysis import validate_program
        validate_program(program)
    return program


def rate_coded_program(spiking_cfg, state_shape: tuple) -> SNNProgram:
    """Single-population program (used by models/spiking_ffn): one encoder
    layer of per-example V shape ``state_shape`` integrating a constant
    current, thresholds/leaks taken verbatim from ``spiking_cfg`` (no
    softplus re-parameterization)."""
    layer = LayerSpec(kind="encoder", n_in=state_shape[-1],
                      n_out=state_shape[-1], threshold=spiking_cfg.threshold,
                      leak=spiking_cfg.leak, state_shape=state_shape)
    return SNNProgram(cfg=None, domain="float", neuron=spiking_cfg.neuron,
                      timesteps=spiking_cfg.timesteps, layers=(layer,),
                      quantize=False)


# ---------------------------------------------------------------------------
# Input presentation
# ---------------------------------------------------------------------------

def present_words(x_words: jax.Array, timesteps: int) -> jax.Array:
    """``x_words`` (B, n_words, d) -> (n_words * timesteps, B, d): each
    word held ``timesteps`` steps (membrane state persists across words —
    the sequential-memory claim)."""
    xs = jnp.repeat(x_words, timesteps, axis=1)
    return jnp.moveaxis(xs, 1, 0)


def present_static(x: jax.Array, timesteps: int) -> jax.Array:
    """``x`` (B, ...) -> (timesteps, B, ...): direct encoding, the same
    frame presented every step."""
    return jnp.broadcast_to(x[None], (timesteps, *x.shape))


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

BACKENDS: dict[str, Callable] = {}


def register_backend(name: str) -> Callable:
    """Decorator registering an execution backend under ``name`` in
    `BACKENDS` (the `run_network` dispatch table)."""
    def deco(fn: Callable) -> Callable:
        BACKENDS[name] = fn
        return fn
    return deco


def run_network(program: SNNProgram, xs: jax.Array, backend: str = "float",
                **kw) -> NetResult:
    """Execute a program on per-timestep input currents xs (T_total, B, ...).

    The float backend consumes xs directly. Integer backends share one float
    encoder pass (`encode`) — the off-macro input layer — then execute the
    on-macro fc stack in their own substrate.

    ``mesh`` (int backends only): a `jax.sharding.Mesh` with "data" and/or
    "model" axes — lanes partition over data, row-tiled fan-in over model
    with an exact integer-psum AccV2V reduction. Results are bit-identical
    to the single-device path (see DESIGN.md "Mesh execution"). The float
    backend's f32 reductions are not order-exact and the bitmacro oracle
    is host-side state; both reject a mesh with ValueError.
    """
    if backend not in BACKENDS:
        raise KeyError(f"unknown backend {backend!r}; have {sorted(BACKENDS)}")
    if backend != "float" and program.domain != "int":
        raise ValueError(f"backend {backend!r} needs an int-domain program "
                         "(compile_network(..., domain='int'))")
    _refuse_recurrent(program, backend, use_sparse=kw.get("use_sparse",
                                                          False),
                      mesh=kw.get("mesh"))
    if backend in ("float", "bitmacro"):
        if kw.pop("mesh", None) is not None:
            raise ValueError(
                f"backend {backend!r} has no mesh execution: float "
                "reductions are not bitwise order-exact across shards and "
                "bitmacro state lives in host BitMacro objects; use an int "
                "device backend (int_ref/pallas/pallas_sparse/ref_events/"
                "pallas_events)")
    return BACKENDS[backend](program, xs, **kw)


def _refuse_recurrent(program: SNNProgram, backend: str, *,
                      use_sparse: bool = False, mesh=None) -> None:
    """Named refusal (`analysis.ContractError`, contract ``recurrent``)
    of a dispatch that cannot execute this program's recurrent layers;
    feed-forward programs pass untouched."""
    if program.recurrent_layers:
        from repro.analysis.kernel_contracts import check_recurrent
        check_recurrent(program, backend, use_sparse=use_sparse, mesh=mesh)


# ---------------------------------------------------------------------------
# float backend — the single temporal executor for training AND the f32
# rendering of integer programs (exact: every value is an integer < 2^24)
# ---------------------------------------------------------------------------

def _w_float(program: SNNProgram, spec: LayerSpec) -> jax.Array:
    if program.domain == "int":
        return spec.w.astype(jnp.float32)
    if program.quantize and spec.quantize:
        return fake_quant_w(spec.w)
    return spec.w


def _float_step(program: SNNProgram, vs: list, xt: jax.Array,
                ss: tuple = ()) -> tuple[list, list, tuple]:
    """One network timestep. ``ss`` holds the previous tick's spikes of
    each recurrent layer (program order), which drive its W_rec rows.
    Returns (new vs, per-neuron-layer spikes, new ss)."""
    neuron = program.neuron
    int_dom = program.domain == "int"
    cur = xt
    vs_new, spikes = [], []
    ss_prev, ss_new = iter(ss), []
    for i, spec in enumerate(program.layers):
        if spec.kind == "readout":
            if cur.ndim > 2:
                cur = cur.reshape(cur.shape[0], -1)
            vs_new.append(vs[i] + cur @ _w_float(program, spec))
            continue
        if spec.kind == "conv":
            current = conv2d(cur, _w_float(program, spec), spec.stride)
        elif spec.kind == "fc":
            if cur.ndim > 2:
                cur = cur.reshape(cur.shape[0], -1)
            if spec.recurrent:              # [s_in(t), s(t-1)] @ [W_in; W_rec]
                cur = jnp.concatenate([cur, next(ss_prev)], axis=-1)
            current = cur @ _w_float(program, spec)
        else:                                     # encoder: identity weight
            current = cur
        if int_dom and spec.scale is not None:    # on-macro (fc or conv)
            # f32 rendering of isa.layer_timestep_int (bit-exact; for convs
            # conv2d == the im2col matmul per position, exactly, in int
            # arithmetic rendered in f32 — all values < 2^24)
            th = spec.threshold.astype(jnp.float32)
            v = clamp_v(vs[i] + current, program.clamp_mode)
            if neuron == "lif":
                v = clamp_v(v - spec.leak.astype(jnp.float32),
                            program.clamp_mode)
            s = spike_compare(v, th, program.clamp_mode).astype(jnp.float32)
            if neuron == "rmp":
                v = clamp_v(jnp.where(s > 0, v - th, v), program.clamp_mode)
            else:
                v = jnp.where(s > 0, 0.0, v)
        else:
            st, s = neuron_step(NeuronState(vs[i]), current, neuron=neuron,
                                threshold=spec.threshold, leak=spec.leak)
            v = st.v
        vs_new.append(v)
        spikes.append(s)
        if spec.recurrent:
            ss_new.append(s)
        cur = s
    return vs_new, spikes, tuple(ss_new)


def _init_vs(program: SNNProgram, batch: int) -> list:
    return [jnp.zeros((batch, *spec.state_shape)) for spec in program.layers]


def _init_spikes(program: SNNProgram, batch: int, dtype) -> tuple:
    """s(-1) = 0 of every recurrent layer: (batch, n_out) each."""
    return tuple(jnp.zeros((batch, program.layers[i].n_out), dtype)
                 for i in program.recurrent_layers)


@register_backend("float")
def run_float(program: SNNProgram, xs: jax.Array, *, return_trace: bool = False,
              collect_rasters: bool = False, collect_sums: bool = False,
              static_input: bool = False) -> NetResult:
    """Differentiable scan over the whole presentation. Aux always carries
    per-step mean spike rates; ``collect_rasters`` additionally stacks the
    full per-layer rasters, ``collect_sums`` carries per-layer spike-count
    sums (rate decoding without materializing rasters).

    ``static_input``: xs is a single (B, ...) frame presented every step
    (direct encoding); the scan closes over it instead of taking a
    timesteps-fold broadcast as a loop operand (which would materialize
    T copies of the activation on training hot paths)."""
    B = xs.shape[0] if static_input else xs.shape[1]
    n_neuron = len(program.neuron_layers)

    def step(carry, xt):
        vs, ss, sums = carry
        vs, spikes, ss = _float_step(program, vs, xt, ss)
        rates = jnp.stack([s.mean() for s in spikes])
        if collect_sums:
            sums = [c + s for c, s in zip(sums, spikes)]
        trace = vs[-1][:, 0] if return_trace else jnp.zeros(B)
        out = (rates, trace, tuple(spikes) if collect_rasters else ())
        return (vs, ss, sums), out

    sums0 = [jnp.zeros((B, *spec.state_shape))
             for spec in program.neuron_layers] if collect_sums else [0.0] * n_neuron
    carry0 = (_init_vs(program, B), _init_spikes(program, B, jnp.float32),
              sums0)
    if static_input:
        (vs, _, sums), (rates, trace, rasters) = jax.lax.scan(
            lambda c, _: step(c, xs), carry0, None, length=program.timesteps)
    else:
        (vs, _, sums), (rates, trace, rasters) = jax.lax.scan(step, carry0,
                                                              xs)
    aux = {"spike_rates": rates, "v_trace": trace}
    if collect_sums:
        aux["spike_sums"] = sums
    v_out = vs[-1]
    return NetResult(v_out=v_out, logits=program.logits(v_out), v_final=vs,
                     rasters=list(rasters) if collect_rasters else None,
                     aux=aux)


# ---------------------------------------------------------------------------
# shared float encoder for the integer backends (off-macro input layer)
# ---------------------------------------------------------------------------

def _encoder_weight(program: SNNProgram, enc: LayerSpec):
    """The conv encoder's effective weight (fake-quant in QAT programs)."""
    return enc.w if not (program.quantize and enc.quantize) \
        else fake_quant_w(enc.w)


def encoder_step(program: SNNProgram, v_enc: jax.Array, frame: jax.Array
                 ) -> tuple[jax.Array, jax.Array]:
    """One tick of the off-macro encoder layer: carried membrane V plus a
    (B, ...) current frame -> (new V, (B, ...) int8 spikes). `encode` scans
    exactly this function, so frame-by-frame streaming reproduces the
    batch raster bit for bit."""
    enc = program.layers[0]
    if enc.kind == "encoder":
        current = frame
    elif enc.kind == "conv":
        current = conv2d(frame, _encoder_weight(program, enc), enc.stride)
    else:
        raise ValueError(
            f"integer backends need an encoder- or conv-led stack, but this "
            f"program's first layer is kind={enc.kind!r} "
            f"({enc.n_in}x{enc.n_out}); FC programs start with an 'encoder' "
            f"layer and conv programs with the conv spike encoder")
    st, s = neuron_step(NeuronState(v_enc), current, neuron=program.neuron,
                        threshold=enc.threshold, leak=enc.leak)
    return st.v, s.astype(jnp.int8)


def encode(program: SNNProgram, xs: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Run ``program``'s off-macro encoder layer alone on ``xs``:
    (T_total, B, ...) currents ->
    ((T_total, B, ...) int8 spikes, final encoder V). Bitwise identical to
    the float backend's encoder layer (same ops on the same values). For
    conv stacks the encoder is the first conv (float weights, spike maps
    out); for FC stacks it is the identity-weight input layer."""
    enc = program.layers[0]
    if enc.kind not in ("encoder", "conv"):
        # same error as the per-tick entry; raise eagerly, not inside scan
        encoder_step(program, None, None)
    v0 = jnp.zeros((xs.shape[1], *enc.state_shape)) if enc.kind == "conv" \
        else jnp.zeros(xs.shape[1:])
    v_enc, spikes = jax.lax.scan(
        lambda v, xt: encoder_step(program, v, xt), v0, xs)
    return spikes, v_enc


def _assemble(program: SNNProgram, rasters: list, v_enc, v_stack: list
              ) -> NetResult:
    v_out = v_stack[-1]
    return NetResult(v_out=v_out, logits=program.logits(v_out),
                     v_final=[v_enc] + list(v_stack), rasters=rasters)


# ---------------------------------------------------------------------------
# int_ref backend — word-level ISA semantics scanned over the network
# ---------------------------------------------------------------------------

def _stack_kernel_args(program: SNNProgram) -> dict:
    """The fused_snn_net argument marshalling shared by int_ref and pallas —
    one place to extend when the stack grows per-layer parameters."""
    stack = program.fc_stack
    kw = dict(
        ws=[jnp.asarray(spec.w) for spec in stack],
        thresholds=tuple(int(spec.threshold) for spec in stack[:-1]),
        leaks=tuple(int(spec.leak) for spec in stack[:-1]),
        neuron=program.neuron, clamp_mode=program.clamp_mode)
    if program.recurrent_layers:      # feed-forward calls keep their key
        kw["recurrent"] = tuple(spec.recurrent for spec in stack[:-1])
    return kw


def _host_events_sharded(spikes, ws, *, mesh, v_init=None, **kw):
    """`ref_events` under a mesh: the host spike-list executor has no
    device placement, so lane (data-axis) partitioning is simulated —
    the batch splits into contiguous per-shard slices executed
    sequentially, rasters/V reassemble by concatenation, and the
    per-slice `EventStats` merge exactly (row events and frame counts
    are sums; lanes never interact). The model axis is a no-op for a
    host oracle — row-tile partials are a device concept — so this path
    validates lane partitioning only."""
    from repro.kernels.fused_snn_net.events import (EventStats,
                                                    fused_snn_net_events)
    from repro.kernels.fused_snn_net.ops import mesh_axis_extents
    n_data, _ = mesh_axis_extents(mesh)
    B = int(spikes.shape[1])
    bounds = [B * k // n_data for k in range(n_data + 1)]
    rs, vs, sts = [], [], []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        if hi == lo:
            continue
        vi = ([np.asarray(v)[lo:hi] for v in v_init]
              if v_init is not None else None)
        r, v, st = fused_snn_net_events(spikes[:, lo:hi], ws, v_init=vi,
                                        **kw)
        rs.append(r)
        vs.append(v)
        sts.append(st)
    rasters = [jnp.concatenate([np.asarray(r[i]) for r in rs], axis=1)
               for i in range(len(rs[0]))]
    v_finals = [jnp.concatenate([np.asarray(v[i]) for v in vs], axis=0)
                for i in range(len(ws))]
    stats = EventStats(
        row_events=tuple(
            sum(np.asarray(st.row_events[i], np.int64) for st in sts)
            for i in range(len(ws))),
        frames=sum(st.frames for st in sts),
        dense_fallbacks=())
    return rasters, v_finals, stats


def _run_fc_stack(program: SNNProgram, spikes: jax.Array, *, use_pallas: bool,
                  use_sparse: bool, block_b: int, interpret: bool,
                  emit_rasters: bool, gate_granularity: int = 1,
                  use_events: bool = False, v_init: Optional[list] = None,
                  event_crossover: float = 1.0, mesh=None,
                  s_init: Optional[list] = None):
    """The fc stack's dispatch: (rasters, v_stack, skips, s_stack), with
    ``s_stack`` the last spike raster of each recurrent layer (empty for
    a feed-forward stack); ``s_init`` resumes it as ``v_init`` resumes V.
    Only the dense executors take recurrent layers: every other path
    refuses them before dispatch (`_refuse_recurrent`)."""
    kw = _stack_kernel_args(program)
    if "recurrent" in kw:             # feed-forward calls keep their keys
        kw["s_init"] = s_init
    if mesh is not None:
        if use_events and not use_pallas:    # host spike-list executor
            out = _host_events_sharded(
                spikes, kw.pop("ws"), mesh=mesh,
                emit_rasters=emit_rasters, v_init=v_init, **kw)
        else:
            from repro.kernels.fused_snn_net.ops import fused_snn_net_mesh
            out = fused_snn_net_mesh(
                spikes, kw.pop("ws"), mesh=mesh, use_pallas=use_pallas,
                use_sparse=use_sparse, gate_granularity=gate_granularity,
                block_b=block_b, interpret=interpret,
                emit_rasters=emit_rasters, v_init=v_init,
                use_events=use_events, event_crossover=event_crossover,
                **kw)
    elif use_events and use_pallas:      # device event-list kernel
        from repro.kernels.fused_snn_net.ops import fused_snn_net_device_events
        out = fused_snn_net_device_events(
            spikes, kw.pop("ws"), block_b=block_b, interpret=interpret,
            emit_rasters=emit_rasters, v_init=v_init,
            event_crossover=event_crossover, **kw)
    elif use_events:                     # host spike-list executor
        from repro.kernels.fused_snn_net.events import fused_snn_net_events
        out = fused_snn_net_events(spikes, kw.pop("ws"),
                                   emit_rasters=emit_rasters,
                                   v_init=v_init, **kw)
    else:
        from repro.kernels.fused_snn_net.ops import fused_snn_net
        out = fused_snn_net(
            spikes, kw.pop("ws"), use_pallas=use_pallas,
            use_sparse=use_sparse, gate_granularity=gate_granularity,
            block_b=block_b, interpret=interpret,
            emit_rasters=emit_rasters, v_init=v_init, **kw)
    return tuple(out) if "recurrent" in kw else (*out, [])


def run_stack_from_raster(program: SNNProgram, spikes_enc: jax.Array, *,
                          use_pallas: bool = False, use_sparse: bool = False,
                          block_b: int = 8, interpret: bool = False,
                          emit_rasters: bool = True,
                          gate_granularity: int = 1):
    """Execute only ``program``'s on-macro fc stack on a supplied encoder
    spike raster ``spikes_enc`` (T_total, B, d) int8 — the public
    raster-in entry point that raster-driven benchmarks (synthetic
    sparsity sweeps) share with the int_ref/pallas backends
    (``use_pallas`` / ``use_sparse`` / ``block_b`` / ``interpret`` /
    ``gate_granularity`` mirror `run_network`'s backend kwargs).
    Returns (rasters, v_stack, skips) with
    ``rasters[0]`` the input raster itself, aligned with
    `count_network_instructions` / `sparsity_report` expectations. Conv
    programs carry an on-macro conv front-end and route through
    `run_network` instead."""
    if program.int_conv_stack:
        raise ValueError("run_stack_from_raster executes the fc stack only; "
                         "this program has on-macro conv layers — execute it "
                         "through run_network (int_ref/pallas backends)")
    _refuse_recurrent(program, "pallas" if use_pallas else "int_ref",
                      use_sparse=use_sparse)
    rasters, v_stack, skips, _ = _run_fc_stack(
        program, spikes_enc, use_pallas=use_pallas, use_sparse=use_sparse,
        gate_granularity=gate_granularity, block_b=block_b,
        interpret=interpret, emit_rasters=emit_rasters)
    full = [spikes_enc] + list(rasters) if emit_rasters else None
    return full, list(v_stack), skips


def _conv_front_end(program: SNNProgram, spikes_enc: jax.Array, *,
                    use_pallas: bool, use_sparse: bool, block_b: int,
                    interpret: bool, gate_granularity: int = 1,
                    use_events: bool = False, v_init: Optional[list] = None,
                    event_crossover: float = 1.0, mesh=None):
    """Run the on-macro int conv layers on encoder spike maps. Each conv
    layer lowers onto the macro grid via im2col (mapping.py): its
    (T, B, H, W, C) input maps become a (T, B*P, k*k*C) patch raster —
    one frame per (example, output position), each claiming a V_MEM neuron
    set — executed by the same fused_snn_net machinery as the fc stack
    (readout=False), so the Pallas kernel, the jnp reference, event gating
    at any granularity, and the event-list executor all serve conv
    programs unchanged. Returns (maps, v_convs, conv_skips): per-layer
    output spike maps (T, B, H_out, W_out, C_out) int8, final V maps, and
    per-layer gate counts (None entries when dense; `events.EventStats`
    entries on the event-list path)."""
    from repro.kernels.fused_snn_net.events import fused_snn_net_events
    from repro.kernels.fused_snn_net.ops import (fused_snn_net,
                                                 fused_snn_net_device_events)
    maps, v_convs, conv_skips = [], [], []
    cur = spikes_enc
    for ci, spec in enumerate(program.int_conv_stack):
        t_total, batch = cur.shape[:2]
        patches = mapping.im2col_raster(cur, spec.w.shape[0], spec.stride)
        out_hw = mapping.conv_out_hw(cur.shape[2:4], spec.w.shape[0],
                                     spec.stride)
        kw = dict(thresholds=(int(spec.threshold),), leaks=(int(spec.leak),),
                  neuron=program.neuron, clamp_mode=program.clamp_mode,
                  readout=False, emit_rasters=True)
        vi = None
        if v_init is not None:
            # conv V state is a (B, H_out, W_out, C) map; the macro executes
            # one frame per (example, output position) — flatten to match
            vi = [jnp.asarray(v_init[ci]).reshape(-1, spec.n_out)]
        if mesh is not None and use_events and not use_pallas:
            # host spike-list executor under a mesh: the patch raster's
            # frame axis is (example, output position) — contiguous
            # per-shard slices are whole frames, so the lane-partition
            # argument applies unchanged
            rasters, v, skips = _host_events_sharded(
                patches.astype(jnp.int8),
                [np.asarray(mapping.pack_conv_weights(spec.w))],
                mesh=mesh, v_init=vi, **kw)
        elif mesh is not None:
            from repro.kernels.fused_snn_net.ops import fused_snn_net_mesh
            rasters, v, skips = fused_snn_net_mesh(
                patches.astype(jnp.int8),
                [jnp.asarray(mapping.pack_conv_weights(spec.w))],
                mesh=mesh, use_pallas=use_pallas, use_sparse=use_sparse,
                gate_granularity=gate_granularity, block_b=block_b,
                interpret=interpret, use_events=use_events,
                event_crossover=event_crossover, v_init=vi, **kw)
        elif use_events and use_pallas:  # device event-list kernel
            rasters, v, skips = fused_snn_net_device_events(
                patches.astype(jnp.int8),
                [jnp.asarray(mapping.pack_conv_weights(spec.w))],
                block_b=block_b, interpret=interpret,
                event_crossover=event_crossover, v_init=vi, **kw)
        elif use_events:                 # host spike-list executor
            rasters, v, skips = fused_snn_net_events(
                patches.astype(jnp.int8),
                [np.asarray(mapping.pack_conv_weights(spec.w))],
                v_init=vi, **kw)
            rasters = [jnp.asarray(r) for r in rasters]
        else:
            rasters, v, skips = fused_snn_net(
                patches.astype(jnp.int8),
                [jnp.asarray(mapping.pack_conv_weights(spec.w))],
                use_pallas=use_pallas, use_sparse=use_sparse,
                gate_granularity=gate_granularity, block_b=block_b,
                interpret=interpret, v_init=vi, **kw)
        cur = rasters[0].reshape(t_total, batch, *out_hw, spec.n_out)
        maps.append(cur)
        v_convs.append(jnp.asarray(v[0]).reshape(batch, *out_hw, spec.n_out))
        conv_skips.append(skips)
    return maps, v_convs, conv_skips


def _run_macro_stack(program: SNNProgram, xs: jax.Array, *, use_pallas: bool,
                     use_sparse: bool, block_b: int = 8,
                     interpret: bool = False, emit_rasters: bool = True,
                     gate_granularity: int = 1, use_events: bool = False,
                     event_crossover: float = 1.0, mesh=None
                     ) -> NetResult:
    """Shared int_ref/pallas/ref_events/pallas_events executor: float
    encoder pass, then the on-macro conv front-end (when present), then the
    fused fc stack. With ``mesh``, the conv and fc dispatches execute under
    shard_map (`kernels.fused_snn_net.ops.fused_snn_net_mesh`); the float
    encoder stays a single global pass (off-macro, elementwise per lane —
    there is nothing to reduce across shards)."""
    spikes_enc, v_enc = encode(program, xs)
    conv_maps, v_convs, conv_skips = _conv_front_end(
        program, spikes_enc, use_pallas=use_pallas, use_sparse=use_sparse,
        gate_granularity=gate_granularity, use_events=use_events,
        block_b=block_b, interpret=interpret,
        event_crossover=event_crossover, mesh=mesh)
    last = conv_maps[-1] if conv_maps else spikes_enc
    flat = last.reshape(*last.shape[:2], -1) if last.ndim > 3 else last
    rasters_fc, v_stack, skips, _ = _run_fc_stack(
        program, flat, use_pallas=use_pallas, use_sparse=use_sparse,
        gate_granularity=gate_granularity, use_events=use_events,
        block_b=block_b, interpret=interpret, emit_rasters=emit_rasters,
        event_crossover=event_crossover, mesh=mesh)
    # rasters[i] = the input raster of macro-stack layer i: spike maps for
    # the conv part (the last conv's map doubles, flattened, as fc input)
    full = ([spikes_enc] + conv_maps + list(rasters_fc)
            if emit_rasters else None)
    res = _assemble(program, full, v_enc,
                    list(v_convs) + [jnp.asarray(v) for v in v_stack])
    if use_events:
        return _attach_event_stats(res, conv_skips, skips)
    res = _attach_skips(res, skips, xs.shape[0], gate_granularity)
    if use_sparse and conv_skips:
        res.aux["conv_skip_counts"] = [
            [np.asarray(b) for b in s] if isinstance(s, list)
            else np.asarray(s) for s in conv_skips]
    return res


def _site_count(s: np.ndarray) -> int:
    """Gate sites per timestep of one skip-count array: tiles x columns."""
    return s.shape[0] * s.shape[1]


def _attach_skips(res: NetResult, skips, timesteps: int,
                  granularity: int = 1) -> NetResult:
    """Stash event-gating statistics on a result: raw skipped-matmul counts
    plus the aggregate skipped-gate fraction (every gate site fires once
    per timestep). At granularity 1 sites are (tile, layer) pairs and the
    fraction keeps its historical name ``skipped_tile_fraction``; at finer
    granularities sites are (tile, layer, row-block) triples, counts come
    as a per-layer list, and the aggregate is ``skipped_block_fraction``."""
    if skips is None:
        return res
    if granularity == 1:
        skips = np.asarray(skips)
        res.aux["skip_counts"] = skips
        res.aux["skipped_tile_fraction"] = float(skips.sum()) / float(
            timesteps * _site_count(skips))
        return res
    skips = [np.asarray(s) for s in skips]
    res.aux["skip_counts"] = skips
    sites = sum(_site_count(s) for s in skips)
    res.aux["skipped_block_fraction"] = float(
        sum(int(s.sum()) for s in skips)) / float(timesteps * sites)
    return res


def _attach_event_stats(res: NetResult, conv_stats: list, fc_stats
                        ) -> NetResult:
    """Fold the per-layer `events.EventStats` of the conv front-end and the
    fc stack into result aux: per-row event counts, silent-row counts, and
    the overall skipped-row fraction — the event-list executor's skipped
    work is exactly its silent (frame, row) pairs."""
    row_events = [r for st in conv_stats for r in st.row_events]
    row_events += list(fc_stats.row_events)
    frames = [st.frames for st in conv_stats for _ in st.row_events]
    frames += [fc_stats.frames] * len(fc_stats.row_events)
    skipped = [f * len(r) - int(r.sum()) for f, r in zip(frames, row_events)]
    possible = sum(f * len(r) for f, r in zip(frames, row_events))
    res.aux["row_events"] = row_events
    res.aux["row_event_frames"] = frames
    res.aux["row_skip_counts"] = skipped
    res.aux["skipped_row_fraction"] = (sum(skipped) / possible
                                       if possible else 0.0)
    # device event-list kernel only: per-layer dense-crossover trip counts
    # (the host executor never falls back and reports empty tuples)
    fallbacks = [f for st in conv_stats for f in st.dense_fallbacks]
    fallbacks += list(fc_stats.dense_fallbacks)
    if fallbacks:
        res.aux["event_dense_fallbacks"] = fallbacks
    return res


@register_backend("int_ref")
def run_int_ref(program: SNNProgram, xs: jax.Array, *,
                use_sparse: bool = False, mesh=None) -> NetResult:
    """Word-level ISA semantics: the pure-jnp network reference (a scan of
    isa.layer_timestep_int over the fc stack, preceded by the im2col conv
    front-end — `_conv_front_end` -> fused_snn_net(readout=False), whose
    patch-raster execution is tested equal to isa.conv_layer_timestep_int)
    that is also the pallas kernel's non-TPU fallback — one implementation
    of the contract, two entry points. ``use_sparse`` runs the lax.cond
    event-gated variant (bit-identical). ``mesh`` executes the macro stack
    under shard_map, bit-identical to the single-device run (`run_network`
    docs)."""
    return _run_macro_stack(program, xs, use_pallas=False,
                            use_sparse=use_sparse, mesh=mesh)


# ---------------------------------------------------------------------------
# pallas backends — the network-level fused kernel (dense and event-gated)
# ---------------------------------------------------------------------------

def _run_pallas(program: SNNProgram, xs: jax.Array, *, block_b: int,
                interpret: bool, emit_rasters: bool, use_sparse: bool,
                gate_granularity: int = 1, mesh=None) -> NetResult:
    return _run_macro_stack(program, xs, use_pallas=True,
                            use_sparse=use_sparse, block_b=block_b,
                            gate_granularity=gate_granularity,
                            interpret=interpret, emit_rasters=emit_rasters,
                            mesh=mesh)


@register_backend("pallas")
def run_pallas(program: SNNProgram, xs: jax.Array, *, block_b: int = 8,
               interpret: bool = False, emit_rasters: bool = True,
               mesh=None) -> NetResult:
    """The fused multi-layer Pallas kernel (dense): all V tiles stay
    VMEM-resident across the timestep loop. ``block_b`` is the batch tile
    per grid step, ``interpret`` runs the kernel in interpret mode (CPU
    CI), ``mesh`` executes under shard_map — per-shard kernels on the
    data axis, the row-partial psum body on the model axis — bit-identical
    either way."""
    return _run_pallas(program, xs, block_b=block_b, interpret=interpret,
                       emit_rasters=emit_rasters, use_sparse=False,
                       mesh=mesh)


@register_backend("pallas_sparse")
def run_pallas_sparse(program: SNNProgram, xs: jax.Array, *, block_b: int = 8,
                      interpret: bool = False, emit_rasters: bool = True,
                      gate_granularity: int = 1, mesh=None) -> NetResult:
    """Event-gated fused kernel: per (timestep, layer, batch-tile) the MXU
    matmul is predicated on tile occupancy (`@pl.when`), realizing the
    paper's event-driven AccW2V; the neuron update is unconditional, so
    results stay bit-identical to every dense backend.

    ``gate_granularity`` is the sub-tile resolution knob: at 1 each layer's
    whole input tile is one gate (aux: ``skip_counts`` (B_tiles, n_layers)
    and ``skipped_tile_fraction``); at G in {2, 4, 8} each 128-lane
    macro-row tile splits into G independently predicated row blocks (aux:
    ``skip_counts`` as a per-layer list of (B_tiles, n_blocks) arrays and
    ``skipped_block_fraction``)."""
    return _run_pallas(program, xs, block_b=block_b, interpret=interpret,
                       emit_rasters=emit_rasters, use_sparse=True,
                       gate_granularity=gate_granularity, mesh=mesh)


@register_backend("ref_events")
def run_ref_events(program: SNNProgram, xs: jax.Array, *,
                   mesh=None) -> NetResult:
    """Spike-list compaction reference (`kernels/fused_snn_net/events`)
    executing ``program`` on ``xs`` (T_total, B, ...) currents:
    every (timestep, example) frame is compacted to (indices, count) and
    AccW2V becomes a gather-matvec over active rows only — work exactly
    proportional to events, the honest upper bound on skippable work (iid
    sparsity that defeats tile/block gates is fully exploited) and the
    word-level contract for per-row skip accounting. Bit-identical to all
    other backends; aux carries ``row_events`` (per-layer per-input-row
    event counts), ``row_skip_counts`` (silent (frame, row) pairs), and
    ``skipped_row_fraction``. ``mesh`` simulates lane partitioning on the
    host (contiguous per-data-shard slices run sequentially; counters
    merge by summation — the model axis is a documented no-op for this
    host executor)."""
    return _run_macro_stack(program, xs, use_pallas=False, use_sparse=False,
                            use_events=True, mesh=mesh)


@register_backend("pallas_events")
def run_pallas_events(program: SNNProgram, xs: jax.Array, *, block_b: int = 8,
                      interpret: bool = False, emit_rasters: bool = True,
                      event_crossover: float = 1.0, mesh=None) -> NetResult:
    """Device-side event-list execution (kernels/fused_snn_net kernel.py,
    ``events=True``): every (timestep, layer, example) frame is compacted
    *in VMEM* (cumsum position map = the fixed-capacity active-row index
    list) and AccW2V runs as a gather-matvec with a dynamic trip count —
    executed work proportional to events at every sparsity structure,
    closing the gap between the `pallas_sparse` block gates and the
    `ref_events` accounting upper bound. Frames whose tile event count
    exceeds ``event_crossover`` of capacity take the dense matmul fallback
    (bit-identical either way; default 1.0 never trips).

    Aux matches `ref_events` (``row_events`` / ``row_skip_counts`` /
    ``skipped_row_fraction`` — the kernel's counters are tested EQUAL to
    the host executor's `EventStats`) plus ``event_dense_fallbacks``, the
    per-layer dense-fallback trip counts."""
    return _run_macro_stack(program, xs, use_pallas=True, use_sparse=False,
                            use_events=True, block_b=block_b,
                            interpret=interpret, emit_rasters=emit_rasters,
                            event_crossover=event_crossover, mesh=mesh)


# ---------------------------------------------------------------------------
# streaming execution — the program-level step API
#
# IMPULSE's deployment mode is *streaming*: membrane potential is persistent
# per-neuron state fused next to the weights, so sequential inputs arrive
# frame by frame and V simply stays resident. `run_network` consumes a whole
# (T, B, ...) presentation in one call; `init_stream_state` / `stream_step`
# expose the same backends one tick at a time, carrying every layer's V as
# an explicit state tree. Because all on-macro arithmetic is integer (exact)
# and the float encoder executes the identical per-tick ops the batch scan
# executes, driving the batch raster frame-by-frame through `stream_step`
# reproduces `run_network` bit for bit — the contract tests/test_stream.py
# sweeps. serve/snn_engine.py builds continuous batching on top: slot lanes
# of one StreamState tree are the V_MEM analogue of LM KV-cache lanes.
# ---------------------------------------------------------------------------

STREAM_BACKENDS = ("float", "int_ref", "pallas", "pallas_sparse",
                   "ref_events", "pallas_events")


class StreamState(NamedTuple):
    """Carried membrane state of a streaming execution: one V leaf per
    program layer in `program.layers` order (encoder first, readout last),
    each (B, *state_shape). Dtypes are backend-native: all-f32 on the float
    backend, f32 encoder V + int32 macro-stack V on the integer backends.
    ``spikes`` is the second kind of carried state: the last spike raster
    of each recurrent layer (`program.recurrent_layers` order), (B, n_out)
    each — int8 on the integer backends, f32 on the float backend; empty
    for a feed-forward program. A NamedTuple — hence a pytree — so serving
    engines can tree-map lane copies over it when admitting/evicting
    requests."""
    vs: tuple
    t: int = 0           # ticks executed (bookkeeping; dynamics are
                         # time-invariant, so t never enters the math)
    spikes: tuple = ()


@dataclass
class StreamOut:
    """What one `stream_step` tick produces. ``rasters[i]`` is the input
    spike raster of macro-stack layer i for THIS tick, (B, n) flat /
    (B, H, W, C) maps with the T axis squeezed — stacking them over ticks
    rebuilds `NetResult.rasters` exactly (None when emit_rasters=False).
    ``skips``/``conv_skips`` carry the event-gating counts of this tick in
    the same layouts `run_network` aux uses: per-call skip-count arrays on
    the gated paths (summing over ticks equals the batch-run counts) or
    `events.EventStats` on the ref_events path."""
    v_out: Any
    logits: Any
    rasters: Optional[list] = None
    skips: Any = None
    conv_skips: Any = None


def _check_stream_backend(program: SNNProgram, backend: str) -> None:
    if backend not in STREAM_BACKENDS:
        raise KeyError(
            f"unknown streaming backend {backend!r}; have "
            f"{STREAM_BACKENDS} (bitmacro is a host-side verification "
            "oracle whose state lives in BitMacro objects, not a pytree — "
            "it has no streaming entry)")
    if backend != "float" and program.domain != "int":
        raise ValueError(f"backend {backend!r} needs an int-domain program "
                         "(compile_network(..., domain='int'))")


def init_stream_state(program: SNNProgram, batch: int,
                      backend: str = "float") -> StreamState:
    """Fresh (all-zero V) state for ``batch`` independent streams."""
    _check_stream_backend(program, backend)
    vs = []
    for i, spec in enumerate(program.layers):
        dtype = jnp.float32 if (backend == "float" or i == 0) else jnp.int32
        vs.append(jnp.zeros((batch, *spec.state_shape), dtype))
    return StreamState(vs=tuple(vs), t=0, spikes=_init_spikes(
        program, batch, jnp.float32 if backend == "float" else jnp.int8))


def stream_step(program: SNNProgram, state: StreamState, frame: jax.Array,
                backend: str = "float", *, emit_rasters: bool = True,
                use_sparse: bool = False, block_b: int = 8,
                interpret: bool = False, gate_granularity: int = 1,
                event_crossover: float = 1.0, mesh=None
                ) -> tuple[StreamState, StreamOut]:
    """Advance every stream one tick: (state, (B, ...) input currents) ->
    (new state, StreamOut). Batch lanes never interact — every op is
    per-lane — so a lane's trajectory is independent of what the other
    lanes serve, which is what makes continuous batching exact.

    Backend kwargs mirror `run_network`: ``use_sparse`` gates the int_ref
    tick, ``block_b``/``interpret`` configure the pallas kernel,
    ``gate_granularity`` refines the pallas_sparse gate. The integer
    backends reuse the fused kernels' one-timestep entry (``v_init``), so
    per-layer V tiles stay VMEM-resident within the tick and only cross
    the call boundary between ticks.

    ``mesh`` (a `jax.sharding.Mesh` with "data"/"model" axes) executes the
    macro-stack dispatches under shard_map, bit-identical to the
    single-device tick (see `run_network`); the float backend rejects a
    mesh (ValueError) because its reductions are not order-exact."""
    _check_stream_backend(program, backend)
    if backend == "float" and mesh is not None:
        raise ValueError(
            "backend 'float' has no mesh execution: float reductions are "
            "not order-exact, so a sharded run could not stay bit-identical")
    _refuse_recurrent(program, backend, use_sparse=use_sparse, mesh=mesh)
    if backend == "float":
        vs, spikes, ss = _float_step(program, list(state.vs), frame,
                                     state.spikes)
        v_out = vs[-1]
        return (StreamState(vs=tuple(vs), t=state.t + 1, spikes=ss),
                StreamOut(v_out=v_out, logits=program.logits(v_out),
                          rasters=list(spikes) if emit_rasters else None))
    use_pallas = backend in ("pallas", "pallas_sparse", "pallas_events")
    use_events = backend in ("ref_events", "pallas_events")
    if backend == "pallas_sparse":
        use_sparse = True
    v_enc, spikes_enc = encoder_step(program, state.vs[0], frame)
    cur = spikes_enc[None]                       # (1, B, ...) one-frame raster
    n_convs = len(program.int_conv_stack)
    conv_maps, v_convs, conv_skips = _conv_front_end(
        program, cur, use_pallas=use_pallas, use_sparse=use_sparse,
        gate_granularity=gate_granularity, use_events=use_events,
        block_b=block_b, interpret=interpret,
        event_crossover=event_crossover,
        v_init=list(state.vs[1:1 + n_convs]) if n_convs else None,
        mesh=mesh)
    last = conv_maps[-1] if conv_maps else cur
    flat = last.reshape(*last.shape[:2], -1) if last.ndim > 3 else last
    rasters_fc, v_stack, skips, s_stack = _run_fc_stack(
        program, flat, use_pallas=use_pallas, use_sparse=use_sparse,
        gate_granularity=gate_granularity, use_events=use_events,
        block_b=block_b, interpret=interpret, emit_rasters=emit_rasters,
        event_crossover=event_crossover,
        v_init=list(state.vs[1 + n_convs:]), mesh=mesh,
        s_init=list(state.spikes))
    new_vs = ((v_enc,) + tuple(v_convs)
              + tuple(jnp.asarray(v) for v in v_stack))
    rasters = None
    if emit_rasters:
        rasters = ([spikes_enc] + [m[0] for m in conv_maps]
                   + [jnp.asarray(r)[0] for r in rasters_fc])
    v_out = jnp.asarray(v_stack[-1])
    return (StreamState(vs=new_vs, t=state.t + 1, spikes=tuple(s_stack)),
            StreamOut(v_out=v_out, logits=program.logits(v_out),
                      rasters=rasters, skips=skips,
                      conv_skips=conv_skips if conv_skips else None))


@dataclass
class MegastepOut:
    """What one K-frame `stream_megastep` block produces — `StreamOut`'s
    block-granular sibling. ``rasters[i]`` keeps its K axis ((K, B, n) flat
    / (K, B, H, W, C) maps): concatenating blocks over a stream rebuilds
    `NetResult.rasters` exactly. ``v_out_traj``/``logits_traj`` are the
    per-tick readout trajectory *within* the block — what lets a server
    finalize a request that finishes mid-block (tick budget exhausted or
    confidence early-exit) with the exact values a tick-by-tick drain
    would have produced. ``frames_consumed`` is the per-lane count of real
    (non-masked) frames integrated, for exact accounting."""
    v_out: Any                    # (B, n_out) readout V after the block
    logits: Any                   # (B, n_out)
    v_out_traj: Any               # (K, B, n_out) per-tick readout V
    logits_traj: Any              # (K, B, n_out)
    frames_consumed: Any          # (B,) int32
    rasters: Optional[list] = None
    skips: Any = None
    conv_skips: Any = None


def stream_megastep(program: SNNProgram, state: StreamState,
                    frames: jax.Array, backend: str = "float", *,
                    active=None, emit_rasters: bool = True,
                    use_sparse: bool = False, block_b: int = 8,
                    interpret: bool = False, gate_granularity: int = 1,
                    event_crossover: float = 1.0, mesh=None
                    ) -> tuple[StreamState, MegastepOut]:
    """Advance every stream K ticks in ONE device dispatch: (state,
    (K, B, ...) pre-staged current block) -> (new state, MegastepOut).

    This is the serving-scale entry: where `stream_step` pays one host
    round-trip per frame, a megastep hands the fused kernels a K-frame
    raster and the per-layer V tiles stay VMEM-resident across the whole
    K loop (the `v_init` chunk-composition property: integer arithmetic
    is exact, so a K-frame call equals K chained one-frame calls bit for
    bit — the fused-V_MEM payoff the paper's streaming mode is built on).

    ``active`` (optional, (B,) ints) is the per-lane active-tick count:
    frames at tick t >= active[lane] are zeroed before integration, so
    evicted/short streams integrate zero current — exactly what a K=1
    engine presents to an idle lane — and ``frames_consumed`` reports
    min(active, K) per lane. The lane still *advances* K ticks (leak and
    reset run on zero current); a server that retires a lane mid-block
    discards the ghost ticks by re-seeding the lane from fresh state.

    ``v_out_traj``/``logits_traj`` expose the readout's per-tick values
    inside the block. On the integer backends the readout accumulator is
    unclamped int32, so the trajectory is recovered exactly as
    ``v_init + cumsum(raster @ w_readout)`` — int addition is associative,
    hence bit-identical to K single ticks (this forces the fc stack to
    emit rasters internally even when ``emit_rasters=False``).

    ``mesh`` (a `jax.sharding.Mesh` with "data"/"model" axes) executes the
    macro-stack dispatches under shard_map — serving lanes partition over
    the data axis, row-tiled fan-in over the model axis — bit-identical to
    the single-device block; the float backend rejects a mesh
    (ValueError)."""
    _check_stream_backend(program, backend)
    if backend == "float" and mesh is not None:
        raise ValueError(
            "backend 'float' has no mesh execution: float reductions are "
            "not order-exact, so a sharded run could not stay bit-identical")
    _refuse_recurrent(program, backend, use_sparse=use_sparse, mesh=mesh)
    frames = jnp.asarray(frames)
    if frames.ndim < 3:
        raise ValueError(
            f"stream_megastep takes a (K, B, *in_shape) frame block, got "
            f"shape {frames.shape}")
    k, b = int(frames.shape[0]), int(frames.shape[1])
    if k < 1:
        raise ValueError("stream_megastep needs K >= 1 frames per block")
    if active is not None:
        act = jnp.asarray(active, jnp.int32)
        live = jnp.arange(k, dtype=jnp.int32)[:, None] < act[None, :]
        frames = jnp.where(
            live.reshape(k, b, *([1] * (frames.ndim - 2))), frames,
            jnp.zeros((), frames.dtype))
        consumed = jnp.minimum(act, k)
    else:
        consumed = jnp.full((b,), k, jnp.int32)
    if backend == "float":
        # eager K-loop, NOT lax.scan: the float (QAT) readout matmul can
        # drift a last ulp when XLA refuses the eager ops under scan, and
        # the contract here is bit-identity with K stream_step ticks
        vs, ss, v_traj, spk = list(state.vs), state.spikes, [], []
        for t in range(k):
            vs, spikes, ss = _float_step(program, vs, frames[t], ss)
            v_traj.append(vs[-1])
            if emit_rasters:
                spk.append(spikes)
        v_traj = jnp.stack(v_traj)
        rasters = ([jnp.stack([s[i] for s in spk])
                    for i in range(len(spk[0]))] if emit_rasters else None)
        return (StreamState(vs=tuple(vs), t=state.t + k, spikes=ss),
                MegastepOut(v_out=vs[-1], logits=program.logits(vs[-1]),
                            v_out_traj=v_traj,
                            logits_traj=program.logits(v_traj),
                            frames_consumed=consumed, rasters=rasters))
    use_pallas = backend in ("pallas", "pallas_sparse", "pallas_events")
    use_events = backend in ("ref_events", "pallas_events")
    if backend == "pallas_sparse":
        use_sparse = True
    # the encoder: under a trace (the serving engine's jitted core) a
    # lax.scan, as `encode` runs it, so the compiled program's size does
    # not grow with K; eagerly a K-loop of the same step, since an
    # un-jitted scan retraces on every call (bit-identical either way:
    # tests/test_stream.py pins eager == scanned)
    if isinstance(frames, jax.core.Tracer):
        v_enc, spikes_enc = jax.lax.scan(
            lambda v, xt: encoder_step(program, v, xt), state.vs[0], frames)
    else:
        v_enc, spk_enc = state.vs[0], []
        for t in range(k):
            v_enc, s = encoder_step(program, v_enc, frames[t])
            spk_enc.append(s)
        spikes_enc = jnp.stack(spk_enc)
    n_convs = len(program.int_conv_stack)
    conv_maps, v_convs, conv_skips = _conv_front_end(
        program, spikes_enc, use_pallas=use_pallas, use_sparse=use_sparse,
        gate_granularity=gate_granularity, use_events=use_events,
        block_b=block_b, interpret=interpret,
        event_crossover=event_crossover,
        v_init=list(state.vs[1:1 + n_convs]) if n_convs else None,
        mesh=mesh)
    last = conv_maps[-1] if conv_maps else spikes_enc
    flat = last.reshape(*last.shape[:2], -1) if last.ndim > 3 else last
    rasters_fc, v_stack, skips, s_stack = _run_fc_stack(
        program, flat, use_pallas=use_pallas, use_sparse=use_sparse,
        gate_granularity=gate_granularity, use_events=use_events,
        block_b=block_b, interpret=interpret, emit_rasters=True,
        event_crossover=event_crossover,
        v_init=list(state.vs[1 + n_convs:]), mesh=mesh,
        s_init=list(state.spikes))
    new_vs = ((v_enc,) + tuple(v_convs)
              + tuple(jnp.asarray(v) for v in v_stack))
    # exact per-tick readout trajectory (see docstring): the readout input
    # raster is the last spiking layer's output, or the stack input when
    # the stack is readout-only
    ro_in = (jnp.asarray(rasters_fc[-1]) if len(rasters_fc)
             else flat).astype(jnp.int32)
    w_ro = jnp.asarray(program.fc_stack[-1].w).astype(jnp.int32)
    v_traj = (jnp.asarray(state.vs[-1])[None]
              + jnp.cumsum(ro_in @ w_ro, axis=0))
    rasters = None
    if emit_rasters:
        rasters = ([spikes_enc] + list(conv_maps)
                   + [jnp.asarray(r) for r in rasters_fc])
    v_out = jnp.asarray(v_stack[-1])
    return (StreamState(vs=new_vs, t=state.t + k, spikes=tuple(s_stack)),
            MegastepOut(v_out=v_out, logits=program.logits(v_out),
                        v_out_traj=v_traj,
                        logits_traj=program.logits(v_traj),
                        frames_consumed=consumed, rasters=rasters,
                        skips=skips,
                        conv_skips=conv_skips if conv_skips else None))


def _bitmacro_layer(inp: np.ndarray, wq: np.ndarray, threshold: int,
                    leak: int, neuron: str, recurrent: bool = False):
    """Execute one spiking layer, (T, F, n_in) bool input frames ->
    ((T, F, n_out) int8 spikes, (F, n_out) final V, InstrCount), on a bank
    of bit-level macros — the distributed multi-macro architecture:

      * frames (batch elements, or (example, output position) pairs for
        im2col-lowered convs) map onto V_MEM neuron sets, 13 per macro
        grid; frame counts beyond 13 claim additional macro banks;
      * fan-in splits over ``row_tiles`` macros (mapping.tile_weights).
        Row tile 0 holds the persistent membrane state and the neuron
        constants; tiles >= 1 accumulate per-timestep partial sums that a
        word-level AccV2V (odd+even cycle per tile) reduces into tile 0
        before the neuron-update sequence runs there. Wrap arithmetic
        makes the split exact: mod-2^11 addition composes, so reduced
        per-tile partials equal the single-accumulate word semantics bit
        for bit (the reason saturate mode is word-level-only, macro.py);
      * a ``recurrent`` layer's ``wq`` carries n_out further W_MEM rows
        after its n_in input rows: each frame's previous-tick spikes issue
        event-driven AccW2V on those rows (zero at t = 0).

    Executed cycles equal `isa.count_layer_instructions` on the input
    raster exactly: 2 AccW2V per event per col tile, 2(row_tiles-1) AccV2V
    reduction cycles per (frame, timestep, col tile), plus the per-neuron
    update sequence."""
    from repro.core.macro import BitMacro
    t_total, n_frames, n_in = inp.shape
    n_out = wq.shape[1]
    if recurrent:      # the W_rec rows are driven by the layer's own raster
        inp = np.concatenate([inp, np.zeros((t_total, n_frames, n_out),
                                            bool)], axis=-1)
        n_in += n_out
    tiling = mapping.fc_tiling(n_in, n_out)
    wq_tiles = mapping.tile_weights(np.asarray(wq))
    n_banks = -(-n_frames // isa.N_NEURON_SETS)
    banks = [[[BitMacro.from_weights(wq_tiles[r, c], threshold=threshold,
                                     leak=leak)
               for c in range(tiling.col_tiles)]
              for r in range(tiling.row_tiles)]
             for _ in range(n_banks)]
    out = np.zeros((t_total, n_frames, n_out), np.int8)
    for t in range(t_total):
        if recurrent and t > 0:
            inp[t, :, n_in - n_out:] = out[t - 1].astype(bool)
        for f in range(n_frames):
            bank, set_idx = divmod(f, isa.N_NEURON_SETS)
            grid = banks[bank]
            for row in np.nonzero(inp[t, f])[0]:        # event-driven AccW2V
                r, macro_row = divmod(int(row), isa.MACRO_IN)
                for c in range(tiling.col_tiles):
                    grid[r][c].acc_w2v(set_idx, macro_row, cycle=0)
                    grid[r][c].acc_w2v(set_idx, macro_row, cycle=1)
            for r in range(1, tiling.row_tiles):        # AccV2V reduction
                for c in range(tiling.col_tiles):
                    partial = grid[r][c].transfer_v(set_idx)
                    for cycle in (0, 1):
                        grid[0][c].acc_v2v(set_idx, partial, cycle)
            spikes = np.concatenate(
                [grid[0][c].neuron_update(set_idx, neuron)
                 for c in range(tiling.col_tiles)])
            out[t, f] = spikes[:n_out].astype(np.int8)
    v = np.stack([
        np.concatenate([banks[f // isa.N_NEURON_SETS][0][c]
                        .read_v(f % isa.N_NEURON_SETS)
                        for c in range(tiling.col_tiles)])
        for f in range(n_frames)])[:, :n_out]
    counts = sum((m.counts for bank in banks for row in bank for m in row),
                 isa.InstrCount())
    return out, v.astype(np.int32), counts


@register_backend("bitmacro")
def run_bitmacro(program: SNNProgram, xs: jax.Array) -> NetResult:
    """Execute ``program``'s on-macro stack on ``xs`` currents through the
    bit-accurate macro model (the silicon oracle).
    Layers with fan-in > 128 split over row-tiled macros
    whose partial sums reduce with word-level AccV2V cycles; conv layers
    lower via im2col onto the same grid (one neuron set per (example,
    output position)); frames beyond 13 neuron sets claim extra macro
    banks; a recurrent layer's W_rec block is its further W_MEM rows, driven
    by the layer's previous-tick spikes. The one remaining constraint is
    the silicon's two's-complement
    *wrap* arithmetic (saturation is a word-level deployment policy, not
    silicon — and the only mode in which split partial sums compose
    exactly; compile with clamp_mode='wrap', see macro.py)."""
    if program.clamp_mode != "wrap":
        raise ValueError("bitmacro executes silicon wrap arithmetic; compile "
                         "the program with clamp_mode='wrap'")
    spikes_enc, v_enc = encode(program, xs)
    cur = np.asarray(spikes_enc).astype(np.int8)       # (T, B, ...) spikes
    t_total, batch = cur.shape[:2]
    stack = program.macro_stack

    rasters = [jnp.asarray(cur)]
    v_stack: list = []
    total = isa.InstrCount()
    for spec in stack[:-1]:
        if spec.kind == "conv":
            patches = np.asarray(mapping.im2col_raster(
                cur, spec.w.shape[0], spec.stride))
            out_hw = mapping.conv_out_hw(cur.shape[2:4], spec.w.shape[0],
                                         spec.stride)
            inp = patches.astype(bool)
            wq = np.asarray(mapping.pack_conv_weights(spec.w))
        else:
            inp = cur.reshape(t_total, -1, spec.n_in).astype(bool)
            wq = np.asarray(spec.w)
        out, v, counts = _bitmacro_layer(inp, wq, int(spec.threshold),
                                         int(spec.leak), program.neuron,
                                         recurrent=spec.recurrent)
        total += counts
        if spec.kind == "conv":
            cur = out.reshape(t_total, batch, *out_hw, spec.n_out)
            v = v.reshape(batch, *out_hw, spec.n_out)
        else:
            cur = out
        rasters.append(jnp.asarray(cur))
        v_stack.append(jnp.asarray(v))
    # readout: word-level wide accumulate (off the bit array, as deployed)
    flat = cur.reshape(t_total, batch, -1)
    wq_readout = np.asarray(stack[-1].w, np.int64)
    v_out = np.zeros((batch, stack[-1].n_out), np.int64)
    for t in range(t_total):
        v_out += flat[t].astype(np.int64) @ wq_readout
    v_stack.append(jnp.asarray(v_out.astype(np.int32)))
    res = _assemble(program, rasters, v_enc, v_stack)
    res.aux["macro_counts"] = total
    return res


# ---------------------------------------------------------------------------
# program-level sparsity measurement + instruction counting (the energy-
# model inputs)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SparsityReport:
    """Measured event statistics of one program execution — the bridge from
    spike rasters to the energy model. Per fc-stack layer i (whose *input*
    raster is the output of neuron layer i): total input events, per-
    timestep occupancy, and the macro-tiling geometry needed to turn events
    into instruction cycles. Built from full rasters (`sparsity_report`,
    exact, per-timestep resolution) or from the float backend's
    ``collect_sums`` aggregates (`sparsity_report_from_sums`, raster-free —
    the training-loop-friendly path). Both feed
    `count_network_instructions(program, report=...)` and
    `energy.measured_edp*`.

    A recurrent layer's W_rec rows are counted apart: ``n_rec[i]`` is its
    recurrent row count (its n_out; 0 for a feed-forward layer) and
    ``rec_events[i]`` the events on those rows — its own spikes of the
    previous tick, so every spike but the last tick's. The instruction
    counts cover fan-in ``n_in + n_rec`` (`recurrent_acc_w2v` isolates the
    W_rec AccW2V cycles); the sparsity properties stay over input rows."""
    n_in: tuple                   # fan-in per macro-stack layer
    n_out: tuple
    neurons: tuple                # per-layer update kind ("rmp"... | "none")
    events: tuple                 # total input spike events per layer
    frames: int                   # (timestep, example) pairs = T_total * B
    timesteps: int
    batch: int
    occupancy_t: Optional[tuple] = None   # per layer: (T_total,) mean input
                                          # occupancy per timestep (rasters
                                          # only; None from sums)
    layer_frames: Optional[tuple] = None  # per-layer frame counts; conv
                                          # layers run T*B*P frames (one per
                                          # output position). None = every
                                          # layer runs ``frames``
    row_events: Optional[tuple] = None    # per layer: (n_in,) int64 events
                                          # per input row over all frames —
                                          # the per-row event columns the
                                          # ref_events backend also reports
    n_rec: Optional[tuple] = None         # per layer: W_rec rows (None:
                                          # feed-forward program)
    rec_events: Optional[tuple] = None    # per layer: events on W_rec rows

    @property
    def _rec(self) -> tuple:
        """(n_rec, rec_events) per layer, zeros when feed-forward."""
        zeros = tuple(0 for _ in self.n_in)
        return (self.n_rec or zeros, self.rec_events or zeros)

    @property
    def frames_by_layer(self) -> tuple:
        """Per-layer frame counts: ``layer_frames`` when set (conv layers
        run one frame per output position), else ``frames`` for every
        layer."""
        return (self.layer_frames if self.layer_frames is not None
                else tuple(self.frames for _ in self.n_in))

    @property
    def layer_sparsity(self) -> tuple:
        """1 - (events / possible events), per macro-stack layer input.
        A zero-frame execution (e.g. an empty serving request) has no gate
        sites; report sparsity 0 — no skip is claimed — rather than
        dividing by zero."""
        return tuple(1.0 - e / (f * n) if f * n else 0.0
                     for e, n, f in zip(self.events, self.n_in,
                                        self.frames_by_layer))

    @property
    def overall_sparsity(self) -> float:
        """Event-weighted network input sparsity (all layers pooled; 0.0
        for a zero-frame execution — see layer_sparsity)."""
        possible = sum(f * n for n, f in zip(self.n_in, self.frames_by_layer))
        return 1.0 - sum(self.events) / possible if possible else 0.0

    @property
    def silent_timestep_fraction(self) -> tuple:
        """Per layer: fraction of timesteps whose whole-batch input raster
        is silent — the whole-batch-granularity skip opportunity (the
        reference gate; per-batch-tile kernels skip at least this often)."""
        if self.occupancy_t is None:
            return tuple(None for _ in self.n_in)
        return tuple(float(np.mean(np.asarray(o) == 0.0))
                     for o in self.occupancy_t)

    @property
    def macro_timesteps(self) -> int:
        """Total macro-timesteps executed: every frame (a (timestep,
        example) pair, or (timestep, example, output position) for conv
        layers) runs its layer's col_tiles macro grids once — the
        normalizer that makes a measured InstrCount comparable to the
        paper's per-neuron per-timestep EDP curve
        (energy.measured_edp_per_neuron_timestep)."""
        return sum(f * mapping.fc_tiling(ni, no).col_tiles
                   for ni, no, f in zip(self.n_in, self.n_out,
                                        self.frames_by_layer))

    @property
    def row_skip_counts(self) -> tuple:
        """Per layer: silent (frame, input-row) pairs — the AccW2V gate
        sites an event-driven (row-granular) executor skips. This is the
        count `ref_events` measures during execution; here it falls out of
        the raster statistics, and the two are tested equal."""
        return tuple(f * n - e
                     for e, n, f in zip(self.events, self.n_in,
                                        self.frames_by_layer))

    @property
    def skipped_row_fraction(self) -> float:
        """Fraction of all (frame, row) gate sites that were silent —
        numerically ``overall_sparsity``, surfaced under the gating name
        so benchmark rows and the CI gate read as work skipped."""
        return self.overall_sparsity

    def block_event_counts(self, granularity: int) -> tuple:
        """Per layer: (n_blocks,) input-event totals per row block at the
        requested gate granularity — the same counted blocks
        `kernel.skip_layout` assigns skip columns to (128/G lanes each at
        G > 1; the whole input width at 1). A block the kernel's gate ever
        skipped for the full batch necessarily has zero events here, and
        each layer's blocks sum back to its total event count."""
        if self.row_events is None:
            raise ValueError("block_event_counts needs per-row event "
                             "columns; build the report from rasters or "
                             "collect_sums (row_events=None)")
        from repro.kernels.fused_snn_net.kernel import (GATE_GRANULARITIES,
                                                        LANE)
        if granularity not in GATE_GRANULARITIES:
            raise ValueError(f"gate granularity must be one of "
                             f"{GATE_GRANULARITIES}, got {granularity}")
        # per-layer block counts, NOT the joint skip_layout: the kernel
        # lays out skip columns per fused_snn_net call (each conv layer is
        # its own call), so the MAX_SKIP_COLS cap must not apply across
        # the whole macro stack here
        out = []
        for rows in self.row_events:
            rows = np.asarray(rows)
            bw = len(rows) if granularity == 1 else LANE // granularity
            nb = -(-len(rows) // bw)
            padded = np.zeros(nb * bw, rows.dtype)
            padded[:len(rows)] = rows
            out.append(padded.reshape(nb, bw).sum(axis=1))
        return tuple(out)

    def instruction_counts(self) -> isa.InstrCount:
        """Event statistics -> instruction cycles (identical to counting the
        rasters directly: both route through
        isa.count_layer_instructions_from_events)."""
        counts = isa.InstrCount()
        for ni, no, neuron, ev, f, nr, re in zip(
                self.n_in, self.n_out, self.neurons, self.events,
                self.frames_by_layer, *self._rec):
            counts += isa.count_layer_instructions_from_events(
                ev + re, f, ni + nr, no, neuron)
        return counts

    @property
    def recurrent_acc_w2v(self) -> tuple:
        """Per layer: the AccW2V cycles issued on W_rec rows (2 per
        recurrent event per column tile; 0 for a feed-forward layer) —
        a part of `instruction_counts`' acc_w2v."""
        return tuple(
            2 * re * mapping.fc_tiling(ni + nr, no).col_tiles
            for ni, no, nr, re in zip(self.n_in, self.n_out, *self._rec))

    def skipped_instruction_counts(self) -> isa.InstrCount:
        """Instruction cycles event-driven execution never issued: the
        AccW2V cycles of every silent (frame, input-row) pair — the
        row-granular skip model behind the Fig. 11b curve (executed +
        skipped == the dense tally at sparsity 0)."""
        counts = isa.InstrCount()
        for ni, no, ev, f, nr, re in zip(self.n_in, self.n_out, self.events,
                                         self.frames_by_layer, *self._rec):
            counts += isa.count_skipped_instructions_from_events(
                ev + re, f, ni + nr, no)
        return counts


def _report_geometry(program: SNNProgram) -> tuple:
    stack = program.macro_stack
    return (tuple(ly.n_in for ly in stack), tuple(ly.n_out for ly in stack),
            tuple("none" if ly.kind == "readout" else program.neuron
                  for ly in stack))


def _report_n_rec(program: SNNProgram) -> Optional[tuple]:
    """W_rec row count per macro-stack layer (None: feed-forward)."""
    if not program.recurrent_layers:
        return None
    return tuple(ly.n_out if ly.recurrent else 0 for ly in program.macro_stack)


def _recurrent_rasters(program: SNNProgram, rs: list) -> list:
    """Per macro-stack layer, the raster its W_rec rows see — its own
    output raster (the next layer's input, ``rs[i + 1]``) one tick late,
    zero at t = 0 — or None for a feed-forward layer. ``rs`` are the
    (T, frames, n) rasters of `_stack_input_rasters`."""
    out = []
    for i, spec in enumerate(program.macro_stack):
        if not spec.recurrent:
            out.append(None)
            continue
        own = rs[i + 1]
        out.append(np.concatenate([np.zeros_like(own[:1]), own[:-1]]))
    return out


def _stack_input_rasters(program: SNNProgram, rasters: list) -> list:
    """Normalize a raster list onto the macro stack: take the trailing
    len(macro_stack) entries (float-domain conv programs emit one raster
    per neuron layer, whose tail is exactly the macro-stack inputs), then
    lower conv-layer entries — (T, B, H, W, C) spike maps — to their
    (T, B*P, k*k*C) im2col patch rasters, the event stream the macro
    actually consumes. FC entries reshape to (T, frames, n_in)."""
    stack = program.macro_stack
    if len(rasters) < len(stack):
        raise ValueError(f"need one input raster per macro-stack layer "
                         f"({len(stack)}), got {len(rasters)}")
    out = []
    for spec, raster in zip(stack, rasters[-len(stack):]):
        r = np.asarray(raster)
        if spec.kind == "conv":
            r = np.asarray(mapping.im2col_raster(r, spec.w.shape[0],
                                                 spec.stride))
        out.append(r.reshape(r.shape[0], -1, spec.n_in))
    return out


def sparsity_report(program: SNNProgram, rasters: list) -> SparsityReport:
    """Exact report from per-layer input rasters (`NetResult.rasters`):
    rasters[i] is (T_total, B, n_in_i) for macro-stack layer i — or the
    (T_total, B, H, W, C) input spike map for a conv layer, which is
    lowered to its im2col patch raster here (events are counted per
    output position, as the macro issues them)."""
    if rasters is None:
        raise ValueError("sparsity_report needs spike rasters; run the "
                         "backend with emit_rasters=True (accounting mode), "
                         "or build the report from collect_sums aggregates")
    n_in, n_out, neurons = _report_geometry(program)
    rs = _stack_input_rasters(program, rasters)
    T = rs[0].shape[0]
    B = int(np.asarray(rasters[-1]).shape[1])     # fc rasters carry batch
    n_rec = _report_n_rec(program)
    return SparsityReport(
        n_in=n_in, n_out=n_out, neurons=neurons,
        events=tuple(int(r.sum()) for r in rs),
        frames=T * B, timesteps=T, batch=B,
        occupancy_t=tuple(r.mean(axis=(1, 2)) for r in rs),
        layer_frames=tuple(T * r.shape[1] for r in rs),
        row_events=tuple(r.astype(np.int64).sum(axis=(0, 1)) for r in rs),
        n_rec=n_rec,
        rec_events=None if n_rec is None else tuple(
            0 if r is None else int(r.astype(np.int64).sum())
            for r in _recurrent_rasters(program, rs)))


def sparsity_report_from_sums(program: SNNProgram, spike_sums: list,
                              timesteps: int) -> SparsityReport:
    """Raster-free report from the float backend's ``collect_sums`` aux:
    spike_sums[i] is the (B, ...) per-neuron spike-count total of neuron
    layer i. The last len(macro_stack) neuron layers feed the macro stack,
    so their totals are exactly the per-layer input event counts. Conv-fed
    layers see each input pixel once per covering patch; im2col is linear,
    so the patch event total is ``im2col(sum map).sum()`` — exact. Per-
    timestep occupancy is not recoverable from sums (occupancy_t=None)."""
    if program.recurrent_layers:
        raise ValueError(
            "sparsity_report_from_sums: a recurrent layer's W_rec events "
            "are its spikes of every tick but the last, which per-neuron "
            "totals cannot separate; build the report from rasters "
            "(sparsity_report)")
    n_in, n_out, neurons = _report_geometry(program)
    stack = program.macro_stack
    sums = spike_sums[-len(stack):]
    if len(sums) != len(n_in):
        raise ValueError(f"need one spike-sum per macro-stack layer input "
                         f"({len(n_in)}), got {len(spike_sums)}")
    B = int(np.asarray(sums[0]).shape[0])
    events, layer_frames, row_events = [], [], []
    for spec, s in zip(stack, sums):
        s = np.asarray(s)
        if spec.kind == "conv":
            patches = np.asarray(mapping.im2col(s, spec.w.shape[0],
                                                spec.stride))
            # int64 element-wise cast before summing: the f32 counts are
            # integer-valued, but f32 *accumulation* loses exactness > 2^24
            rows = patches.astype(np.int64).reshape(-1, spec.n_in).sum(axis=0)
            layer_frames.append(timesteps * B
                                * patches.shape[1] * patches.shape[2])
        else:
            rows = s.astype(np.int64).reshape(-1, spec.n_in).sum(axis=0)
            layer_frames.append(timesteps * B)
        row_events.append(rows)
        events.append(int(rows.sum()))
    return SparsityReport(
        n_in=n_in, n_out=n_out, neurons=neurons, events=tuple(events),
        frames=timesteps * B, timesteps=timesteps, batch=B,
        layer_frames=tuple(layer_frames), row_events=tuple(row_events))


def count_network_instructions(program: SNNProgram, rasters: list = None, *,
                               report: Optional[SparsityReport] = None
                               ) -> isa.InstrCount:
    """Fold the per-layer event counts over the whole program. ``rasters[i]``
    is the input raster of macro-stack layer i (conv layers take their input
    spike maps, lowered to im2col patch rasters here); identical rasters
    (which all backends are tested to produce) give identical counts by
    construction. Row-tiled layers include the AccV2V partial-sum reduction
    term (isa.count_layer_instructions_from_events) that the bitmacro
    backend executes cycle-for-cycle. Alternatively pass a `SparsityReport`
    (``report=...``) — the raster-free accounting path; both routes share
    one counting implementation."""
    if report is not None:
        return report.instruction_counts()
    if rasters is None:
        raise ValueError("instruction counting needs spike rasters (run the "
                         "backend with emit_rasters=True, accounting mode) "
                         "or a SparsityReport")
    counts = isa.InstrCount()
    rs = _stack_input_rasters(program, rasters)
    for spec, r, rec in zip(program.macro_stack, rs,
                            _recurrent_rasters(program, rs)):
        if rec is not None:               # AccW2V over [input; W_rec] rows
            r = np.concatenate([r, rec], axis=-1)
        counts += isa.count_layer_instructions(
            r, spec.fan_in, spec.n_out,
            "none" if spec.kind == "readout" else program.neuron)
    return counts
