"""Trainable SNN stack: the paper's IMDB sentiment net and MNIST LeNet5-mod.

Training follows DIET-SNN [3]: surrogate-gradient BPTT with trainable
per-layer threshold and leak, QAT to the macro's 6-bit weights. All temporal
execution routes through the network-level pipeline (core.pipeline): this
module only owns parameter init and the task-facing wrappers. Inference has
two program domains that are tested to agree:
  * float domain — fake-quantized weights, float V (QAT training semantics);
  * int domain   — true int8 weights + 11-bit V, executable on any of the
    int_ref / pallas / bitmacro backends, producing the spike rasters and
    instruction counts that drive the energy model.

Paper network (IMDB): GloVe-100d word -> encoder(100 IF/RMP neurons, spike
encoding) -> FC 100x128 -> FC 128x128 (both spiking, on-macro) -> FC 128x1
accumulate-only readout; each word presented `timesteps`(=10) steps, membrane
potentials persist across words (the sequential-memory claim, Fig. 1/10).
29,312 trainable weights (paper: 29.3K).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.impulse_snn import SNNModelConfig
from repro.core import pipeline


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_fc_snn(key: jax.Array, cfg: SNNModelConfig) -> dict:
    sizes = cfg.layer_sizes
    keys = jax.random.split(key, len(sizes) - 1)
    layers = []
    for j, (k, (n_in, n_out)) in enumerate(zip(keys, zip(sizes[:-1],
                                                         sizes[1:]))):
        # a recurrent layer's block is [W_in; W_rec]: n_out further rows
        fan_in = n_in + n_out if cfg.is_recurrent(j) else n_in
        w = jax.random.normal(k, (fan_in, n_out), jnp.float32) * (
            2.0 / np.sqrt(fan_in))
        layers.append({"w": w})
    n_spiking = len(sizes) - 2                    # last layer is accumulate-only
    return {
        "layers": layers,
        "threshold": jnp.full((n_spiking + 1,), cfg.spiking.threshold),  # [0] = encoder
        "leak": jnp.full((n_spiking + 1,), cfg.spiking.leak),
    }


def param_count(params: dict) -> int:
    return sum(int(np.prod(ly["w"].shape)) for ly in params["layers"])


# ---------------------------------------------------------------------------
# IMDB sentiment wrappers (float / QAT and deployed integer programs)
# ---------------------------------------------------------------------------

def sentiment_apply(params: dict, x_words: jax.Array, cfg: SNNModelConfig,
                    quantize: bool = True, return_trace: bool = False):
    """x_words: (B, n_words, d_in). Returns logits (B,) = final output V, plus
    aux dict (per-layer mean spike rates per timestep; optional V trace)."""
    program = pipeline.compile_network(cfg, params, domain="float",
                                       quantize=quantize)
    xs = pipeline.present_words(x_words, cfg.timesteps)
    res = pipeline.run_network(program, xs, "float", return_trace=return_trace)
    return res.logits[:, 0], res.aux


def sentiment_loss(params, x_words, labels, cfg: SNNModelConfig, quantize=True):
    logits, aux = sentiment_apply(params, x_words, cfg, quantize)
    # scale: output V grows with n_words*T; normalize for a stable BCE
    z = logits / (cfg.timesteps * x_words.shape[1]) * 8.0
    loss = jnp.mean(jnp.maximum(z, 0) - z * labels + jnp.log1p(jnp.exp(-jnp.abs(z))))
    acc = jnp.mean((logits > 0) == (labels > 0.5))
    return loss, {"accuracy": acc, **aux}


def sentiment_apply_int(params: dict, x_words: jax.Array, cfg: SNNModelConfig,
                        backend: str = "int_ref", **backend_kw):
    """Integer-domain inference (the deployed macro program) on any integer
    backend ("int_ref" | "pallas" | "bitmacro"). Returns (logits_float,
    spike_rasters list[(T_total, B, n)], instruction counts). In serving
    mode (pallas with emit_rasters=False) rasters and counts are None —
    event accounting needs the rasters."""
    program = pipeline.compile_network(cfg, params, domain="int")
    xs = pipeline.present_words(x_words, cfg.timesteps)
    res = pipeline.run_network(program, xs, backend, **backend_kw)
    counts = (pipeline.count_network_instructions(program, res.rasters)
              if res.rasters is not None else None)
    return res.logits[:, 0], res.rasters, counts


# ---------------------------------------------------------------------------
# MNIST LeNet5-mod (conv spike encoder + on-macro convs/FCs)
# ---------------------------------------------------------------------------

def init_lenet_snn(key: jax.Array, cfg: SNNModelConfig) -> dict:
    keys = jax.random.split(key, len(cfg.conv_spec) + len(cfg.layer_sizes) - 1)
    convs = []
    c_in = cfg.in_shape[-1]
    for i, (c_out, k, stride) in enumerate(cfg.conv_spec):
        w = jax.random.normal(keys[i], (k, k, c_in, c_out)) * (2.0 / np.sqrt(k * k * c_in))
        convs.append({"w": w})          # stride lives in cfg (params stay float)
        c_in = c_out
    layers = []
    sizes = cfg.layer_sizes
    for j, (n_in, n_out) in enumerate(zip(sizes[:-1], sizes[1:])):
        w = jax.random.normal(keys[len(cfg.conv_spec) + j], (n_in, n_out)) * (2.0 / np.sqrt(n_in))
        layers.append({"w": w})
    n_spiking = len(cfg.conv_spec) + len(sizes) - 2
    return {"convs": convs, "layers": layers,
            "threshold": jnp.full((n_spiking,), cfg.spiking.threshold),
            "leak": jnp.full((n_spiking,), cfg.spiking.leak)}


def lenet_apply(params: dict, images: jax.Array, cfg: SNNModelConfig,
                quantize: bool = True):
    """images: (B, H, W, C). Returns class logits (B, n_classes) = output V.
    Direct encoding: the image is the input current every timestep; the first
    conv is the (unquantized) spike encoder."""
    program = pipeline.compile_network(cfg, params, domain="float",
                                       quantize=quantize)
    return pipeline.run_network(program, images, "float",
                                static_input=True).v_out


def lenet_apply_int(params: dict, images: jax.Array, cfg: SNNModelConfig,
                    backend: str = "int_ref", **backend_kw):
    """Integer-domain LeNet5-mod inference — the deployed conv program: the
    first conv stays the float spike encoder, later convs lower onto the
    macro grid via im2col (6b weights, 11b V), FCs ride the fused stack.
    Runs on any integer backend ("int_ref" | "pallas" | "pallas_sparse" |
    "bitmacro", the latter needing clamp_mode='wrap'). Returns
    (logits (B, n_classes), spike rasters, instruction counts) — rasters
    and counts None in serving mode (emit_rasters=False)."""
    program = pipeline.compile_network(cfg, params, domain="int",
                                       **{k: backend_kw.pop(k)
                                          for k in ("clamp_mode",)
                                          if k in backend_kw})
    xs = pipeline.present_static(images, cfg.timesteps)
    res = pipeline.run_network(program, xs, backend, **backend_kw)
    counts = (pipeline.count_network_instructions(program, res.rasters)
              if res.rasters is not None else None)
    return res.logits, res.rasters, counts


def lenet_loss(params, images, labels, cfg: SNNModelConfig, quantize=True):
    logits = lenet_apply(params, images, cfg, quantize)
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    acc = jnp.mean(jnp.argmax(logits, -1) == labels)
    return loss, {"accuracy": acc}
