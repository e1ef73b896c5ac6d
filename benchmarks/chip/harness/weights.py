"""Seeded weights, made on the device in one jitted call.

Every on-macro layer's weights are drawn as the repo's initialisers draw
them (Gaussian, std 2/sqrt(fan_in)) and put on the configuration's W grid
with max |w| = W_MAX, then handed to the program as floats ``q * s``. The
program's own quantisation (max|w| / W_MAX, round) then recovers ``q``
whatever the last bit of its division, so the integer weights the
reference uses are the benchmark's, never read back from the program.
Each spiking layer's integer threshold ``theta`` is fixed here too, and
the raw (pre-softplus) parameter the program takes is solved from it; the
program's float path lands within ~1e-6 grid units of ``theta``.

The encoder keeps the configuration's raw threshold. The conv encoder's
float weights sit on a k/8 grid, so that its convolution is exact in any
matmul precision, on the chip as in the reference.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class MacroLayer:
    """One on-macro layer as the reference sees it."""
    kind: str                 # conv | fc | readout
    q: jax.Array              # int8 weights: HWIO for conv, (n_in, n_out)
    theta: jax.Array          # int32 threshold (0 for the readout)
    scale: jax.Array          # f32 grid step
    stride: int = 1


@dataclass(frozen=True)
class Truth:
    """What the benchmark made, for the reference."""
    enc_threshold_raw: jax.Array   # the program's raw threshold vector
    enc_w: jax.Array | None        # conv encoder float HWIO weights
    enc_stride: int
    layers: tuple                  # MacroLayer, stack order, readout last


def seed_key(seed: int) -> jax.Array:
    """A JAX key from any whole number (the driver's seeds pass 2**31)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def w_max(config: dict) -> int:
    return 2 ** (config["w_bits"] - 1) - 1


def _softplus_np(x: float) -> float:
    return float(np.log1p(np.exp(x)))


def nominal_threshold(config: dict) -> float:
    """The float threshold the program derives from the raw one:
    softplus(raw) + 1e-3 (float64 here)."""
    return _softplus_np(config["threshold"]) + 1e-3


def _shapes(config: dict):
    """(kind, weight shape, fan_in, stride) per weighted layer, encoder
    conv first when there is one."""
    out = []
    c_in = config["in_shape"][-1] if config["conv_spec"] else None
    for i, (c_out, k, stride) in enumerate(config["conv_spec"]):
        out.append(("enc_conv" if i == 0 else "conv", (k, k, c_in, c_out),
                    k * k * c_in, stride))
        c_in = c_out
    sizes = config["layer_sizes"]
    for j, (n_in, n_out) in enumerate(zip(sizes[:-1], sizes[1:])):
        kind = "readout" if j == len(sizes) - 2 else "fc"
        out.append((kind, (n_in, n_out), n_in, 1))
    return out


def make(config: dict, seed: int) -> tuple[dict, Truth]:
    """(params for ``compile_network``, the benchmark's own truth)."""
    layers = _shapes(config)
    wm = float(w_max(config))
    th_nom = nominal_threshold(config)

    @jax.jit
    def gen(key):
        keys = jax.random.split(key, len(layers))
        enc_w, made = None, []
        for k, (kind, shape, fan_in, _) in zip(keys, layers):
            w = jax.random.normal(k, shape, jnp.float32) * (2.0 / np.sqrt(fan_in))
            if kind == "enc_conv":
                enc_w = jnp.clip(jnp.round(w * 8.0), -16, 16) / 8.0
                continue
            s = jnp.max(jnp.abs(w)) / wm
            q = jnp.clip(jnp.round(w / s), -wm, wm)
            theta = jnp.maximum(jnp.round(th_nom / s), 1.0)
            # raw = softplus^-1(theta * s - 1e-3)
            raw = jnp.log(jnp.expm1(theta * s - 1e-3))
            made.append((q.astype(jnp.int8), theta.astype(jnp.int32), s,
                         q * s, raw))
        return enc_w, made

    enc_w, made = gen(seed_key(seed))
    weighted = [ly for ly in layers if ly[0] != "enc_conv"]   # aligns with made
    spiking = [m for m, ly in zip(made, weighted) if ly[0] != "readout"]
    # raw thresholds in the program's neuron-layer order: encoder, then
    # on-macro convs, then spiking FCs
    raw_th = jnp.concatenate([
        jnp.full((1,), config["threshold"], jnp.float32),
        jnp.stack([m[4] for m in spiking])])
    params = {
        "layers": [{"w": m[3]} for m, ly in zip(made, weighted)
                   if ly[0] != "conv"],
        "threshold": raw_th,
        "leak": jnp.full(raw_th.shape, config["leak"], jnp.float32)}
    if config["conv_spec"]:
        params["convs"] = [{"w": enc_w}] + [
            {"w": m[3]} for m, ly in zip(made, weighted) if ly[0] == "conv"]
    macro = tuple(
        MacroLayer(kind=kind, q=q, scale=s, stride=stride,
                   theta=theta if kind != "readout" else jnp.int32(0))
        for (q, theta, s, _, _), (kind, _, _, stride) in zip(made, weighted))
    truth = Truth(enc_threshold_raw=raw_th, enc_w=enc_w,
                  enc_stride=(config["conv_spec"][0][2]
                              if config["conv_spec"] else 1),
                  layers=macro)
    return params, truth
