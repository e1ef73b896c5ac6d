"""The benchmark's own copy of a recurrent configuration: its program
configuration, seeded weights, plain reference and work count.

A configuration file with ``recurrent`` (one flag per hidden FC layer)
describes a network whose recurrent layer l takes the layer below's spikes
and its own spikes of the previous tick through one 6-bit block
[W_in; W_rec] (n_in + n_out rows):

    v_l = clamp(v_l + s_in(t) @ W_in + s_l(t-1) @ W_rec)   # 11-bit word
    s_l(t) = v_l >= theta_l;  v_l = clamp(v_l - theta_l) where it fired

with s_l(-1) = 0; the encoder and the readout are those of
`harness.reference`. Each hidden layer's integer threshold is the file's
``thetas`` entry. Weights are drawn as `harness.weights` draws them
(Gaussian, std 2/sqrt(fan_in), fan_in counting the W_rec rows), the
whole block put on one W grid with max |w| = W_MAX, and handed to the
program as floats ``q * s``; the reference uses the integers.

`drivers/batch_recurrent.py` hands `make`, `run_batch` and
`model_config` to the batch driver in place of the feed-forward ones.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from harness import reference
from harness.spec import load_module
from harness.weights import MacroLayer, seed_key, w_max


@dataclasses.dataclass(frozen=True)
class RecurrentTruth:
    """What the benchmark made, for the reference: the encoder's raw
    threshold vector and the macro layers (readout last), a recurrent
    layer's ``q`` being its whole [W_in; W_rec] block."""
    enc_threshold_raw: jax.Array
    layers: tuple                  # MacroLayer, stack order, readout last
    recurrent: tuple               # per macro layer


def model_config(config: dict):
    """The program's configuration object, built from the file's numbers;
    ``recurrent`` is what a program without recurrent layers refuses."""
    from repro.configs.base import SpikingConfig
    from repro.configs.impulse_snn import SNNModelConfig
    return SNNModelConfig(
        arch_id=config["name"], layer_sizes=tuple(config["layer_sizes"]),
        spiking=SpikingConfig(
            neuron=config["neuron"], timesteps=config["timesteps"],
            threshold=config["threshold"], leak=config["leak"],
            w_bits=config["w_bits"], v_bits=config["v_bits"]),
        timesteps=config["timesteps"], task=config["task"],
        recurrent=tuple(bool(r) for r in config["recurrent"]))


def macro_layers(config: dict) -> list:
    """Logical on-macro layers: kind (``rfc`` for a recurrent FC), fan-in
    (input rows; a recurrent layer adds n_out W_rec rows), fan-out, and
    whether it is the stack's first."""
    sizes, rec = config["layer_sizes"], config["recurrent"]
    layers = []
    for j, (n_in, n_out) in enumerate(zip(sizes[:-1], sizes[1:])):
        if j == len(sizes) - 2:
            kind = "readout"
        else:
            kind = "rfc" if rec[j] else "fc"
        layers.append(dict(kind=kind, n_in=n_in, n_out=n_out, positions=1,
                           in_elems=n_in, first=j == 0))
    return layers


def required(config: dict, batch: int, frames: int) -> tuple:
    """(int8 ops, least HBM bytes) of one pass of ``batch`` lanes over
    ``frames`` frames through the on-macro stack (`work/<kind>.py`)."""
    ops = nbytes = 0
    for ly in macro_layers(config):
        o, b = load_module("work", ly["kind"]).count(ly, batch, frames)
        ops += o
        nbytes += b
    return ops, nbytes


def macs_per_lane_frame(config: dict) -> int:
    return required(config, 1, 1)[0] // 2


def make(config: dict, seed: int) -> tuple[dict, RecurrentTruth]:
    """(params for ``compile_network``, the benchmark's own truth)."""
    model_config(config)                  # refuse early where unsupported
    shapes = [(ly["n_in"] + (ly["n_out"] if ly["kind"] == "rfc" else 0),
               ly["n_out"]) for ly in macro_layers(config)]
    wm = float(w_max(config))
    thetas = [float(t) for t in config["thetas"]] + [0.0]

    @jax.jit
    def gen(key):
        made = []
        for k, shape, theta in zip(jax.random.split(key, len(shapes)),
                                   shapes, thetas):
            w = jax.random.normal(k, shape, jnp.float32) * (
                2.0 / np.sqrt(shape[0]))
            s = jnp.max(jnp.abs(w)) / wm
            q = jnp.clip(jnp.round(w / s), -wm, wm)
            # raw = softplus^-1(theta * s - 1e-3)
            raw = jnp.log(jnp.expm1(theta * s - 1e-3))
            made.append((q.astype(jnp.int8), s, q * s, raw))
        return made

    made = gen(seed_key(seed))
    raw_th = jnp.concatenate([
        jnp.full((1,), config["threshold"], jnp.float32),
        jnp.stack([m[3] for m in made[:-1]])])
    params = {"layers": [{"w": m[2]} for m in made], "threshold": raw_th,
              "leak": jnp.full(raw_th.shape, config["leak"], jnp.float32)}
    kinds = [ly["kind"] for ly in macro_layers(config)]
    layers = tuple(
        MacroLayer(kind="readout" if kind == "readout" else "fc", q=q,
                   scale=s, theta=jnp.int32(theta))
        for (q, s, _, _), kind, theta in zip(made, kinds, thetas))
    return params, RecurrentTruth(
        enc_threshold_raw=raw_th, layers=layers,
        recurrent=tuple(kind == "rfc" for kind in kinds))


def drop_w_rec(truth: RecurrentTruth) -> RecurrentTruth:
    """A control: the same network with every W_rec row zeroed."""
    layers = tuple(
        dataclasses.replace(ly, q=ly.q.at[ly.q.shape[0] - ly.q.shape[1]:]
                            .set(0)) if rec else ly
        for ly, rec in zip(truth.layers, truth.recurrent))
    return dataclasses.replace(truth, layers=layers)


@partial(jax.jit, static_argnums=(0,))
def _run_batch(static, th, qs, thetas, xs):
    """Final V per macro layer and spike total per hidden layer."""
    recurrent, v_lo, v_hi = static
    b = xs.shape[1]
    vs0 = tuple(jnp.zeros((b, q.shape[1]), jnp.int32) for q in qs)
    ss0 = tuple(jnp.zeros((b, q.shape[1]), jnp.int8) for q in qs[:-1])
    n0 = tuple(jnp.zeros((), jnp.int32) for _ in qs[:-1])

    def step(state, x):
        v_enc, vs, ss, n = state
        v_enc = v_enc + x
        s = (v_enc >= th).astype(jnp.int8)
        v_enc = v_enc - th * s
        vs, ss, n = list(vs), list(ss), list(n)
        for i, (q, theta) in enumerate(zip(qs, thetas)):
            if i < len(ss) and recurrent[i]:          # [s_in, s(t-1)] rows
                s = jnp.concatenate([s, ss[i]], axis=1)
            acc = jax.lax.dot(s, q, preferred_element_type=jnp.int32)
            if i == len(qs) - 1:                      # readout, unclamped
                vs[i] = vs[i] + acc
                continue
            v = jnp.clip(vs[i] + acc, v_lo, v_hi)
            fired = v >= theta
            vs[i] = jnp.clip(jnp.where(fired, v - theta, v), v_lo, v_hi)
            s = ss[i] = fired.astype(jnp.int8)
            n[i] = n[i] + jnp.sum(s, dtype=jnp.int32)
        return (v_enc, tuple(vs), tuple(ss), tuple(n)), None

    (_, vs, _, n), _ = jax.lax.scan(
        step, (jnp.zeros_like(xs[0]), vs0, ss0, n0), xs)
    return vs, n


def _run(truth: RecurrentTruth, xs: jax.Array, v_bits: int) -> tuple:
    static = (truth.recurrent, -(2 ** (v_bits - 1)), 2 ** (v_bits - 1) - 1)
    return _run_batch(static, reference.encoder_threshold(truth),
                      tuple(ly.q for ly in truth.layers),
                      tuple(ly.theta for ly in truth.layers), xs)


def run_batch(truth: RecurrentTruth, xs: jax.Array, v_bits: int) -> tuple:
    """Final V of every macro layer (readout last) after presenting the
    (T, B, n_in) currents ``xs`` from rest."""
    return _run(truth, xs, v_bits)[0]


def hidden_densities(truth: RecurrentTruth, xs: jax.Array,
                     v_bits: int) -> list:
    """Share of neuron-frames on which each hidden layer fires in the
    reference run on ``xs`` (how the file's ``thetas`` were chosen)."""
    t, b = xs.shape[:2]
    return [int(n) / (t * b * ly.q.shape[1])
            for n, ly in zip(_run(truth, xs, v_bits)[1], truth.layers)]
