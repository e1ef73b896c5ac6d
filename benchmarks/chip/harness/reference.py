"""The plain reference of the IMPULSE network semantics, written from the
paper's description and the configuration file alone.

* Encoder (off-macro, float32): ``v += I`` (the current, or the conv
  encoder's output); a spike where ``v >= th``; RMP soft reset ``v -= th``.
  ``th = softplus(raw) + 1e-3`` is evaluated eagerly on the device, as the
  configuration's parameterisation defines it.
* On-macro layers (int32 words): ``v = clamp(v + s @ W)`` into the V word
  (11 bits), a spike where ``v >= theta``, ``v = clamp(v - theta)`` where it
  spiked. Convs apply W at every output position (SAME padding, stride).
* Readout: ``v += s @ W``, unclamped; logits are ``v * scale``.

It takes the benchmark's integer weights and thresholds (`weights.Truth`)
and nothing that the program made. ``lower_precision`` gives the control:
the same network on a coarser weight grid.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from harness.weights import Truth


def encoder_threshold(truth: Truth) -> jax.Array:
    """softplus(raw) + 1e-3 of the encoder, evaluated eagerly on the
    device like the configuration's parameterisation."""
    return (jax.nn.softplus(truth.enc_threshold_raw) + 1e-3)[0]


def lower_precision(truth: Truth, w_bits: int, from_bits: int) -> Truth:
    """The control: every on-macro layer re-gridded to ``w_bits``-bit
    weights (scale and thresholds follow the coarser grid)."""
    f = 2 ** (from_bits - w_bits)
    wm = 2 ** (w_bits - 1) - 1
    layers = tuple(dataclasses.replace(
        ly, q=jnp.clip(jnp.round(ly.q.astype(jnp.float32) / f), -wm, wm
                       ).astype(jnp.int8),
        theta=(jnp.maximum(jnp.round(ly.theta / f), 1).astype(jnp.int32)
               if ly.kind != "readout" else ly.theta),
        scale=ly.scale * f) for ly in truth.layers)
    return dataclasses.replace(truth, layers=layers)


def _conv(x, w, stride, precision=None):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _macro_acc(kind, spikes, q, stride):
    """s @ W (fc) or the conv of a spike map, exact in int32."""
    if kind == "conv":
        acc = _conv(spikes.astype(jnp.float32), q.astype(jnp.float32),
                    stride, precision=jax.lax.Precision.HIGHEST)
        return jnp.round(acc).astype(jnp.int32)
    flat = spikes.reshape(spikes.shape[0], -1).astype(jnp.int8)
    return jax.lax.dot(flat, q, preferred_element_type=jnp.int32)


def _step(kinds, strides, v_lo, v_hi, th, enc_w, enc_stride, qs, thetas,
          state, x):
    """One timestep of the whole network on a (B, ...) current frame."""
    v_enc, vs = state
    cur = x if enc_w is None else _conv(x, enc_w, enc_stride,
                                        precision=jax.lax.Precision.HIGHEST)
    v_enc = v_enc + cur
    s = (v_enc >= th).astype(jnp.float32)
    v_enc = v_enc - th * s
    new = []
    for kind, stride, q, theta, v in zip(kinds, strides, qs, thetas, vs):
        acc = _macro_acc(kind, s, q, stride)
        if kind == "readout":
            new.append(v + acc)
            continue
        v = jnp.clip(v + acc, v_lo, v_hi)
        fired = v >= theta
        v = jnp.clip(jnp.where(fired, v - theta, v), v_lo, v_hi)
        new.append(v)
        s = fired
    return v_enc, tuple(new)


def _init_state(truth: Truth, x0: jax.Array):
    """Zero encoder and macro V for the batch of current frame ``x0``."""
    b = x0.shape[0]
    if truth.enc_w is None:
        v_enc = jnp.zeros_like(x0)
        shape = x0.shape[1:]
    else:
        v_enc = jnp.zeros(jax.eval_shape(
            lambda a: _conv(a, truth.enc_w, truth.enc_stride), x0).shape,
            jnp.float32)
        shape = v_enc.shape[1:]
    vs = []
    for ly in truth.layers:
        if ly.kind == "conv":
            h = -(-shape[0] // ly.stride)
            w = -(-shape[1] // ly.stride)
            shape = (h, w, ly.q.shape[-1])
        else:
            shape = (ly.q.shape[-1],)
        vs.append(jnp.zeros((b, *shape), jnp.int32))
    return v_enc, tuple(vs)


def _static(truth: Truth, v_bits: int):
    return (tuple(ly.kind for ly in truth.layers),
            tuple(ly.stride for ly in truth.layers),
            -(2 ** (v_bits - 1)), 2 ** (v_bits - 1) - 1, truth.enc_stride)


@partial(jax.jit, static_argnums=(0,))
def _run_batch(static, th, enc_w, qs, thetas, state0, xs):
    kinds, strides, v_lo, v_hi, enc_stride = static
    step = partial(_step, kinds, strides, v_lo, v_hi, th, enc_w, enc_stride,
                   qs, thetas)
    (_, vs), _ = jax.lax.scan(lambda st, x: (step(st, x), None), state0, xs)
    return vs


def run_batch(truth: Truth, xs: jax.Array, v_bits: int) -> tuple:
    """Final V of every macro layer (readout last) after presenting the
    (T, B, ...) currents ``xs`` from rest."""
    return _run_batch(_static(truth, v_bits), encoder_threshold(truth),
                      truth.enc_w, tuple(ly.q for ly in truth.layers),
                      tuple(ly.theta for ly in truth.layers),
                      _init_state(truth, xs[0]), xs)


@partial(jax.jit, static_argnums=(0, 1))
def _run_streams(static, t_max, th, enc_w, qs, thetas, state0, pool,
                 offsets, lengths):
    kinds, strides, v_lo, v_hi, enc_stride = static
    step = partial(_step, kinds, strides, v_lo, v_hi, th, enc_w, enc_stride,
                   qs, thetas)

    def body(t, st):
        x = pool[offsets + jnp.minimum(t, lengths - 1)]
        new = step(st, x)
        live = t < lengths
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(
                live.reshape((-1,) + (1,) * (a.ndim - 1)), a, b), new, st)

    return jax.lax.fori_loop(0, t_max, body, state0)[1]


def run_streams(truth: Truth, pool: jax.Array, offsets, lengths,
                v_bits: int) -> tuple:
    """Each stream ``pool[offset : offset + length]`` presented from rest;
    final V of every macro layer per stream (readout last)."""
    offsets = jnp.asarray(offsets, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    t_max = int(jnp.max(lengths))
    state0 = _init_state(truth, pool[offsets])
    return _run_streams(_static(truth, v_bits), t_max,
                        encoder_threshold(truth), truth.enc_w,
                        tuple(ly.q for ly in truth.layers),
                        tuple(ly.theta for ly in truth.layers), state0,
                        pool, offsets, lengths)
