"""The work a configuration requires, summed over its on-macro layers by
the count of each layer kind (``work/<kind>.py``). The layers come from
the configuration file's logical shapes. The float encoder is off-macro
and outside the count."""
from __future__ import annotations

from harness.spec import load_module


def macro_layers(config: dict) -> list:
    """Logical on-macro layers: kind, fan-in, fan-out, output positions,
    input elements per lane-frame, and whether it is the stack's first."""
    layers = []
    if config["conv_spec"]:
        h, w, c = config["in_shape"]
        enc_out, _, enc_stride = config["conv_spec"][0]
        h, w, c = -(-h // enc_stride), -(-w // enc_stride), enc_out
        for c_out, k, stride in config["conv_spec"][1:]:
            ho, wo = -(-h // stride), -(-w // stride)
            layers.append(dict(kind="conv", n_in=k * k * c, n_out=c_out,
                               positions=ho * wo, in_elems=h * w * c))
            h, w, c = ho, wo, c_out
    sizes = config["layer_sizes"]
    for j, (n_in, n_out) in enumerate(zip(sizes[:-1], sizes[1:])):
        layers.append(dict(kind="readout" if j == len(sizes) - 2 else "fc",
                           n_in=n_in, n_out=n_out, positions=1,
                           in_elems=n_in))
    for i, ly in enumerate(layers):
        ly["first"] = i == 0
    return layers


def macs_per_lane_frame(config: dict) -> int:
    return sum(ly["n_in"] * ly["n_out"] * ly["positions"]
               for ly in macro_layers(config))


def required(config: dict, batch: int, frames: int) -> tuple:
    """(int8 ops, least HBM bytes) of one pass of ``batch`` lanes over
    ``frames`` frames through the on-macro stack."""
    ops = nbytes = 0
    for ly in macro_layers(config):
        o, b = load_module("work", ly["kind"]).count(ly, batch, frames)
        ops += o
        nbytes += b
    return ops, nbytes
