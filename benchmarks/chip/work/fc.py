"""Required work of one spiking FC layer (on-macro).

From logical shapes only: ``n_in`` x ``n_out`` weights, one AccW2V per
lane-frame. Padding, batch tiles and re-fetches are the implementation's,
not the model's, and are not counted.
"""


def count(layer: dict, batch: int, frames: int) -> tuple:
    """(int8 ops, least HBM bytes) of ``batch`` lanes x ``frames`` frames:
    ops are 2 x the dense MACs; bytes are the int8 weights once, the
    int32 final V once and, for the stack's first layer, its int8 input
    spikes once."""
    macs = layer["n_in"] * layer["n_out"] * batch * frames
    nbytes = layer["n_in"] * layer["n_out"] + batch * layer["n_out"] * 4
    if layer["first"]:
        nbytes += batch * frames * layer["in_elems"]
    return 2 * macs, nbytes
