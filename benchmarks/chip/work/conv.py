"""Required work of one on-macro conv layer.

From logical shapes only: the k*k*c_in fan-in applied at every output
position (``positions`` = H_out * W_out). The im2col patch raster, padded
channels and batch tiles are the implementation's and are not counted;
the input is the layer's spike map (H * W * c_in per lane-frame), read
once.
"""


def count(layer: dict, batch: int, frames: int) -> tuple:
    """(int8 ops, least HBM bytes) of ``batch`` lanes x ``frames`` frames:
    2 x MACs at every output position; the int8 kernel once, the int32
    final V map once and, for the stack's first layer, its int8 input
    spike maps once."""
    per_pos = layer["n_in"] * layer["n_out"]
    macs = per_pos * layer["positions"] * batch * frames
    nbytes = per_pos + batch * layer["positions"] * layer["n_out"] * 4
    if layer["first"]:
        nbytes += batch * frames * layer["in_elems"]
    return 2 * macs, nbytes
