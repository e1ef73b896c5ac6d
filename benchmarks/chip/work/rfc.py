"""Required work of one recurrent spiking FC layer (on-macro).

From logical shapes only: the n_in input rows and the n_out recurrent
rows that carry the layer's own spikes of the previous tick, ``n_in +
n_out`` rows x ``n_out`` weights, one AccW2V per lane-frame. Padding,
batch tiles and re-fetches are the implementation's, not the model's,
and are not counted.
"""


def count(layer: dict, batch: int, frames: int) -> tuple:
    """(int8 ops, least HBM bytes) of ``batch`` lanes x ``frames`` frames:
    ops are 2 x the dense MACs over fan-in n_in + n_out; bytes are the
    int8 weight block [W_in; W_rec] once, the int32 final V once and, for
    the stack's first layer, its int8 input spikes once. The raster the
    W_rec rows read never leaves the kernel."""
    rows = layer["n_in"] + layer["n_out"]
    macs = rows * layer["n_out"] * batch * frames
    nbytes = rows * layer["n_out"] + batch * layer["n_out"] * 4
    if layer["first"]:
        nbytes += batch * frames * layer["in_elems"]
    return 2 * macs, nbytes
