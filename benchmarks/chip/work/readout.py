"""Required work of the accumulate-only readout layer (on-macro)."""


def count(layer: dict, batch: int, frames: int) -> tuple:
    """(int8 ops, least HBM bytes): 2 x the dense MACs; the int8 weights
    once, the int32 readout V and the float32 logits once each."""
    macs = layer["n_in"] * layer["n_out"] * batch * frames
    nbytes = layer["n_in"] * layer["n_out"] + batch * layer["n_out"] * 8
    if layer["first"]:
        nbytes += batch * frames * layer["in_elems"]
    return 2 * macs, nbytes
