"""outside_kernel_share.batch: device busy time outside the fused
kernel's events over device busy time, in the traced batch window: the
pipeline front end (``core/pipeline.py``: float encoder, im2col conv
front end, padding). Moves frames_per_s."""

#: the fused kernel's device events, by the name they carry in the trace:
#: the enclosing jit's name, one per kernel call (``%fused_snn_net.3``)
KERNEL = r"^%fused_snn_net(\.\d+)?$"


def read(r):
    if r.trace is None or not r.trace.ops:
        return None
    busy = r.trace.busy_s
    if busy <= 0:
        return None
    return 100.0 * r.trace.busy_outside_s(KERNEL) / busy
