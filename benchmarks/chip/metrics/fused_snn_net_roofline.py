"""fused_snn_net_roofline: the fused kernel's share of its roofline
(``kernels/fused_snn_net``). Least time is the larger of the required
int8 ops over the int8 peak and the least HBM bytes over the HBM
bandwidth (``work/``, from logical layer shapes; the float encoder is
not counted), for the calls traced; kernel time is the summed device
time of the kernel's events in the trace. Moves frames_per_s."""
from harness.work import required

#: the fused kernel's device events, by the name they carry in the trace:
#: the enclosing jit's name, one per kernel call (``%fused_snn_net.3``)
KERNEL = r"^%fused_snn_net(\.\d+)?$"


def read(r):
    if r.trace is None:
        return None
    kernel_s = r.trace.op_seconds(KERNEL)
    if kernel_s <= 0:
        return None
    ops, nbytes = required(r.config, r.counters["batch"], r.counters["frames"])
    calls = r.counters["traced_calls"]
    least = max(ops * calls / r.peaks["int8_ops_per_s"],
                nbytes * calls / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
