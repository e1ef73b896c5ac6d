"""fused_snn_net_roofline.ssc: the fused kernel's share of its roofline
in a recurrent cell. As ``fused_snn_net_roofline``, but the required work
counts a recurrent layer's fan-in as n_in + n_out (its W_rec rows,
``work/rfc.py``, through `harness.recurrent.required`). Moves
frames_per_s."""
from harness.recurrent import required

#: the fused kernel's device events, by the name they carry in the trace
KERNEL = r"^%fused_snn_net(\.\d+)?$"


def read(r):
    if r.trace is None or "recurrent" not in r.config:
        return None
    kernel_s = r.trace.op_seconds(KERNEL)
    if kernel_s <= 0:
        return None
    ops, nbytes = required(r.config, r.counters["batch"], r.counters["frames"])
    calls = r.counters["traced_calls"]
    least = max(ops * calls / r.peaks["int8_ops_per_s"],
                nbytes * calls / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
