"""mfu.batch: the whole ``run_network`` call's share of the chip's int8
peak: frames_per_s x the int8 ops the model requires per lane-frame
(``work/``, on-macro layers only; the float encoder is not counted) over
the peak. Moves frames_per_s."""
from harness.work import required


def read(r):
    fps = r.counters.get("frames_per_s")
    if not fps:
        return None
    ops, _ = required(r.config, 1, 1)
    return 100.0 * fps * ops / r.peaks["int8_ops_per_s"]
