"""From a profiler trace to the numbers the per-layer metrics read.

``capture`` runs a function under ``jax.profiler`` and returns a
`Reduced`: the device operations of the traced window (the "XLA Ops"
lines of each ``/device:`` plane), their busy-interval union averaged
over the devices, and the host's events for naming idle gaps.
"""
from __future__ import annotations

import glob
import re
import shutil
import tempfile
import time
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
#: host lines of the thread that drives the device: the Python thread's
#: spans and the runtime's work on its behalf (launch, allocate, copy)
HOST_LINE = re.compile(r"^(python3|main/\d+)$")


def op_name(event_name: str) -> str:
    """An "XLA Ops" event is named by its whole HLO instruction
    (``%fused_snn_net.1 = (...) custom-call(...)``); keep the name before
    `` = ``, so that a pattern cannot match an operand."""
    return event_name.split(" = ", 1)[0]


def union_length(intervals: np.ndarray) -> float:
    """Total length covered by (start, end) rows, overlaps counted once."""
    if len(intervals) == 0:
        return 0.0
    iv = intervals[np.argsort(intervals[:, 0])]
    total, cur_s, cur_e = 0.0, iv[0, 0], iv[0, 1]
    for s, e in iv[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    return float(total + cur_e - cur_s)


def gaps(intervals: np.ndarray) -> np.ndarray:
    """(start, end) rows of the idle gaps between busy intervals."""
    if len(intervals) < 2:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    g = np.stack([ends[:-1], iv[1:, 0]], axis=1)
    return g[g[:, 1] > g[:, 0]]


@dataclass
class Reduced:
    """One traced window. Times in seconds."""
    window_s: float
    ops: dict                 # device index -> list of (name, start, end)
    host: list = field(default_factory=list)   # (name, start, end)

    def _iv(self, dev, pattern=None):
        rows = [(s, e) for n, s, e in self.ops[dev]
                if pattern is None or re.search(pattern, n)]
        return np.asarray(rows, float).reshape(-1, 2)

    @property
    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the devices."""
        if not self.ops:
            return 0.0
        return float(np.mean([union_length(self._iv(d)) for d in self.ops]))

    def op_seconds(self, pattern: str) -> float:
        """Summed device time of the operations whose name matches
        ``pattern``, averaged over the devices."""
        if not self.ops:
            return 0.0
        return float(np.mean([
            sum(e - s for n, s, e in self.ops[d] if re.search(pattern, n))
            for d in self.ops]))

    def busy_outside_s(self, pattern: str) -> float:
        """Busy seconds not covered by operations matching ``pattern``."""
        if not self.ops:
            return 0.0
        out = []
        for d in self.ops:
            out.append(union_length(self._iv(d))
                       - union_length(self._iv(d, pattern)))
        return float(np.mean(out))

    def top_ops(self, n: int = 10) -> list:
        tot = defaultdict(float)
        for d in self.ops:
            for name, s, e in self.ops[d]:
                tot[name] += (e - s) / len(self.ops)
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> list:
        """The longest gaps on the first device, each named by the
        innermost host event that spans its middle."""
        if not self.ops:
            return []
        g = gaps(self._iv(min(self.ops)))
        g = g[np.argsort(g[:, 0] - g[:, 1])][:n]
        out = []
        for s, e in g:
            mid = 0.5 * (s + e)
            cover = [(he - hs, name) for name, hs, he in self.host
                     if hs <= mid <= he]
            out.append([min(cover)[1] if cover else "(no host event)",
                        float(e - s)])
        return out


def read_xplane(path: str, window_s: float) -> Reduced:
    """Reduce one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                idx = int(plane.name.rsplit(":", 1)[1])
                ops[idx] = [(op_name(ev.name), ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9)
                            for ev in line.events]
            elif plane.name.startswith("/host:") and HOST_LINE.match(line.name):
                host.extend((ev.name, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9)
                            for ev in line.events)
    return Reduced(window_s=window_s, ops=ops, host=host)


def capture(fn, tmp_root: str | None = None) -> tuple:
    """Run ``fn()`` under the profiler: (fn's result, `Reduced`). The
    trace is written under the temporary directory and deleted."""
    import jax
    d = tempfile.mkdtemp(prefix="chipbench-trace-", dir=tmp_root)
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        t0 = time.perf_counter()
        try:
            out = fn()
        finally:
            window = time.perf_counter() - t0
            jax.profiler.stop_trace()
        files = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
        if not files:
            raise RuntimeError("the profiler wrote no .xplane.pb file")
        return out, read_xplane(files[0], window)
    finally:
        shutil.rmtree(d, ignore_errors=True)
