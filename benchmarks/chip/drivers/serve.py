"""Open-loop serving: requests due on the wall clock, submitted to
`SNNServeEngine` as they fall due, the engine stepped whenever it holds
work.

Phases, each on the same schedule: prefill (about the longest request's
service time, so the window sees a steady mix of lengths), the measured
window, a traced sub-window (``--trace 1`` only), then the drain: arrivals
go on until every request due in the window has finished, or the drain
limit passes. Latency runs from a request's due time to its logits on the
host. Throughput is the lane-frames the engine advanced for live requests
inside the window over the window's length.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from harness import checks, reference, traffic, weights
from harness.outcome import (Check, CompileCounter, GcWatch, Outcome,
                             StallWatch, settle)
from harness.spec import model_config
from harness.trace import capture


@dataclass
class Pump:
    """Feeds one engine from one schedule. Times are seconds after
    ``t0``, the schedule's start."""
    eng: object
    sched: traffic.Schedule
    t0: float
    submit_s: np.ndarray = None
    finish_s: np.ndarray = None
    results: dict = field(default_factory=dict)
    step_s: list = field(default_factory=list)   # (start, end, cpu) per step
    nxt: int = 0
    harvested: int = 0
    done_frames: int = 0

    def __post_init__(self):
        n = len(self.sched.due_s)
        self.submit_s = np.full(n, np.nan)
        self.finish_s = np.full(n, np.nan)

    def served_frames(self) -> int:
        """Lane-frames advanced for requests so far: finished requests'
        ticks plus the cursors of those in flight."""
        return self.done_frames + sum(
            s.cursor for s in self.eng.slots if s.req is not None)

    def until(self, stop) -> float:
        """Run until ``stop(now)``; returns the time it stopped at."""
        from repro.serve import SNNRequest
        sched, eng = self.sched, self.eng
        n = len(sched.due_s)
        while True:
            now = time.perf_counter() - self.t0
            if stop(now):
                return now
            while self.nxt < n and sched.due_s[self.nxt] <= now:
                i = self.nxt
                off = sched.offsets[i]
                with TraceAnnotation("SNNServeEngine.submit"):
                    eng.submit(SNNRequest(
                        rid=i, frames=sched.pool[off:off + sched.frames[i]]))
                self.submit_s[i] = now
                self.nxt += 1
            if self.nxt > len(self.results):
                a, cpu = time.perf_counter() - self.t0, time.thread_time()
                with TraceAnnotation("SNNServeEngine.step"):
                    eng.step()
                b = time.perf_counter() - self.t0
                self.step_s.append((a, b, time.thread_time() - cpu))
                for req in eng.finished[self.harvested:]:
                    self.finish_s[req.rid] = b
                    self.results[req.rid] = (req.logits, req.v_out)
                    self.done_frames += req.ticks
                self.harvested = len(eng.finished)
            elif self.nxt < n:
                time.sleep(min(max(sched.due_s[self.nxt] - now, 0.0), 5e-4))
            else:
                return now


def make_engine(program, settings: dict, kernel_kw: dict):
    from repro.serve import SNNServeEngine
    return SNNServeEngine(program, batch_slots=settings["batch_slots"],
                          backend=settings["backend"],
                          track_events=settings["track_events"],
                          step_kw=dict(kernel_kw) or None)


def warm_up(eng, pool: np.ndarray) -> None:
    """Compile every program the window drives: admit, one dispatch of the
    full lane page, retire."""
    from repro.serve import SNNRequest
    for i in range(2):
        eng.submit(SNNRequest(rid=-1 - i, frames=pool[i:i + 2 + i]))
    while len(eng.finished) < 2:
        eng.step()
    eng.finished.clear()


def phases(eng, sched, *, prefill_s: float, seconds: float, trace_s: float,
           drain_limit_s: float, counter: CompileCounter,
           gcw: GcWatch, stalls: StallWatch) -> dict:
    """Run prefill, window, traced sub-window and drain on ``sched``."""
    p = Pump(eng, sched, time.perf_counter())
    w0 = p.until(lambda now: now >= prefill_s)
    f0, q0, k0 = p.served_frames(), eng.queue.qsize(), len(p.step_s)
    with counter.counting(), gcw.counting(), stalls.counting():
        w1 = p.until(lambda now: now >= prefill_s + seconds)
    f1, q1, k1 = p.served_frames(), eng.queue.qsize(), len(p.step_s)
    reduced = None
    if trace_s > 0:
        _, reduced = capture(lambda: p.until(
            lambda now: now >= w1 + trace_s))
    due = sched.due_s
    in_window = np.flatnonzero((due >= w0) & (due < w1))
    p.until(lambda now: now >= w1 + trace_s + drain_limit_s
            or not np.isnan(p.finish_s[in_window]).any())
    steps = np.asarray(p.step_s[k0:k1]).reshape(-1, 3)
    late = p.submit_s[in_window] - due[in_window]
    return dict(pump=p, w0=w0, w1=w1, frames=f1 - f0, queue=(q0, q1),
                in_window=in_window, steps=steps, reduced=reduced,
                late_ms=late * 1e3)


def compare(got_v, got_logits, want_v, scale: float, unfinished: int,
            limits: dict) -> list:
    """The checks of a serve run: each finished request's readout V and
    logits against the reference's readout V (rows in the same order),
    and the requests due in the window that never finished."""
    return [Check("readout_v_mismatch", checks.mismatches(got_v, want_v),
                  limits["readout_v_mismatch"]),
            Check("logit_rel_gap",
                  checks.logit_rel_gap(got_logits, want_v, scale),
                  limits["logit_rel_gap"]),
            Check("unfinished_in_window", unfinished,
                  limits["unfinished_in_window"])]


def run(r) -> Outcome:
    from repro.core import pipeline
    cell, seed, st = r.cell, r.seed, r.cell.settings
    counter, gcw, stalls = CompileCounter(), GcWatch(), StallWatch()
    params, truth = weights.make(cell.config, seed)
    program = pipeline.compile_network(model_config(cell.config), params,
                                       domain="int")
    trace_s = st["trace_s"] if r.trace else 0.0
    horizon = st["prefill_s"] + r.seconds + trace_s + st["drain_limit_s"]
    sched = traffic.generator(cell.traffic).schedule(
        cell.traffic, cell.config, st["rate_rps"], horizon, seed)
    eng = make_engine(program, st, r.kernel_kw)
    warm_up(eng, sched.pool)
    settle()
    t_sched = time.perf_counter()
    out = phases(eng, sched, prefill_s=st["prefill_s"], seconds=r.seconds,
                 trace_s=trace_s, drain_limit_s=st["drain_limit_s"],
                 counter=counter, gcw=gcw, stalls=stalls)
    setup_s = t_sched + out["w0"] - r.t_start
    p, idx = out["pump"], out["in_window"]
    window_s = out["w1"] - out["w0"]
    lat = (p.finish_s[idx] - sched.due_s[idx]) * 1e3
    unfinished = int(np.isnan(lat).sum())
    lat = lat[~np.isnan(lat)]
    frames_per_s = out["frames"] / window_s
    steps = out["steps"]
    counters = {"window_s": window_s, "frames_per_s": frames_per_s,
                "ticks": len(steps),
                "step_s_sum": float((steps[:, 1] - steps[:, 0]).sum()),
                "requests_in_window": len(idx)}
    peak = r.memory_peak()
    del eng, program

    done = np.array(sorted(p.results), np.int64)
    vs = reference.run_streams(truth, jax.numpy.asarray(sched.pool),
                               sched.offsets[done], sched.frames[done],
                               cell.config["v_bits"])
    found = compare(np.stack([p.results[i][1] for i in done]),
                    np.stack([p.results[i][0] for i in done]),
                    np.asarray(vs[-1]), float(truth.layers[-1].scale),
                    unfinished, st["limits"])
    q0, q1 = out["queue"]
    late = out["late_ms"]
    return Outcome(
        setup_s=setup_s,
        end_to_end={"frames_per_s": frames_per_s, "setup_s": setup_s,
                    "p95_latency_ms": (float(np.percentile(lat, 95))
                                       if len(lat) else float("nan"))},
        counters=counters,
        checks=found,
        attempted=len(idx), failed=unfinished, memory_peak_bytes=peak,
        reduced=out["reduced"],
        notes=[f"compilations in the window: {counter.count}", gcw.note(),
               stalls.note("tick", [(p.t0 + a, p.t0 + b, c)
                                    for a, b, c in steps]),
               (f"generator lateness: p99 {np.percentile(late, 99):.3f} ms, "
                f"max {late.max():.3f} ms over the window's {len(late)} "
                "submissions" if len(late) else "no submissions in the window"),
               f"queue at window open/close: {q0}/{q1}; requests due in "
               f"the window: {len(idx)}, compared: {len(done)}",
               f"ticks in the window: {len(steps)}",
               f"latency p50 {np.percentile(lat, 50):.1f} ms over "
               f"{len(lat)} requests" if len(lat) else "no latencies"])
