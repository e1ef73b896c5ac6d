"""Offline batch scoring: ``run_network`` on the cell's backend over full
batches, back to back, each call ending with its logits on the host.

The call is jitted whole, as a scoring job compiles it: called eagerly,
``run_network`` retraces its encoder scan on every call, which would
compile inside the window. Lane-frames are B x T_total per finished
call. Every call's logits are compared with the reference. A seeded
reservoir of calls also keeps every macro layer's final V on the device,
through a second compiled call that returns it beside the logits; those
are compared too.
"""
from __future__ import annotations

import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from harness import checks, reference, traffic, weights
from harness.outcome import (Check, CompileCounter, GcWatch, Outcome,
                             StallWatch, settle)
from harness.spec import model_config
from harness.trace import capture

#: calls whose full macro V is kept for the comparison
RESERVOIR = 8
#: seconds of back-to-back calls after the first of each program, so
#: that the window opens on a device and host already in their stride
WARM_S = 3.0


def compare(kept: list, logits: list, want: list, scale: float,
            limits: dict) -> list:
    """The checks of a batch run: ``kept`` [(batch index, macro V
    tuple)] and ``logits`` [(batch index, logits)] against the
    reference's macro V per distinct batch (``want``, readout last)."""
    v_bad = sum(checks.mismatches(np.asarray(g), np.asarray(w))
                for bi, vs in kept for g, w in zip(vs, want[bi]))
    gap = max(checks.logit_rel_gap(lg, want[bi][-1], scale)
              for bi, lg in logits)
    return [Check("v_mismatch", v_bad, limits["v_mismatch"]),
            Check("logit_rel_gap", gap, limits["logit_rel_gap"])]


def run(r) -> Outcome:
    from repro.core import pipeline
    cell, seed = r.cell, r.seed
    counter, gcw, stalls = CompileCounter(), GcWatch(), StallWatch()
    params, truth = weights.make(cell.config, seed)
    program = pipeline.compile_network(model_config(cell.config), params,
                                       domain="int")
    xs_list = traffic.generator(cell.traffic).batches(cell.traffic,
                                                      cell.config, seed)
    backend = cell.settings["backend"]

    def call(xs):
        return pipeline.run_network(program, xs, backend, emit_rasters=False,
                                    **r.kernel_kw)

    def with_v(xs):
        res = call(xs)
        return res.logits, tuple(res.v_final[1:])

    score = jax.jit(lambda xs: call(xs).logits)
    score_v = jax.jit(with_v)
    for xs in xs_list:                       # warm up: every program once,
        np.asarray(score(xs))                # then back to back for WARM_S
        np.asarray(score_v(xs)[0])
    t_warm = time.perf_counter()
    while time.perf_counter() - t_warm < WARM_S:
        for xs in xs_list:
            np.asarray(score(xs))
    t_frames, batch = xs_list[0].shape[:2]
    settle()
    setup_s = time.perf_counter() - r.t_start

    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    kept, logits, spans = [], [], []
    calls = 0
    with counter.counting(), gcw.counting(), stalls.counting():
        t0 = time.perf_counter()
        while True:
            bi = calls % len(xs_list)
            slot = calls if calls < RESERVOIR else rng.integers(0, calls + 1)
            a, cpu = time.perf_counter(), time.thread_time()
            with TraceAnnotation("run_network"):
                if slot < RESERVOIR:
                    lg, vs = score_v(xs_list[bi])
                else:
                    lg = score(xs_list[bi])
                logits.append((bi, np.asarray(lg)))
            spans.append((a, time.perf_counter(), time.thread_time() - cpu))
            if slot < len(kept):
                kept[slot] = (bi, vs)
            elif slot < RESERVOIR:
                kept.append((bi, vs))
            calls += 1
            if time.perf_counter() - t0 >= r.seconds:
                break
        window_s = time.perf_counter() - t0
    frames_per_s = calls * batch * t_frames / window_s
    counters = {"calls": calls, "window_s": window_s,
                "frames_per_s": frames_per_s, "batch": int(batch),
                "frames": int(t_frames)}

    reduced = None
    if r.trace:
        n = cell.settings["trace_calls"]
        def traced():
            for i in range(n):
                with TraceAnnotation("run_network"):
                    np.asarray(score(xs_list[i % len(xs_list)]))
        _, reduced = capture(traced)
        counters["traced_calls"] = n

    peak = r.memory_peak()
    del score, score_v, program
    want = [reference.run_batch(truth, xs, cell.config["v_bits"])
            for xs in xs_list]
    found = compare(kept, logits, want, float(truth.layers[-1].scale),
                    cell.settings["limits"])
    return Outcome(
        setup_s=setup_s,
        end_to_end={"frames_per_s": frames_per_s, "setup_s": setup_s},
        counters=counters,
        checks=found,
        attempted=calls, failed=0, memory_peak_bytes=peak, reduced=reduced,
        notes=[f"compilations in the window: {counter.count}", gcw.note(),
               stalls.note("call", spans),
               f"calls in the window: {calls}, kept for the V check: "
               f"{len(kept)}"])
