"""Profiler spans of `SNNServeEngine.step`.

A short drain is recorded with `jax.profiler` and read back with
`jax.profiler.ProfileData`: one ``snn_engine.step`` span per `step` call,
every phase span inside a step, the retire children inside a retire, one
``admit``/``evict`` span per request carrying its ``rid``, and per tick
exactly the spans the engine's work list gives (so no span sits in a
per-lane loop). Recording changes no output.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import SpikingConfig
from repro.configs.impulse_snn import SNNModelConfig
from repro.core import pipeline, snn
from repro.serve import SNNRequest, SNNServeEngine

LENGTHS = [2, 4, 1, 0, 3, 2, 5]     # words; rid 3 finishes at admission
STOP_RID = 4                        # early exit: invalidates a staged block
RETIRE_CHILDREN = ("snn_engine.fetch", "snn_engine.account",
                   "snn_engine.evict")


@pytest.fixture(scope="module")
def program():
    """A small FC program; the static analyzer is tested elsewhere."""
    cfg = SNNModelConfig(
        arch_id="test", layer_sizes=(37, 50, 20, 3),
        spiking=SpikingConfig(neuron="rmp", timesteps=3, threshold=1.0,
                              leak=0.0625, w_bits=6, v_bits=11),
        timesteps=3)
    params = snn.init_fc_snn(jax.random.PRNGKey(3), cfg)
    return cfg, pipeline.compile_network(cfg, params, domain="int",
                                         validate=False)


def _engine(program, cfg, **ekw):
    eng = SNNServeEngine(program, batch_slots=2, pages=2, backend="int_ref",
                         validate=False, **ekw)
    for rid, nw in enumerate(LENGTHS):
        x = np.random.default_rng(60 + rid).standard_normal(
            (1, nw, cfg.layer_sizes[0])).astype(np.float32)
        frames = np.asarray(pipeline.present_words(jnp.asarray(x),
                                                   cfg.timesteps))[:, 0]
        eng.submit(SNNRequest(rid=rid, frames=frames, stop_threshold=(
            0.5 if rid == STOP_RID else None)))
    return eng


def _drain(eng) -> list:
    """Step to the end; per step, what the engine's own state says the
    tick did: (pages dispatched, admissions, evictions)."""
    pages = []
    work_list = eng.active_by_page

    def spy():
        out = work_list()
        pages.append(len(out))
        return out

    eng.active_by_page = spy
    ticks = []
    while not eng.queue.empty() or any(s.req for s in eng.slots):
        q, f = eng.queue.qsize(), len(eng.finished)
        eng.step()
        evicted = sum(1 for r in eng.finished[f:] if r.ticks > 0)
        ticks.append((pages[-1], q - eng.queue.qsize(), evicted))
    return ticks


def _spans(trace_dir) -> list:
    """(name, start_ns, end_ns, stats) of every ``snn_engine.*`` host event."""
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                        dict(ev.stats))
                       for ev in line.events
                       if ev.name.startswith("snn_engine."))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(child, parents) -> list:
    return [p for p in parents if p[1] <= child[1] and child[2] <= p[2]]


def _within(parent, spans) -> list:
    return [s for s in spans if parent[1] <= s[1] and s[2] <= parent[2]]


# every (megastep, double_buffer) pair; accounting on in half of them (the
# benchmark's serving cell runs with it off)
@pytest.mark.parametrize("megastep,double_buffer,track_events", [
    (1, False, True), (1, True, False), (4, False, False), (4, True, True)],
    ids=["k1-tracked", "k1-db", "k4", "k4-db-tracked"])
def test_engine_spans(tmp_path, program, megastep, double_buffer,
                      track_events):
    cfg, program = program
    ekw = dict(megastep=megastep, double_buffer=double_buffer,
               track_events=track_events)
    plain = _engine(program, cfg, **ekw)
    _drain(plain)
    eng = _engine(program, cfg, **ekw)
    jax.profiler.start_trace(str(tmp_path))
    try:
        ticks = _drain(eng)
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)

    # recording changes no output
    want = {r.rid: r for r in plain.finished}
    assert sorted(want) == list(range(len(LENGTHS)))
    for r in eng.finished:
        assert r.ticks == want[r.rid].ticks
        np.testing.assert_array_equal(r.logits, want[r.rid].logits)
        np.testing.assert_array_equal(r.v_out, want[r.rid].v_out)

    steps = [s for s in spans if s[0] == "snn_engine.step"]
    assert len(steps) == eng.steps == len(ticks)
    assert [s[3]["step_num"] for s in steps] == list(range(len(ticks)))
    retires = [s for s in spans if s[0] == "snn_engine.retire"]
    for s in spans:
        if s[0] != "snn_engine.step":
            assert len(_inside(s, steps)) == 1, s
        if s[0] in RETIRE_CHILDREN:
            assert len(_inside(s, retires)) == 1, s

    def rids(name):
        return sorted(s[3]["rid"] for s in spans if s[0] == name)

    assert rids("snn_engine.admit") == list(range(len(LENGTHS)))
    assert rids("snn_engine.evict") == sorted(
        r.rid for r in eng.finished if r.ticks > 0)
    assert sorted(s[3]["page"] for s in spans
                  if s[0] == "snn_engine.dispatch") == sorted(
        s[3]["page"] for s in retires)
    assert {s[3]["k"] for s in spans
            if s[0] == "snn_engine.dispatch"} == {megastep}

    # per tick: the step, 4 per dispatched page (stage, dispatch, retire,
    # fetch), account per page when tracking, stage_next when double
    # buffered, one per admission and one per eviction
    for step, (pages, admitted, evicted) in zip(steps, ticks):
        got = [s[0] for s in _within(step, spans)]
        want_n = 1 + admitted + evicted
        if pages:
            want_n += pages * (4 + track_events) + double_buffer
        assert len(got) == want_n, (step[3], got)
        assert got.count("snn_engine.dispatch") == pages
        assert got.count("snn_engine.account") == pages * track_events
        assert got.count("snn_engine.stage_next") == (
            double_buffer and pages > 0)
