"""The six ``engine_*_ms.serve`` readers: the serving engine's tick split
by the program's phase spans."""
import pytest

from harness import spec
from harness.outcome import Readings
from harness.trace import Reduced

PHASES = ("admit", "stage", "dispatch", "fetch", "retire", "step_self")


def read_all(reduced) -> dict:
    r = Readings(counters={}, trace=reduced, config={}, peaks={})
    return {p: spec.load_module("metrics", f"engine_{p}_ms.serve").read(r)
            for p in PHASES}


def test_readers_split_the_step_span():
    ms = 1e-3
    host = [
        ("SNNServeEngine.step", 0.0, 10.1 * ms),      # the driver's span
        # tick 1: 10 ms
        ("snn_engine.step", 0.0, 10 * ms),
        ("snn_engine.admit", 0.5 * ms, 1 * ms),
        ("snn_engine.stage", 1 * ms, 2 * ms),
        ("snn_engine.dispatch", 2 * ms, 4 * ms),
        ("snn_engine.retire", 4 * ms, 9 * ms),
        ("snn_engine.fetch", 4 * ms, 6 * ms),
        ("snn_engine.account", 6 * ms, 7 * ms),
        ("snn_engine.evict", 7 * ms, 8 * ms),
        # tick 2: 10 ms, double buffered
        ("snn_engine.step", 20 * ms, 30 * ms),
        ("snn_engine.stage", 20 * ms, 21 * ms),
        ("snn_engine.dispatch", 21 * ms, 22 * ms),
        ("snn_engine.stage_next", 22 * ms, 23 * ms),
        ("snn_engine.retire", 23 * ms, 29 * ms),
        ("snn_engine.fetch", 23 * ms, 27 * ms),
        # outside every recorded step: cut by the trace's edges
        ("snn_engine.retire", 29.5 * ms, 30.5 * ms),
        ("snn_engine.fetch", 40 * ms, 41 * ms),
    ]
    got = read_all(Reduced(window_s=0.05, ops={}, host=host))
    want = {"admit": 0.25, "stage": 1.5, "dispatch": 1.5, "fetch": 3.0,
            "retire": 2.5, "step_self": 1.25}
    assert got == pytest.approx(want, abs=1e-9)
    assert sum(got.values()) == pytest.approx(10.0, abs=1e-9)


def test_readers_find_nothing_without_engine_spans():
    """A program without the spans (the parent's) reads nothing."""
    host = [("SNNServeEngine.step", 0.0, 0.004),
            ("np.asarray(jax.Array)", 0.001, 0.002)]
    assert set(read_all(Reduced(window_s=0.01, ops={}, host=host))
               .values()) == {None}
    assert set(read_all(None).values()) == {None}


def test_recorded_serve_trace():
    """24 ticks of imdb-serve on one TPU v5 lite, with the engine's spans
    (~150 of 256 lanes busy). The readers' split is cross-checked here by
    summing, per step span, every engine span that lies inside it."""
    from pathlib import Path

    from harness.trace import read_xplane
    path = Path(__file__).parent / "data" / "imdb-serve.xplane.pb"
    r = read_xplane(str(path), window_s=0.12396136799999624)
    got = read_all(r)
    want = {"admit": 1.6706504166666678, "stage": 0.5803470833333357,
            "dispatch": 0.8479458333333362, "fetch": 1.6509532916666632,
            "retire": 0.21147208333333806, "step_self": 0.1447357916666622}
    assert got == pytest.approx(want, abs=1e-9)

    steps = [(s, e) for n, s, e in r.host if n == "snn_engine.step"]
    assert len(steps) == 24
    inside = {}
    for n, s, e in r.host:
        if n.startswith("snn_engine.") and n != "snn_engine.step" and any(
                a <= s and e <= b for a, b in steps):
            inside[n] = inside.get(n, 0.0) + (e - s) * 1e3 / len(steps)
    step_ms = sum(e - s for s, e in steps) * 1e3 / len(steps)
    assert step_ms == pytest.approx(5.106104500000003, abs=1e-9)
    assert sum(got.values()) == pytest.approx(step_ms, abs=1e-9)
    assert got["fetch"] == pytest.approx(inside["snn_engine.fetch"])
    assert got["retire"] == pytest.approx(
        inside["snn_engine.retire"] - inside["snn_engine.fetch"])
    assert got["step_self"] == pytest.approx(step_ms - sum(
        inside[n] for n in ("snn_engine.admit", "snn_engine.stage",
                            "snn_engine.dispatch", "snn_engine.retire")))
    # the longest idle gaps fall inside the engine's spans, not the
    # driver's span around the whole call
    assert r.top_ops(1)[0][0] == "%fused_snn_net.1"
    assert all(n.startswith("snn_engine.") or n == "(no host event)"
               for n, _ in r.idle_gaps())
