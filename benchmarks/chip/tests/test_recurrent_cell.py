"""The recurrent cell's own pieces: its work count against hand
arithmetic, its reference against the program at a size a test run
holds, and its two controls failing the checks."""
import dataclasses

import pytest

import control_ssc
from harness import recurrent, spec


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell("ssc-batch")


def test_macs_per_lane_frame(cell):
    """774,000 MACs per lane-frame; the W_rec rows carry 320,000 (41%)."""
    assert recurrent.macs_per_lane_frame(cell.config) == \
        (700 + 400) * 400 + (400 + 400) * 400 + 400 * 35 == 774_000


def test_bytes(cell):
    b, t = 1024, 250
    ops, nbytes = recurrent.required(cell.config, b, t)
    assert ops == 2 * 774_000 * b * t
    spikes_in = b * t * 700                       # int8, once
    w = 1100 * 400 + 800 * 400 + 400 * 35         # int8 [W_in; W_rec], once
    v = b * 4 * (400 + 400 + 35)                  # int32 final V
    logits = b * 35 * 4                           # float32
    assert nbytes == spikes_in + w + v + logits


def _tiny(cell):
    return dataclasses.replace(
        cell, traffic={**cell.traffic, "batch": 8, "words": 40})


def test_program_matches_reference(cell):
    """The driver's program (compiled from the cell's files, int_ref)
    equals the reference's final V of every macro layer."""
    import numpy as np
    from harness import traffic
    from repro.core import pipeline
    tiny = _tiny(cell)
    params, truth = recurrent.make(tiny.config, 2 ** 33 + 21)
    program = pipeline.compile_network(
        recurrent.model_config(tiny.config), params, domain="int")
    xs = traffic.generator(tiny.traffic).batches(
        tiny.traffic, tiny.config, 2 ** 33 + 21)[0]
    got = pipeline.run_network(program, xs, "int_ref").v_final[1:]
    want = recurrent.run_batch(truth, xs, tiny.config["v_bits"])
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    dens = recurrent.hidden_densities(truth, xs, tiny.config["v_bits"])
    assert all(0.01 <= d <= 0.5 for d in dens), dens


def test_controls_fail(cell):
    found = control_ssc.control_checks(_tiny(cell), seed=2 ** 33 + 21)
    assert set(found) == {"w5", "no_w_rec"}
    for name, checks in found.items():
        assert not all(c.ok for c in checks), (name, [
            (c.name, c.value) for c in checks])
