"""A whole run, chip check skipped, with the timed path broken
underneath: ``correct`` has to come out false for every fault a cell can
have. The cells are one chip each, so there is no exchange between chips
to leave out."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import run
from harness import spec
from rehearse import tiny
from repro.core import pipeline
from repro.kernels.fused_snn_net import ops

SEED = 2 ** 33 + 17


def _run(name):
    cell = tiny(spec.load_cell(name))
    return run.run_cell(cell, SEED, 1.0, False, jax.devices()[:1],
                        {"interpret": True}, peaks_kind="TPU v5 lite")


def _half_batch(monkeypatch):
    """run_network scores the first half of the batch; the rest get the
    mean of that half."""
    orig = pipeline.run_network

    def broken(program, xs, backend="float", **kw):
        half = xs.shape[1] // 2
        res = orig(program, xs[:, :half], backend, **kw)

        def fill(a):
            rest = jnp.broadcast_to(jnp.mean(a, axis=0).astype(a.dtype),
                                    (xs.shape[1] - half, *a.shape[1:]))
            return jnp.concatenate([a, rest])
        return dataclasses.replace(
            res, logits=fill(res.logits), v_out=fill(res.v_out),
            v_final=[fill(v) for v in res.v_final])
    monkeypatch.setattr(pipeline, "run_network", broken)


def _state_unchanged(monkeypatch):
    """The fused kernel hands back its state as it came in (zero V)."""
    orig = ops.fused_snn_net

    def broken(spikes, ws, **kw):
        rasters, v_finals, skips = orig(spikes, ws, **kw)
        init = kw.get("v_init")
        if init is None:
            init = [jnp.zeros_like(v) for v in v_finals]
        return rasters, list(init), skips
    monkeypatch.setattr(ops, "fused_snn_net", broken)


def _answer_altered(monkeypatch):
    """One readout value is off by one where the kernel produces it."""
    orig = ops.fused_snn_net

    def broken(spikes, ws, **kw):
        rasters, v_finals, skips = orig(spikes, ws, **kw)
        if kw.get("readout", True):
            v_finals = list(v_finals[:-1]) + [v_finals[-1].at[0, 0].add(1)]
        return rasters, v_finals, skips
    monkeypatch.setattr(ops, "fused_snn_net", broken)


def _serve_state_unchanged(monkeypatch):
    """A serving tick hands back the lanes' state as it came in."""
    orig = pipeline.stream_megastep

    def broken(program, state, frames, backend="float", **kw):
        _, out = orig(program, state, frames, backend, **kw)
        return state, out
    monkeypatch.setattr(pipeline, "stream_megastep", broken)


def _serve_answer_altered(monkeypatch):
    """Lane 0's readout is off by one where the tick produces it."""
    orig = pipeline.stream_megastep

    def broken(program, state, frames, backend="float", **kw):
        st, out = orig(program, state, frames, backend, **kw)
        return st, dataclasses.replace(
            out, v_out_traj=out.v_out_traj.at[:, 0].add(1),
            logits_traj=out.logits_traj.at[:, 0].add(1.0))
    monkeypatch.setattr(pipeline, "stream_megastep", broken)


@pytest.mark.parametrize("name", ["imdb-batch", "mnist-batch"])
@pytest.mark.parametrize("fault", [_half_batch, _state_unchanged,
                                   _answer_altered])
def test_batch_fault_is_caught(monkeypatch, name, fault):
    fault(monkeypatch)
    line = _run(name)
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("fault", [_serve_state_unchanged,
                                   _serve_answer_altered])
def test_serve_fault_is_caught(monkeypatch, fault):
    from repro.serve import snn_engine
    monkeypatch.setattr(snn_engine, "_MEGASTEP_JIT", {})
    fault(monkeypatch)
    line = _run("imdb-serve")
    assert line["correct"] is False, line["checks"]


def test_sound_run_is_correct():
    for name in ("imdb-serve", "imdb-batch", "mnist-batch"):
        line = _run(name)
        assert line["correct"] is True, (name, line["checks"])
