"""Streaming execution contract: the step API and the SNN serving engine.

Two claims are swept here:

  1. *Stream == batch, bit for bit.* Driving a presentation frame-by-frame
     through `stream_step` (per-layer V carried as an explicit state tree)
     reproduces `run_network` exactly — rasters, final V, logits, and the
     event-gating skip counters — on every streaming backend, every neuron
     model, both clamp modes, odd shapes, and conv stacks. This is the
     paper's fused-V_MEM property restated at the API boundary: membrane
     state is *state*, not a per-call temporary.

  2. *Slots are invisible.* The continuous-batching SNN engine serves each
     request bit-identically to running it alone (batch lanes never
     interact), and its per-slot event accounting finalizes into
     SparsityReports equal to the ones the batch path derives from full
     rasters — so serving-time skip accounting feeds the energy model with
     no drift.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SpikingConfig
from repro.configs.impulse_snn import SNNModelConfig
from repro.core import energy, pipeline, snn
from repro.serve import SNNRequest, SNNServeEngine
from repro.serve.engine import EngineUndrained
from repro.serve.snn_engine import merge_reports

LENET_S = SNNModelConfig(
    arch_id="lenet-s",
    conv_spec=((4, 3, 1), (6, 3, 2)),
    in_shape=(8, 8, 1),
    layer_sizes=(4 * 4 * 6, 10, 3),
    spiking=SpikingConfig(neuron="rmp", timesteps=2, threshold=1.0,
                          leak=0.0625, w_bits=6, v_bits=11),
    timesteps=2, task="multiclass")


def _make(layer_sizes, neuron, n_words, batch, seed=0):
    cfg = SNNModelConfig(
        arch_id="test", layer_sizes=layer_sizes,
        spiking=SpikingConfig(neuron=neuron, timesteps=3, threshold=1.0,
                              leak=0.0625, w_bits=6, v_bits=11),
        timesteps=3)
    params = snn.init_fc_snn(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed + 7)
    x = jnp.asarray(rng.standard_normal(
        (batch, n_words, layer_sizes[0])).astype(np.float32))
    return cfg, params, x


def _stream(program, xs, backend, **kw):
    """Run xs through stream_step tick by tick; returns (state, stacked
    rasters, accumulated skips, last StreamOut)."""
    state = program.init_state(xs.shape[1], backend)
    frames_r, skips = [], None
    out = None
    for t in range(xs.shape[0]):
        state, out = program.step(state, xs[t], backend, **kw)
        frames_r.append(out.rasters)
        # event backends return EventStats (a NamedTuple: `+` would
        # concatenate, not add) — their counters are checked elsewhere
        if out.skips is not None and backend not in ("ref_events",
                                                     "pallas_events"):
            if skips is None:
                skips = out.skips
            elif isinstance(skips, list):
                skips = [a + b for a, b in zip(skips, out.skips)]
            else:
                skips = skips + out.skips
    rasters = [np.stack([np.asarray(fr[i]) for fr in frames_r])
               for i in range(len(frames_r[0]))]
    return state, rasters, skips, out


def _assert_stream_matches_batch(program, xs, backend, tag, **kw):
    run_kw = dict(kw)
    if backend == "float":
        run_kw = {"collect_rasters": True}
    res = pipeline.run_network(program, xs, backend, **run_kw)
    state, rasters, skips, out = _stream(program, xs, backend, **kw)
    assert state.t == xs.shape[0]
    for i, (a, b) in enumerate(zip(state.vs, res.v_final)):
        np.testing.assert_array_equal(
            np.asarray(a, np.float64), np.asarray(b, np.float64),
            err_msg=f"{tag} V {i}")
    ref = res.rasters[-len(rasters):]      # float emits per-neuron-layer
    for i, (a, b) in enumerate(zip(rasters, ref)):
        np.testing.assert_array_equal(
            a.astype(np.int8), np.asarray(b).astype(np.int8),
            err_msg=f"{tag} raster {i}")
    np.testing.assert_array_equal(np.asarray(out.v_out, np.int64)
                                  if backend != "float" else out.v_out,
                                  np.asarray(res.v_out, np.int64)
                                  if backend != "float" else res.v_out,
                                  err_msg=f"{tag} v_out")
    np.testing.assert_allclose(np.asarray(out.logits),
                               np.asarray(res.logits), err_msg=f"{tag} logits")
    return res, skips


BACKEND_KW = [
    ("float", {}),
    ("int_ref", {}),
    ("int_ref", {"use_sparse": True}),
    ("pallas", {"interpret": True, "block_b": 4}),
    ("pallas_sparse", {"interpret": True, "block_b": 4}),
    ("pallas_sparse", {"interpret": True, "block_b": 4,
                       "gate_granularity": 4}),
    ("ref_events", {}),
    ("pallas_events", {"interpret": True, "block_b": 4}),
]


def _case_id(b, k):
    gran = f"-g{k['gate_granularity']}" if "gate_granularity" in k else ""
    return f"{b}{gran}{'-sparse' if k.get('use_sparse') else ''}"


@pytest.mark.parametrize("backend,kw", BACKEND_KW,
                         ids=[_case_id(b, k) for b, k in BACKEND_KW])
def test_stream_matches_batch_all_backends(backend, kw):
    """The full backend set on one program: frame-by-frame streaming is
    bit-identical to the batch raster run, skip counters included."""
    cfg, params, x = _make((37, 50, 20, 3), "rmp", 3, 2)
    program = pipeline.compile_network(cfg, params, domain="int")
    xs = pipeline.present_words(x, cfg.timesteps)
    res, skips = _assert_stream_matches_batch(program, xs, backend,
                                              f"{backend}/{kw}", **kw)
    if skips is not None:                  # summed per-tick gate counters
        ref = res.aux["skip_counts"]
        if isinstance(ref, list):
            for a, b in zip(skips, ref):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_array_equal(np.asarray(skips), np.asarray(ref))


@pytest.mark.parametrize("clamp_mode", ["saturate", "wrap"])
@pytest.mark.parametrize("neuron", ["if", "lif", "rmp"])
def test_stream_neuron_clamp_sweep(neuron, clamp_mode):
    """Neuron x clamp sweep on ragged shapes, int_ref + event-gated pallas."""
    cfg, params, x = _make((37, 50, 20, 3), neuron, 2, 2, seed=3)
    program = pipeline.compile_network(cfg, params, domain="int",
                                       clamp_mode=clamp_mode)
    xs = pipeline.present_words(x, cfg.timesteps)
    for backend, kw in [("int_ref", {}),
                        ("pallas_sparse", {"interpret": True, "block_b": 4})]:
        _assert_stream_matches_batch(program, xs, backend,
                                     f"{neuron}/{clamp_mode}/{backend}", **kw)


@pytest.mark.parametrize("backend,kw", [
    ("int_ref", {}),
    ("pallas", {"interpret": True, "block_b": 4}),
    ("ref_events", {}),
    ("pallas_events", {"interpret": True, "block_b": 4}),
])
def test_stream_conv_stack(backend, kw):
    """Conv programs stream too: the im2col front-end threads per-conv V
    maps through the state tree."""
    cfg = LENET_S
    params = snn.init_lenet_snn(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal(
        (2, *cfg.in_shape)).astype(np.float32)) * 2.0
    program = pipeline.compile_network(cfg, params, domain="int",
                                       clamp_mode="wrap")
    xs = pipeline.present_static(x, cfg.timesteps)
    _assert_stream_matches_batch(program, xs, backend, f"conv/{backend}",
                                 **kw)


def test_stream_float_domain_program():
    """The QAT (float-domain) program streams on the float backend with the
    same state-tree contract. True-float accumulation is NOT bit-stable
    between the scanned batch loop and eager per-tick ops (XLA fuses them
    differently; last-ulp drift), so this checks to f32 tolerance — the
    bit-identity guarantee belongs to the integer domain, where the float
    backend is an exact integer rendering and IS swept bit-exact above."""
    cfg, params, x = _make((20, 16, 8, 2), "lif", 2, 3, seed=5)
    program = pipeline.compile_network(cfg, params, domain="float")
    xs = pipeline.present_words(x, cfg.timesteps)
    res = pipeline.run_network(program, xs, "float", collect_rasters=True)
    state, rasters, _, out = _stream(program, xs, "float")
    for i, (a, b) in enumerate(zip(state.vs, res.v_final)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, err_msg=f"float-domain V {i}")
    for i, (a, b) in enumerate(zip(rasters, res.rasters)):
        np.testing.assert_allclose(a, np.asarray(b),
                                   err_msg=f"float-domain raster {i}")
    np.testing.assert_allclose(np.asarray(out.logits),
                               np.asarray(res.logits), atol=1e-5)


def test_stream_serving_mode_no_rasters():
    """emit_rasters=False: same state trajectory and outputs, no raster
    emission (the serving configuration)."""
    cfg, params, x = _make((37, 50, 20, 3), "rmp", 2, 2, seed=9)
    program = pipeline.compile_network(cfg, params, domain="int")
    xs = pipeline.present_words(x, cfg.timesteps)
    st_a = program.init_state(2, "int_ref")
    st_b = program.init_state(2, "int_ref")
    for t in range(xs.shape[0]):
        st_a, out_a = program.step(st_a, xs[t], "int_ref")
        st_b, out_b = program.step(st_b, xs[t], "int_ref",
                                   emit_rasters=False)
        assert out_b.rasters is None
    for a, b in zip(st_a.vs, st_b.vs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(out_a.v_out),
                                  np.asarray(out_b.v_out))


def test_stream_state_validation():
    cfg, params, _ = _make((37, 50, 20, 3), "rmp", 2, 2)
    program = pipeline.compile_network(cfg, params, domain="int")
    with pytest.raises(KeyError, match="bitmacro"):
        program.init_state(2, "bitmacro")
    fprogram = pipeline.compile_network(cfg, params, domain="float")
    with pytest.raises(ValueError, match="int-domain"):
        fprogram.init_state(2, "int_ref")
    state = program.init_state(2, "int_ref")
    assert len(state.vs) == len(program.layers) and state.t == 0


# ---------------------------------------------------------------------------
# SNN serving engine
# ---------------------------------------------------------------------------

def _imdb_like_program(seed=0, layer_sizes=(37, 50, 20, 3), neuron="rmp"):
    cfg, params, _ = _make(layer_sizes, neuron, 2, 2, seed=seed)
    return cfg, pipeline.compile_network(cfg, params, domain="int")


def _word_request(cfg, rid, n_words, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((1, n_words, cfg.layer_sizes[0])).astype(
        np.float32)
    frames = np.asarray(pipeline.present_words(
        jnp.asarray(x), cfg.timesteps))[:, 0]
    return SNNRequest(rid=rid, frames=frames), jnp.asarray(x)


@pytest.mark.parametrize("backend,kw", [
    ("int_ref", {}),
    ("pallas_sparse", {"interpret": True, "block_b": 4}),
    ("pallas_events", {"interpret": True, "block_b": 4}),
])
def test_snn_engine_staggered_equals_isolated(backend, kw):
    """Staggered admits/evictions (5 requests of different lengths through
    2 slots): every request's v_out/logits equal an isolated batch run of
    its own frames, and its per-slot SparsityReport equals the report the
    batch path builds from full rasters."""
    cfg, program = _imdb_like_program()
    eng = SNNServeEngine(program, batch_slots=2, backend=backend,
                         step_kw=kw)
    reqs = [_word_request(cfg, rid, nw, seed=40 + rid)
            for rid, nw in enumerate([2, 4, 1, 3, 2])]
    for r, _ in reqs:
        eng.submit(r)
    done = eng.run_until_drained()
    assert sorted(r.rid for r in done) == list(range(5))
    for rid, (_, x) in enumerate(reqs):
        r = next(d for d in done if d.rid == rid)
        xs = pipeline.present_words(x, cfg.timesteps)
        iso = pipeline.run_network(program, xs, "int_ref")
        np.testing.assert_array_equal(r.v_out, np.asarray(iso.v_out)[0],
                                      err_msg=f"rid {rid}")
        np.testing.assert_allclose(r.logits, np.asarray(iso.logits)[0],
                                   err_msg=f"rid {rid}")
        ref = pipeline.sparsity_report(program, iso.rasters)
        assert r.report.events == ref.events
        assert r.report.layer_frames == ref.layer_frames
        for a, b in zip(r.report.row_events, ref.row_events):
            np.testing.assert_array_equal(a, b)
        assert r.report.instruction_counts() == ref.instruction_counts()


def test_snn_engine_accounting_closes_energy_loop():
    """Per-slot skip accounting -> merged SparsityReport -> measured EDP:
    executed + skipped instruction cycles close against the dense tally,
    and the merged report equals the sum of its parts."""
    cfg, program = _imdb_like_program(seed=2)
    eng = SNNServeEngine(program, batch_slots=2, backend="int_ref",
                         step_kw={"use_sparse": True})
    for rid in range(4):
        eng.submit(_word_request(cfg, rid, 2, seed=60 + rid)[0])
    done = eng.run_until_drained()
    agg = eng.aggregate_report()
    assert agg.instruction_counts().total == sum(
        r.report.instruction_counts().total for r in done)
    assert agg.frames == sum(r.report.frames for r in done)
    # executed + skipped == dense (the Fig. 11b closure, serving-side)
    from repro.core import isa
    dense = isa.InstrCount()
    for ni, no, neuron, f in zip(agg.n_in, agg.n_out, agg.neurons,
                                 agg.frames_by_layer):
        dense += isa.count_layer_instructions_from_events(f * ni, f, ni, no,
                                                          neuron)
    both = agg.instruction_counts() + agg.skipped_instruction_counts()
    assert both.acc_w2v == dense.acc_w2v
    assert energy.measured_edp(agg.instruction_counts()) > 0
    assert 0.0 < agg.skipped_row_fraction < 1.0
    with pytest.raises(ValueError):
        merge_reports([])


def test_snn_engine_early_exit_and_tick_budget():
    """Per-slot stop conditions: a confident readout (stop_threshold) exits
    before the frame budget; max_ticks truncates the stream; both record
    the ticks actually served."""
    cfg, program = _imdb_like_program(seed=4)
    req_full, x = _word_request(cfg, 0, 4, seed=11)
    t_total = len(req_full.frames)
    # threshold early exit: pick a threshold below the final |logit| so the
    # exit must trigger at or before the end — then check it used the
    # *first* tick whose logit cleared it
    xs = pipeline.present_words(x, cfg.timesteps)
    state = program.init_state(1, "int_ref")
    traj = []
    for t in range(t_total):
        state, out = program.step(state, xs[t], "int_ref")
        traj.append(float(np.max(np.abs(np.asarray(out.logits)))))
    thr = max(traj) * 0.5
    first = next(t for t, v in enumerate(traj) if v >= thr) + 1
    req = SNNRequest(rid=0, frames=np.asarray(req_full.frames),
                     stop_threshold=thr)
    eng = SNNServeEngine(program, batch_slots=1, backend="int_ref")
    eng.submit(req)
    done = eng.run_until_drained()
    assert done[0].ticks == first
    # fixed tick budget
    req2 = SNNRequest(rid=1, frames=np.asarray(req_full.frames), max_ticks=3)
    eng2 = SNNServeEngine(program, batch_slots=1, backend="int_ref")
    eng2.submit(req2)
    assert eng2.run_until_drained()[0].ticks == 3


def test_snn_engine_undrained_raises():
    """The tick cap never silently drops work — same contract as the LM
    engine: EngineUndrained carries the partial finished list, and the
    engine can keep draining afterwards."""
    cfg, program = _imdb_like_program(seed=6)
    eng = SNNServeEngine(program, batch_slots=1, backend="int_ref",
                         megastep=1)
    for rid in range(3):
        eng.submit(_word_request(cfg, rid, 2, seed=80 + rid)[0])
    with pytest.raises(EngineUndrained) as ei:
        eng.run_until_drained(max_ticks=7)       # 3 reqs x 6 ticks > 7
    assert ei.value.pending >= 1
    partial = len(ei.value.finished)
    assert partial < 3
    done = eng.run_until_drained()               # resumable: finish the rest
    assert sorted(r.rid for r in done) == [0, 1, 2]
    # the exception snapshot does not alias the live finished list
    assert len(ei.value.finished) == partial


def test_snn_engine_rejects_wrong_frame_shape():
    cfg, program = _imdb_like_program(seed=8)
    eng = SNNServeEngine(program, batch_slots=1)
    with pytest.raises(ValueError, match="frame shape"):
        eng.submit(SNNRequest(rid=0, frames=np.zeros((4, 5), np.float32)))


def test_snn_engine_conv_program():
    """Conv programs serve through the engine too: image frames in, the
    per-slot accounting counts conv events per (output position, patch
    row) — and still closes against the batch-path report."""
    cfg = LENET_S
    params = snn.init_lenet_snn(jax.random.PRNGKey(0), cfg)
    program = pipeline.compile_network(cfg, params, domain="int")
    rng = np.random.default_rng(1)
    eng = SNNServeEngine(program, batch_slots=2, backend="int_ref")
    xs_all = []
    for rid in range(3):
        x = rng.standard_normal((1, *cfg.in_shape)).astype(np.float32) * 2.0
        frames = np.asarray(pipeline.present_static(
            jnp.asarray(x), cfg.timesteps))[:, 0]
        eng.submit(SNNRequest(rid=rid, frames=frames))
        xs_all.append(x)
    done = eng.run_until_drained()
    assert len(done) == 3
    for r in done:
        xs = pipeline.present_static(jnp.asarray(xs_all[r.rid]),
                                     cfg.timesteps)
        iso = pipeline.run_network(program, xs, "int_ref")
        np.testing.assert_array_equal(r.v_out, np.asarray(iso.v_out)[0],
                                      err_msg=f"rid {r.rid}")
        ref = pipeline.sparsity_report(program, iso.rasters)
        assert r.report.events == ref.events
        assert r.report.layer_frames == ref.layer_frames


def test_snn_engine_empty_and_zero_budget_requests():
    """Degenerate requests finish at admit without occupying a slot or
    running a tick, and their zero-frame reports stay well-defined
    (no division by zero in the sparsity fractions or aggregation)."""
    cfg, program = _imdb_like_program(seed=10)
    eng = SNNServeEngine(program, batch_slots=1, backend="int_ref")
    eng.submit(SNNRequest(rid=0, frames=np.zeros((0, 37), np.float32)))
    eng.submit(SNNRequest(rid=1, frames=np.zeros((4, 37), np.float32),
                          max_ticks=0))
    done = eng.run_until_drained()
    assert sorted(r.rid for r in done) == [0, 1]
    for r in done:
        assert r.ticks == 0                      # no spurious tick ran
        assert r.report.frames == 0
        assert r.report.skipped_row_fraction == 0.0
        assert r.report.overall_sparsity == 0.0
        assert r.report.layer_sparsity == (0.0,) * len(r.report.n_in)
    assert eng.ticks == 0                        # the engine never stepped
    assert eng.aggregate_report().skipped_row_fraction == 0.0
    # and a zero-budget request queued behind real work does not stall it
    eng2 = SNNServeEngine(program, batch_slots=1, backend="int_ref")
    real, _ = _word_request(cfg, 2, 1, seed=90)
    eng2.submit(SNNRequest(rid=3, frames=np.zeros((0, 37), np.float32)))
    eng2.submit(real)
    done2 = eng2.run_until_drained()
    assert sorted(r.rid for r in done2) == [2, 3]
    assert next(r for r in done2 if r.rid == 2).ticks == cfg.timesteps
