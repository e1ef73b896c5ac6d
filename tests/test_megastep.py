"""Megastep contract: K frames per device dispatch is invisible.

Integer V_MEM arithmetic is exact, so advancing a stream K ticks in one
fused-kernel dispatch must be *bit-identical* to K chained single-tick
calls — rasters, readout trajectory, final state, and the skip counters
that feed the energy model. The sweeps here pin that at both layers:

  1. `stream_megastep` vs tick-by-tick `stream_step` on every streaming
     backend, every neuron/clamp combination, conv stacks, ragged chunk
     sizes (stream length not a multiple of K), and per-lane active
     masks (short/evicted lanes integrate zero current).
  2. The serving engine: a K-megastep drain over a paged V-slot pool
     (double-buffered or not) finishes every request bit-identically to
     the K=1 drain, and a seeded Poisson-arrival soak keeps the drain
     contract and per-request report closure under admission churn.

The drain-path bug round rides along: vacated lanes are re-seeded with
fresh zero state at evict (so device ledgers close at any occupancy),
zero-budget requests finish with a shape-consistent zero ``v_out``, and
``aggregate_report`` raises the named ``ReportUnavailable`` instead of a
generic merge error.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SpikingConfig
from repro.configs.impulse_snn import SNNModelConfig
from repro.core import pipeline, snn
from repro.serve import ReportUnavailable, SNNRequest, SNNServeEngine
from repro.serve.snn_engine import _pack_lanes, _unpack_lanes, merge_reports

LENET_S = SNNModelConfig(
    arch_id="lenet-s",
    conv_spec=((4, 3, 1), (6, 3, 2)),
    in_shape=(8, 8, 1),
    layer_sizes=(4 * 4 * 6, 10, 3),
    spiking=SpikingConfig(neuron="rmp", timesteps=2, threshold=1.0,
                          leak=0.0625, w_bits=6, v_bits=11),
    timesteps=2, task="multiclass")

BACKEND_KW = [
    ("float", {}),
    ("int_ref", {}),
    ("int_ref", {"use_sparse": True}),
    ("pallas", {"interpret": True, "block_b": 4}),
    ("pallas_sparse", {"interpret": True, "block_b": 4,
                       "gate_granularity": 4}),
    ("ref_events", {}),
    ("pallas_events", {"interpret": True, "block_b": 4}),
]


def _case_id(b, k):
    gran = f"-g{k['gate_granularity']}" if "gate_granularity" in k else ""
    return f"{b}{gran}{'-sparse' if k.get('use_sparse') else ''}"


def _make(layer_sizes=(37, 50, 20, 3), neuron="rmp", n_words=3, batch=2,
          seed=0, clamp_mode="saturate", conv=None):
    cfg = SNNModelConfig(
        arch_id="test", layer_sizes=layer_sizes,
        spiking=SpikingConfig(neuron=neuron, timesteps=3, threshold=1.0,
                              leak=0.0625, w_bits=6, v_bits=11),
        timesteps=3)
    rng = np.random.default_rng(seed + 7)
    if conv is not None:
        cfg = conv
        params = snn.init_lenet_snn(jax.random.PRNGKey(seed), cfg)
        program = pipeline.compile_network(cfg, params, domain="int",
                                           clamp_mode=clamp_mode)
        x = jnp.asarray(rng.standard_normal(
            (batch, *cfg.in_shape)).astype(np.float32)) * 2.0
        return cfg, program, pipeline.present_static(x, cfg.timesteps)
    params = snn.init_fc_snn(jax.random.PRNGKey(seed), cfg)
    program = pipeline.compile_network(cfg, params, domain="int",
                                       clamp_mode=clamp_mode)
    x = jnp.asarray(rng.standard_normal(
        (batch, n_words, cfg.layer_sizes[0])).astype(np.float32))
    return cfg, program, pipeline.present_words(x, cfg.timesteps)


def _tickwise(program, xs, backend, **kw):
    """Reference: T single-tick stream_step calls. Returns per-tick
    v_out, logits, rasters and the final state."""
    state = program.init_state(xs.shape[1], backend)
    vs, ls, rs = [], [], []
    for t in range(xs.shape[0]):
        state, out = program.step(state, xs[t], backend, **kw)
        vs.append(np.asarray(out.v_out))
        ls.append(np.asarray(out.logits))
        rs.append([np.asarray(r) for r in out.rasters])
    return state, np.stack(vs), np.stack(ls), rs


def _megastep_chunks(program, xs, backend, chunks, **kw):
    """Drive xs through stream_megastep in the given chunk sizes."""
    state = program.init_state(xs.shape[1], backend)
    vs, ls, rs = [], [], []
    t = 0
    for k in chunks:
        state, out = program.megastep(state, xs[t:t + k], backend, **kw)
        assert out.v_out_traj.shape[0] == k
        vs.append(np.asarray(out.v_out_traj))
        ls.append(np.asarray(out.logits_traj))
        for tt in range(k):
            rs.append([np.asarray(r[tt]) for r in out.rasters])
        np.testing.assert_array_equal(np.asarray(out.frames_consumed),
                                      np.full(xs.shape[1], k))
        # the last trajectory entries ARE the single-step outputs
        np.testing.assert_array_equal(np.asarray(out.v_out), vs[-1][-1])
        np.testing.assert_array_equal(np.asarray(out.logits), ls[-1][-1])
        t += k
    return state, np.concatenate(vs), np.concatenate(ls), rs


def _assert_states_equal(a, b, tag):
    for i, (va, vb) in enumerate(zip(a.vs, b.vs)):
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                      err_msg=f"{tag} layer {i} V")


def _assert_megastep_matches(program, xs, backend, chunks, tag, **kw):
    ref_state, ref_v, ref_l, ref_r = _tickwise(program, xs, backend, **kw)
    got_state, got_v, got_l, got_r = _megastep_chunks(program, xs, backend,
                                                      chunks, **kw)
    np.testing.assert_array_equal(got_v, ref_v, err_msg=f"{tag} v_traj")
    np.testing.assert_array_equal(got_l, ref_l, err_msg=f"{tag} logits")
    for t, (ga, ra) in enumerate(zip(got_r, ref_r)):
        for li, (g, r) in enumerate(zip(ga, ra)):
            np.testing.assert_array_equal(
                g, r, err_msg=f"{tag} raster t={t} layer={li}")
    _assert_states_equal(got_state, ref_state, tag)


@pytest.mark.parametrize("backend,kw", BACKEND_KW,
                         ids=[_case_id(b, k) for b, k in BACKEND_KW])
def test_megastep_matches_single_tick_all_backends(backend, kw):
    """K-frame dispatch == K single-tick dispatches, bit for bit, on the
    full backend set — including a ragged final chunk (T=9 split 4+4+1,
    stream length not a multiple of K)."""
    _, program, xs = _make()
    for chunks in ([4, 4, 1], [1] * 9, [9]):
        _assert_megastep_matches(program, xs, backend, chunks,
                                 f"{backend}/{kw}/chunks={chunks}", **kw)


@pytest.mark.parametrize("clamp_mode", ["saturate", "wrap"])
@pytest.mark.parametrize("neuron", ["if", "lif", "rmp"])
def test_megastep_neuron_clamp_sweep(neuron, clamp_mode):
    """Neuron x clamp sweep (ragged shapes): the K-loop preserves the
    V_MEM update law under both overflow policies."""
    _, program, xs = _make(layer_sizes=(13, 11, 3), neuron=neuron,
                           clamp_mode=clamp_mode, seed=3)
    for backend, kw in [("int_ref", {"use_sparse": True}),
                        ("pallas_sparse", {"interpret": True,
                                           "block_b": 4})]:
        _assert_megastep_matches(program, xs, backend, [4, 5],
                                 f"{neuron}/{clamp_mode}/{backend}", **kw)


@pytest.mark.parametrize("backend,kw", [
    ("int_ref", {}),
    ("pallas", {"interpret": True, "block_b": 4}),
])
def test_megastep_conv_stack(backend, kw):
    """Conv front-end programs megastep bit-identically too — the (K, B,
    H, W, C) frame block threads through im2col unchanged."""
    _, program, xs = _make(conv=LENET_S, seed=5)
    xs = jnp.concatenate([xs, xs])        # two presentations, T=4
    _assert_megastep_matches(program, xs, backend, [3, 1], f"conv/{backend}",
                             **kw)


def test_megastep_active_mask_zero_fills_short_lanes():
    """Per-lane active counts: a lane active for only n < K ticks
    integrates zero current afterwards — exactly what a zero-padded
    stream of the same length produces — and frames_consumed reports n."""
    _, program, xs = _make(batch=3)
    k = 6
    active = np.array([4, 2, 6])
    state0 = program.init_state(3, "int_ref")
    state, out = program.megastep(state0, xs[:k], "int_ref",
                                  active=jnp.asarray(active))
    np.testing.assert_array_equal(np.asarray(out.frames_consumed), active)
    # reference: mask the block on the host, run tick by tick
    padded = np.asarray(xs[:k]).copy()
    for lane, n in enumerate(active):
        padded[n:, lane] = 0.0
    ref_state, ref_v, ref_l, _ = _tickwise(program, jnp.asarray(padded),
                                           "int_ref")
    np.testing.assert_array_equal(np.asarray(out.v_out_traj), ref_v)
    np.testing.assert_array_equal(np.asarray(out.logits_traj), ref_l)
    _assert_states_equal(state, ref_state, "active-mask")


def test_megastep_validates_frames_block():
    _, program, xs = _make()
    state = program.init_state(2, "int_ref")
    with pytest.raises(ValueError, match="megastep"):
        program.megastep(state, xs[0], "int_ref")      # missing K axis
    with pytest.raises(ValueError, match="megastep"):
        program.megastep(state, xs[:0], "int_ref")     # K=0 block


# ---------------------------------------------------------------------------
# serving engine: megastep/paged drains == K=1 drain, Poisson soak, and the
# drain-path bug round
# ---------------------------------------------------------------------------

def _program(seed=0):
    cfg = SNNModelConfig(
        arch_id="test", layer_sizes=(37, 50, 20, 3),
        spiking=SpikingConfig(neuron="rmp", timesteps=3, threshold=1.0,
                              leak=0.0625, w_bits=6, v_bits=11),
        timesteps=3)
    params = snn.init_fc_snn(jax.random.PRNGKey(seed), cfg)
    return cfg, pipeline.compile_network(cfg, params, domain="int")


def _word_request(cfg, rid, n_words, seed, **req_kw):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((1, n_words, cfg.layer_sizes[0])).astype(
        np.float32)
    frames = np.asarray(pipeline.present_words(
        jnp.asarray(x), cfg.timesteps))[:, 0]
    return SNNRequest(rid=rid, frames=frames, **req_kw)


def _drain(program, cfg, backend, kw, lengths, *, slots=2, seed=40,
           stop_rid=None, arrivals=None, **ekw):
    eng = SNNServeEngine(program, batch_slots=slots, backend=backend,
                         step_kw=kw, **ekw)
    for rid, nw in enumerate(lengths):
        req = _word_request(cfg, rid, nw, seed=seed + rid,
                            stop_threshold=(0.5 if rid == stop_rid
                                            else None))
        if arrivals is not None:
            req.arrival_tick = arrivals[rid]
        eng.submit(req)
    done = sorted(eng.run_until_drained(max_ticks=50_000),
                  key=lambda r: r.rid)
    assert len(done) == len(lengths)
    return eng, done


def _assert_drains_equal(ref, got, tag):
    for a, b in zip(ref, got):
        assert a.ticks == b.ticks, f"{tag} rid {a.rid} ticks"
        np.testing.assert_array_equal(np.asarray(a.logits),
                                      np.asarray(b.logits),
                                      err_msg=f"{tag} rid {a.rid} logits")
        np.testing.assert_array_equal(np.asarray(a.v_out),
                                      np.asarray(b.v_out),
                                      err_msg=f"{tag} rid {a.rid} v_out")
        for la, lb in zip(a.report.row_events, b.report.row_events):
            np.testing.assert_array_equal(
                np.asarray(la), np.asarray(lb),
                err_msg=f"{tag} rid {a.rid} row_events")
        assert a.report.instruction_counts() == b.report.instruction_counts()


@pytest.mark.parametrize("backend,kw", [
    ("int_ref", {"use_sparse": True}),
    ("pallas_sparse", {"interpret": True, "block_b": 2}),
    ("pallas_events", {"interpret": True, "block_b": 2}),
])
@pytest.mark.parametrize("megastep,pages,db", [
    (4, 2, True), (8, 1, False), (16, 3, True)])
def test_engine_megastep_drain_matches_k1(backend, kw, megastep, pages, db):
    """The bit-identity bar: a K-megastep drain over a paged pool (with
    or without double-buffered upload) finishes every request — ragged
    lengths, one early-exit request — identically to the K=1 drain:
    logits, V, ticks, per-request reports, and the merged report."""
    cfg, program = _program()
    lengths = [2, 4, 1, 3, 2, 1]
    ref_eng, ref = _drain(program, cfg, backend, kw, lengths, stop_rid=3,
                          megastep=1)
    got_eng, got = _drain(program, cfg, backend, kw, lengths, stop_rid=3,
                          megastep=megastep, pages=pages, double_buffer=db)
    _assert_drains_equal(ref, got, f"{backend}/K={megastep}")
    a, b = ref_eng.aggregate_report(), got_eng.aggregate_report()
    assert a.events == b.events and a.frames == b.frames
    assert a.instruction_counts() == b.instruction_counts()


def test_engine_poisson_soak_drain_and_report_closure():
    """Offered-load churn: seeded Poisson arrivals over a paged pool keep
    the drain contract (all requests finish; idle ticks advance the
    frame clock until the head arrives) and per-request report closure —
    every finished request's report equals the batch path's report of
    its own frames, and latency >= service time."""
    cfg, program = _program(seed=2)
    rng = np.random.default_rng(9)
    lengths = [2, 1, 3, 2, 1, 2, 3, 1]
    arrivals = np.cumsum(rng.exponential(4.0, len(lengths))).astype(int)
    eng, done = _drain(program, cfg, "int_ref", {"use_sparse": True},
                       lengths, slots=2, arrivals=list(arrivals),
                       megastep=4, pages=2, double_buffer=True)
    assert eng.queue.empty() and not any(s.req for s in eng.slots)
    assert eng.clock >= int(arrivals[-1])  # idle ticks advanced the clock
    for rid, (r, nw) in enumerate(zip(done, lengths)):
        assert r.ticks == nw * cfg.timesteps
        assert r.latency_ticks >= r.ticks
        assert r.finish_clock >= r.arrival_tick + r.ticks
        rng_i = np.random.default_rng(40 + rid)
        x = jnp.asarray(rng_i.standard_normal(
            (1, nw, cfg.layer_sizes[0])).astype(np.float32))
        iso = pipeline.run_network(program,
                                   pipeline.present_words(x, cfg.timesteps),
                                   "int_ref")
        np.testing.assert_array_equal(r.v_out, np.asarray(iso.v_out)[0])
        ref = pipeline.sparsity_report(program, iso.rasters)
        assert r.report.events == ref.events
        assert r.report.instruction_counts() == ref.instruction_counts()
    merged = merge_reports([r.report for r in done])
    agg = eng.aggregate_report()
    assert agg.events == merged.events
    assert agg.instruction_counts() == merged.instruction_counts()


def test_engine_idle_lane_reset_restores_fresh_state():
    """The idle-lane fix: eviction scatters fresh zero state back into the
    vacated lane, so after a full drain every page's V tree equals the
    engine's fresh template — an idle lane dispatched in a later tick
    contributes zero events instead of replaying stale V."""
    cfg, program = _program()
    eng, _ = _drain(program, cfg, "int_ref", {"use_sparse": True},
                    [3, 1, 2], slots=2, megastep=2, pages=2)
    for page, state in enumerate(eng.states):
        for li, (v, f) in enumerate(zip(state.vs, eng._fresh.vs)):
            v = np.asarray(v)
            np.testing.assert_array_equal(
                v, np.broadcast_to(np.asarray(f), v.shape),
                err_msg=f"page {page} layer {li} not reset")


def test_zero_budget_request_finishes_with_zero_v_out():
    """Drain-path regression: a request admitted with nothing to stream
    (no frames, or max_ticks <= 0) finishes immediately with a
    *shape-consistent* zero v_out/logits — not None — and a stamped
    finish clock."""
    cfg, program = _program()
    eng = SNNServeEngine(program, batch_slots=1, backend="int_ref")
    empty = SNNRequest(rid=0, frames=np.zeros((0, 37), np.float32))
    capped = _word_request(cfg, 1, 2, seed=3, max_ticks=0)
    eng.submit(empty)
    eng.submit(capped)
    done = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    for r in done:
        assert r.ticks == 0 and r.finish_clock is not None
        assert r.v_out.shape == (cfg.layer_sizes[-1],)
        assert r.v_out.dtype == np.int32           # int domain
        np.testing.assert_array_equal(r.v_out, 0)
        np.testing.assert_array_equal(np.asarray(r.logits), 0)


def test_aggregate_report_named_errors():
    """Drain-path regression: aggregate_report raises the named
    ReportUnavailable — not a generic merge ValueError — both when event
    tracking is off and when nothing has finished yet."""
    cfg, program = _program()
    eng = SNNServeEngine(program, batch_slots=1, backend="int_ref",
                         track_events=False)
    eng.submit(_word_request(cfg, 0, 1, seed=5))
    eng.run_until_drained()
    with pytest.raises(ReportUnavailable, match="track_events"):
        eng.aggregate_report()
    eng2 = SNNServeEngine(program, batch_slots=1, backend="int_ref")
    with pytest.raises(ReportUnavailable, match="finished"):
        eng2.aggregate_report()


# ---------------------------------------------------------------------------
# the packed fetch: a jitted engine reads each page's results in one
# device->host transfer
# ---------------------------------------------------------------------------

def _count_dispatches(eng):
    """Wrap a jitted engine's dispatch to count page dispatches."""
    calls = []
    inner = eng._dispatch

    def counted(*args):
        calls.append(1)
        return inner(*args)
    eng._dispatch = counted
    return calls


def _mid_block_exit(program, frames, k):
    """A stop threshold whose first crossing is a new |logit| maximum at a
    tick that does not end a K-block, and the ticks it leaves served."""
    state = program.init_state(1, "int_ref")
    traj = []
    for t in range(len(frames)):
        state, out = program.step(state, jnp.asarray(frames[t:t + 1]),
                                  "int_ref")
        traj.append(float(np.max(np.abs(np.asarray(out.logits)))))
    for t in range(1, len(traj)):
        if (t + 1) % k and traj[t] > max(traj[:t]):
            return traj[t], t + 1
    raise AssertionError("no mid-block crossing in this stream")


@pytest.mark.parametrize("backend,kw", [
    ("int_ref", {}),
    ("pallas", {"interpret": True, "block_b": 2}),
])
@pytest.mark.parametrize("megastep,pages", [(1, 1), (3, 2)])
@pytest.mark.parametrize("n_out", [1, 3])
def test_engine_packed_fetch_equals_isolated(backend, kw, megastep, pages,
                                             n_out):
    """On the jitted path each dispatched page's results come back in one
    packed read (``device_reads`` == page dispatches), and every request's
    logits, readout V, ticks and finish clock equal an isolated run of the
    frames it served: full streams, a tick budget that ends mid-block and
    a confidence exit mid-block. All requests fit the pool at once, so
    each is admitted at clock 0 and finishes at clock == ticks."""
    cfg = SNNModelConfig(
        arch_id="test", layer_sizes=(37, 50, 20, n_out),
        spiking=SpikingConfig(neuron="rmp", timesteps=3, threshold=1.0,
                              leak=0.0625, w_bits=6, v_bits=11),
        timesteps=3)
    params = snn.init_fc_snn(jax.random.PRNGKey(5), cfg)
    program = pipeline.compile_network(cfg, params, domain="int")
    reqs = [_word_request(cfg, 0, 4, seed=70),
            _word_request(cfg, 1, 3, seed=71, max_ticks=7),
            _word_request(cfg, 2, 2, seed=72),
            _word_request(cfg, 3, 5, seed=73)]
    thr, exit_ticks = _mid_block_exit(program, reqs[3].frames, 3)
    reqs[3].stop_threshold = thr
    want_ticks = [12, 7, 6, exit_ticks]
    eng = SNNServeEngine(program, batch_slots=4 // pages, backend=backend,
                         step_kw=kw, pages=pages, megastep=megastep,
                         track_events=False)
    calls = _count_dispatches(eng)
    for r in reqs:
        eng.submit(r)
    done = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    assert eng.device_reads == len(calls) > 0
    for r, ticks in zip(done, want_ticks):
        assert (r.ticks, r.finish_clock) == (ticks, ticks), r.rid
        iso = pipeline.run_network(
            program, jnp.asarray(r.frames[:ticks, None]), "int_ref")
        assert r.v_out.dtype == np.int32 and r.logits.dtype == np.float32
        np.testing.assert_array_equal(r.v_out, np.asarray(iso.v_out)[0],
                                      err_msg=f"rid {r.rid} v_out")
        np.testing.assert_array_equal(r.logits, np.asarray(iso.logits)[0],
                                      err_msg=f"rid {r.rid} logits")


def _block_out(k=3, b=4, n_out=2, seed=0):
    rng = np.random.default_rng(seed)
    logits = rng.standard_normal((k, b, n_out)).astype(np.float32)
    logits.flat[:4] = [-0.0, np.inf, 1e-45, np.nan]    # bits, not values
    return pipeline.MegastepOut(
        v_out=None, logits=None,
        v_out_traj=jnp.asarray(rng.integers(-2**31, 2**31 - 1,
                                            (k, b, n_out), np.int32)),
        logits_traj=jnp.asarray(logits),
        frames_consumed=jnp.asarray(rng.integers(0, k + 1, b, np.int32)))


def test_pack_lanes_round_trip_is_bit_exact():
    """`_unpack_lanes(_pack_lanes(out))` gives back every bit of the
    trajectories and the frame counts, special floats included."""
    out = _block_out()
    packed = _pack_lanes(out)
    assert packed.shape == (4, 2 * 3 * 2 + 1) and packed.dtype == jnp.int32
    logits, v_traj, consumed = _unpack_lanes(np.asarray(packed), 3, 2)
    np.testing.assert_array_equal(
        logits.view(np.uint32), np.asarray(out.logits_traj).view(np.uint32))
    np.testing.assert_array_equal(v_traj, np.asarray(out.v_out_traj))
    np.testing.assert_array_equal(consumed, np.asarray(out.frames_consumed))


@pytest.mark.parametrize("field,dtype", [("v_out_traj", jnp.int16),
                                         ("logits_traj", jnp.bfloat16),
                                         ("frames_consumed", jnp.uint8)])
def test_pack_lanes_refuses_other_dtypes(field, dtype):
    """A result that is not the 32-bit type the host reads back is
    refused, never converted."""
    out = _block_out()
    bad = dataclasses.replace(
        out, **{field: getattr(out, field).astype(dtype)})
    with pytest.raises(TypeError, match=field):
        _pack_lanes(bad)


@pytest.mark.parametrize("backend,reads", [("int_ref", 1), ("ref_events", 3)])
def test_engine_device_reads_per_page_dispatch(monkeypatch, backend, reads):
    """``device_reads`` counts the fetch's device->host reads: one packed
    array per dispatched page on the jitted path, the three result arrays
    on the eager path."""
    cfg, program = _program()
    eng = SNNServeEngine(program, batch_slots=2, backend=backend,
                         pages=2, megastep=2)
    if eng._dispatch is not None:
        calls = _count_dispatches(eng)
    else:
        calls, inner = [], pipeline.stream_megastep

        def counted(*args, **kw):
            calls.append(1)
            return inner(*args, **kw)
        monkeypatch.setattr(pipeline, "stream_megastep", counted)
    for rid, nw in enumerate([2, 1, 3]):
        eng.submit(_word_request(cfg, rid, nw, seed=80 + rid))
    eng.run_until_drained()
    assert len(calls) > eng.ticks         # two pages dispatched some ticks
    assert eng.device_reads == reads * len(calls)
