"""The serving engine picks K itself: frames per dispatch from a ladder.

An engine built without ``megastep`` advances each page, each tick, by
the smallest rung of its K ladder (1, 8, 64, ... up to what the Pallas
kernel's VMEM contract and the readout horizon admit) that covers the longest
remaining budget among the page's lanes, and never carries the frame
clock past a queued request's future arrival. Every rung is compiled
when the engine is built. The pins here:

  1. the ladder: its rungs come from the contract and the horizon;
  2. the pick: smallest covering rung, the top-rung cap, the arrival cap;
  3. bit-identity: a ladder drain equals the ``megastep=1`` drain (and
     the frame clock too, where arrivals decide admission);
  4. the jitted megastep scans its encoder: it equals K single ticks, and
     its program does not grow with K;
  5. a built engine never compiles or traces while it serves.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import ContractError, check_kernel_contracts
from repro.configs.base import SpikingConfig
from repro.configs.impulse_snn import SNNModelConfig, get_snn_config
from repro.core import pipeline, snn
from repro.serve import SNNRequest, SNNServeEngine
from repro.serve.snn_engine import _ladder

from test_megastep import LENET_S, _make, _tickwise

PALLAS = ("pallas", {"interpret": True, "block_b": 2})
BACKENDS = [("int_ref", {}), PALLAS]
# a 1-slot drain of these budgets dispatches every rung of (1, 8, 64, 512)
EVERY_RUNG = (700, 50, 5, 1)


def _program(seed=0):
    cfg = SNNModelConfig(
        arch_id="test", layer_sizes=(37, 50, 20, 3),
        spiking=SpikingConfig(neuron="rmp", timesteps=3, threshold=1.0,
                              leak=0.0625, w_bits=6, v_bits=11),
        timesteps=3)
    params = snn.init_fc_snn(jax.random.PRNGKey(seed), cfg)
    return pipeline.compile_network(cfg, params, domain="int")


def _request(rid, n, seed=0, **kw):
    rng = np.random.default_rng(seed + rid)
    return SNNRequest(rid=rid, frames=rng.standard_normal(
        (n, 37)).astype(np.float32), **kw)


def _record(eng) -> list:
    """Wrap the engine's dispatch: per page dispatch, (frame clock at the
    tick's start, K, the queued head's arrival tick or None)."""
    seen, inner = [], eng._dispatch

    def recorded(st, block, counts):
        head = None if eng.queue.empty() else eng.queue.peek().arrival_tick
        seen.append((eng.clock, block.shape[0], head))
        return inner(st, block, counts)
    eng._dispatch = recorded
    return seen


def _drain(eng, reqs) -> dict:
    for r in reqs:
        eng.submit(r)
    done = {r.rid: r for r in eng.run_until_drained(max_ticks=50_000)}
    assert sorted(done) == sorted(r.rid for r in reqs)
    return done


# ---------------------------------------------------------------------------
# the ladder
# ---------------------------------------------------------------------------

def test_ladder_rungs_from_contract_and_horizon():
    """Powers of 8 from 1, cut by the readout horizon, by the int32 frame
    counts and by the first K the kernel contract refuses — for
    impulse-imdb's serving dispatch at block_b 8 that is K=4096 (K=2048
    fits), so its ladder is (1, 8, 64, 512)."""
    def up_to(top):
        def contract(k):
            if k > top:
                raise ContractError(f"vmem_budget: K={k}", where="pallas")
        return contract
    assert _ladder(up_to(4096), None) == (1, 8, 64, 512, 4096)
    assert _ladder(up_to(4096), 100) == (1, 8, 64)
    assert _ladder(up_to(4096), 7) == (1,)
    assert _ladder(up_to(2 ** 40), None)[-1] == 8 ** 10    # < 2 ** 31

    cfg = get_snn_config("impulse-imdb")
    program = pipeline.compile_network(
        cfg, snn.init_fc_snn(jax.random.PRNGKey(0), cfg), domain="int")

    def contract(block_b):
        return lambda k: check_kernel_contracts(
            program, "pallas", frames=k, streaming=True, block_b=block_b,
            emit_rasters=False)
    contract(8)(2048)
    with pytest.raises(ContractError, match="vmem_budget"):
        contract(8)(4096)
    assert _ladder(contract(8), None) == (1, 8, 64, 512)
    with pytest.raises(ContractError, match="vmem_budget"):
        contract(64)(512)
    assert _ladder(contract(64), None) == (1, 8, 64)


def test_engine_ladder_follows_backend_and_pins():
    """The engine sizes its ladder from its own contract (a wide row tile
    caps it; int_ref takes the `pallas` kernel's, where rasters cost
    VMEM), the eager backends and ``validate=False`` builds keep K=1, and
    an explicit ``megastep`` pins K."""
    cfg = get_snn_config("impulse-imdb")
    imdb = pipeline.compile_network(
        cfg, snn.init_fc_snn(jax.random.PRNGKey(0), cfg), domain="int")
    eng = SNNServeEngine(imdb, batch_slots=2, backend="pallas",
                         track_events=False,
                         step_kw={"interpret": True, "block_b": 64})
    assert eng.rungs == (1, 8, 64)
    assert sorted(eng._rungs.exes) == [1, 8, 64]
    program = _program()
    assert SNNServeEngine(program, batch_slots=2).rungs == (1, 8, 64, 512)
    assert SNNServeEngine(program, batch_slots=2,
                          track_events=False).rungs == (1, 8, 64, 512, 4096)
    for kw in ({"backend": "pallas_events",
                "step_kw": {"interpret": True, "block_b": 2}},
               {"backend": "ref_events"}, {"backend": "float"},
               {"validate": False}):
        assert SNNServeEngine(program, batch_slots=2, **kw).rungs == (1,)
    assert SNNServeEngine(program, batch_slots=2, megastep=5).rungs == (5,)


# ---------------------------------------------------------------------------
# the pick
# ---------------------------------------------------------------------------

def test_engine_picks_smallest_covering_rung():
    """One lane, budgets 700, 50, 5, 1 in turn: K = 512 twice (the top
    rung caps 700; 188 left needs 512), then 64, 8, 1. Two lanes of 20 and
    3 frames: one block of 64. ``frames_dispatched`` sums the rungs."""
    program = _program()
    eng = SNNServeEngine(program, batch_slots=1)
    seen = _record(eng)
    done = _drain(eng, [_request(i, n) for i, n in enumerate(EVERY_RUNG)])
    assert [k for _, k, _ in seen] == [512, 512, 64, 8, 1]
    assert eng.ticks == 5 and eng.frames_dispatched == 1097 == eng.clock
    assert [done[i].ticks for i in range(4)] == list(EVERY_RUNG)

    eng = SNNServeEngine(program, batch_slots=2)
    seen = _record(eng)
    _drain(eng, [_request(0, 20), _request(1, 3)])
    assert [k for _, k, _ in seen] == [64]


@pytest.mark.parametrize("backend,kw", BACKENDS, ids=["int_ref", "pallas"])
def test_engine_blocks_stop_at_the_head_arrival(backend, kw):
    """While the queue head's arrival is ahead, no block carries the frame
    clock past it, so with free lanes every request is admitted at its
    arrival tick, as at K=1: logits, readout V, ticks and finish clocks
    equal the ``megastep=1`` drain's."""
    program = _program(seed=1)
    arrivals = [(100, 0), (30, 37), (9, 45), (600, 300), (3, 301)]

    def reqs():
        return [_request(i, n, seed=5, arrival_tick=a)
                for i, (n, a) in enumerate(arrivals)]
    eng = SNNServeEngine(program, batch_slots=2, pages=2, backend=backend,
                         step_kw=kw)
    seen = _record(eng)
    got = _drain(eng, reqs())
    want = _drain(SNNServeEngine(program, batch_slots=2, pages=2,
                                 backend=backend, step_kw=kw, megastep=1),
                  reqs())
    assert any(k > 1 for _, k, _ in seen)
    for clock, k, head in seen:
        assert head is None or head <= clock or clock + k <= head
    for rid, r in want.items():
        assert got[rid].finish_clock == r.finish_clock == \
            arrivals[rid][1] + r.ticks
        assert got[rid].ticks == r.ticks
        np.testing.assert_array_equal(got[rid].logits, r.logits)
        np.testing.assert_array_equal(got[rid].v_out, r.v_out)


# ---------------------------------------------------------------------------
# bit-identity with the K=1 drain
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pages,db", [(1, False), (2, True), (2, False)],
                         ids=["1page", "2pages-db", "2pages"])
@pytest.mark.parametrize("backend,kw", BACKENDS, ids=["int_ref", "pallas"])
def test_ladder_drain_matches_k1(backend, kw, pages, db):
    """Ragged budgets from 1 to 600 frames, one confident early exit and
    lanes re-used as requests finish: every request's logits, readout V,
    ticks and event report equal the ``megastep=1`` drain's."""
    program = _program(seed=2)
    lengths = [1, 2, 13, 70, 5, 600, 9, 64]

    def reqs():
        return [_request(i, n, seed=9,
                         stop_threshold=0.5 if i == 3 else None)
                for i, n in enumerate(lengths)]
    want = _drain(SNNServeEngine(program, batch_slots=2, pages=pages,
                                 backend=backend, step_kw=kw, megastep=1),
                  reqs())
    eng = SNNServeEngine(program, batch_slots=2, pages=pages,
                         backend=backend, step_kw=kw, double_buffer=db)
    got = _drain(eng, reqs())
    assert want[3].ticks < lengths[3]                # the exit fired
    assert eng.frames_dispatched / eng.ticks > 8     # blocks, not frames
    for rid, r in want.items():
        assert got[rid].ticks == r.ticks, rid
        np.testing.assert_array_equal(got[rid].logits, r.logits)
        np.testing.assert_array_equal(got[rid].v_out, r.v_out)
        for a, b in zip(got[rid].report.row_events, r.report.row_events):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("db", [False, True], ids=["single", "db"])
def test_host_blocks_reused_not_cleared(db):
    """A page's host block for each K is written in place tick after tick
    (one buffer, two with ``double_buffer``): a shorter request after a
    longer one overwrites only the frames it streams, and the stale tail
    left in the buffer, past the lane's active count, changes no answer —
    logits and readout V equal the ``megastep=1`` drain's."""
    program = _program(seed=6)
    lengths = [60, 9, 50, 3]

    def reqs():
        return [_request(i, n, seed=13) for i, n in enumerate(lengths)]
    want = _drain(SNNServeEngine(program, batch_slots=1, megastep=1), reqs())
    eng = SNNServeEngine(program, batch_slots=1, double_buffer=db)
    seen = _record(eng)
    got = _drain(eng, reqs())
    assert [k for _, k, _ in seen] == [64, 64, 64, 8]
    ring = eng._host_blocks[(0, 64)]
    assert len(ring) == 1 + db
    assert any(np.any(block[50:60] != 0) for block, _ in ring)  # stale
    for rid, r in want.items():
        assert got[rid].ticks == r.ticks
        np.testing.assert_array_equal(got[rid].logits, r.logits)
        np.testing.assert_array_equal(got[rid].v_out, r.v_out)


# ---------------------------------------------------------------------------
# the jitted megastep scans its encoder
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("conv", [False, True], ids=["fc", "conv"])
@pytest.mark.parametrize("backend,kw", [
    ("int_ref", {}), ("pallas", {"interpret": True, "block_b": 4})],
    ids=["int_ref", "pallas"])
def test_jitted_megastep_equals_single_ticks(backend, kw, conv):
    """Under jit the encoder runs as a lax.scan: chunks of K frames equal
    K single ticks bit for bit (V trajectory, logits, final state), and
    the traced program has as many equations at K=2 as at K=5."""
    _, program, xs = _make(conv=LENET_S if conv else None, seed=4)
    if conv:
        xs = jnp.concatenate([xs] * 3)      # three presentations, T=6

    def mega(st, block):
        st, out = program.megastep(st, block, backend, **kw)
        return st, out.v_out_traj, out.logits_traj
    ref_state, ref_v, ref_l, _ = _tickwise(program, xs, backend, **kw)
    state, vs, ls, t = program.init_state(xs.shape[1], backend), [], [], 0
    for k in (2, xs.shape[0] - 2):
        state, v, logits = jax.jit(mega)(state, xs[t:t + k])
        vs.append(np.asarray(v))
        ls.append(np.asarray(logits))
        t += k
    np.testing.assert_array_equal(np.concatenate(vs), ref_v)
    np.testing.assert_array_equal(np.concatenate(ls), ref_l)
    for a, b in zip(state.vs, ref_state.vs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    st0 = program.init_state(xs.shape[1], backend)
    sizes = {k: len(jax.make_jaxpr(mega)(st0, xs[:k]).jaxpr.eqns)
             for k in (2, 5)}
    assert sizes[2] == sizes[5], sizes


# ---------------------------------------------------------------------------
# no compile while serving
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,kw,ekw", [
    ("int_ref", {}, {}),
    ("int_ref", {"use_sparse": True}, {"pages": 2, "double_buffer": True}),
    ("pallas", {"interpret": True, "block_b": 2}, {"track_events": False}),
], ids=["int_ref", "int_ref-2pages-db", "pallas"])
def test_built_engine_never_compiles(backend, kw, ekw):
    """Once built, an engine serves a drain that dispatches every rung
    without one backend compile or jaxpr trace: admission's lane reset
    and each rung's executable were compiled at build."""
    eng = SNNServeEngine(_program(seed=3), batch_slots=1, backend=backend,
                         step_kw=kw, **ekw)
    reqs = [_request(i, n, seed=11)
            for i, n in enumerate(sorted(eng.rungs, reverse=True))]
    seen, heard = _record(eng), []

    def hear(name, _secs, **_kw):
        if name in ("/jax/core/compile/backend_compile_duration",
                    "/jax/core/compile/jaxpr_trace_duration"):
            heard.append(name)
    jax.monitoring.register_event_duration_secs_listener(hear)
    try:
        _drain(eng, reqs)
    finally:
        jax.monitoring.unregister_event_duration_listener(hear)
    assert {k for _, k, _ in seen} == set(eng.rungs)
    assert len(eng.rungs) >= 4
    assert heard == []
