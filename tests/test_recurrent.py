"""Recurrent spiking FC layers against a plain int32 reference.

A recurrent layer l takes the layer below's spikes s_in(t) and its own
previous-tick spikes s_l(t-1) through one 6-bit weight block [W_in; W_rec]:

    V_l(t) = clamp(V_l(t-1) + s_in(t) @ W_in + s_l(t-1) @ W_rec)
    s_l(t) = V_l(t) >= theta_l ;  V_l(t) -= theta_l where it fired (RMP)

with s_l(-1) = 0. `plain_reference` below is those equations in
jax.numpy int32, on integer weights and thresholds drawn here; the program
is built from float parameters that quantize back onto exactly those
integers, so nothing the program computes feeds the reference.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import ContractError, check_kernel_contracts, \
    check_program
from repro.configs.base import SpikingConfig
from repro.configs.impulse_snn import SNNModelConfig, get_snn_config
from repro.core import isa, pipeline, snn
from repro.kernels.fused_snn_net.ops import fused_snn_net
from repro.launch.mesh import make_host_mesh
from repro.serve import SNNRequest, SNNServeEngine

SIZES = (20, 24, 16, 3)
T, B = 12, 5
V_LO, V_HI = -1024, 1023
#: encoder input = spike x threshold x (1 + 2^-16): the float encoder then
#: re-emits the raster exactly (the residual stays far below a threshold)
EVENT_MARGIN = 1.0 + 2.0 ** -16


def _cfg(sizes=SIZES, recurrent=(True, True)):
    return SNNModelConfig(
        arch_id="rsnn-test", layer_sizes=sizes,
        spiking=SpikingConfig(neuron="rmp", timesteps=1, threshold=1.0,
                              leak=0.0625, w_bits=6, v_bits=11),
        timesteps=1, task="multiclass", recurrent=recurrent)


@dataclasses.dataclass
class Net:
    """Integer truth of a seeded network and the params that encode it."""
    hidden: list          # [(q_in, q_rec, theta)] per recurrent layer
    q_ro: np.ndarray
    params: dict
    enc_th: float


def make_net(seed: int, sizes=SIZES, theta=24) -> Net:
    rng = np.random.default_rng(seed)

    def grid(shape):
        q = np.clip(np.round(rng.standard_normal(shape) * 12), -31, 31)
        q.flat[rng.integers(q.size)] = 31          # max |q| = 31: exact grid
        return q.astype(np.int32)

    hidden, layers, raw_th = [], [], [1.0]
    for n_in, n_out in zip(sizes[:-2], sizes[1:-1]):
        block = grid((n_in + n_out, n_out))
        s = 0.01 + 0.01 * rng.random()
        hidden.append((block[:n_in], block[n_in:], theta))
        layers.append({"w": jnp.asarray(block * s, jnp.float32)})
        raw_th.append(float(np.log(np.expm1(theta * s - 1e-3))))
    q_ro = grid(sizes[-2:])
    layers.append({"w": jnp.asarray(q_ro * 0.02, jnp.float32)})
    th = jnp.asarray(raw_th, jnp.float32)
    enc_th = float(jax.nn.softplus(th)[0] + 1e-3)
    return Net(hidden=hidden, q_ro=q_ro, enc_th=enc_th,
               params={"layers": layers, "threshold": th,
                       "leak": jnp.zeros_like(th)})


def plain_reference(raster, net: Net, mode: str = "saturate"):
    """The recurrent network's equations in plain int32 jnp: (T, B, n0)
    input spikes -> (hidden V list, hidden last spikes, readout V, readout
    V per tick)."""
    def clamp(v):
        if mode == "wrap":
            return jnp.mod(v - V_LO, V_HI - V_LO + 1) + V_LO
        return jnp.clip(v, V_LO, V_HI)

    def fires(v, th):
        return (clamp(v - th) >= 0) if mode == "wrap" else (v >= th)

    b = raster.shape[1]
    vs = [jnp.zeros((b, qi.shape[1]), jnp.int32) for qi, _, _ in net.hidden]
    ss = [jnp.zeros_like(v) for v in vs]
    v_ro = jnp.zeros((b, net.q_ro.shape[1]), jnp.int32)
    traj = []
    for t in range(raster.shape[0]):
        s = jnp.asarray(raster[t], jnp.int32)
        for l, (qi, qr, th) in enumerate(net.hidden):
            v = clamp(vs[l] + s @ jnp.asarray(qi) + ss[l] @ jnp.asarray(qr))
            fired = fires(v, th)
            vs[l] = clamp(jnp.where(fired, v - th, v))
            ss[l] = fired.astype(jnp.int32)
            s = ss[l]
        v_ro = v_ro + s @ jnp.asarray(net.q_ro)
        traj.append(v_ro)
    return vs, ss, v_ro, jnp.stack(traj)


def _raster(seed, t=T, b=B, n=SIZES[0], density=0.35):
    rng = np.random.default_rng(seed)
    return (rng.random((t, b, n)) < density).astype(np.int8)


def _currents(raster, net):
    return jnp.asarray(raster.astype(np.float32) * np.float32(
        net.enc_th * EVENT_MARGIN))


@pytest.fixture(scope="module")
def net():
    return make_net(0)


@pytest.fixture(scope="module")
def program(net):
    return pipeline.compile_network(_cfg(), net.params, domain="int")


def _assert_matches(vs, v_ro, want):
    for got, ref in zip(vs, want[0]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(v_ro), np.asarray(want[2]))


def test_reference_network_is_active(net):
    """Guard: both recurrent layers fire, neither saturates, and W_rec
    moves V — otherwise every comparison below would be vacuous."""
    raster = _raster(1)
    vs, ss, _, _ = plain_reference(raster, net)
    no_rec = dataclasses.replace(net, hidden=[
        (qi, np.zeros_like(qr), th) for qi, qr, th in net.hidden])
    vs0 = plain_reference(raster, no_rec)[0]
    for v, v0, s in zip(vs, vs0, ss):
        assert 0 < float(np.mean(np.asarray(s))) < 0.9
        assert not np.array_equal(np.asarray(v), np.asarray(v0))


def test_program_recovers_the_integer_truth(net, program):
    for spec, (qi, qr, th) in zip(program.fc_stack, net.hidden):
        assert spec.recurrent and spec.fan_in == spec.n_in + spec.n_out
        np.testing.assert_array_equal(np.asarray(spec.w),
                                      np.concatenate([qi, qr]))
        assert int(spec.threshold) == th
    np.testing.assert_array_equal(np.asarray(program.fc_stack[-1].w),
                                  net.q_ro)


@pytest.mark.parametrize("backend", ["float", "int_ref", "pallas",
                                     "bitmacro"])
def test_backend_matches_plain_reference(net, backend):
    """Every backend that takes recurrent layers equals the reference bit
    for bit (bitmacro in its silicon wrap arithmetic)."""
    mode = "wrap" if backend == "bitmacro" else "saturate"
    prog = pipeline.compile_network(_cfg(), net.params, domain="int",
                                    clamp_mode=mode)
    t, b = (6, 2) if backend == "bitmacro" else (T, B)
    raster = _raster(2, t, b)
    kw = {"interpret": True} if backend == "pallas" else {}
    res = pipeline.run_network(prog, _currents(raster, net), backend, **kw)
    want = plain_reference(raster, net, mode)
    _assert_matches(res.v_final[1:-1], res.v_final[-1], want)
    if backend != "float":
        np.testing.assert_array_equal(np.asarray(res.rasters[0]), raster)


@pytest.mark.parametrize("backend", ["float", "int_ref", "pallas"])
def test_stream_step_carries_the_raster(net, program, backend):
    """Frame by frame through `stream_step`: V and the recurrent rasters
    cross every call boundary and the result equals the reference."""
    raster = _raster(3)
    xs = _currents(raster, net)
    kw = {"interpret": True} if backend == "pallas" else {}
    st = program.init_state(B, backend)
    for t in range(T):
        st, out = program.step(st, xs[t], backend, **kw)
    want = plain_reference(raster, net)
    _assert_matches(st.vs[1:-1], st.vs[-1], want)
    for got, ref in zip(st.spikes, want[1]):
        np.testing.assert_array_equal(np.asarray(got).astype(np.int32),
                                      np.asarray(ref))


@pytest.mark.parametrize("k", [3, 5])
@pytest.mark.parametrize("backend", ["int_ref", "pallas"])
def test_megastep_blocks_compose(net, program, backend, k):
    """K-frame megasteps (a ragged last block included) equal one whole
    call: final V, last rasters and the per-tick readout trajectory."""
    raster = _raster(4)
    xs = _currents(raster, net)
    kw = {"interpret": True} if backend == "pallas" else {}
    st = program.init_state(B, backend)
    traj = []
    for t0 in range(0, T, k):
        st, out = program.megastep(st, xs[t0:t0 + k], backend, **kw)
        traj.append(np.asarray(out.v_out_traj))
    want = plain_reference(raster, net)
    _assert_matches(st.vs[1:-1], st.vs[-1], want)
    for got, ref in zip(st.spikes, want[1]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_array_equal(np.concatenate(traj), np.asarray(want[3]))


@pytest.mark.parametrize("use_pallas", [False, True], ids=["ref", "pallas"])
def test_kernel_chunks_compose(program, use_pallas):
    """`fused_snn_net` split at a chunk boundary, threading V (`v_init`)
    and the rasters (`s_init`), equals one whole call."""
    spikes = jnp.asarray(_raster(5, n=SIZES[0]))
    kw = pipeline._stack_kernel_args(program)
    ws = kw.pop("ws")
    kw.update(use_pallas=use_pallas, interpret=True, emit_rasters=True)
    whole = fused_snn_net(spikes, ws, **kw)
    a = fused_snn_net(spikes[:7], ws, **kw)
    b = fused_snn_net(spikes[7:], ws, v_init=a[1], s_init=a[3], **kw)
    for got, want in zip(b[1] + b[3], whole[1] + whole[3]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for r_a, r_b, r_w in zip(a[0], b[0], whole[0]):
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(r_a), np.asarray(r_b)]),
            np.asarray(r_w))


def test_program_that_ignores_w_rec_fails_the_comparison(net, program):
    """The comparison has teeth: a program whose W_rec rows are dropped
    (zeroed) runs, but differs from the reference."""
    layers = tuple(
        dataclasses.replace(ly, w=jnp.asarray(ly.w).at[ly.n_in:].set(0))
        if ly.recurrent else ly for ly in program.layers)
    broken = dataclasses.replace(program, layers=layers)
    raster = _raster(6)
    res = pipeline.run_network(broken, _currents(raster, net), "int_ref")
    want = plain_reference(raster, net)
    assert any(not np.array_equal(np.asarray(g), np.asarray(w))
               for g, w in zip(res.v_final[1:-1], want[0]))


def _requests(net, n=7, seed=7, n_in=SIZES[0]):
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n):
        raster = _raster(100 + rid, t=int(rng.integers(3, 14)), b=1,
                         n=n_in)[:, 0]
        reqs.append((raster, SNNRequest(
            rid=rid, frames=np.asarray(_currents(raster[:, None], net))[:, 0],
            arrival_tick=2 * rid)))
    return reqs


#: two hidden layers of one width: the macro stack holds two fc layers
#: with equal kind, fan-in and fan-out, told apart only by position
EQUAL_WIDTHS = (16, 16, 16, 3)


@pytest.mark.parametrize("backend,sizes", [
    ("int_ref", SIZES), ("pallas", SIZES), ("int_ref", EQUAL_WIDTHS)],
    ids=["int_ref", "pallas", "int_ref-equal-widths"])
def test_engine_drain_matches_reference(backend, sizes):
    """Staggered admissions into 3 lanes at K=4: lanes are evicted and
    re-admitted, and every request's readout V and W_rec event count equal
    its run alone from rest."""
    net = make_net(0, sizes)
    program = pipeline.compile_network(_cfg(sizes), net.params, domain="int")
    reqs = _requests(net, n_in=sizes[0])
    eng = SNNServeEngine(
        program, batch_slots=3, backend=backend, megastep=4,
        step_kw={"interpret": True} if backend == "pallas" else {})
    for _, r in reqs:
        eng.submit(r)
    done = {r.rid: r for r in eng.run_until_drained()}
    assert len(done) == len(reqs)
    for raster, r in reqs:
        want = plain_reference(raster[:, None], net)
        np.testing.assert_array_equal(done[r.rid].v_out,
                                      np.asarray(want[2])[0])
        alone = pipeline.run_network(program, jnp.asarray(r.frames)[:, None],
                                     "int_ref")
        rep = pipeline.sparsity_report(program, alone.rasters)
        assert done[r.rid].report.rec_events == rep.rec_events
        assert done[r.rid].report.events == rep.events
    assert all(sum(done[r.rid].report.rec_events[li] for _, r in reqs)
               for li in (0, 1))           # both W_rec blocks see events


@pytest.mark.parametrize("backend", ["int_ref", "pallas"])
def test_engine_ladder_drain_matches_k1(backend):
    """16 → 16r → 16r → 3 with the engine picking K: the recurrent raster
    rides each block, and every request's readout V, logits, ticks and
    W_rec event counts equal the ``megastep=1`` drain's."""
    net = make_net(3, EQUAL_WIDTHS)
    program = pipeline.compile_network(_cfg(EQUAL_WIDTHS), net.params,
                                       domain="int")
    kw = {"interpret": True} if backend == "pallas" else {}

    def drain(**ekw):
        eng = SNNServeEngine(program, batch_slots=3, backend=backend,
                             step_kw=kw, **ekw)
        for _, r in _requests(net, n_in=EQUAL_WIDTHS[0]):
            eng.submit(r)
        return eng, {r.rid: r for r in eng.run_until_drained()}
    _, want = drain(megastep=1)
    eng, got = drain()
    assert eng.frames_dispatched > eng.ticks          # blocks of K > 1
    for rid, r in want.items():
        assert got[rid].ticks == r.ticks
        np.testing.assert_array_equal(got[rid].v_out, r.v_out)
        np.testing.assert_array_equal(got[rid].logits, r.logits)
        assert got[rid].report.rec_events == r.report.rec_events


def test_evicted_lane_spike_state_resets(net, program):
    """A finished request leaves its lane with zero rasters (and V), so
    the next admission starts from s(-1) = 0."""
    raster = _raster(9, t=6, b=1)[:, 0]
    frames = np.asarray(_currents(raster[:, None], net))[:, 0]
    want = plain_reference(raster[:, None], net)
    assert any(int(np.asarray(s).sum()) for s in want[1])  # last tick fired
    eng = SNNServeEngine(program, batch_slots=1, backend="int_ref")
    eng.submit(SNNRequest(rid=0, frames=frames))
    while eng.step() or not eng.queue.empty():
        pass
    for leaf in eng.state.spikes:
        assert not np.asarray(leaf).any()
    eng.submit(SNNRequest(rid=1, frames=frames))
    eng.run_until_drained()
    np.testing.assert_array_equal(eng.finished[-1].v_out,
                                  np.asarray(want[2])[0])


def _mesh(n_data, n_model):
    if jax.device_count() < n_data * n_model:
        pytest.skip(f"needs {n_data * n_model} devices "
                    "(--xla_force_host_platform_device_count)")
    return make_host_mesh(n_data * n_model, model=n_model)


@pytest.mark.parametrize("how", ["pallas_sparse", "pallas_events",
                                 "ref_events", "int_ref_use_sparse",
                                 "model_mesh"])
def test_unsupported_paths_refuse_by_name(net, program, how):
    """Paths without recurrent execution refuse at dispatch with a named
    ContractError, and at contract check time too."""
    xs = _currents(_raster(10), net)
    kw, backend = {}, how
    if how == "int_ref_use_sparse":
        backend, kw = "int_ref", {"use_sparse": True}
    elif how == "model_mesh":
        backend, kw = "int_ref", {"mesh": _mesh(2, 2)}
    with pytest.raises(ContractError, match="recurrent") as e:
        pipeline.run_network(program, xs, backend, **kw)
    assert "recurrent" in e.value.where
    if backend in ("pallas_sparse", "pallas_events", "ref_events"):
        with pytest.raises(ContractError, match="recurrent"):
            program.step(program.init_state(B, backend), xs[0], backend)
        with pytest.raises(ContractError, match="recurrent"):
            check_kernel_contracts(program, backend)


@pytest.mark.parametrize("backend", ["int_ref", "pallas"])
def test_data_mesh_matches_reference(net, program, backend):
    """On a data-only mesh the lanes, their V and their rasters shard
    together: a batch run and a serving drain equal the reference."""
    mesh = _mesh(4, 1)
    kw = {"interpret": True} if backend == "pallas" else {}
    raster = _raster(12, b=8)
    res = pipeline.run_network(program, _currents(raster, net), backend,
                               mesh=mesh, **kw)
    _assert_matches(res.v_final[1:-1], res.v_final[-1],
                    plain_reference(raster, net))
    reqs = _requests(net, n=6)
    eng = SNNServeEngine(program, batch_slots=4, backend=backend,
                         megastep=4, step_kw=kw, mesh=mesh)
    for _, r in reqs:
        eng.submit(r)
    done = {r.rid: r for r in eng.run_until_drained()}
    for raster, r in reqs:
        np.testing.assert_array_equal(
            done[r.rid].v_out,
            np.asarray(plain_reference(raster[:, None], net)[2])[0])


def test_instruction_counts_include_recurrent_rows(net):
    """The raster-level counts equal the cycles the bit-level macros
    executed, AccW2V on the W_rec rows included, and `SparsityReport`
    separates those W_rec cycles per layer."""
    prog = pipeline.compile_network(_cfg(), net.params, domain="int",
                                    clamp_mode="wrap")
    raster = _raster(11, t=5, b=2)
    res = pipeline.run_network(prog, _currents(raster, net), "bitmacro")
    counted = pipeline.count_network_instructions(prog, res.rasters)
    # the readout accumulates off the bit array: not in the macro counts
    readout = isa.count_layer_instructions(np.asarray(res.rasters[-1]),
                                           SIZES[-2], SIZES[-1], "none")
    assert counted == res.aux["macro_counts"] + readout
    rep = pipeline.sparsity_report(prog, res.rasters)
    assert rep.instruction_counts() == counted
    rec_spikes = [int(np.asarray(r)[:-1].sum()) for r in res.rasters[1:]]
    assert rep.rec_events == (rec_spikes[0], rec_spikes[1], 0)
    assert rep.n_rec == (24, 16, 0)
    assert rep.recurrent_acc_w2v == (2 * rec_spikes[0] * 2,
                                     2 * rec_spikes[1] * 2, 0)
    with pytest.raises(ValueError, match="recurrent"):
        pipeline.sparsity_report_from_sums(prog, [], 5)


def test_static_analysis_covers_the_recurrent_rows(program):
    """The V bound spans fan-in n_in + n_out and the VMEM estimate counts
    W_rec and the carried rasters."""
    rng = check_program(program)
    for ly, spec in zip(rng.layers, program.fc_stack):
        w = np.asarray(spec.w).astype(np.int64)
        assert ly.increment.lo == int(np.minimum(w, 0).sum(axis=0).min())
        assert ly.increment.hi == int(np.maximum(w, 0).sum(axis=0).max())
    ff = pipeline.compile_network(
        _cfg(recurrent=()), snn.init_fc_snn(jax.random.PRNGKey(0),
                                            _cfg(recurrent=())),
        domain="int")
    rec_bytes = check_kernel_contracts(program).vmem_bytes
    ff_bytes = check_kernel_contracts(ff).vmem_bytes
    assert rec_bytes - ff_bytes == 2 * (128 * 128 + 3 * 8 * 128 * 4)


def test_feed_forward_params_are_refused_by_a_recurrent_config():
    params = snn.init_fc_snn(jax.random.PRNGKey(0), _cfg(recurrent=()))
    with pytest.raises(ValueError, match="W_rec"):
        pipeline.compile_network(_cfg(), params, domain="int")


def test_rsnn_ssc_full_widths():
    """rsnn-ssc compiles at its full widths: 774,000 weights (MACs per
    lane-frame), 41% of them on the W_rec rows."""
    cfg = get_snn_config("rsnn-ssc")
    params = snn.init_fc_snn(jax.random.PRNGKey(0), cfg)
    assert snn.param_count(params) == 774_000
    prog = pipeline.compile_network(cfg, params, domain="int")
    rec_rows = sum(ly.n_out * ly.n_out for ly in prog.fc_stack
                   if ly.recurrent)
    assert rec_rows == 320_000
    assert [ly.tiling.row_tiles for ly in prog.fc_stack] == [9, 7, 4]
