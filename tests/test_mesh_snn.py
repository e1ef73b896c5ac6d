"""Mesh-sharded SNN execution: bit-identity with the single-device path.

The claim under test is the tentpole of the `repro.dist` wiring: because
every on-macro reduction is integer (the per-shard partial V is unclamped
int32, the cross-shard `psum` is the AccV2V reduction — exact under the
mod-2^11 word, with the single clamp applied *after* the reduction), a
`jax.sharding.Mesh` execution of `run_network` / `stream_megastep` /
`SNNServeEngine` is **bit-identical** to the single-device run — rasters,
per-layer V, readout V, logits, and the event-counter ledgers. Swept here
on 4 forced host devices (conftest sets
``--xla_force_host_platform_device_count=4``) over mesh shape x backend x
neuron x clamp mode x row-tiled shapes, at megastep K in {1, 8}, and
through a serving drain on a partitioned pool.

`dist.sharding._fit` unit tests ride along: a dropped axis warns with the
extents, and a *required* axis that cannot shard raises `ShardingError`
instead of silently replicating.
"""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import SpikingConfig
from repro.configs.impulse_snn import SNNModelConfig
from repro.core import pipeline, snn
from repro.dist import sharding
from repro.dist.sharding import ShardingError
from repro.launch.mesh import make_host_mesh
from repro.serve import SNNRequest, SNNServeEngine
from repro.serve.snn_engine import merge_reports

needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="mesh suite needs >= 4 devices "
           "(--xla_force_host_platform_device_count=4)")

#: (n_data, n_model) mesh shapes over 4 devices: pure data-parallel, pure
#: model-parallel (row tiles), and the mixed square
MESH_SHAPES = ((4, 1), (1, 4), (2, 2))


def _make(layer_sizes=(300, 150, 20, 3), neuron="rmp", n_words=3, batch=4,
          seed=0, clamp="saturate"):
    """A row-tiled program (fan-in 300 > LANE=128 splits over macros) and
    a (T, B, d) presentation."""
    cfg = SNNModelConfig(
        arch_id="test", layer_sizes=layer_sizes,
        spiking=SpikingConfig(neuron=neuron, timesteps=3, threshold=1.0,
                              leak=0.0625, w_bits=6, v_bits=11),
        timesteps=3)
    params = snn.init_fc_snn(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed + 7)
    x = jnp.asarray(rng.standard_normal(
        (batch, n_words, layer_sizes[0])).astype(np.float32))
    program = pipeline.compile_network(cfg, params, domain="int",
                                       clamp_mode=clamp)
    return program, pipeline.present_words(x, cfg.timesteps)


def _make_conv(seed=0):
    """A conv-front-end program: the mesh dispatch must also cover the
    im2col patch-raster calls."""
    cfg = SNNModelConfig(
        arch_id="lenet-s", conv_spec=((4, 3, 1), (6, 3, 2)),
        in_shape=(8, 8, 1), layer_sizes=(4 * 4 * 6, 10, 3),
        spiking=SpikingConfig(neuron="rmp", timesteps=2, threshold=1.0,
                              leak=0.0625, w_bits=6, v_bits=11),
        timesteps=2, task="multiclass")
    params = snn.init_lenet_snn(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed + 3)
    x = jnp.asarray(rng.standard_normal((4, 8, 8, 1)).astype(np.float32))
    program = pipeline.compile_network(cfg, params, domain="int")
    return program, pipeline.present_static(x, cfg.timesteps)


def _assert_results_equal(ref, got, tag, *, events=False):
    """Every observable of a NetResult, bit for bit."""
    for i, (a, b) in enumerate(zip(ref.rasters, got.rasters)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{tag} raster {i}")
    for i, (a, b) in enumerate(zip(ref.v_final, got.v_final)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{tag} V {i}")
    np.testing.assert_array_equal(np.asarray(ref.v_out),
                                  np.asarray(got.v_out),
                                  err_msg=f"{tag} v_out")
    np.testing.assert_array_equal(np.asarray(ref.logits),
                                  np.asarray(got.logits),
                                  err_msg=f"{tag} logits")
    if events:
        for i, (a, b) in enumerate(zip(ref.aux["row_events"],
                                       got.aux["row_events"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{tag} row_events {i}")
        assert ref.aux["row_event_frames"] == got.aux["row_event_frames"]


BACKEND_KW = [
    ("int_ref", {}),
    ("pallas", {"interpret": True, "block_b": 4}),
    ("pallas_sparse", {"interpret": True, "block_b": 4}),
    ("pallas_sparse", {"interpret": True, "block_b": 4,
                       "gate_granularity": 4}),
    ("ref_events", {}),
    ("pallas_events", {"interpret": True, "block_b": 4}),
]


def _case_id(b, k):
    return b + (f"-g{k['gate_granularity']}" if "gate_granularity" in k
                else "")


# ---------------------------------------------------------------------------
# run_network bit-identity
# ---------------------------------------------------------------------------

@needs4
@pytest.mark.parametrize("backend,kw", BACKEND_KW,
                         ids=[_case_id(b, k) for b, k in BACKEND_KW])
@pytest.mark.parametrize("shape", MESH_SHAPES,
                         ids=[f"d{d}m{m}" for d, m in MESH_SHAPES])
def test_mesh_matches_single_device(shape, backend, kw):
    """Every int backend, every mesh shape, one row-tiled program: the
    mesh run equals the single-device run bit for bit."""
    program, xs = _make()
    mesh = make_host_mesh(4, model=shape[1])
    ref = pipeline.run_network(program, xs, backend, **kw)
    got = pipeline.run_network(program, xs, backend, mesh=mesh, **kw)
    _assert_results_equal(ref, got, f"{shape}/{backend}",
                          events=backend in ("ref_events", "pallas_events"))


@needs4
@pytest.mark.parametrize("neuron", ["if", "lif", "rmp"])
@pytest.mark.parametrize("clamp", ["saturate", "wrap"])
def test_mesh_neuron_clamp_sweep(neuron, clamp):
    """Neuron x clamp on ragged, non-dividing shapes (B=3 does not divide
    data=2; widths are not multiples of model=2): padding and the
    post-psum clamp stay exact in both word policies."""
    program, xs = _make(layer_sizes=(37, 51, 19, 3), neuron=neuron,
                        batch=3, clamp=clamp, seed=5)
    mesh = make_host_mesh(4, model=2)
    for backend, kw in (("int_ref", {}),
                        ("pallas", {"interpret": True, "block_b": 4})):
        ref = pipeline.run_network(program, xs, backend, **kw)
        got = pipeline.run_network(program, xs, backend, mesh=mesh, **kw)
        _assert_results_equal(ref, got, f"{neuron}/{clamp}/{backend}")


@needs4
@pytest.mark.parametrize("backend,kw",
                         [("int_ref", {}),
                          ("pallas", {"interpret": True, "block_b": 4}),
                          ("ref_events", {})],
                         ids=["int_ref", "pallas", "ref_events"])
def test_mesh_conv_front_end(backend, kw):
    """Conv programs: the im2col patch-raster dispatches execute under the
    mesh too (patch frames partition as whole (example, position) frames)."""
    program, xs = _make_conv()
    mesh = make_host_mesh(4, model=2)
    ref = pipeline.run_network(program, xs, backend, **kw)
    got = pipeline.run_network(program, xs, backend, mesh=mesh, **kw)
    _assert_results_equal(ref, got, f"conv/{backend}",
                          events=backend == "ref_events")


@needs4
def test_float_and_bitmacro_reject_mesh():
    """Non-mesh backends fail loudly instead of silently ignoring the
    mesh: float reductions are not order-exact, bitmacro state is host-
    side."""
    program, xs = _make()
    mesh = make_host_mesh(4)
    with pytest.raises(ValueError, match="no mesh execution"):
        pipeline.run_network(program, xs, "float", mesh=mesh)
    with pytest.raises(ValueError, match="no mesh execution"):
        pipeline.stream_megastep(
            program, pipeline.init_stream_state(program, 4), xs[:2],
            "float", mesh=mesh)
    with pytest.raises(ValueError):
        SNNServeEngine(program, backend="float", mesh=mesh)


# ---------------------------------------------------------------------------
# streaming megasteps on a mesh
# ---------------------------------------------------------------------------

@needs4
@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("backend,kw",
                         [("int_ref", {}),
                          ("pallas", {"interpret": True, "block_b": 4}),
                          ("pallas_events", {"interpret": True,
                                             "block_b": 4})],
                         ids=["int_ref", "pallas", "pallas_events"])
def test_mesh_megastep_stream(k, backend, kw):
    """Driving a presentation through K-frame megastep blocks on a (2, 2)
    mesh reproduces the meshless drive exactly: carried state, per-tick
    readout trajectories, and frames_consumed."""
    program, xs = _make(n_words=4)             # T_total = 12
    mesh = make_host_mesh(4, model=2)
    st_a = st_b = pipeline.init_stream_state(program, 4, backend)
    for lo in range(0, xs.shape[0], k):
        block = xs[lo:lo + k]
        if block.shape[0] < k:                 # ragged tail: mask it
            pad = jnp.zeros((k - block.shape[0], *block.shape[1:]),
                            block.dtype)
            active = np.full(4, block.shape[0], np.int32)
            block = jnp.concatenate([block, pad])
        else:
            active = None
        st_a, out_a = pipeline.stream_megastep(program, st_a, block,
                                               backend, active=active, **kw)
        st_b, out_b = pipeline.stream_megastep(program, st_b, block,
                                               backend, active=active,
                                               mesh=mesh, **kw)
        np.testing.assert_array_equal(np.asarray(out_a.v_out_traj),
                                      np.asarray(out_b.v_out_traj))
        np.testing.assert_array_equal(np.asarray(out_a.logits_traj),
                                      np.asarray(out_b.logits_traj))
        np.testing.assert_array_equal(np.asarray(out_a.frames_consumed),
                                      np.asarray(out_b.frames_consumed))
    for i, (a, b) in enumerate(zip(st_a.vs, st_b.vs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"carried V {i}")


# ---------------------------------------------------------------------------
# serving on a partitioned pool
# ---------------------------------------------------------------------------

def _requests(n=7, t=9, d=300, seed=11):
    rng = np.random.default_rng(seed)
    out = []
    for r in range(n):
        out.append(SNNRequest(
            rid=r, frames=rng.standard_normal((t, d)).astype(np.float32)))
    return out


def _drain(program, mesh, backend, kw, megastep=4, pages=2):
    """Drain `_requests` through a paged engine; on the jitted path also
    keep each page dispatch's packed per-lane results."""
    eng = SNNServeEngine(program, batch_slots=4, backend=backend,
                         step_kw=kw, pages=pages, megastep=megastep,
                         mesh=mesh)
    eng.packed = []
    if eng._dispatch is not None:
        inner = eng._dispatch

        def kept(*args):
            out = inner(*args)
            eng.packed.append(out[1])
            return out
        eng._dispatch = kept
    for r in _requests():
        eng.submit(r)
    eng.run_until_drained()
    return eng


@needs4
@pytest.mark.parametrize("backend,kw",
                         [("int_ref", {}),
                          ("pallas", {"interpret": True, "block_b": 4})],
                         ids=["int_ref", "pallas"])
def test_mesh_serving_ladder_drain(backend, kw):
    """With the engine picking K, a mesh-partitioned pool (lanes over
    data=2, rows over model=2) serves every request as the single-device
    ``megastep=1`` engine does, and its blocks land on the data shards
    the compiled rungs take."""
    program, _ = _make()
    a = _drain(program, None, backend, kw, megastep=1)
    b = SNNServeEngine(program, batch_slots=4, backend=backend, step_kw=kw,
                       pages=2, mesh=make_host_mesh(4, model=2))
    compiles = []

    def hear(name, _secs, **_kw):
        if name in ("/jax/core/compile/backend_compile_duration",
                    "/jax/core/compile/jaxpr_trace_duration"):
            compiles.append(name)
    jax.monitoring.register_event_duration_secs_listener(hear)
    try:
        for r in _requests():
            b.submit(r)
        b.run_until_drained()
    finally:
        jax.monitoring.unregister_event_duration_listener(hear)
    assert not compiles
    assert b.frames_dispatched > b.ticks
    for ra, rb in zip(sorted(a.finished, key=lambda r: r.rid),
                      sorted(b.finished, key=lambda r: r.rid)):
        assert ra.ticks == rb.ticks
        np.testing.assert_array_equal(ra.logits, rb.logits)
        np.testing.assert_array_equal(ra.v_out, rb.v_out)


@needs4
@pytest.mark.parametrize("backend,kw",
                         [("int_ref", {}),
                          ("ref_events", {}),
                          ("pallas_events", {"interpret": True,
                                             "block_b": 4})],
                         ids=["int_ref", "ref_events", "pallas_events"])
def test_mesh_serving_drain(backend, kw):
    """A full drain on a mesh-partitioned paged pool (2 pages x 4 lanes,
    lanes sharded over data=2, rows over model=2, K=4 megasteps) serves
    every request bit-identically to the single-device engine, and the
    event accounting closes: per-request reports, the merged aggregate,
    and — on the event backends — the device ledger. On the jitted path
    both engines read each page dispatch's results as one packed array,
    which the mesh engine keeps sharded over data like its lanes."""
    program, _ = _make()
    mesh = make_host_mesh(4, model=2)
    a = _drain(program, None, backend, kw)
    b = _drain(program, mesh, backend, kw)
    assert len(a.finished) == len(b.finished) == 7
    if a._dispatch is not None:
        assert a.device_reads == len(a.packed) > 0
        assert b.device_reads == len(b.packed) == len(a.packed)
        for p in b.packed:
            assert p.sharding.shard_shape(p.shape)[0] == \
                p.shape[0] // mesh.shape["data"]
    for ra, rb in zip(sorted(a.finished, key=lambda r: r.rid),
                      sorted(b.finished, key=lambda r: r.rid)):
        np.testing.assert_array_equal(ra.logits, rb.logits,
                                      err_msg=f"rid {ra.rid} logits")
        np.testing.assert_array_equal(ra.v_out, rb.v_out,
                                      err_msg=f"rid {ra.rid} v_out")
        assert (ra.ticks, ra.finish_clock) == (rb.ticks, rb.finish_clock)
        for i, (x, y) in enumerate(zip(ra.report.row_events,
                                       rb.report.row_events)):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y),
                err_msg=f"rid {ra.rid} row_events {i}")
    # aggregate closure: merging the mesh engine's per-request reports
    # equals merging the single-device engine's
    agg_a = a.aggregate_report()
    agg_b = merge_reports([r.report for r in b.finished])
    assert agg_a.events == agg_b.events
    assert agg_a.frames == agg_b.frames
    for x, y in zip(agg_a.row_events, agg_b.row_events):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    if backend in ("ref_events", "pallas_events"):
        da, db = a.device_event_stats(), b.device_event_stats()
        assert da.frames == db.frames
        for x, y in zip(da.row_events, db.row_events):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert (a.device_skipped_row_fraction()
                == b.device_skipped_row_fraction())


# ---------------------------------------------------------------------------
# dist.sharding._fit: warning + ShardingError (satellite fix)
# ---------------------------------------------------------------------------

@needs4
def test_fit_divisibility_drop_warns(caplog):
    """A proposal whose dimension does not divide the mesh extent degrades
    to replication AND warns with the axis and extents — never silently."""
    mesh = make_host_mesh(4, model=2)          # data=2, model=2
    with caplog.at_level(logging.WARNING, logger="repro.dist.sharding"):
        spec = sharding._fit(("data",), (5,), mesh)
    assert spec == P(None)
    rendered = [r.getMessage() for r in caplog.records]
    assert any("dropping axis 'data'" in m for m in rendered)
    assert any("size 5 does not divide mesh extent 2" in m
               for m in rendered)


@needs4
def test_fit_required_axis_raises():
    """The same drop on an explicitly *required* axis raises ShardingError
    (with the extents) instead of degrading."""
    mesh = make_host_mesh(4, model=2)
    with pytest.raises(ShardingError, match="does not divide mesh extent"):
        sharding._fit(("data",), (5,), mesh, required=("data",))
    # a missing mesh axis is equally fatal when required
    with pytest.raises(ShardingError, match="missing from mesh"):
        sharding._fit(("banks",), (4,), mesh, required=("banks",))
    # ...but silently replicates when not required (generic-rule contract)
    assert sharding._fit(("banks",), (4,), mesh) == P(None)


@needs4
def test_fit_size_one_extent_is_honoured(caplog):
    """A size-1 mesh axis counts as honoured (sharding over it IS
    replication): no warning, no error, even when required."""
    mesh = make_host_mesh(4, model=1)          # data=4, model=1
    with caplog.at_level(logging.WARNING, logger="repro.dist.sharding"):
        spec = sharding._fit(("model",), (5,), mesh, required=("model",))
    assert spec == P(None)
    assert not caplog.records


@needs4
def test_logical_spec_snn_axes():
    """The SNN logical axes resolve onto the mesh: lanes/banks -> data,
    macro_row_tile -> model; an unknown *required* name raises."""
    mesh = make_host_mesh(4, model=2)
    assert sharding.logical_spec(mesh, ("lane", None), (8, 16)) \
        == P("data", None)
    assert sharding.logical_spec(mesh, ("macro_row_tile", None), (6, 16),
                                 required=("macro_row_tile",)) \
        == P("model", None)
    assert sharding.logical_spec(mesh, ("bank",), (2,)) == P("data")
    with pytest.raises(ShardingError, match="resolves to no mesh axis"):
        sharding.logical_spec(mesh, ("lane",), (8,), required=("lanez",))


@needs4
def test_snn_state_specs_places_lanes():
    """Streaming-state placement: every array leaf's lane axis shards over
    data; the scalar tick counter replicates."""
    program, _ = _make()
    mesh = make_host_mesh(4, model=2)
    st = pipeline.init_stream_state(program, 4, "int_ref")
    specs = sharding.snn_state_specs(st, mesh)
    for s in specs.vs:
        assert s.spec == P("data", None)
    assert specs.t.spec == P()
