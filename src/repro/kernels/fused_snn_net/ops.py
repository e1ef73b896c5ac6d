"""jit'd public wrapper for the network-level fused SNN kernel: padding,
dispatch, and the pure-JAX fallback for non-TPU backends.

Padding correctness: layer widths pad to the 128-lane tile. Padded *input*
lanes are harmless because the next layer's padded weight ROWS are zero, so
junk spikes fired by padded lanes (their V integrates only leak) contribute
exactly nothing downstream; rasters and V are sliced back to logical widths
before returning.

``use_sparse`` selects the event-gated execution path (see kernel.py): the
AccW2V matmul of a layer is skipped whenever its input tile is all-silent,
while the neuron update still runs every timestep — bit-identical to the
dense path by construction. ``gate_granularity`` refines the gate below
the tile: at G in {2, 4, 8} each 128-lane macro-row tile splits into G row
blocks whose partial matmuls are predicated independently (partials add
unclamped, one clamp after the last block — still bit-identical). Both the
Pallas kernel and the pure-jnp reference implement the gate (`@pl.when` /
`lax.cond`), and both report skipped-matmul counts for the accounting
layer: a (tiles, n_layers) array at granularity 1, a per-layer list of
(tiles, n_blocks_i) arrays at finer granularities (block counts vary with
each layer's fan-in — `kernel.skip_layout`).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.fused_snn_net.kernel import (fused_snn_net_pallas,
                                                skip_layout)

LANE = 128


def _ref_blocks(n_in: int, granularity: int) -> list:
    """Lane-block spans of one layer's logical input width — the same
    counted blocks `kernel.skip_layout` assigns skip columns to."""
    if granularity == 1:
        return [(0, n_in)]
    bw = LANE // granularity
    return [(lo, min(lo + bw, n_in)) for lo in range(0, n_in, bw)]


def _pad_axis(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _check_stack(spikes: jax.Array, ws: list) -> None:
    """Chain-alignment on LOGICAL widths (padded widths can coincide for
    mismatched stacks): layer i's fan-in == layer i-1's fan-out. Raises
    (rather than asserts) so the contract survives ``python -O``."""
    if not ws:
        raise ValueError("fused_snn_net needs a non-empty weight stack "
                         "(spiking FCs first, readout last); got ws=[]")
    prev = spikes.shape[2]
    for i, w in enumerate(ws):
        if w.ndim != 2:
            raise ValueError(f"ws[{i}] must be a 2-D (n_in, n_out) weight "
                             f"matrix, got shape {w.shape}")
        if w.shape[0] != prev:
            raise ValueError(
                f"layer chain misaligned: ws[{i}] has fan-in {w.shape[0]} "
                f"but the previous layer emits {prev} lanes")
        prev = w.shape[1]


@partial(jax.jit, static_argnames=("thresholds", "leaks", "neuron",
                                   "clamp_mode", "block_b", "use_pallas",
                                   "interpret", "emit_rasters", "use_sparse",
                                   "gate_granularity", "readout",
                                   "use_events", "event_crossover"))
def fused_snn_net(spikes: jax.Array, ws: list, *, thresholds: tuple,
                  leaks: tuple, neuron: str = "rmp",
                  clamp_mode: str = "saturate", block_b: int = 8,
                  use_pallas: bool = True, interpret: bool = False,
                  emit_rasters: bool = True, use_sparse: bool = False,
                  gate_granularity: int = 1, readout: bool = True,
                  v_init: list = None, use_events: bool = False,
                  event_crossover: float = 1.0):
    """Run a (T, B, N0) encoder spike raster through the whole fc stack.

    ``ws``: per-layer int8 weights, spiking FCs first, readout last;
    ``thresholds``/``leaks``: per-spiking-layer ints on each layer's grid.
    ``readout=False`` runs an all-spiking stack — every layer in ``ws`` is a
    spiking FC (one threshold/leak each, no accumulate-only tail); conv
    layers lowered onto im2col patch rasters execute this way.
    Returns (rasters, v_finals, skips): per-spiking-layer output rasters
    (T, B, N_i) int8 (empty list when emit_rasters=False), per-layer
    final V (B, N_i) int32 (readout last), and — in ``use_sparse`` mode —
    skipped-matmul counts; at ``gate_granularity`` 1 a (B_tiles, n_layers)
    int32 array for the Pallas kernel (one row per batch tile) or
    (1, n_layers) for the reference (whose tile is the whole batch); at
    granularity G in {2, 4, 8} a per-layer list of (B_tiles, n_blocks_i)
    arrays, one column per 128/G-lane row block of that layer's fan-in;
    ``skips`` is None when dense.

    ``use_pallas=False`` selects a pure-jnp reference with identical
    semantics (scan of isa.layer_timestep_int over the stack).

    ``v_init`` (streaming entry): per-layer (B, n_out) int32 membrane state
    (logical widths, readout last) resuming a previous call instead of
    starting from V = 0. Integer accumulation is exact, so splitting a
    presentation into chunks that thread final V back in as ``v_init``
    reproduces the single-call result bit for bit — the contract
    `core.pipeline.stream_step` is built on.

    ``use_events`` selects the Pallas event-list execution (kernel.py
    module docs): on-device compaction + gather-matvec AccW2V with a dense
    fallback above ``event_crossover`` occupancy. ``skips`` is then a dict
    ``{"row_events": [per-layer (B_tiles, n_in) int32 counts],
    "dense_fallbacks": (B_tiles, n_layers) int32}`` — wrap with
    `fused_snn_net_device_events` to get an `events.EventStats`.
    """
    thresholds, leaks = tuple(thresholds), tuple(leaks)
    _check_stack(spikes, ws)
    if v_init is not None and len(v_init) != len(ws):
        raise ValueError(f"v_init needs one (B, n_out) state per layer "
                         f"({len(ws)}), got {len(v_init)}")
    if gate_granularity != 1 and not use_sparse:
        raise ValueError("gate_granularity is an event-gating knob; pass "
                         "use_sparse=True to gate at granularity "
                         f"{gate_granularity}")
    if use_events and use_sparse:
        raise ValueError("use_events (event-list execution) and use_sparse "
                         "(row-block gating) are mutually exclusive")
    if use_events and not use_pallas:
        raise ValueError("use_events is the Pallas event-list kernel; the "
                         "host-side executor is events.fused_snn_net_events")
    if use_events and not 0.0 <= event_crossover <= 1.0:
        raise ValueError("event_crossover is a fraction of tile event "
                         f"capacity and must lie in [0, 1], got "
                         f"{event_crossover}")
    # validates granularity and enforces the gate-column cap for BOTH
    # execution paths (the reference mirrors the kernel's counted blocks)
    widths = (spikes.shape[2],) + tuple(w.shape[1] for w in ws)
    if use_sparse:
        n_blocks, _, _ = skip_layout(widths[:len(ws)], gate_granularity)
    n_spiking = len(ws) - 1 if readout else len(ws)
    if len(thresholds) != n_spiking or len(leaks) != n_spiking:
        raise ValueError(
            f"need one threshold/leak per spiking layer ({n_spiking} with "
            f"readout={readout}), got {len(thresholds)}/{len(leaks)}")
    if not use_pallas:
        return _fused_snn_net_ref(spikes, ws, thresholds, leaks, neuron,
                                  clamp_mode, emit_rasters, use_sparse,
                                  readout, gate_granularity, v_init)
    T, B, N0 = spikes.shape
    s = _pad_axis(_pad_axis(spikes.astype(jnp.int8), 2, LANE), 1, block_b)
    ws_p = [_pad_axis(_pad_axis(w.astype(jnp.int8), 0, LANE), 1, LANE)
            for w in ws]
    v_init_p = None
    if v_init is not None:
        # padded batch rows / lanes resume from 0 V, exactly as a
        # from-scratch call initializes them — padding junk stays invisible
        v_init_p = [_pad_axis(_pad_axis(v.astype(jnp.int32), 1, LANE),
                              0, block_b) for v in v_init]
    params = jnp.asarray([[t, lk] for t, lk in zip(thresholds, leaks)],
                         jnp.int32).reshape(len(thresholds), 2)
    rasters, v_finals, skips = fused_snn_net_pallas(
        s, ws_p, params, neuron=neuron, clamp_mode=clamp_mode,
        block_b=block_b, emit_rasters=emit_rasters, interpret=interpret,
        sparse=use_sparse, granularity=gate_granularity, has_readout=readout,
        logical_widths=widths, batch_logical=B, v_init=v_init_p,
        events=use_events, event_crossover=event_crossover)
    rasters = [r[:, :B, :w.shape[1]]
               for r, w in zip(rasters, ws[:n_spiking])]
    v_finals = [v[:B, :w.shape[1]] for v, w in zip(v_finals, ws)]
    if use_events:
        row_counts, fallbacks = skips
        skips = {"row_events": [rc[:, :w.shape[0]]      # logical rows only
                                for rc, w in zip(row_counts, ws)],
                 "dense_fallbacks": fallbacks}
    if use_sparse and gate_granularity != 1:
        split, off = [], 0
        for n in n_blocks:             # site columns -> per-layer arrays
            split.append(skips[:, off:off + n])
            off += n
        skips = split
    return rasters, v_finals, skips


def fused_snn_net_device_events(spikes, ws, *, thresholds: tuple,
                                leaks: tuple, neuron: str = "rmp",
                                clamp_mode: str = "saturate",
                                block_b: int = 8, interpret: bool = False,
                                emit_rasters: bool = True,
                                readout: bool = True, v_init: list = None,
                                event_crossover: float = 1.0):
    """`fused_snn_net(use_events=True)` with the device counters folded into
    an `events.EventStats` — the same third-element contract the host
    `events.fused_snn_net_events` executor returns, so the accounting layer
    (`core.pipeline._attach_event_stats`) treats both identically.

    Not jit'd (the jit boundary is the inner `fused_snn_net` call): the
    per-tile int32 row counts come off device here and sum to host int64 —
    per-layer totals over a long presentation overflow int32 at scale, and
    `EventStats.row_events` is specified int64.
    """
    import numpy as np

    from repro.kernels.fused_snn_net.events import EventStats

    rasters, v_finals, skips = fused_snn_net(
        spikes, ws, thresholds=tuple(thresholds), leaks=tuple(leaks),
        neuron=neuron, clamp_mode=clamp_mode, block_b=block_b,
        use_pallas=True, interpret=interpret, emit_rasters=emit_rasters,
        readout=readout, v_init=v_init, use_events=True,
        event_crossover=event_crossover)
    T, B = spikes.shape[0], spikes.shape[1]
    row_events = tuple(np.asarray(rc, np.int64).sum(axis=0)
                       for rc in skips["row_events"])
    fallbacks = tuple(int(c) for c in
                      np.asarray(skips["dense_fallbacks"],
                                 np.int64).sum(axis=0))
    stats = EventStats(row_events=row_events, frames=T * B,
                       dense_fallbacks=fallbacks)
    return rasters, v_finals, stats


# ---------------------------------------------------------------------------
# mesh execution — the multi-device entry (`repro.dist` wiring)
# ---------------------------------------------------------------------------

def mesh_axis_extents(mesh) -> tuple:
    """``(n_data, n_model)`` extents of the SNN mesh axes — "data" carries
    serving lanes / macro banks (batch), "model" carries macro row tiles
    (fan-in) — with 1 for an axis the mesh does not name."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(sizes.get("data", 1)), int(sizes.get("model", 1))


def mesh_padded_widths(widths: tuple, n_model: int) -> tuple:
    """Layer widths padded up to multiples of the model-axis extent so
    every layer's fan-in rows split evenly over the shards. Shared with
    `analysis.kernel_contracts` — the ``mesh_split`` contract row
    re-derives exactly these numbers."""
    return tuple(-(-int(w) // n_model) * n_model for w in widths)


def mesh_rowpartial_tick(vs, counts, frame, ws_l, *, widths: tuple,
                         n_spiking: int, thresholds: tuple, leaks: tuple,
                         neuron: str, clamp_mode: str, use_events: bool):
    """One model-parallel frame tick — the AccV2V reduction across devices,
    exposed at module level so `analysis.trace_check` can trace exactly the
    dispatched body under an abstract mesh (`jax.make_jaxpr(...,
    axis_env=...)`), no devices needed.

    Each model shard owns a row tile of every layer's weights (``ws_l``,
    already sliced by shard_map) and computes that tile's UNCLAMPED int32
    partial V; the cross-shard integer psum is the word-level AccV2V cycle
    (exact under mod-2^11 wrap: int32 addition is associative and clamp_v
    composes after the full sum — the same single-clamp-after-partials
    trick sub-tile gating uses), and the one clamp runs after the
    reduction. ``vs``/``counts`` are the per-layer carry (``counts`` empty
    unless ``use_events``); ``frame`` is the (B_local, pw[0]) int spike
    frame. Returns ``(vs, counts, rasters_t)``.
    """
    from repro.core.isa import neuron_dynamics_int
    from repro.core.quant import clamp_v
    vs, counts = list(vs), list(counts)
    cur = frame.astype(jnp.int32)                # (B_l, pw[0])
    rasters_t = []
    for i, w_l in enumerate(ws_l):
        if use_events:
            # path-independent per-row event counters on the LOGICAL
            # input rows (the padded tail is junk)
            counts[i] = counts[i] + jnp.sum(cur[:, :widths[i]], axis=0)
        rows = w_l.shape[0]                      # pw[i] // n_model
        lo = jax.lax.axis_index("model") * rows
        blk = jax.lax.dynamic_slice_in_dim(cur, lo, rows, axis=1)
        total = jax.lax.psum(blk @ w_l.astype(jnp.int32), "model")
        if i < n_spiking:
            v = clamp_v(vs[i] + total, clamp_mode)
            vs[i], spk = neuron_dynamics_int(
                v, neuron=neuron, threshold=jnp.int32(thresholds[i]),
                leak=jnp.int32(leaks[i]), reset=jnp.int32(0),
                clamp_mode=clamp_mode)
            cur = spk.astype(jnp.int32)
            rasters_t.append(spk.astype(jnp.int8))
        else:                                    # unclamped readout
            vs[i] = vs[i] + total
    return tuple(vs), tuple(counts), tuple(rasters_t)


@partial(jax.jit, static_argnames=("mesh", "thresholds", "leaks", "neuron",
                                   "clamp_mode", "block_b", "use_pallas",
                                   "interpret", "emit_rasters", "use_sparse",
                                   "gate_granularity", "readout",
                                   "use_events", "event_crossover"))
def _fused_snn_net_mesh_core(spikes, ws, v_init, *, mesh, thresholds, leaks,
                             neuron, clamp_mode, block_b, use_pallas,
                             interpret, emit_rasters, use_sparse,
                             gate_granularity, readout, use_events,
                             event_crossover):
    """The traced mesh body (see `fused_snn_net_mesh` for the contract).
    ``mesh`` is hashable, hence a static argname: the shard_map in/out
    specs are built per (mesh, shapes, flags) trace. ``v_init`` is always
    a concrete per-layer list here (zeros for a from-scratch run) so the
    shard_map operand tree is structurally fixed."""
    from repro.dist.sharding import logical_spec
    n_data, n_model = mesh_axis_extents(mesh)
    T, B, N0 = spikes.shape
    widths = (N0,) + tuple(w.shape[1] for w in ws)
    n_spiking = len(ws) - 1 if readout else len(ws)
    s = _pad_axis(spikes.astype(jnp.int8), 1, n_data)
    vi = [_pad_axis(v.astype(jnp.int32), 0, n_data) for v in v_init]

    if n_model == 1:
        # pure lane (data) parallelism: every shard runs the REAL
        # single-device executor — fused pallas kernel, gated kernel, or
        # jnp reference — on its contiguous lane slice. Lanes never
        # interact, so per-shard results equal the single-device values
        # bit for bit and reassemble by concatenation.
        def body(s_l, ws_l, vi_l):
            r, v, sk = fused_snn_net(
                s_l, list(ws_l), thresholds=thresholds, leaks=leaks,
                neuron=neuron, clamp_mode=clamp_mode, block_b=block_b,
                use_pallas=use_pallas, interpret=interpret,
                emit_rasters=emit_rasters, use_sparse=use_sparse,
                gate_granularity=gate_granularity, readout=readout,
                v_init=list(vi_l), use_events=use_events,
                event_crossover=event_crossover)
            return list(r), list(v), sk

        lane_spec = logical_spec(mesh, (None, "lane", None), s.shape,
                                 required=("lane",))
        in_specs = (lane_spec, [P()] * len(ws), [P("data")] * len(ws))
        r_spec = [P(None, "data", None)] * (n_spiking if emit_rasters else 0)
        v_spec = [P("data")] * len(ws)
        if use_events:
            # per-shard kernel counter blocks: one row per local batch
            # tile — global assembly stacks the tile rows in lane order
            sk_spec = {"row_events": [P("data")] * len(ws),
                       "dense_fallbacks": P("data")}
        elif use_sparse:
            sk_spec = ([P("data")] * len(ws) if gate_granularity != 1
                       else P("data"))
        else:
            sk_spec = None
        rasters, v_finals, skips = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs,
            out_specs=(r_spec, v_spec, sk_spec),
            check_vma=False)(s, list(ws), vi)
        return ([r[:, :B] for r in rasters], [v[:B] for v in v_finals],
                skips)

    # model parallelism: the AccV2V reduction across devices — see
    # `mesh_rowpartial_tick` (the traceable per-frame body). Widths pad to
    # n_model multiples; padded output lanes may fire junk spikes (their V
    # only integrates leak) but feed zero weight rows downstream, exactly
    # the LANE-padding argument of the single-device wrapper.
    pw = mesh_padded_widths(widths, n_model)
    s = _pad_axis(s, 2, n_model)
    ws_p = [_pad_axis(_pad_axis(w.astype(jnp.int8), 0, n_model), 1, n_model)
            for w in ws]
    vi = [_pad_axis(v, 1, n_model) for v in vi]

    def body(s_l, ws_l, vi_l):
        def tick(carry, frame):
            vs, counts, rasters_t = mesh_rowpartial_tick(
                carry[0], carry[1], frame, ws_l, widths=widths,
                n_spiking=n_spiking, thresholds=thresholds, leaks=leaks,
                neuron=neuron, clamp_mode=clamp_mode, use_events=use_events)
            return ((vs, counts), rasters_t if emit_rasters else ())

        counts0 = tuple(jnp.zeros((widths[i],), jnp.int32)
                        for i in range(len(ws_l))) if use_events else ()
        (vs, counts), rasters = jax.lax.scan(
            tick, (tuple(vi_l), counts0), s_l)
        rasters = [r[:, :, :w] for r, w in zip(rasters, widths[1:])]
        vs = [v[:, :w] for v, w in zip(vs, widths[1:])]
        # lane-partition counters pool over the data axis; every model
        # shard then holds the identical global counts
        counts = [jax.lax.psum(c, "data") for c in counts]
        return list(rasters), list(vs), list(counts)

    lane_spec = logical_spec(mesh, (None, "lane", None), s.shape,
                             required=("lane",))
    w_specs = [logical_spec(mesh, ("macro_row_tile", None), w.shape,
                            required=("macro_row_tile",)) for w in ws_p]
    in_specs = (lane_spec, w_specs, [P("data")] * len(ws))
    r_spec = [P(None, "data", None)] * (n_spiking if emit_rasters else 0)
    v_spec = [P("data")] * len(ws)
    c_spec = [P(None)] * len(ws) if use_events else []
    rasters, v_finals, counts = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs,
        out_specs=(r_spec, v_spec, c_spec),
        check_vma=False)(s, ws_p, vi)
    return ([r[:, :B] for r in rasters], [v[:B] for v in v_finals],
            counts if use_events else None)


def fused_snn_net_mesh(spikes: jax.Array, ws: list, *, mesh,
                       thresholds: tuple, leaks: tuple, neuron: str = "rmp",
                       clamp_mode: str = "saturate", block_b: int = 8,
                       use_pallas: bool = True, interpret: bool = False,
                       emit_rasters: bool = True, use_sparse: bool = False,
                       gate_granularity: int = 1, readout: bool = True,
                       v_init: list = None, use_events: bool = False,
                       event_crossover: float = 1.0):
    """`fused_snn_net` on a `jax.sharding.Mesh` — same stack, same
    results, executed under shard_map. Placement is config-driven through
    `repro.dist.sharding`'s logical axes: "lane" (batch) partitions over
    the data axis, "macro_row_tile" (fan-in rows) over the model axis.

    Execution splits on the model extent:

      * model extent 1 — pure lane parallelism: each shard runs the real
        single-device executor (fused pallas kernel included) on its lane
        slice; lanes never interact, so results are bit-identical and
        concatenate. Skip/event counters are the per-shard kernels' own
        blocks stacked in lane order — identical to the single-device
        counters whenever ``block_b`` divides the per-shard batch.
      * model extent > 1 — the AccV2V all-reduce: each shard computes its
        row tile's unclamped int32 partial V, an integer ``psum`` reduces
        across shards (exact — int32 addition is associative and mod-2^11
        wrap composes), and the single clamp runs after the reduction.
        The body is the XLA row-partial scan (a pallas kernel cannot span
        the cross-device reduction); ``use_pallas`` then only selects
        counter conventions. Row-block gate counters are a per-device
        kernel feature and come back as None on this path.

    Args/shapes match `fused_snn_net` (spikes (T, B, N0) int8, per-layer
    ws (n_in, n_out) int8, optional per-layer ``v_init`` (B, n_out)
    int32). Batch pads to the data extent and widths to the model extent
    with zeros — padded lanes integrate nothing and are sliced off.

    Returns (rasters, v_finals, skips); on the event path (``use_events``)
    ``skips`` is an `events.EventStats` folded on the host — do not call
    that combination under an outer jit.

    Raises ValueError on a misaligned stack or invalid flag combination,
    `repro.dist.sharding.ShardingError` if a required axis cannot be
    honoured (cannot happen after padding; defensive).
    """
    thresholds, leaks = tuple(thresholds), tuple(leaks)
    _check_stack(spikes, ws)
    if v_init is not None and len(v_init) != len(ws):
        raise ValueError(f"v_init needs one (B, n_out) state per layer "
                         f"({len(ws)}), got {len(v_init)}")
    if gate_granularity != 1 and not use_sparse:
        raise ValueError("gate_granularity is an event-gating knob; pass "
                         "use_sparse=True to gate at granularity "
                         f"{gate_granularity}")
    if use_events and use_sparse:
        raise ValueError("use_events (event-list execution) and use_sparse "
                         "(row-block gating) are mutually exclusive")
    if use_events and not use_pallas:
        raise ValueError("use_events is the device event-list path; the "
                         "host executor shards at the pipeline level "
                         "(core.pipeline._host_events_sharded)")
    T, B = int(spikes.shape[0]), int(spikes.shape[1])
    n_data, n_model = mesh_axis_extents(mesh)
    if v_init is None:
        v_init = [jnp.zeros((B, w.shape[1]), jnp.int32) for w in ws]
    rasters, v_finals, skips = _fused_snn_net_mesh_core(
        spikes, list(ws), list(v_init), mesh=mesh, thresholds=thresholds,
        leaks=leaks, neuron=neuron, clamp_mode=clamp_mode, block_b=block_b,
        use_pallas=use_pallas, interpret=interpret,
        emit_rasters=emit_rasters, use_sparse=use_sparse,
        gate_granularity=gate_granularity, readout=readout,
        use_events=use_events, event_crossover=event_crossover)
    if use_events:
        import numpy as np

        from repro.kernels.fused_snn_net.events import EventStats
        if n_model == 1:
            row_events = tuple(np.asarray(rc, np.int64).sum(axis=0)
                               for rc in skips["row_events"])
            fallbacks = tuple(int(c) for c in
                              np.asarray(skips["dense_fallbacks"],
                                         np.int64).sum(axis=0))
        else:
            row_events = tuple(np.asarray(c, np.int64) for c in skips)
            fallbacks = ()       # no dense-fallback machinery on this path
        skips = EventStats(row_events=row_events, frames=T * B,
                           dense_fallbacks=fallbacks)
    return rasters, v_finals, skips


def _fused_snn_net_ref(spikes, ws, thresholds, leaks, neuron, clamp_mode,
                       emit_rasters, use_sparse=False, readout=True,
                       gate_granularity=1, v_init=None):
    """Pure-jnp oracle: the word-level ISA scanned over the network. In
    ``use_sparse`` mode the AccW2V matmul of each lane block (the whole
    layer at granularity 1) is wrapped in a `lax.cond` on whole-batch
    occupancy (the reference's tile = the whole batch) and per-(layer,
    block) skipped-step counts ride along. Block partials accumulate
    unclamped; one clamp after the last block matches the dense
    clamp-after-accumulate bit for bit (clamp_v is idempotent when every
    block is silent)."""
    from repro.core.isa import layer_timestep_int, neuron_dynamics_int
    from repro.core.quant import clamp_v
    B = spikes.shape[1]
    spiking_ws = ws[:-1] if readout else ws
    blocks = [_ref_blocks(w.shape[0], gate_granularity) for w in ws]

    def gated_acc(v, w, cur, spans, clamp):
        skipped = []
        for lo, hi in spans:
            blk = cur[:, lo:hi]
            occupied = jnp.sum(blk) > 0
            v = jax.lax.cond(
                occupied,
                lambda v, blk=blk, lo=lo, hi=hi:
                    v + blk @ w[lo:hi].astype(jnp.int32),
                lambda v: v, v)
            skipped.append(jnp.logical_not(occupied).astype(jnp.int32))
        v = clamp_v(v, clamp_mode) if clamp else v
        return v, jnp.stack(skipped)

    def step(carry, s_t):
        vs, skips = list(carry[0]), list(carry[1])
        cur = s_t.astype(jnp.int32)
        rasters = []
        skipped = []
        for i, w in enumerate(spiking_ws):
            if use_sparse:
                v, sk = gated_acc(vs[i], w, cur, blocks[i], clamp=True)
                skipped.append(sk)
                vs[i], cur = neuron_dynamics_int(
                    v, neuron=neuron, threshold=jnp.int32(thresholds[i]),
                    leak=jnp.int32(leaks[i]), reset=jnp.int32(0),
                    clamp_mode=clamp_mode)
            else:
                vs[i], cur = layer_timestep_int(
                    vs[i], w, cur, neuron=neuron,
                    threshold=jnp.int32(thresholds[i]),
                    leak=jnp.int32(leaks[i]),
                    reset=jnp.int32(0), clamp_mode=clamp_mode)
            rasters.append(cur.astype(jnp.int8))
        if readout:
            if use_sparse:
                vs[-1], sk = gated_acc(vs[-1], ws[-1], cur, blocks[-1],
                                       clamp=False)
                skipped.append(sk)
            else:
                vs[-1] = vs[-1] + cur @ ws[-1].astype(jnp.int32)
        if use_sparse:
            skips = [s + d for s, d in zip(skips, skipped)]
        return (tuple(vs), tuple(skips)), tuple(rasters)

    if v_init is not None:
        vs0 = tuple(v.astype(jnp.int32) for v in v_init)
    else:
        vs0 = tuple(jnp.zeros((B, w.shape[1]), jnp.int32) for w in ws)
    skips0 = tuple(jnp.zeros((len(b),), jnp.int32) for b in blocks)
    (vs, skips), rasters = jax.lax.scan(step, (vs0, skips0),
                                        spikes.astype(jnp.int8))
    if not use_sparse:
        out_skips = None
    elif gate_granularity == 1:        # legacy (1, n_layers) layout
        out_skips = jnp.stack([s[0] for s in skips])[None]
    else:
        out_skips = [s[None] for s in skips]
    return ((list(rasters) if emit_rasters else []), list(vs), out_skips)
