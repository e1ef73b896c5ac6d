"""Pallas TPU kernel: the ENTIRE SNN stack fused into one kernel.

`fused_snn_step` realizes IMPULSE's W/V fusion within one layer; this kernel
is the network-level analogue of the paper's fused array. One `pallas_call`
executes encoder-spikes -> every spiking FC -> accumulate readout for the
whole `T_total` presentation:

  * every layer's V tile is a VMEM *scratch* buffer that persists across the
    in-kernel timestep loop — membrane potentials never visit HBM at all
    (not even once per layer as in per-layer dispatch);
  * inter-layer spike activations are kernel-local values: layer i's fired
    vector feeds layer i+1's MXU matmul in the same loop iteration, so the
    T*B*N spike traffic between layers also never touches HBM;
  * weights for ALL layers are loaded HBM->VMEM once per batch tile and
    stay resident (the IMDB stack is ~33 KB of int8 — V_MEM-sized).

HBM traffic: per-layer dispatch moves O(L*T*B*N) spike bytes + O(L*B*N) V
bytes; fused-net moves O(T*B*N_in) input + O(B*N) final V. The optional
raster outputs (`emit_rasters`, needed for event/energy accounting) add the
output spike stores back — serving uses emit_rasters=False.

Event-gated mode (``sparse=True``) is the execution-side realization of the
paper's sparsity claim (Fig. 11): per (timestep, layer, batch-tile) the
kernel reduces the in-VMEM int8 spike tile to occupancy counts and wraps
the MXU matmul + V accumulate in `@pl.when(count > 0)` — an all-silent tile
issues zero AccW2V work, exactly like silent input rows issue no AccW2V
cycles on silicon. ``granularity`` selects the gate's sub-tile resolution:
at 1 a layer's whole input tile is one gate (the original tile gate); at
G in {2, 4, 8} each 128-lane macro-row tile splits into G row blocks of
128/G lanes and every block's *partial* matmul is predicated independently.
Partial sums accumulate unclamped into the same V scratch and the 11-bit
clamp is applied once after the last block — exactly the dense kernel's
single clamp-after-accumulate, so row-block gating stays bit-identical in
both clamp modes (intermediate saturation would not commute). The *neuron
update* (leak / SpikeCheck / reset) still runs every timestep: LIF leaks
and RMP can re-fire with zero input, and the macro's update sequence is
unconditional too (the `u` term in the Fig. 11b EDP model). Padded
lanes/rows are zero-masked before occupancy is taken (their junk spikes
multiply zero weight rows, so masking changes no visible output but keeps
silence detection on logical lanes); row blocks made entirely of padding
are not emitted at all (a masked block contributes zero) and are excluded
from the skip count. Skipped-matmul counts per (batch-tile, gate site)
come back as an extra output — `skip_layout` defines the column map.

Grid: (B // block_b,). The network dimension is NOT gridded: layer widths
are padded to the 128-lane MXU tile and the whole stack fits VMEM (the
macro's 128x12 geometry guarantees layer tiles are tiny). The timestep loop
is an in-kernel fori_loop — a grid dimension over T would evict V.

Streaming entry (``v_init``): the V scratch tiles normally initialize to
zero — one call owns the whole presentation. For streaming execution
(core/pipeline `stream_step`, serve/snn_engine) the caller passes the
per-layer membrane state carried from the previous tick as extra inputs;
the kernel seeds its VMEM V tiles from them and runs the same loop for a
one-timestep (or any chunk-length) call. Because integer accumulation is
exact, chunked calls that thread V compose bit-identically with one full-T
call — the macro's "V_MEM never leaves the array" claim, restated at the
call boundary as "V leaves VMEM only between ticks".

Event-list mode (``events=True``) is the fully event-driven execution the
gated modes approximate: instead of predicating dense matmuls on tile /
row-block occupancy, each (timestep, layer, example) int8 spike frame is
*compacted* in VMEM and AccW2V becomes a gather-matvec over the active
rows only — executed work proportional to events at every sparsity
structure, including the iid-Bernoulli rasters that defeat tile and block
gates entirely (an 85%-sparse iid frame runs 15% of its row work here, vs
~100% under any block gate).

  Compaction layout: the inclusive prefix sum ``pos = cumsum(frame)`` over
  the padded n_in lanes IS the fixed-capacity active-row index list —
  entry p (0-based) of the list is the unique lane r with ``pos[r] == p+1``
  and ``frame[r] == 1``, decoded with a one-hot lane match; the list's
  count is the frame's event total and its capacity is the padded n_in
  (so no frame can overflow it). The prefix sum of the whole tile is one
  exact int8 matmul against the upper-triangular ones matrix (Mosaic
  lowers no cumsum). The occupancy-based early-out is the gather loop's
  dynamic trip count: a `fori_loop(0, count)` issues exactly ``count``
  weight-row gathers and rank-1 accumulates into the V scratch — an
  all-silent frame issues zero AccW2V work. Each gather loads the
  ``GATHER_ROWS``-row aligned block of the VMEM-resident int8 weight tile
  that holds the row and selects the row with a one-hot mask: a dynamic
  int8 row load must start on a packed (32, 128) tile boundary.

  Dense fallback (``event_crossover``): gathering beats the MXU only while
  frames are sparse. Per (timestep, layer, batch-tile), when the tile's
  event count exceeds ``event_crossover`` of its (block_b x logical-width)
  capacity, the whole tile falls back to the existing dense matmul under
  `@pl.when` — the same single-clamp-after-accumulate dense path, so the
  fallback is bit-identical by construction (integer addition commutes:
  gathering rows in ascending-index order equals the dense row sum
  exactly). Fallback trips are counted per layer in an extra output.

  Accounting: the kernel reduces every masked input frame to per-row event
  counts (an extra (tiles, n_in) output); summed over tiles these equal
  `events.EventStats.row_events` EXACTLY — the word-level per-row skip
  contract `ref_events` defines — independent of which execution path ran.
  Padded lanes and padded batch rows are zero-masked before compaction
  (junk spikes would gather zero weight rows — harmless numerically, but
  they would burn gather iterations and corrupt the event counts).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import clamp_v, spike_compare

LANE = 128              # MXU lane tile == the macro's 128-row fan-in
GATE_GRANULARITIES = (1, 2, 4, 8)
MAX_SKIP_COLS = 1024    # gate-site columns the skip output will carry
GATHER_ROWS = 32        # int8 sublane tile: rows per aligned gather load


def skip_layout(in_widths: tuple, granularity: int
                ) -> tuple[tuple, tuple, int]:
    """Column map of the skip-count output: gate site (layer i, block g)
    reports in column ``offsets[i] + g``.

    ``in_widths``: per-layer *logical* (pre-padding) input widths. At
    granularity 1 every layer is one gate (whole input tile — the legacy
    layout, one column per layer); at G > 1 each layer has
    ceil(width / (128/G)) counted blocks — blocks living entirely in lane
    padding are never emitted, so they hold no column. Returns
    (n_cols per layer, column offsets per layer, padded lane width of the
    output). Raises a ValueError when the layout exceeds ``MAX_SKIP_COLS``
    (the former fixed 128-lane output silently truncated instead)."""
    if granularity not in GATE_GRANULARITIES:
        raise ValueError(f"gate granularity must be one of "
                         f"{GATE_GRANULARITIES}, got {granularity}")
    if granularity == 1:
        n_cols = tuple(1 for _ in in_widths)
    else:
        bw = LANE // granularity
        n_cols = tuple(-(-w // bw) for w in in_widths)
    total = sum(n_cols)
    if total > MAX_SKIP_COLS:
        raise ValueError(
            f"skip-count layout needs {total} gate columns "
            f"({len(in_widths)} layers at granularity {granularity}) but the "
            f"output carries at most MAX_SKIP_COLS={MAX_SKIP_COLS}; lower "
            "the granularity or split the stack")
    offsets, off = [], 0
    for n in n_cols:
        offsets.append(off)
        off += n
    lanes = max(LANE, -(-total // LANE) * LANE)
    return n_cols, tuple(offsets), lanes


def _net_kernel(*refs, n_spiking: int, has_readout: bool, neuron: str,
                clamp_mode: str, timesteps: int, emit_rasters: bool,
                sparse: bool, granularity: int, logical_widths: tuple,
                batch_logical: int, block_b: int, has_v_init: bool,
                events: bool = False, dense_thresholds: tuple = ()):
    """Ref layout (inputs, outputs, scratch):
      inputs : spikes_ref (T, Bt, N0p) int8; w_refs[i] (Nip, Nop) int8 for
               the n_spiking FCs (+ readout when has_readout); params_ref
               (n_spiking, 2) int32 rows of [threshold, leak];
               v_init_refs[i] (Bt, Nop) int32 per layer (only when
               has_v_init) — membrane state carried in from a previous
               streaming tick;
      outputs: raster_refs[i] (T, Bt, Nop) int8 per spiking FC (only when
               emit_rasters); v_out_refs[i] (Bt, Nop) int32 per layer
               (readout last); skip_ref (1, skip_lanes) int32 (only when
               sparse) — gate site (layer i, block g) counts skipped
               matmuls in column skip_layout offsets[i] + g; in events
               mode instead row_refs[i] (1, Nip) int32 per layer — this
               tile's per-input-row event counts — then fallback_ref
               (1, LANE) int32, column i counting the timesteps layer i
               took the dense-crossover fallback;
      scratch: v_refs[i] (Bt, Nop) int32 per layer — the fused V_MEM tiles.

    ``has_readout=False`` runs an all-spiking stack (no accumulate-only
    tail) — the shape conv layers lowered onto im2col patch rasters take.
    ``events`` selects the compacted event-list execution of AccW2V (module
    docs); ``dense_thresholds[i]`` is the per-layer tile event count above
    which the dense fallback fires.
    """
    n_w = n_spiking + (1 if has_readout else 0)
    spikes_ref = refs[0]
    w_refs = refs[1:1 + n_w]
    params_ref = refs[1 + n_w]
    pos = 2 + n_w
    v_init_refs = refs[pos:pos + n_w] if has_v_init else ()
    pos += n_w if has_v_init else 0
    raster_refs = refs[pos:pos + n_spiking] if emit_rasters else ()
    pos += n_spiking if emit_rasters else 0
    v_out_refs = refs[pos:pos + n_w]
    pos += n_w
    skip_ref = refs[pos] if sparse else None
    pos += 1 if sparse else 0
    row_refs = refs[pos:pos + n_w] if events else ()
    pos += n_w if events else 0
    fallback_ref = refs[pos] if events else None
    pos += 1 if events else 0
    v_refs = refs[pos:]

    ws = [w_refs[i][...] for i in range(n_w)]     # VMEM-resident weights
    for i, vref in enumerate(v_refs):
        vref[...] = v_init_refs[i][...] if has_v_init else jnp.zeros_like(vref)
    if sparse or events:
        b0 = pl.program_id(0) * block_b
    if sparse:
        skip_ref[...] = jnp.zeros_like(skip_ref)
        n_cols, col_off, skip_lanes = skip_layout(
            logical_widths[:n_w], granularity)
    if events:
        for rref in row_refs:
            rref[...] = jnp.zeros_like(rref)
        fallback_ref[...] = jnp.zeros_like(fallback_ref)

    def mask_pad(x, n_logical):
        """Zero padded lanes (>= n_logical) and padded batch rows. Padded
        positions carry junk spikes whose downstream weight rows are zero —
        masking changes no visible output, but keeps the occupancy test on
        logical events only."""
        bt, n = x.shape
        lane_ok = jax.lax.broadcasted_iota(jnp.int32, (bt, n), 1) < n_logical
        row_ok = (jax.lax.broadcasted_iota(jnp.int32, (bt, n), 0) + b0
                  ) < batch_logical
        return jnp.where(lane_ok & row_ok, x, 0)

    def accumulate_events(i, cur):
        """Event-list AccW2V (module docs): compact each example's masked
        frame to (cumsum position map, count) and gather-accumulate the
        active weight rows with a dynamic-trip-count fori_loop — work
        proportional to events. Above the dense-crossover event count the
        whole tile falls back to one dense matmul. Both paths add to V
        *unclamped* through the ref (predicated writes must go through
        refs); one clamp after the accumulate — outside the `@pl.when`s —
        equals the dense single clamp-after-accumulate bit for bit. The
        per-row event counters accumulate unconditionally, so the
        accounting contract (== ref_events' EventStats) is independent of
        which path executed."""
        n_in_p = ws[i].shape[0]
        cur32 = cur.astype(jnp.int32)
        row_refs[i][...] = row_refs[i][...] + jnp.sum(cur32, axis=0,
                                                      keepdims=True)
        total = jnp.sum(cur32)
        go_dense = total > dense_thresholds[i]

        @pl.when(go_dense)
        def _dense(i=i, cur=cur):
            acc = jax.lax.dot_general(cur, ws[i], (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.int32)
            v_refs[i][...] = v_refs[i][...] + acc
            lane = jax.lax.broadcasted_iota(jnp.int32,
                                            fallback_ref.shape, 1)
            fallback_ref[...] = fallback_ref[...] + jnp.where(lane == i, 1, 0)

        @pl.when(jnp.logical_not(go_dense))
        def _gather(i=i, cur=cur, cur32=cur32, n_in_p=n_in_p):
            n_out_p = ws[i].shape[1]
            lanes = jax.lax.broadcasted_iota(jnp.int32, (1, n_in_p), 1)
            # inclusive prefix sum of every row at once, as an exact int8
            # matmul with the upper-triangular ones matrix (Mosaic has no
            # cumsum lowering): pos_all[b, k] = sum_{j <= k} cur[b, j]
            tri = (jax.lax.broadcasted_iota(jnp.int32, (n_in_p, n_in_p), 0)
                   <= jax.lax.broadcasted_iota(jnp.int32, (n_in_p, n_in_p),
                                               1)).astype(jnp.int8)
            pos_all = jax.lax.dot_general(cur, tri, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.int32)
            sub_iota = jax.lax.broadcasted_iota(jnp.int32,
                                                (GATHER_ROWS, n_out_p), 0)
            for b in range(block_b):
                s = cur32[b:b + 1, :]                    # (1, Nip) 0/1
                pos_map = pos_all[b:b + 1, :]            # the compacted list
                count = jnp.sum(s)

                def ev_body(p, acc, s=s, pos_map=pos_map, lanes=lanes, i=i):
                    hit = (pos_map == p + 1) & (s > 0)   # one-hot lane match
                    idx = jnp.sum(jnp.where(hit, lanes, 0))
                    # gather one W row: load the aligned int8 row block that
                    # holds it (single-row dynamic int8 loads must start on
                    # a packed-tile boundary), then select the row
                    base = pl.multiple_of(
                        jax.lax.div(idx, GATHER_ROWS) * GATHER_ROWS,
                        GATHER_ROWS)
                    blk = w_refs[i][pl.ds(base, GATHER_ROWS), :]
                    row = jnp.sum(jnp.where(sub_iota == idx - base,
                                            blk.astype(jnp.int32), 0),
                                  axis=0, keepdims=True)
                    return acc + row

                acc_b = jax.lax.fori_loop(
                    0, count, ev_body,
                    jnp.zeros((1, n_out_p), jnp.int32))
                v_refs[i][b:b + 1, :] = v_refs[i][b:b + 1, :] + acc_b

        v = v_refs[i][...]
        if i < n_spiking:
            v = clamp_v(v, clamp_mode)
        v_refs[i][...] = v
        return v

    def accumulate(i, cur):
        """AccW2V for a whole layer: binary matmul on the MXU. Returns the
        accumulated (clamped; readout unclamped) V value. Dense mode is
        pure compute — the caller stores V once after the neuron update.
        Sparse mode must go through the ref (only ref writes can be
        predicated): each of the layer's row blocks (one at granularity 1)
        issues its partial matmul under `@pl.when(block occupied)`; silent
        blocks skip the MXU work entirely and bump their skip column.
        Partials add to V *unclamped*; one clamp after the last block
        equals the dense single clamp-after-accumulate bit for bit (and a
        fully silent layer reduces to clamp_v(v), which is idempotent)."""
        if events:
            return accumulate_events(i, cur)
        if not sparse:
            acc = jax.lax.dot_general(cur, ws[i], (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.int32)
            v = v_refs[i][...] + acc
            return clamp_v(v, clamp_mode) if i < n_spiking else v
        bw = ws[i].shape[0] if granularity == 1 else LANE // granularity
        upd = jnp.zeros_like(skip_ref)
        lane_iota = jax.lax.broadcasted_iota(jnp.int32, (1, skip_lanes), 1)
        for g in range(n_cols[i]):     # counted blocks cover logical lanes
            blk = cur[:, g * bw:(g + 1) * bw]
            occupied = jnp.sum(blk.astype(jnp.int32)) > 0

            @pl.when(occupied)
            def _do(i=i, g=g, blk=blk):
                acc = jax.lax.dot_general(
                    blk, ws[i][g * bw:(g + 1) * bw, :],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                v_refs[i][...] = v_refs[i][...] + acc

            upd = upd + jnp.where(lane_iota == col_off[i] + g,
                                  jnp.logical_not(occupied).astype(jnp.int32),
                                  0)
        skip_ref[...] = skip_ref[...] + upd
        v = v_refs[i][...]
        if i < n_spiking:
            v = clamp_v(v, clamp_mode)
        v_refs[i][...] = v
        return v

    def body(t, carry):
        cur = spikes_ref[t]                                    # (Bt, N0p) int8
        if sparse or events:
            cur = mask_pad(cur, logical_widths[0])
        for i in range(n_spiking):
            v = accumulate(i, cur)
            if neuron == "lif":                                # AccV2V(-leak)
                v = clamp_v(v - params_ref[i, 1], clamp_mode)
            fired = spike_compare(v, params_ref[i, 0], clamp_mode)  # SpikeCheck
            if neuron == "rmp":                                # AccV2V(-th), gated
                v = clamp_v(jnp.where(fired, v - params_ref[i, 0], v),
                            clamp_mode)
            else:                                              # ResetV
                v = jnp.where(fired, 0, v)
            v_refs[i][...] = v
            cur = fired.astype(jnp.int8)                       # stays in VMEM
            if sparse or events:
                cur = mask_pad(cur, logical_widths[i + 1])
            if emit_rasters:
                raster_refs[i][pl.ds(t, 1)] = cur[None]
        if has_readout:
            # readout: wide int32 accumulate, no 11b clamp
            v_out = accumulate(n_spiking, cur)
            if not sparse and not events:   # gated modes already wrote the ref
                v_refs[n_spiking][...] = v_out
        return carry

    jax.lax.fori_loop(0, timesteps, body, 0)
    for i in range(n_w):
        v_out_refs[i][...] = v_refs[i][...]


def fused_snn_net_pallas(spikes: jax.Array, ws: list, params: jax.Array, *,
                         neuron: str, clamp_mode: str, block_b: int,
                         emit_rasters: bool, interpret: bool = False,
                         sparse: bool = False, granularity: int = 1,
                         logical_widths: tuple = (),
                         batch_logical: int = 0, has_readout: bool = True,
                         v_init: list = None, events: bool = False,
                         event_crossover: float = 1.0):
    """Dispatch the network kernel. Shapes must be pre-padded: spikes
    (T, B, N0p) int8 with B % block_b == 0; ws[i] (Nip, Nop) int8 with every
    dim a 128 multiple and Nip == previous Nop; params (n_spiking, 2) int32.
    ``has_readout=False`` treats every layer in ws as spiking (conv stacks
    lowered to patch rasters run this way — no accumulate-only tail).

    ``sparse`` selects the event-gated kernel; it needs ``logical_widths``
    (the pre-padding width of the input raster and of every layer's output,
    len(ws)+1 entries) and ``batch_logical`` (pre-padding B) to mask padding
    junk out of the occupancy test. ``granularity`` sets the gate's
    sub-tile resolution (`skip_layout`): 1 gates whole input tiles, G in
    {2, 4, 8} gates row blocks of 128/G lanes independently.

    ``v_init`` (streaming entry): per-layer (B, Nop) int32 membrane state,
    pre-padded like ws, seeding the VMEM V scratch instead of zeros — the
    carried state of a `stream_step` tick.

    ``events`` selects the compacted event-list execution of AccW2V (module
    docs) — mutually exclusive with ``sparse``; needs the same
    ``logical_widths`` / ``batch_logical`` masking inputs. A tile whose
    event count exceeds ``event_crossover`` of its block_b x logical-width
    capacity takes the dense fallback (1.0 can never trip — strict >; 0.0
    always trips).

    Returns (rasters, v_finals, skips): rasters — list of (T, B, Nop) int8
    per spiking layer ([] when emit_rasters=False); v_finals — list of
    (B, Nop) int32 per layer, readout last; skips — (B // block_b, n_sites)
    int32 skipped-matmul counts per (batch tile, gate site) in sparse mode
    (site columns per `skip_layout`; n_sites == len(ws) at granularity 1);
    in events mode the pair (row_counts, fallbacks) with row_counts[i]
    (B // block_b, Nip) int32 per-input-row event counts per tile and
    fallbacks (B // block_b, len(ws)) int32 dense-fallback trip counts;
    None otherwise.
    """
    T, B, _ = spikes.shape
    n_spiking = len(ws) - 1 if has_readout else len(ws)
    grid = (B // block_b,)
    if sparse and events:
        raise ValueError("sparse (row-block gating) and events (event-list "
                         "execution) are mutually exclusive kernel modes")
    if (sparse or events) and len(logical_widths) != len(ws) + 1:
        raise ValueError("sparse/events mode needs len(ws)+1 logical widths, "
                         f"got {len(logical_widths)} for {len(ws)} layers")
    if sparse:
        n_cols, _, skip_lanes = skip_layout(tuple(logical_widths[:len(ws)]),
                                            granularity)
    dense_thresholds = ()
    if events:
        if len(ws) > LANE:
            raise ValueError(f"events mode carries one fallback column per "
                             f"layer in a {LANE}-lane output; got {len(ws)} "
                             "layers")
        # tile event capacity is block_b x logical input width; strict >
        # means crossover 1.0 never trips and 0.0 always does (count >= 0)
        dense_thresholds = tuple(
            int(event_crossover * block_b * logical_widths[i]) if
            event_crossover > 0.0 else -1
            for i in range(len(ws)))
    kernel = functools.partial(
        _net_kernel, n_spiking=n_spiking, has_readout=has_readout,
        neuron=neuron, clamp_mode=clamp_mode, timesteps=T,
        emit_rasters=emit_rasters, sparse=sparse, granularity=granularity,
        logical_widths=tuple(logical_widths),
        batch_logical=batch_logical, block_b=block_b,
        has_v_init=v_init is not None, events=events,
        dense_thresholds=dense_thresholds)

    in_specs = [pl.BlockSpec((T, block_b, spikes.shape[2]),
                             lambda b: (0, b, 0))]
    in_specs += [pl.BlockSpec(w.shape, lambda b: (0, 0)) for w in ws]
    in_specs += [pl.BlockSpec(params.shape, lambda b: (0, 0))]
    if v_init is not None:
        if len(v_init) != len(ws):
            raise ValueError(f"v_init needs one (B, Nop) state per layer "
                             f"({len(ws)}), got {len(v_init)}")
        in_specs += [pl.BlockSpec((block_b, w.shape[1]), lambda b: (b, 0))
                     for w in ws]

    out_specs, out_shape = [], []
    if emit_rasters:
        for w in ws[:n_spiking]:
            out_specs.append(pl.BlockSpec((T, block_b, w.shape[1]),
                                          lambda b: (0, b, 0)))
            out_shape.append(jax.ShapeDtypeStruct((T, B, w.shape[1]), jnp.int8))
    for w in ws:
        out_specs.append(pl.BlockSpec((block_b, w.shape[1]), lambda b: (b, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, w.shape[1]), jnp.int32))
    # per-tile counter rows: (tiles, 1, X) arrays in (1, X) blocks (the
    # tile axis squeezed) — a (1, X) block of a (tiles, X) array breaks the
    # TPU tiling rule (second-minor block dim a multiple of 8 or the whole
    # dim) as soon as the grid has more than one tile
    def counter(width):
        out_specs.append(pl.BlockSpec((pl.Squeezed(), 1, width),
                                      lambda b: (b, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B // block_b, 1, width),
                                              jnp.int32))

    if sparse:
        counter(skip_lanes)
    if events:
        for w in ws:
            counter(w.shape[0])
        counter(LANE)

    scratch = [pltpu.VMEM((block_b, w.shape[1]), jnp.int32) for w in ws]

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(spikes, *ws, params, *(v_init if v_init is not None else ()))
    outs = list(outs)
    skips = None
    if sparse:
        skips = outs.pop()[:, 0, :sum(n_cols)]
    elif events:
        fallbacks = outs.pop()[:, 0, :len(ws)]
        row_counts = [rc[:, 0] for rc in outs[-len(ws):]]
        del outs[-len(ws):]
        skips = (row_counts, fallbacks)
    rasters = outs[:n_spiking] if emit_rasters else []
    v_finals = outs[n_spiking:] if emit_rasters else outs
    return rasters, v_finals, skips
