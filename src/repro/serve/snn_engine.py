"""Streaming SNN serving engine: continuous batching over persistent
membrane-potential slots.

The LM engine's per-slot state is a KV-cache lane; IMPULSE's per-slot state
is the membrane-potential tree — V_MEM fused next to the weights is exactly
the state that makes streaming serving natural on this architecture. This
engine mirrors `ServeEngine`:

  * a paged V-slot pool: ``pages`` pages of ``batch_slots`` lanes, each
    page owning one `pipeline.StreamState` tree. A fresh request is
    admitted into any free lane across pages (admit-by-lane-copy: its zero
    state is scattered into the lane along each leaf's structurally-
    determined batch axis — the same B-vs-B+1 probe the LM engine uses on
    its cache tree), and each engine tick dispatches only the occupied
    pages;
  * K-frame megasteps: every dispatch advances a page K frames via
    `pipeline.stream_megastep`, K picked per tick and page from a short
    ladder of block lengths compiled before serving (the smallest that
    covers the page's longest remaining budget), unless ``megastep`` pins
    it. The next K frames of each lane's stream are pre-staged into one
    (K, B, *in_shape) host block, lanes whose stream runs out inside the
    block integrate zero current
    (active-mask contract), and requests that finish mid-block are
    finalized from the block's exact per-tick readout trajectory. Batch
    lanes never interact (every op is per-lane), so each request's output
    is bit-identical to serving it alone — at any K;
  * double-buffered upload (``double_buffer=True``): after dispatching
    tick t's block, tick t+1's block is staged host-side and shipped with
    `jax.device_put` while the device computes; the staged block is keyed
    by K and per-lane (request, cursor) metadata and rebuilt on any
    mismatch (early exit, admission, eviction), so speculation never changes
    results;
  * admission control: requests carry an ``arrival_tick`` on the engine's
    frame clock (``clock`` advances K per engine tick, idle ticks
    included, and a block never carries it past a queued head's arrival)
    and are not admitted before it — a seeded Poisson arrival process is
    just a sorted submission with exponential gaps;
  * per-slot stop conditions: fixed tick budget (the frame sequence runs
    out) or readout-threshold early exit (|logit| confidence);
  * per-slot event accounting: input events per macro-stack layer row are
    accumulated from each block's rasters — credited only up to the tick
    the request actually served — and finalize into a per-request
    `pipeline.SparsityReport` exactly like the batch path's reports
    (tests close the loop against isolated runs).

Event-gated ticks come from the backend choice: ``pallas_sparse`` /
``int_ref(use_sparse=True)`` skip silent-tile work inside the tick,
``ref_events`` executes the spike-list upper bound on the host, and
``pallas_events`` executes it on device (VMEM compaction + gather-matvec).
The per-slot row-skip accounting is backend-independent (it reads the
rasters); the event backends additionally feed a pooled *device ledger*
(`device_event_stats`) — the counters the executing kernel itself reports,
over ALL lanes of every dispatched page. A vacated lane is re-seeded with
fresh zero state at evict time, so idle lanes are silent (zero current
into zero V emits no spikes at any depth) and the ledger's row-event
counters close against the summed per-request tallies on partially-
occupied engines too. The two residual gaps are ghost ticks (an early-exit
request's lane keeps integrating its remaining staged frames until the
block ends; the request's own accounting discards them, the device ledger
cannot) and LIF wrap-mode leak wraparound on very long idle stretches —
which is why per-request accounting stays raster-based.

Every tick is annotated for `jax.profiler`: one ``snn_engine.step`` step
span per `step` call, and phase spans inside it (``admit``, ``stage``,
``dispatch`` with its K, ``stage_next``, ``retire`` with its ``fetch``,
``account`` and ``evict`` children; docs/serving.md "Tracing"). With no trace being
recorded a span costs about a microsecond and changes no output.
"""
from __future__ import annotations

import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.core import pipeline
from repro.core.pipeline import SNNProgram, SparsityReport
from repro.serve.engine import SlotEngine, lane_scatter, probe_batch_axes


class ReportUnavailable(RuntimeError):
    """`aggregate_report` has nothing to aggregate: event tracking is
    disabled on this engine, or no request has finished yet. Named so
    operators don't mistake it for a report-geometry mismatch inside
    `merge_reports`."""


@dataclass
class SNNRequest:
    rid: int
    frames: np.ndarray                    # (T, *in_shape) input currents
    max_ticks: Optional[int] = None       # default: len(frames)
    stop_threshold: Optional[float] = None  # early exit when max|logit| >= thr
    arrival_tick: int = 0                 # earliest admission, engine clock
    # -- filled at finish ----------------------------------------------------
    logits: Optional[np.ndarray] = None
    v_out: Optional[np.ndarray] = None
    ticks: int = 0
    finish_clock: Optional[int] = None    # engine clock at the finish tick
    report: Optional[SparsityReport] = None

    @property
    def latency_ticks(self) -> Optional[int]:
        """Frame-clock request latency: queueing + service, arrival to
        finish (None until finished)."""
        if self.finish_clock is None:
            return None
        return self.finish_clock - self.arrival_tick


@dataclass
class _Slot:
    req: Optional[SNNRequest] = None
    cursor: int = 0                       # next frame index to present
    ticks: int = 0
    serial: int = -1                      # admission sequence number
    row_events: list = field(default_factory=list)
    # recurrent layers: events on their W_rec rows so far, and the spike
    # count of the last served tick (the W_rec input of the next one)
    rec_events: list = field(default_factory=list)
    rec_last: list = field(default_factory=list)


class _ArrivalQueue:
    """Submission-order FIFO with head peek: arrival-gated admission needs
    to inspect the head's ``arrival_tick`` without consuming it. Exposes
    the `queue.Queue` surface `SlotEngine.run_until_drained` relies on
    (``empty``/``qsize``) plus ``put``/``get``/``peek``."""

    def __init__(self):
        self._q: deque = deque()

    def put(self, item) -> None:
        self._q.append(item)

    def get(self):
        return self._q.popleft()

    def peek(self):
        return self._q[0]

    def empty(self) -> bool:
        return not self._q

    def qsize(self) -> int:
        return len(self._q)


def merge_reports(reports: list) -> SparsityReport:
    """Pool per-request reports (batch=1 each) into one workload report:
    events/row_events/frame counts add; the merged report's instruction
    counts equal the sum of the parts (counting is linear in events and
    frames), so engine-level EDP accounting stays exact."""
    if not reports:
        raise ValueError("merge_reports needs at least one report")
    head = reports[0]
    for r in reports[1:]:
        if (r.n_in, r.n_out, r.neurons, r.n_rec) != (
                head.n_in, head.n_out, head.neurons, head.n_rec):
            raise ValueError("cannot merge reports of different programs")
    return SparsityReport(
        n_rec=head.n_rec,
        rec_events=None if head.n_rec is None else tuple(
            sum(r.rec_events[i] for r in reports)
            for i in range(len(head.n_in))),
        n_in=head.n_in, n_out=head.n_out, neurons=head.neurons,
        events=tuple(sum(r.events[i] for r in reports)
                     for i in range(len(head.n_in))),
        frames=sum(r.frames for r in reports),
        timesteps=sum(r.timesteps for r in reports),
        batch=1,
        layer_frames=tuple(sum(r.frames_by_layer[i] for r in reports)
                           for i in range(len(head.n_in))),
        row_events=tuple(
            sum(np.asarray(r.row_events[i], np.int64) for r in reports)
            for i in range(len(head.n_in))))


# (id(program), backend, kw, rasters, mesh) -> (program ref, jitted core,
# {(K, lanes): compiled executable})
_MEGASTEP_JIT = {}
# the K ladder's ratio: rungs 1, 8, 64, ..., so below the top rung a page
# runs fewer than 8x the frames its longest lane needs, at one compile per
# rung
_RUNG_STEP = 8
# backends whose dispatch runs eagerly (no jitted core): the event-list
# executors fold their ledgers to host numpy inside the op wrapper, and the
# float backend's QAT ops stay eager for bit-identity with stream_step
_EAGER_BACKENDS = ("float", "ref_events", "pallas_events")


def _pack_lanes(out: pipeline.MegastepOut) -> jax.Array:
    """The per-lane results the host reads after a megastep, as one
    lane-major int32 array of shape (B, 2·K·n_out + 1): the block's
    logit trajectory (bit-cast from float32, exact), its readout V
    trajectory, then frames consumed. The lane axis stays first, so a
    mesh engine's packed output is sharded over ``data`` like the state.
    `_unpack_lanes` is the host-side inverse."""
    for name, a, dtype in (("logits_traj", out.logits_traj, jnp.float32),
                           ("v_out_traj", out.v_out_traj, jnp.int32),
                           ("frames_consumed", out.frames_consumed,
                            jnp.int32)):
        if a.dtype != dtype:    # refuse, never convert: the packing is exact
            raise TypeError(f"{name} is {a.dtype}; packing needs {dtype}")
    k, b, n_out = out.v_out_traj.shape

    def lane_major(a):
        return jnp.moveaxis(a, 1, 0).reshape(b, k * n_out)
    return jnp.concatenate(
        [lax.bitcast_convert_type(lane_major(out.logits_traj), jnp.int32),
         lane_major(out.v_out_traj), out.frames_consumed[:, None]], axis=1)


def _unpack_lanes(host: np.ndarray, k: int, n_out: int) -> tuple:
    """`_pack_lanes`'s host array -> ((K, B, n_out) float32 logits,
    (K, B, n_out) int32 readout V, (B,) frames consumed), as views."""
    b, kn = host.shape[0], k * n_out
    logits = host[:, :kn].view(np.float32).reshape(b, k, n_out)
    v_traj = host[:, kn:2 * kn].reshape(b, k, n_out)
    return logits.swapaxes(0, 1), v_traj.swapaxes(0, 1), host[:, -1]


class _PackedOut(NamedTuple):
    """What the jitted megastep core returns after the new state:
    `_pack_lanes` of the block, then the outputs kept apart (rasters only
    with ``track_events``)."""
    packed: jax.Array
    rasters: Optional[list]
    skips: Any
    conv_skips: Any


def _jit_megastep(program, backend, step_kw, emit_rasters, mesh=None):
    """Jitted megastep core shared across engines over the same program.

    SNNProgram is frozen and holds device arrays (unhashable), so the
    cache is keyed by ``id`` with a weakref guard against id reuse (a
    `jax.sharding.Mesh` IS hashable, so ``mesh`` keys directly). The
    core returns ``(state, packed, rasters, skips, conv_skips)``: the
    per-lane results the host reads come back as one packed array
    (`_pack_lanes`), so retiring a page is one device→host read, and the
    block's final readout V and logits, which the engine never reads, are
    no outputs at all.
    """
    return _megastep_entry(program, backend, step_kw, emit_rasters,
                           mesh)[0]


def _megastep_entry(program, backend, step_kw, emit_rasters, mesh) -> tuple:
    """The cache entry behind `_jit_megastep`: the jitted core and its
    compiled executables, {(K, lanes): executable}."""
    key = (id(program), backend, tuple(sorted(step_kw.items())),
           emit_rasters, mesh)
    hit = _MEGASTEP_JIT.get(key)
    if hit is not None and hit[0]() is program:
        return hit[1:]

    def _core(st, block, counts):
        st2, out = pipeline.stream_megastep(
            program, st, block, backend, active=counts,
            emit_rasters=emit_rasters, mesh=mesh, **step_kw)
        return st2, _pack_lanes(out), out.rasters, out.skips, out.conv_skips

    fn, exes = jax.jit(_core), {}
    _MEGASTEP_JIT[key] = (weakref.ref(program), fn, exes)
    return fn, exes


class _Rungs:
    """One compiled executable of the jitted megastep core per rung of
    the engine's K ladder, compiled ahead of serving: a dispatch calls the
    executable of its block's length, so no dispatch traces or compiles."""

    def __init__(self, program, backend, step_kw, emit_rasters, mesh,
                 state, lanes, frame_shape, rungs):
        fn, exes = _megastep_entry(program, backend, step_kw, emit_rasters,
                                   mesh)
        for k in rungs:
            if (k, lanes) not in exes:
                exes[(k, lanes)] = fn.lower(
                    state,
                    jax.ShapeDtypeStruct((k, lanes, *frame_shape),
                                         jnp.float32),
                    jax.ShapeDtypeStruct((lanes,), jnp.int32)).compile()
        self.exes = {k: exes[(k, lanes)] for k in rungs}

    def __call__(self, st, block, counts):
        return self.exes[block.shape[0]](st, block, counts)

    def upload(self, block: np.ndarray, counts: np.ndarray) -> tuple:
        """Put a host block and its active counts where the rung's
        executable takes them (the data shards of a mesh engine)."""
        _, at_block, at_counts = self.exes[block.shape[0]].input_shardings[0]
        return (jax.device_put(block, at_block),
                jax.device_put(counts, at_counts))


def _ladder(contract, horizon: Optional[int]) -> tuple:
    """The rungs of the K ladder: 1, then powers of `_RUNG_STEP` while
    ``contract(K)`` (a Pallas kernel's contracts at K frames per dispatch,
    whose VMEM estimate grows with K until the budget refuses it) passes
    and K stays within the readout's proven ``horizon`` and the int32
    frame counts."""
    from repro.analysis import ContractError
    rungs = [1]
    while True:
        k = rungs[-1] * _RUNG_STEP
        if k > np.iinfo(np.int32).max or (horizon is not None
                                            and k > horizon):
            break
        try:
            contract(k)
        except ContractError:
            break
        rungs.append(k)
    return tuple(rungs)


class SNNServeEngine(SlotEngine):
    """Continuous batching for streaming SNN inference (see module docs).

    ``backend`` is any `pipeline.STREAM_BACKENDS` entry; ``step_kw`` passes
    through to `stream_megastep` (block_b / interpret / gate_granularity /
    use_sparse / event_crossover). ``track_events=False`` disables raster
    emission and per-slot accounting — the pure-serving configuration in
    which inter-layer spikes never leave the kernel.

    ``pages`` × ``batch_slots`` is the lane pool; ``double_buffer`` stages
    the next block while the current one computes. K, the frames a page
    advances per dispatch, is the engine's own pick by default
    (``megastep=None``): each tick every dispatched page takes the
    smallest rung of the K ladder (`rungs`) that covers the longest
    remaining budget among its lanes, capped at the top rung, and never
    carries the frame clock past a queued head's future ``arrival_tick``.
    The ladder is 1, 8, 64, ... up to the largest K that the VMEM contract
    of this engine's Pallas kernel and the readout's proven horizon admit
    (a backend with no VMEM contract of its own, int_ref, takes the ladder
    of the `pallas` kernel it is the reference for), and every rung is
    compiled before the engine serves.
    The eager backends (float, ref_events, pallas_events), and engines
    built with ``validate=False`` (no analysis to size the ladder from),
    dispatch K=1. An explicit ``megastep`` pins K; ``megastep=1`` (one
    page) reproduces the classic tick-by-tick engine exactly.

    ``validate`` (default on) runs the static analyzer at engine build
    time: the kernel contracts of this exact (backend, K, step_kw)
    dispatch are verified before the first tick — the VMEM budget scales
    with K — and the program's `RangeReport` caps admission: a request
    whose tick budget, plus the ghost frames the lane may integrate after
    it (up to the top rung, less one), exceeds the readout's proven
    ``max_safe_frames`` (the horizon past which the unclamped int32
    accumulator can overflow) is rejected at `submit` with a named
    `RangeError` instead of silently serving garbage logits.

    ``mesh`` (a `jax.sharding.Mesh` with "data"/"model" axes) partitions
    the paged V-slot pool: each page's state tree is placed with its lane
    axis sharded over the data mesh axis (`dist.sharding.snn_state_specs`)
    and every megastep dispatch executes under shard_map — serving lanes
    over data shards, row-tiled fan-in over model shards — bit-identical
    to the single-device engine (every per-request output and both event
    ledgers). The float backend rejects a mesh (ValueError)."""

    def __init__(self, program: SNNProgram, *, batch_slots: int = 4,
                 backend: str = "int_ref", track_events: bool = True,
                 step_kw: Optional[dict] = None, validate: bool = True,
                 pages: int = 1, megastep: Optional[int] = None,
                 double_buffer: bool = False, mesh=None):
        if pages < 1:
            raise ValueError(f"pages must be >= 1, got {pages}")
        if megastep is not None and megastep < 1:
            raise ValueError(f"megastep must be >= 1, got {megastep}")
        if mesh is not None and backend == "float":
            raise ValueError(
                "backend 'float' has no mesh execution: float reductions "
                "are not order-exact, so a sharded engine could not stay "
                "bit-identical to the single-device path")
        self.program = program
        self.backend = backend
        self.mesh = mesh
        self.B = batch_slots                  # lanes per page
        self.pages = pages
        self.double_buffer = double_buffer
        self.track_events = track_events
        self.step_kw = dict(step_kw or {})
        self.max_safe_ticks: Optional[int] = None
        # frames per dispatch this engine may pick from, smallest first
        self.rungs: tuple = (megastep or 1,)
        if validate:
            from repro.analysis import check_kernel_contracts, check_program

            def contract(k, backend=backend):
                return check_kernel_contracts(
                    program, backend, frames=k, streaming=True,
                    emit_rasters=track_events,
                    block_b=self.step_kw.get("block_b", 8),
                    gate_granularity=self.step_kw.get("gate_granularity", 1),
                    event_crossover=self.step_kw.get("event_crossover", 1.0),
                    use_sparse=self.step_kw.get("use_sparse", False),
                    mesh=mesh)
            contract(self.rungs[0])
            self.max_safe_ticks = check_program(
                program, frames=1).max_safe_frames
            if megastep is None and backend not in _EAGER_BACKENDS:
                sizing = backend if backend.startswith("pallas") else "pallas"
                self.rungs = _ladder(lambda k: contract(k, sizing),
                                     self.max_safe_ticks)
        self.states = [pipeline.init_stream_state(program, batch_slots,
                                                  backend)
                       for _ in range(pages)]
        if mesh is not None:
            # place each page's pool on the mesh: lane axis over the data
            # shards (snn_state_specs degrades to replication when the
            # lane count does not divide), scalars replicated
            from repro.dist import sharding as dist_sharding
            self.states = [
                jax.device_put(st, dist_sharding.snn_state_specs(st, mesh))
                for st in self.states]
        self._fresh = pipeline.init_stream_state(program, 1, backend)
        # structurally-determined batch axis per state leaf (same B-vs-B+1
        # probe ServeEngine runs on its cache tree, shapes only — no
        # device allocation); leaves without a batch axis (the tick
        # counter) map to None and stay shared
        probe = jax.eval_shape(lambda: pipeline.init_stream_state(
            program, batch_slots + 1, backend))
        self._batch_axes = probe_batch_axes(self.states[0], probe)
        self.slots = [_Slot() for _ in range(pages * batch_slots)]
        self.queue = _ArrivalQueue()
        self.finished: list[SNNRequest] = []
        self._n_in, self._n_out, self._neurons = \
            pipeline._report_geometry(program)
        self._n_rec = pipeline._report_n_rec(program)
        # frames each macro-stack layer runs per engine tick and per lane:
        # 1 for FC layers, H_out*W_out output positions for im2col'd convs
        self._lane_frames = tuple(
            int(np.prod(ly.state_shape[:-1])) if ly.kind == "conv" else 1
            for ly in program.macro_stack)
        # per-tick input frame shape: the conv encoder consumes cfg.in_shape
        # images; FC/encoder-led programs consume their input-layer currents
        self._frame_shape = (tuple(program.cfg.in_shape)
                             if program.layers[0].kind == "conv"
                             else tuple(program.layers[0].state_shape))
        self.ticks = 0                    # engine ticks executed (dispatches)
        self.steps = 0                    # step() calls, idle ones included
        self.clock = 0                    # frame clock: K per engine tick
        self.frames_dispatched = 0        # K summed over page dispatches
        # the per-page megastep dispatch is jitted (the LM engine jits its
        # decode_step the same way) and compiled here for every rung, so
        # that serving never compiles. The compiled core is cached per
        # (program, backend, step_kw): every engine over the same program
        # — the warmup drain in the benchmark, a restarted server — shares
        # its compiles. The eager backends take the direct path.
        self._rungs = self._dispatch = None
        if backend not in _EAGER_BACKENDS:
            # admission's lane reset compiles on first use too: run it
            # once per page here (a no-op on the zero pool), not while
            # serving
            self.states = [lane_scatter(self._fresh, st, self._batch_axes, 0)
                           for st in self.states]
            self._rungs = self._dispatch = _Rungs(
                program, backend, self.step_kw, track_events, mesh,
                self.states[0], batch_slots, self._frame_shape, self.rungs)
        self._admit_seq = 0
        self._staged: dict = {}           # page -> (meta, device block, counts)
        self._host_blocks: dict = {}      # (page, K) -> [(host block, upload)]
        # pooled device-side event ledger (event backends only): per-layer
        # row-event counters as the executing kernel reports them
        self._event_backend = backend in ("ref_events", "pallas_events")
        self.device_row_events: Optional[list] = None
        self.device_dense_fallbacks: Optional[list] = None
        self.device_ticks = 0             # frame ticks dispatched, all pages
        self.device_reads = 0             # device->host array reads in fetch

    @property
    def state(self) -> pipeline.StreamState:
        """Page 0's state tree (back-compat introspection handle for the
        classic single-page engine)."""
        return self.states[0]

    # -- request intake ------------------------------------------------------
    def submit(self, req: SNNRequest) -> None:
        """Enqueue ``req`` (an `SNNRequest` whose ``frames`` is a
        (T, *in_shape) current block) for arrival-gated FIFO admission.

        Raises ``ValueError`` when the request's frame shape does not
        match the program input, and `analysis.RangeError` when its tick
        budget — rounded up to the K-block horizon the lane will actually
        execute — exceeds the readout accumulator's proven
        ``max_safe_frames`` (validate=True engines only)."""
        if req.frames.shape[1:] != tuple(self._frame_shape):
            raise ValueError(
                f"request {req.rid}: frame shape {req.frames.shape[1:]} "
                f"does not match the program input {self._frame_shape}")
        budget = self._tick_budget(req)
        # the lane executes whole blocks: a request finishing mid-block
        # still integrates (masked, zero-current) ticks to the block edge,
        # so the proven-safe horizon must cover them: the K-rounded budget
        # at a pinned K, else up to a top-rung block less the one frame
        # served in it (a block cut short by an arrival is not top-sized)
        top = self.rungs[-1]
        horizon = (-(-budget // top) * top if len(self.rungs) == 1
                   else budget + top - 1)
        if self.max_safe_ticks is not None and horizon > self.max_safe_ticks:
            from repro.analysis import RangeError
            raise RangeError(
                f"request {req.rid} streams {budget} ticks "
                f"({horizon} with blocks of up to K={top}) but the readout's "
                f"unclamped int32 accumulator is only proven safe for "
                f"{self.max_safe_ticks} frames; split the stream or cap "
                "max_ticks", where="readout")
        self.queue.put(req)

    @staticmethod
    def _tick_budget(req: SNNRequest) -> int:
        """Ticks this request may stream: its frame count, clipped by an
        explicit non-negative max_ticks."""
        if req.max_ticks is None:
            return len(req.frames)
        return min(len(req.frames), max(req.max_ticks, 0))

    def _admit(self) -> None:
        for i, slot in enumerate(self.slots):
            if slot.req is not None:
                continue
            # like the LM engine's finish-at-admit: a request with nothing
            # to stream (no frames, or max_ticks <= 0) never occupies a
            # slot or runs a spurious tick — keep draining the queue until
            # one actually needs ticks
            while not self.queue.empty():
                head = self.queue.peek()
                if head.arrival_tick > self.clock:
                    return    # FIFO: the head gates later submissions too
                with TraceAnnotation("snn_engine.admit", rid=head.rid):
                    if self._admit_one(i, slot):
                        break

    def _admit_one(self, i: int, slot: _Slot) -> bool:
        """Pop the queue head into free slot ``i``; False when it needs no
        ticks and finished at admission, leaving the slot free."""
        req = self.queue.get()
        if self._tick_budget(req) == 0:
            req.logits = np.zeros(self._n_out[-1], np.float32)
            # shape-consistent readout V so degenerate requests finalize
            # like every other finish (backend-native dtype: f32 float
            # backend, int32 macro readout)
            req.v_out = np.zeros(
                self._n_out[-1],
                np.float32 if self.backend == "float" else np.int32)
            req.finish_clock = self.clock
            if self.track_events:   # reports only when accounting
                req.report = self._finalize_report(self._fresh_slot(req))
            self.finished.append(req)
            return False
        # admit-by-lane-copy: the fresh request's (zero) V tree, and the
        # zero spike raster of each recurrent layer, enter the slot's
        # lane; the V_MEM lane is the KV-cache analogue
        page, lane = divmod(i, self.B)
        self.states[page] = lane_scatter(
            self._fresh, self.states[page], self._batch_axes, lane)
        slot.req = req
        slot.cursor = 0
        slot.ticks = 0
        slot.serial = self._admit_seq
        self._admit_seq += 1
        fresh = self._fresh_slot(req)
        slot.row_events = fresh.row_events
        slot.rec_events, slot.rec_last = fresh.rec_events, fresh.rec_last
        return True

    def _fresh_slot(self, req: SNNRequest) -> _Slot:
        """A slot for ``req`` with zeroed event tallies (s(-1) = 0)."""
        zeros = [0] * len(self._n_in)
        return _Slot(req=req,
                     row_events=[np.zeros(n, np.int64) for n in self._n_in],
                     rec_events=list(zeros), rec_last=list(zeros))

    # -- per-slot event accounting ------------------------------------------
    def _account(self, rasters: list, served: list) -> None:
        """Fold one block's macro-stack input rasters into the served
        slots' per-row event tallies. ``served`` is [(slot, lane, ticks)]
        — a request is credited only the ticks it actually served, so
        ghost ticks past a mid-block finish never enter its report.
        `_stack_input_rasters` lowers conv spike maps to their im2col
        patch rasters, so conv layers count events per (output position,
        patch row) — exactly as the macro issues them; lane l owns the
        l-th block of P contiguous frames."""
        rs = pipeline._stack_input_rasters(
            self.program, [np.asarray(r) for r in rasters])
        for li, (r, p) in enumerate(zip(rs, self._lane_frames)):
            counts = r.astype(np.int64)       # (K, B * P_l, n_in_l)
            for i, lane, n in served:
                self.slots[i].row_events[li] += \
                    counts[:n, lane * p:(lane + 1) * p].sum(axis=(0, 1))
        # a recurrent layer's W_rec rows see its own output (the next
        # layer's input) one tick late: the tick before the block, then
        # every served tick of the block but the last
        for li in (li for li, ly in enumerate(self.program.macro_stack)
                   if ly.recurrent):
            own = rs[li + 1]                  # (K, B, n_out) fc raster
            for i, lane, n in served:
                if n == 0:
                    continue
                slot = self.slots[i]
                ticks = own[:n, lane].astype(np.int64).sum(axis=1)
                slot.rec_events[li] += slot.rec_last[li] + int(ticks[:-1].sum())
                slot.rec_last[li] = int(ticks[-1])

    def _account_device(self, out, k: int) -> None:
        """Pool one dispatch's executor-reported `EventStats` (fc stack in
        ``out.skips``, one per conv layer in ``out.conv_skips``) into the
        engine-lifetime device ledger. These are the counters the event
        executor measured while running — for `pallas_events`, on device —
        over ALL lanes of the dispatched page, ``k`` frames each (module
        docs)."""
        rows = [np.asarray(r, np.int64)
                for st in (out.conv_skips or []) for r in st.row_events]
        rows += [np.asarray(r, np.int64) for r in out.skips.row_events]
        fbs = [int(f) for st in (out.conv_skips or [])
               for f in st.dense_fallbacks]
        fbs += [int(f) for f in out.skips.dense_fallbacks]
        if self.device_row_events is None:
            self.device_row_events = rows
            self.device_dense_fallbacks = fbs if fbs else None
        else:
            self.device_row_events = [a + b for a, b in
                                      zip(self.device_row_events, rows)]
            if fbs:
                self.device_dense_fallbacks = [
                    a + b for a, b in zip(self.device_dense_fallbacks, fbs)]
        self.device_ticks += k

    def device_event_stats(self):
        """The pooled device ledger as an `events.EventStats`: per-layer
        row-event counters summed over every dispatch so far, frames =
        device_ticks * batch_slots lane-frames (device_ticks accumulates
        K per dispatched page; exact for FC stacks — conv layers run
        ``lane_frames`` frames per lane per tick, use
        `device_skipped_row_fraction` for the pooled fraction there).
        Since vacated lanes are re-seeded with zero state, the row-event
        counters close exactly against the summed per-slot raster tallies
        at any occupancy — the serving-side closure tests assert it —
        modulo the ghost ticks of mid-block early exits (module docs)."""
        from repro.kernels.fused_snn_net.events import EventStats
        if self.device_row_events is None:
            raise ValueError("no device ledger: the engine has not ticked "
                             "on an event backend (ref_events/pallas_events)")
        return EventStats(
            row_events=tuple(self.device_row_events),
            frames=self.device_ticks * self.B,
            dense_fallbacks=(tuple(self.device_dense_fallbacks)
                             if self.device_dense_fallbacks is not None
                             else ()))

    def device_skipped_row_fraction(self) -> float:
        """Pooled skipped-row fraction of the device ledger, with each
        layer's frame count scaled by its lane-frames (conv layers run one
        frame per output position)."""
        if self.device_row_events is None:
            raise ValueError("no device ledger: the engine has not ticked "
                             "on an event backend (ref_events/pallas_events)")
        possible = sum(self.device_ticks * self.B * p * n
                       for p, n in zip(self._lane_frames, self._n_in))
        events = sum(int(r.sum()) for r in self.device_row_events)
        return 1.0 - events / possible if possible else 0.0

    def _finalize_report(self, slot: _Slot) -> SparsityReport:
        """The per-request SparsityReport: batch 1, one timestep per served
        tick — same geometry/accounting as `pipeline.sparsity_report` on an
        isolated run of the request's frames."""
        t = slot.ticks
        row_events = tuple(np.asarray(r, np.int64) for r in slot.row_events)
        return SparsityReport(
            n_in=self._n_in, n_out=self._n_out, neurons=self._neurons,
            events=tuple(int(r.sum()) for r in row_events),
            frames=t, timesteps=t, batch=1,
            layer_frames=tuple(t * p for p in self._lane_frames),
            row_events=row_events, n_rec=self._n_rec,
            rec_events=(None if self._n_rec is None
                        else tuple(slot.rec_events)))

    # -- frame staging -------------------------------------------------------
    def _lanes(self, page: int, ahead: int = 0):
        """(slot index, request, cursor, budget) of each lane of ``page``
        still streaming after ``ahead`` more frames (0: as it stands)."""
        for i in self.page_lanes(page):
            req = self.slots[i].req
            if req is None:
                continue
            budget = self._tick_budget(req)
            cursor = min(self.slots[i].cursor + ahead, budget)
            if cursor < budget:
                yield i, req, cursor, budget

    def _rung(self, need: int, clock: int) -> int:
        """Frames for a dispatch at frame clock ``clock`` whose longest lane
        has ``need`` frames left: the smallest rung covering it (the top
        rung when none does), cut to the largest rung that does not carry
        the clock past the queue head's future arrival."""
        k = next((r for r in self.rungs if r >= need), self.rungs[-1])
        if not self.queue.empty():
            gap = self.queue.peek().arrival_tick - clock
            if gap > 0:
                k = min(k, max((r for r in self.rungs if r <= gap),
                               default=self.rungs[0]))
        return k

    def _page_rung(self, page: int, ahead: int = 0,
                   clock: Optional[int] = None) -> Optional[int]:
        """The rung ``page`` dispatches at ``clock`` (default: now) once
        its lanes have advanced ``ahead`` frames; None when no lane would
        be left streaming."""
        need = max((b - c for *_, c, b in self._lanes(page, ahead)),
                   default=0)
        if not need:
            return None
        return self._rung(need, self.clock if clock is None else clock)

    def _block_meta(self, page: int, k: int) -> tuple:
        """Identity of the K-frame block a page would dispatch right now:
        K, then per occupied lane (admission serial, cursor, staged tick
        count). The key that validates a speculatively staged block."""
        return (k,) + tuple((self.slots[i].serial, c, min(b - c, k))
                            for i, _, c, b in self._lanes(page))

    def _build_block(self, page: int, k: int, ahead: int = 0):
        """Assemble one page's (K, B, *in_shape) frame block and per-lane
        active counts from each lane's cursor, advanced ``ahead`` frames
        for double-buffer speculation, and upload them. Returns (meta,
        device block, counts).

        The host block is one of the page's buffers for this K, reused
        tick after tick: only the live lanes' frames are written, since
        `stream_megastep` zeroes every frame past a lane's active count,
        and a written buffer uploads several times faster than fresh zero
        pages. The buffers take turns (two with ``double_buffer``, so the
        block staged ahead never overwrites the one being dispatched); a
        buffer is rewritten only after its last upload has landed and the
        dispatch that read it has been retired (on a CPU the device array
        may alias the host buffer)."""
        ring = self._host_blocks.setdefault((page, k), [])
        if len(ring) <= self.double_buffer:
            block = np.zeros((k, self.B, *self._frame_shape), np.float32)
        else:
            block, landed = ring.pop(0)
            landed.block_until_ready()
        counts = np.zeros(self.B, np.int32)
        meta = [k]
        for i, req, cursor, budget in self._lanes(page, ahead):
            n = min(budget - cursor, k)
            block[:n, i % self.B] = req.frames[cursor:cursor + n]
            counts[i % self.B] = n
            meta.append((self.slots[i].serial, cursor, n))
        if self._rungs is not None:
            on_device = self._rungs.upload(block, counts)
        else:
            on_device = jax.device_put(block), counts
        ring.append((block, on_device[0]))
        return (tuple(meta), *on_device)

    def _stage_block(self, page: int, k: int):
        """The K-frame block a page dispatches this tick: the
        double-buffered upload when its metadata still matches (no early
        exit, admission, eviction or other K invalidated the speculation),
        else built fresh."""
        staged = self._staged.pop(page, None)
        if staged is not None and staged[0] == self._block_meta(page, k):
            return staged[1], staged[2]
        return self._build_block(page, k)[1:]

    def _stage_next(self, ks: dict, clock: int) -> None:
        """Double buffer: stage tick t+1's blocks (host assembly + device
        upload) while tick t's dispatches compute, each page ``ks[page]``
        frames on and at frame clock ``clock``. Pure speculation —
        `_stage_block` re-validates against live metadata, so a mismatch
        costs one rebuild and never changes results."""
        for page, k in ks.items():
            k_next = self._page_rung(page, ahead=k, clock=clock)
            if k_next is not None:
                self._staged[page] = self._build_block(page, k_next, k)

    # -- engine tick ---------------------------------------------------------
    def step(self) -> int:
        """One engine tick: admit, then one megastep per occupied page.
        Returns #active slots remaining after evictions."""
        with StepTraceAnnotation("snn_engine.step", step_num=self.steps):
            self.steps += 1
            return self._tick()

    def _tick(self) -> int:
        self._admit()
        by_page = self.active_by_page()
        if not by_page:
            if not self.queue.empty():
                # only future arrivals remain: idle ticks still advance
                # the frame clock so Poisson schedules reach their
                # arrival times under run_until_drained
                self.clock += self._rung(
                    self.queue.peek().arrival_tick - self.clock, self.clock)
            return 0
        outs, ks = {}, {}
        for page in sorted(by_page):
            k = ks[page] = self._page_rung(page)
            with TraceAnnotation("snn_engine.stage", page=page):
                block, counts = self._stage_block(page, k)
            with TraceAnnotation("snn_engine.dispatch", page=page, k=k):
                if self._dispatch is not None:
                    self.states[page], *rest = self._dispatch(
                        self.states[page], block, counts)
                    outs[page] = _PackedOut(*rest)
                else:
                    self.states[page], outs[page] = pipeline.stream_megastep(
                        self.program, self.states[page], block,
                        self.backend, active=counts,
                        emit_rasters=self.track_events, mesh=self.mesh,
                        **self.step_kw)
            self.frames_dispatched += k
        # a page dispatching fewer frames than the longest finished every
        # lane inside its block (its K covered them all), so the clock
        # advances by the longest block
        start, self.clock = self.clock, self.clock + max(ks.values())
        if self.double_buffer:
            with TraceAnnotation("snn_engine.stage_next"):
                self._stage_next(ks, self.clock)
        self.ticks += 1
        for page in sorted(by_page):
            with TraceAnnotation("snn_engine.retire", page=page):
                self._retire_page(page, by_page[page], outs[page], ks[page],
                                  start)
        return sum(1 for s in self.slots if s.req is not None)

    def _fetch(self, out, k: int) -> tuple:
        """Read one page's per-lane results to the host: (K, B, n_out)
        logit and readout V trajectories and (B,) frames consumed. The
        jitted path reads its packed array in one transfer; the eager
        path (a `MegastepOut`) reads the three arrays one by one."""
        if isinstance(out, _PackedOut):
            with TraceAnnotation("snn_engine.fetch", reads=1):
                host = np.asarray(out.packed)
            self.device_reads += 1
            return _unpack_lanes(host, k, self._n_out[-1])
        with TraceAnnotation("snn_engine.fetch", reads=3):
            fetched = (np.asarray(out.logits_traj),
                       np.asarray(out.v_out_traj),
                       np.asarray(out.frames_consumed))
        self.device_reads += 3
        return fetched

    def _retire_page(self, page: int, lanes: list, out, k: int,
                     start: int) -> None:
        """Account one page's K-frame megastep, dispatched at frame clock
        ``start``, and finalize the requests that finished inside it —
        from the block's per-tick readout trajectory, at the exact tick a
        K=1 drain would have stopped on."""
        logits, v_traj, consumed = self._fetch(out, k)
        served, fins = [], []
        for i in lanes:
            slot = self.slots[i]
            req = slot.req
            lane = i % self.B
            n = int(consumed[lane])
            # the first served tick that exhausts the budget or, earlier,
            # reads a confident logit (compared in float64, as a Python
            # float threshold is)
            left = self._tick_budget(req) - slot.cursor
            fin = left - 1 if left <= n else None
            if req.stop_threshold is not None:
                peak = np.abs(logits[:n, lane]).max(axis=-1)
                hit = np.flatnonzero(peak.astype(np.float64)
                                     >= req.stop_threshold)
                if hit.size and (fin is None or hit[0] < fin):
                    fin = int(hit[0])
            credit = n if fin is None else fin + 1
            served.append((i, lane, credit))
            slot.cursor += credit
            slot.ticks += credit
            if fin is not None:
                fins.append((i, lane, fin))
        by_rasters = self.track_events and out.rasters is not None
        by_device = self._event_backend and out.skips is not None
        if by_rasters or by_device:
            with TraceAnnotation("snn_engine.account"):
                if by_rasters:
                    self._account(out.rasters, served)
                if by_device:
                    self._account_device(out, k)
        for i, lane, fin in fins:
            slot = self.slots[i]
            req = slot.req
            with TraceAnnotation("snn_engine.evict", rid=req.rid):
                req.logits = logits[fin, lane].copy()
                req.v_out = v_traj[fin, lane].copy()
                req.ticks = slot.ticks
                req.finish_clock = start + fin + 1
                if self.track_events:
                    req.report = self._finalize_report(slot)
                self.finished.append(req)
                # idle lanes are silent: re-seed the vacated lane with
                # fresh zero state (V and recurrent rasters) so deeper
                # layers cannot keep leaking/firing from carried state
                # until re-admission
                self.states[page] = lane_scatter(
                    self._fresh, self.states[page], self._batch_axes, lane)
                self.slots[i] = _Slot()

    # run_until_drained (and its EngineUndrained contract) comes from
    # SlotEngine — one drain loop shared with the LM engine.

    # -- workload accounting -------------------------------------------------
    def aggregate_report(self) -> SparsityReport:
        """Pooled SparsityReport over every finished request — the
        engine-level skipped-work/EDP accounting input. Raises
        `ReportUnavailable` when there is nothing to pool (event tracking
        off, or no request finished yet)."""
        if not self.track_events:
            raise ReportUnavailable(
                "event tracking is disabled (track_events=False): "
                "per-request SparsityReports were never accumulated; build "
                "the engine with track_events=True for accounting")
        reps = [r.report for r in self.finished if r.report is not None]
        if not reps:
            raise ReportUnavailable(
                "no finished requests yet: the aggregate report pools "
                "per-request reports, which exist only after a request "
                "finishes (run_until_drained / step)")
        return merge_reports(reps)
