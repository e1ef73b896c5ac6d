"""Static cost model over the traced dispatch jaxprs (DESIGN.md §7.5).

Walks the same closed jaxprs `trace_check` verifies and counts, per fused
call, the MXU MACs and the HBM<->VMEM bytes the compiled artifact will
move — then ties that machine-level tally back to the ISA contract:

* **geometry validation** — the traced timestep scan must run exactly
  ``program.timesteps`` iterations, every dense `dot_general` must
  contract the (lane-padded) layer widths the program declares, and every
  `pallas_call` grid must cover exactly ``ceil(batch / block_b)`` batch
  blocks. A dot that contracts anything else means the compiled path
  silently changed shape — that is a `TraceError`, not a cost.
* **cost closure** — `dense_instr` folds the *trace-validated* geometry
  (T, batch, logical widths, neuron kind) through
  `isa.count_layer_instructions_from_events` with dense (every-input-
  spiking) events; `check_cost_closure` proves this equals
  `pipeline.count_network_instructions` on explicit all-ones rasters
  exactly — the jaxpr, the config-derived counter, and the ISA
  accounting all describe the same workload or the check fails.

Conventions of the bytes model (documented, not inferred): a
`pallas_call` moves each operand/result array once, plus one extra fetch
per additional grid step for *grid-invariant* operands — the 2-D arrays
(weight tiles, per-layer parameter rows) that every batch block re-reads;
3-D operands (the spike frames) are partitioned across the grid. Backends
with no `pallas_call` (``int_ref``) charge the top-level dispatch
operands/results once. MACs are *dense* MXU work: `lax.cond` branches
count as their maximum, so the event kernel is charged both its dense
`@pl.when` and its gather `@pl.when` (whose prefix sum is a block_b x
n_in x n_in matmul) — an upper bound, since one of the two runs per
tile and step. A `dot_general` inside an unbounded `while` is rejected
outright.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.analysis.trace_check import (TraceCheck, TraceError, _aval_dtype,
                                        _aval_shape, _grid_size,
                                        _program_calls, _sub_regions,
                                        root_region)
from repro.core import isa


@dataclass(frozen=True)
class DotSite:
    """One traced `dot_general`: contracted geometry and its static trip
    count (product of enclosing scan lengths and pallas grids)."""
    m: int
    k: int
    n: int
    trip: int

    @property
    def macs(self) -> int:
        return self.m * self.k * self.n * self.trip


@dataclass(frozen=True)
class CallCost:
    """Machine-level cost of one fused call's batch dispatch."""
    call: str
    macs: int
    hbm_bytes: int
    dots: tuple                    # tuple[DotSite, ...]
    scan_lengths: tuple
    grids: tuple


@dataclass(frozen=True)
class TraceCostReport:
    """Per-dispatch MAC/byte tallies plus the dense ISA instruction
    counts derived from the trace-validated geometry. ``instr`` must
    close exactly against `pipeline.count_network_instructions` on
    all-ones rasters (`check_cost_closure`)."""
    backend: str
    batch: int
    timesteps: int
    calls: tuple                   # tuple[CallCost, ...]
    instr: isa.InstrCount

    @property
    def macs(self) -> int:
        return sum(c.macs for c in self.calls)

    @property
    def hbm_bytes(self) -> int:
        return sum(c.hbm_bytes for c in self.calls)


def _nbytes(atom) -> int:
    shape = _aval_shape(atom) or ()
    dt = _aval_dtype(atom)
    if dt is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dt).itemsize


def _dot_mkn(eqn) -> tuple:
    """(M, K, N) of a dot_general from its dimension_numbers: M = lhs
    free x batch dims, K = contracted dims, N = rhs free dims."""
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    lshape = _aval_shape(eqn.invars[0]) or ()
    rshape = _aval_shape(eqn.invars[1]) or ()
    k = int(np.prod([lshape[i] for i in lc], dtype=np.int64)) if lc else 1
    m = int(np.prod([d for i, d in enumerate(lshape) if i not in lc],
                    dtype=np.int64))
    n = int(np.prod([d for i, d in enumerate(rshape)
                     if i not in rc and i not in _rb],
                    dtype=np.int64))
    del lb
    return m, k, n


def _walk_cost(region, trip: int, dots: list, scans: list, grids: list,
               bytes_acc: list, where: str) -> None:
    for eqn in region.jaxpr.eqns:
        p = eqn.primitive.name
        if p == "dot_general":
            m, k, n = _dot_mkn(eqn)
            dots.append(DotSite(m=m, k=k, n=n, trip=trip))
        elif p == "scan":
            length = int(eqn.params.get("length", 1))
            scans.append(length)
            for sub in _sub_regions(eqn, region):
                _walk_cost(sub, trip * length, dots, scans, grids,
                           bytes_acc, where)
        elif p == "while":
            for sub in _sub_regions(eqn, region):
                before = len(dots)
                _walk_cost(sub, trip, dots, scans, grids, bytes_acc, where)
                if len(dots) != before:
                    raise TraceError(
                        "cost: dot_general inside an unbounded 'while' at "
                        f"{sub.path or '/'} — MXU work with a dynamic "
                        "trip count cannot be statically accounted",
                        where=where)
        elif p == "cond":
            branch_dots: list = []
            for sub in _sub_regions(eqn, region):
                bd: list = []
                _walk_cost(sub, trip, bd, scans, grids, bytes_acc, where)
                branch_dots.append(bd)
            if branch_dots:        # dense bound: the costliest branch
                branch_dots.sort(key=lambda bd: sum(d.macs for d in bd))
                dots.extend(branch_dots[-1])
        elif p == "pallas_call":
            g = _grid_size(eqn)
            grids.append(g)
            operands = list(eqn.invars)
            moved = sum(_nbytes(a) for a in (*operands, *eqn.outvars))
            invariant = sum(_nbytes(a) for a in operands
                            if len(_aval_shape(a) or ()) == 2)
            bytes_acc.append(trip * (moved + (g - 1) * invariant))
            for sub in _sub_regions(eqn, region):
                _walk_cost(sub, trip * g, dots, scans, grids, bytes_acc,
                           where)
        else:
            for sub in _sub_regions(eqn, region):
                _walk_cost(sub, trip, dots, scans, grids, bytes_acc, where)


def _padded(widths: tuple, backend: str) -> tuple:
    if backend == "int_ref":
        return tuple(int(w) for w in widths)
    from repro.analysis.kernel_contracts import _pad_lane
    return tuple(_pad_lane(int(w)) for w in widths)


def _validate_geometry(program, backend: str, call: str, widths: tuple,
                       cost: CallCost, *, batch: int, block_b: int,
                       where: str) -> None:
    T = int(program.timesteps)
    if T not in cost.scan_lengths:
        raise TraceError(
            f"cost: no scan of length {T} (the timestep loop) in the "
            f"traced '{call}' dispatch — scan lengths {cost.scan_lengths}",
            where=where)
    if backend != "int_ref":
        grid_want = -(-batch // block_b)
        bad = [g for g in cost.grids if g != grid_want]
        if not cost.grids or bad:
            raise TraceError(
                f"cost: pallas grid(s) {cost.grids} in '{call}' do not "
                f"cover batch {batch} in {block_b}-row blocks "
                f"(want {grid_want})", where=where)
    from repro.analysis.kernel_contracts import call_recurrent
    rec = call_recurrent(program, call)
    pw = _padded(widths, backend)
    m_want = batch if backend == "int_ref" else min(block_b, batch)
    for i in range(len(widths) - 1):
        n_want = pw[i + 1]
        # a recurrent layer contracts its input rows and its W_rec rows:
        # one concatenated dot on int_ref, two lane-aligned ones in Pallas
        if i < len(rec) and rec[i]:
            k_wants = ((pw[i] + pw[i + 1],) if backend == "int_ref"
                       else (pw[i], pw[i + 1]))
        else:
            k_wants = (pw[i],)
        hit = []
        for k_want in k_wants:
            if backend == "pallas_sparse":
                got = [d for d in cost.dots
                       if d.n == n_want and k_want % d.k == 0]
            else:
                got = [d for d in cost.dots
                       if d.k == k_want and d.n == n_want]
            if not got:
                raise TraceError(
                    f"cost: no dot_general contracting layer {i} of "
                    f"'{call}' (want K={k_want} N={n_want}; traced "
                    f"{[(d.m, d.k, d.n) for d in cost.dots]}) — the "
                    "compiled path changed shape", where=where)
            hit += got
        if any(d.m != m_want for d in hit):
            raise TraceError(
                f"cost: dot_general M={sorted({d.m for d in hit})} for "
                f"layer {i} of '{call}', want the {m_want}-row batch "
                "block", where=where)


def _conv_input_maps(program) -> list:
    """(H, W, C) input spike-map shape of every conv macro-stack layer:
    the previous conv layer's state shape (the first takes H, W from the
    network input), with channels always the packed kernel's c_in — the
    channel count the macro's patch rows actually carry."""
    shapes, hw = [], tuple(getattr(program.cfg, "in_shape", ())[:2])
    for spec in program.macro_stack:
        if spec.kind != "conv":
            continue
        shapes.append((*hw, int(spec.w.shape[2])))
        hw = tuple(spec.state_shape[:2])
    return shapes


def _dense_conv_counts(in_map: tuple, kernel: int, stride: int) -> tuple:
    """(positions, events_per_frame-pair): for a SAME-padded conv over an
    all-ones (H, W, C) map, the output position count and the total
    non-padding patch cells per (example, timestep) — border patches see
    the zero padding, so the dense event count is *less* than
    positions x k*k*C. Pure numpy re-derivation of the im2col geometry."""
    from repro.core.mapping import same_pads
    h, w, c = in_map
    h_out, lo_h, hi_h = same_pads(h, kernel, stride)
    w_out, lo_w, hi_w = same_pads(w, kernel, stride)
    p = np.pad(np.ones((h, w), np.int64), ((lo_h, hi_h), (lo_w, hi_w)))
    cells = 0
    for di in range(kernel):
        for dj in range(kernel):
            cells += int(p[di:di + (h_out - 1) * stride + 1:stride,
                           dj:dj + (w_out - 1) * stride + 1:stride].sum())
    return h_out * w_out, cells * c


def dense_instr(program, batch: int) -> isa.InstrCount:
    """ISA instruction counts for the dense (every-input-spiking)
    workload, folded from the trace-validated geometry: per macro-stack
    layer, frames = T * batch * output-positions and events from the
    SAME-padded patch geometry (conv) or frames * fan-in (fc), through
    the same `count_layer_instructions_from_events` the raster accounting
    uses. A recurrent layer's W_rec rows see its own spikes one tick
    late, so dense they carry (T - 1) * batch * n_out events (s(-1) = 0).
    """
    T = int(program.timesteps)
    counts = isa.InstrCount()
    conv_maps = iter(_conv_input_maps(program))
    for spec in program.macro_stack:
        if spec.kind == "conv":
            in_map = next(conv_maps)
            pos, ev_frame = _dense_conv_counts(
                in_map, int(spec.w.shape[0]), int(spec.stride))
            want_pos = int(np.prod(spec.state_shape[:-1], dtype=np.int64))
            if pos != want_pos:
                raise TraceError(
                    f"cost: conv geometry drift — SAME-padded im2col of "
                    f"{in_map} gives {pos} output positions, the program "
                    f"state shape {spec.state_shape} declares {want_pos}",
                    where="cost_closure")
            frames = T * batch * pos
            events = T * batch * ev_frame
        else:
            frames = T * batch
            events = frames * int(spec.n_in)
            if spec.recurrent:
                events += (T - 1) * batch * int(spec.n_out)
        neuron = "none" if spec.kind == "readout" else program.neuron
        counts += isa.count_layer_instructions_from_events(
            events, frames, int(spec.fan_in), int(spec.n_out), neuron)
    return counts


def dense_rasters(program, batch: int) -> list:
    """All-ones input rasters for every macro-stack layer — the explicit
    dense workload `pipeline.count_network_instructions` counts. Conv
    layers take their full input spike *map*, which the counter lowers
    through the same im2col the macro executes (so its dense events
    include the SAME-padding zeros `dense_instr` accounts analytically)."""
    T = int(program.timesteps)
    conv_maps = iter(_conv_input_maps(program))
    out = []
    for spec in program.macro_stack:
        if spec.kind == "conv":
            out.append(np.ones((T, batch, *next(conv_maps)), np.int8))
        else:
            out.append(np.ones((T, batch, int(spec.n_in)), np.int8))
    return out


def check_cost_closure(program, batch: int = 8) -> isa.InstrCount:
    """Prove the trace-geometry dense counts equal the raster-accounting
    dense counts exactly; returns the agreed `InstrCount` or raises
    `TraceError` naming the first diverging field."""
    from repro.core.pipeline import count_network_instructions
    got = dense_instr(program, batch)
    want = count_network_instructions(program,
                                      rasters=dense_rasters(program, batch))
    if got != want:
        raise TraceError(
            f"cost: dense instruction closure failed — trace-geometry "
            f"counts {got} != raster-accounting counts {want}; the "
            "compiled dispatch and the ISA accounting describe different "
            "workloads", where="cost_closure")
    return got


def build_cost_report(program, backend: str, batch_jaxprs: dict, *,
                      batch: int, block_b: int,
                      checks: list = None) -> TraceCostReport:
    """Cost-walk every fused call's traced batch jaxpr, validate its
    geometry against the program, and fold the dense ISA counts. Appends
    `TraceCheck` rows to ``checks`` when given."""
    calls = []
    for name, _layer_names, widths, _n_spiking in _program_calls(program):
        closed = batch_jaxprs.get(name)
        if closed is None:
            continue
        where = f"{backend}:cost:{name}"
        dots: list = []
        scans: list = []
        grids: list = []
        bytes_acc: list = []
        root = root_region(closed, path="")
        _walk_cost(root, 1, dots, scans, grids, bytes_acc, where)
        if not bytes_acc:          # no pallas_call: charge the dispatch
            jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
            bytes_acc.append(sum(_nbytes(a) for a in
                                 (*jaxpr.invars, *jaxpr.outvars)))
        cost = CallCost(call=name, macs=sum(d.macs for d in dots),
                        hbm_bytes=int(sum(bytes_acc)), dots=tuple(dots),
                        scan_lengths=tuple(scans), grids=tuple(grids))
        _validate_geometry(program, backend, name, widths, cost,
                           batch=batch, block_b=block_b, where=where)
        if checks is not None:
            checks.append(TraceCheck(
                "cost_geometry", where,
                f"{len(dots)} dot site(s) match declared widths; "
                f"macs={cost.macs} hbm_bytes={cost.hbm_bytes}"))
        calls.append(cost)
    return TraceCostReport(backend=backend, batch=batch,
                           timesteps=int(program.timesteps),
                           calls=tuple(calls),
                           instr=dense_instr(program, batch))
