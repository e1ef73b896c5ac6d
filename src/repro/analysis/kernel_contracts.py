"""Static pre-dispatch verification of the fused-kernel contracts.

The Pallas kernels (kernels/fused_snn_net) assume properties of the
compiled program + dispatch parameters that, when violated, surface as
opaque `pallas_call` lowering failures or silent VMEM thrash. This pass
re-derives each assumption **from config alone** — no tracing, no device —
and rejects a bad dispatch with a `ContractError` naming the contract and
the offending call, before any kernel is built:

  contract            | what is verified
  --------------------|---------------------------------------------------
  backend             | known execution backend; bitmacro demands wrap
                      | arithmetic (silicon has no saturation logic)
  chain_alignment     | layer i's fan-in == layer i-1's fan-out (flattened
                      | across the conv->fc boundary) — the property that
                      | keeps every `pl.ds` gather row inside its weight
                      | tile
  grid_divisibility   | block_b >= 1; the wrapper pads B up to a block_b
                      | multiple, so grid = ceil(B / block_b) always
                      | divides evenly after padding
  gate_granularity    | granularity in GATE_GRANULARITIES, and only the
                      | gated backend may request sub-tile gating
  skip_layout         | the gate-site column map fits MAX_SKIP_COLS
  event_crossover     | dense-fallback crossover in [0, 1]
  fallback_columns    | events mode carries one fallback column per layer
                      | in a LANE-wide output: len(ws) <= LANE per call
  gather_bounds       | events-mode index lists are capacity-bounded by
                      | the padded fan-in (index < padded rows of the
                      | VMEM-resident weight tile, by construction of the
                      | prefix-sum/one-hot decode — reported with the
                      | numbers)
  vmem_budget         | the per-`pallas_call` VMEM residency — spike block
                      | across the whole T loop + all weight tiles + all V
                      | scratch/out tiles + rasters + counters — fits the
                      | per-core budget
  megastep            | streaming dispatches advance K >= 1 frames per
                      | call (`pipeline.stream_megastep`); the VMEM
                      | estimate scales its spike/raster blocks with K
                      | (``frames=K``), so a K that overflows the budget
                      | is rejected here, before the engine's first tick
  mesh_axes           | a mesh-sharded dispatch names "data"/"model"
                      | extents; float/bitmacro have no mesh execution
  mesh_split          | per fused call under model-parallel row tiling:
                      | the padded fan-in divides evenly into per-shard
                      | row tiles (chain alignment is preserved because
                      | every shard slices rows of the same padded
                      | fan-in and the integer psum reassembles the full
                      | width), and the per-shard residency — weight
                      | tiles shrink 1/n_model, spike/V blocks stay full
                      | width — fits the VMEM budget

Each on-macro conv layer dispatches its own fused call over its im2col
patch raster (T stays, batch becomes B*P, per-grid-cell residency is
B-independent); the fc stack is one further call. The budget estimate is
deliberately a slight over-count (it ignores nothing that is resident) and
excludes only compiler temporaries, which the default margin absorbs.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

from repro.analysis.intervals import AnalysisError
from repro.kernels.fused_snn_net.kernel import (GATE_GRANULARITIES, LANE,
                                                MAX_SKIP_COLS, skip_layout)

#: per-core VMEM (~16 MiB on current TPUs — see the Pallas guide); the
#: checker budgets a margin below it for compiler temporaries
VMEM_BYTES = 16 * 2 ** 20
VMEM_BUDGET_BYTES = int(VMEM_BYTES * 0.75)

PALLAS_BACKENDS = ("pallas", "pallas_sparse", "pallas_events")
KNOWN_BACKENDS = PALLAS_BACKENDS + ("float", "int_ref", "ref_events",
                                    "bitmacro")


class ContractError(AnalysisError):
    """A kernel contract is violated for this (program, dispatch) pair."""


@dataclass(frozen=True)
class ContractCheck:
    """One verified contract: name, where it was checked, the numbers."""
    contract: str
    where: str
    detail: str


@dataclass(frozen=True)
class KernelCall:
    """Checked geometry of one fused `pallas_call` dispatch."""
    name: str                  # "conv[i]" | "fc_stack"
    layer_names: tuple
    logical_widths: tuple      # (n_in, n_out_0, n_out_1, ...)
    padded_widths: tuple
    vmem_bytes: int


@dataclass(frozen=True)
class ContractReport:
    backend: str
    block_b: int
    frames: int
    calls: tuple               # tuple[KernelCall, ...] (empty off-device)
    checks: tuple              # tuple[ContractCheck, ...] all satisfied

    @property
    def vmem_bytes(self) -> int:
        """Largest single-call VMEM residency (calls run sequentially)."""
        return max((c.vmem_bytes for c in self.calls), default=0)


def _pad_lane(n: int) -> int:
    return max(LANE, -(-n // LANE) * LANE)


def _mesh_extents(mesh) -> dict:
    """Mesh axis extents from a `jax.sharding.Mesh` or a plain
    ``{axis_name: extent}`` dict (the device-free form `tools/
    check_invariants.py --mesh` validates geometries with)."""
    if isinstance(mesh, dict):
        return {str(k): int(v) for k, v in mesh.items()}
    return {str(n): int(s)
            for n, s in zip(mesh.axis_names, mesh.devices.shape)}


def _flat_width(spec) -> int:
    """Flattened output width of a layer (conv output maps flatten into
    the first FC's fan-in)."""
    if spec.state_shape:
        return int(np.prod(spec.state_shape))
    return int(spec.n_out)


def _check_chain(program, checks: list) -> None:
    """Fan-in / fan-out alignment across the whole stack: the property
    that keeps every gather row inside its weight tile."""
    cur: Optional[int] = None
    for idx, spec in enumerate(program.layers):
        name = f"{spec.kind}[{idx}] {spec.n_in}x{spec.n_out}"
        if spec.kind in ("fc", "readout"):
            if cur is not None and spec.n_in != cur:
                raise ContractError(
                    f"chain_alignment: fan-in {spec.n_in} != {cur} lanes "
                    "emitted by the previous layer", where=name)
        elif spec.kind == "conv" and spec.w is not None:
            # .shape, not np.asarray: float-domain programs compile under
            # jit/grad traces and tracers cannot materialize
            kh, kw, c_in = spec.w.shape[:3]
            if spec.n_in != kh * kw * c_in:
                raise ContractError(
                    f"chain_alignment: im2col fan-in {spec.n_in} != "
                    f"{kh}x{kw}x{c_in} patch width", where=name)
        cur = _flat_width(spec)
    checks.append(ContractCheck(
        "chain_alignment", "program",
        f"{len(program.layers)} layers aligned"))


def _call_vmem_bytes(widths: tuple, *, n_spiking: int, frames: int,
                     block_b: int, backend: str, gate_granularity: int,
                     emit_rasters: bool, streaming: bool,
                     staged_in_elems: int = 0) -> int:
    """VMEM bytes resident in one grid step of one fused call.

    ``staged_in_elems`` — raw input elements per frame of the streamed
    presentation (prod of ``cfg.in_shape`` for conv-led programs, the
    input-layer width otherwise). A K-frame megastep pre-stages the next
    K frames of every lane as one ``(K, B, *in_shape)`` float32 block
    alongside the kernel's own operands, so its residency scales with K
    too; pass it for the call that consumes the staged block (the first).
    """
    inp = _pad_lane(widths[0])
    outs = [_pad_lane(w) for w in widths[1:]]
    ins_p = [inp] + outs[:-1]
    n = frames * block_b * inp                       # spike block, int8
    n += sum(i * o for i, o in zip(ins_p, outs))     # weight tiles, int8
    n += len(widths[1:]) * 2 * 4                     # params rows
    n += 2 * sum(block_b * o * 4 for o in outs)      # V scratch + V out
    if streaming:
        n += sum(block_b * o * 4 for o in outs)      # v_init blocks
        n += frames * block_b * staged_in_elems * 4  # staged frame block
    if emit_rasters:
        n += frames * block_b * sum(outs[:n_spiking])
    if backend == "pallas_sparse":
        _, _, lanes = skip_layout(tuple(widths[:-1]), gate_granularity)
        n += lanes * 4
    if backend == "pallas_events":
        n += sum(i * 4 for i in ins_p) + LANE * 4    # row counters + fallback
        # prefix-sum operands: the int8 triangular ones matrix and the int32
        # position map of the tile, per layer
        n += sum(i * i + block_b * i * 4 for i in ins_p)
    return n


def _program_calls(program) -> list:
    """(name, layer_names, logical widths, n_spiking) per fused dispatch."""
    calls = []
    for i, spec in enumerate(program.int_conv_stack):
        calls.append((f"conv[{i}]",
                      (f"conv[{i}] {spec.n_in}x{spec.n_out}",),
                      (spec.n_in, spec.n_out), 1))
    stack = program.fc_stack
    if stack:
        names = tuple(f"{s.kind} {s.n_in}x{s.n_out}" for s in stack)
        widths = (stack[0].n_in,) + tuple(s.n_out for s in stack)
        calls.append(("fc_stack", names, widths, len(stack) - 1))
    return calls


def check_kernel_contracts(program, backend: str = "pallas", *,
                           frames: Optional[int] = None, block_b: int = 8,
                           gate_granularity: int = 1,
                           event_crossover: float = 1.0,
                           use_sparse: bool = False,
                           emit_rasters: bool = True,
                           streaming: bool = False,
                           vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
                           mesh=None) -> ContractReport:
    """Verify every kernel contract of dispatching ``program`` on
    ``backend`` with these parameters; raise `ContractError` naming the
    violated contract and call otherwise.

    ``frames`` is the per-dispatch raster length the VMEM estimate uses
    (default ``program.timesteps``; streaming ticks pass 1). Off-device
    backends (float / int_ref / ref_events) have no kernel contracts
    beyond chain alignment and return an empty-call report; ``bitmacro``
    additionally demands wrap arithmetic.

    ``mesh`` — a `jax.sharding.Mesh` or a plain ``{axis: extent}`` dict
    (no devices needed) — additionally verifies the mesh-execution
    contracts: float/bitmacro reject a mesh, the model-parallel row split
    of every fused call keeps chain alignment (per-shard row tiles of the
    same padded fan-in, reassembled by the integer psum), and the
    per-shard VMEM residency fits the budget.
    """
    if frames is None:
        frames = int(program.timesteps)
    checks: list = []
    if streaming:
        if not isinstance(frames, int) or frames < 1:
            raise ContractError(
                f"megastep: a streaming dispatch advances K >= 1 frames "
                f"per call, got K={frames!r}", where="stream")
        checks.append(ContractCheck(
            "megastep", "stream",
            f"K={frames} frame(s) per dispatch; spike/raster VMEM blocks "
            "scale linearly with K"))
    if backend not in KNOWN_BACKENDS:
        raise ContractError(
            f"unknown execution backend {backend!r}; have "
            f"{sorted(KNOWN_BACKENDS)}", where="backend")
    if backend != "float" and program.domain != "int":
        raise ContractError(
            f"backend {backend!r} executes int-domain programs only; this "
            f"program is domain={program.domain!r} "
            "(compile_network(..., domain='int'))", where="backend")
    if backend == "bitmacro" and program.clamp_mode != "wrap":
        raise ContractError(
            "bitmacro executes silicon wrap arithmetic; compile the "
            "program with clamp_mode='wrap'", where="backend")
    n_data = n_model = 1
    if mesh is not None:
        if backend in ("float", "bitmacro"):
            raise ContractError(
                f"mesh_axes: backend {backend!r} has no mesh execution "
                "(float reductions are not order-exact; bitmacro state "
                "lives in host BitMacro objects)", where="mesh")
        sizes = _mesh_extents(mesh)
        n_data = sizes.get("data", 1)
        n_model = sizes.get("model", 1)
        if n_data < 1 or n_model < 1:
            raise ContractError(
                f"mesh_axes: axis extents must be >= 1, got data={n_data} "
                f"model={n_model}", where="mesh")
        checks.append(ContractCheck(
            "mesh_axes", "mesh",
            f"data={n_data} (lanes/banks partition) x model={n_model} "
            f"(row-tiled fan-in partition); axes {sorted(sizes)}"))
    _check_chain(program, checks)

    if gate_granularity not in GATE_GRANULARITIES:
        raise ContractError(
            f"gate_granularity: must be one of {GATE_GRANULARITIES}, got "
            f"{gate_granularity}", where=backend)
    if (gate_granularity != 1 and backend != "pallas_sparse"
            and not use_sparse):
        raise ContractError(
            f"gate_granularity: sub-tile gating (granularity "
            f"{gate_granularity}) needs the gated path (pallas_sparse, or "
            f"int_ref with use_sparse=True), not {backend!r}",
            where=backend)
    if backend == "pallas_events" and not 0.0 <= event_crossover <= 1.0:
        raise ContractError(
            f"event_crossover: dense-fallback crossover must lie in "
            f"[0, 1], got {event_crossover}", where=backend)

    if backend not in PALLAS_BACKENDS:
        return ContractReport(backend=backend, block_b=block_b,
                              frames=frames, calls=(), checks=tuple(checks))

    if not isinstance(block_b, int) or block_b < 1:
        raise ContractError(
            f"grid_divisibility: block_b must be a positive int, got "
            f"{block_b!r}", where=backend)
    checks.append(ContractCheck(
        "grid_divisibility", backend,
        f"block_b={block_b}; B pads to the next multiple, grid=ceil(B/"
        f"{block_b})"))

    # the K-frame megastep stages a (K, B, *in_shape) float32 frame block
    # for the call that consumes the raw presentation (the first)
    staged_in_elems = 0
    if streaming:
        staged_in_elems = int(np.prod(
            program.cfg.in_shape if program.layers[0].kind == "conv"
            else program.layers[0].state_shape))

    calls = []
    for ci, (name, layer_names, widths, n_spiking) in enumerate(
            _program_calls(program)):
        if backend == "pallas_sparse":
            try:
                n_cols, _, _ = skip_layout(tuple(widths[:-1]),
                                           gate_granularity)
            except ValueError as e:
                raise ContractError(f"skip_layout: {e}", where=name) from e
            checks.append(ContractCheck(
                "skip_layout", name,
                f"{sum(n_cols)} gate columns <= MAX_SKIP_COLS="
                f"{MAX_SKIP_COLS} at granularity {gate_granularity}"))
        if backend == "pallas_events":
            n_layers = len(widths) - 1
            if n_layers > LANE:
                raise ContractError(
                    f"fallback_columns: events mode carries one fallback "
                    f"column per layer in a {LANE}-lane output; got "
                    f"{n_layers} layers", where=name)
            caps = tuple(_pad_lane(w) for w in widths[:-1])
            checks.append(ContractCheck(
                "gather_bounds", name,
                f"event-list capacity per layer = padded fan-in {caps}; "
                "prefix-sum/one-hot indices < capacity by construction"))
        vmem = _call_vmem_bytes(
            widths, n_spiking=n_spiking, frames=frames, block_b=block_b,
            backend=backend, gate_granularity=gate_granularity,
            emit_rasters=emit_rasters, streaming=streaming,
            staged_in_elems=staged_in_elems if ci == 0 else 0)
        if vmem > vmem_budget_bytes:
            raise ContractError(
                f"vmem_budget: one grid step holds {vmem} bytes resident "
                f"(T={frames} spike block + staged frames + weight tiles "
                f"+ V tiles + counters) > budget {vmem_budget_bytes} "
                f"({VMEM_BYTES} per core with compiler margin); shrink "
                "block_b, chunk the presentation, or split the stack",
                where=name)
        checks.append(ContractCheck(
            "vmem_budget", name,
            f"{vmem} bytes resident <= {vmem_budget_bytes}"))
        if mesh is not None:
            from repro.kernels.fused_snn_net.ops import mesh_padded_widths
            mw = mesh_padded_widths(widths, n_model)
            rows = tuple(w // n_model for w in mw[:-1])
            if any(w % n_model for w in mw):
                raise ContractError(       # unreachable by construction
                    f"mesh_split: padded widths {mw} do not divide "
                    f"n_model={n_model}", where=name)
            # per-shard residency: weight tiles shrink 1/n_model (each
            # shard holds its row tile), spike/V blocks stay full width
            # (cur is replicated, the partial V is full width pre-psum)
            ins_p = [_pad_lane(widths[0])] + [_pad_lane(w)
                                              for w in widths[1:-1]]
            w_bytes = sum(i * _pad_lane(o)
                          for i, o in zip(ins_p, widths[1:]))
            vmem_shard = vmem - w_bytes + -(-w_bytes // n_model)
            if vmem_shard > vmem_budget_bytes:
                raise ContractError(
                    f"mesh_split: one model shard holds {vmem_shard} "
                    f"bytes resident (weights/{n_model} + full-width "
                    f"spike/V blocks) > budget {vmem_budget_bytes}",
                    where=name)
            checks.append(ContractCheck(
                "mesh_split", name,
                f"fan-in rows {mw[:-1]} split {n_model}-way into "
                f"{rows}-row shard tiles (chain alignment preserved: "
                f"every shard slices the same padded fan-in; psum "
                f"reassembles the full width); per-shard residency "
                f"{vmem_shard} bytes <= {vmem_budget_bytes}"))
        calls.append(KernelCall(
            name=name, layer_names=layer_names,
            logical_widths=tuple(int(w) for w in widths),
            padded_widths=tuple(_pad_lane(w) for w in widths),
            vmem_bytes=vmem))
    return ContractReport(backend=backend, block_b=block_b, frames=frames,
                          calls=tuple(calls), checks=tuple(checks))
