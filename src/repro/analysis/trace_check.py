"""Jaxpr-level verification of the compiled kernel dispatches (DESIGN.md
§7.5 "Trace verification").

`program_check` and `kernel_contracts` prove properties *re-derived from
config*; this pass verifies the artifact JAX actually compiles. Every
registered int backend's real dispatch — `ops.fused_snn_net` (batch), the
``v_init`` step entry, the K-frame megastep int tail (fused call + readout
trajectory cumsum), and the model-parallel row-partial tick of
`fused_snn_net_mesh` under an *abstract* mesh (`jax.make_jaxpr(...,
axis_env=...)`, no devices) — is traced to a closed jaxpr and statically
checked:

  property         | what is verified on the traced jaxpr
  -----------------|----------------------------------------------------
  dtype            | no float avals anywhere on the int-domain path, no
                   | ``convert_element_type`` to float; every
                   | `dot_general` accumulates in int32
  determinism      | no RNG primitives; float reductions are excluded by
                   | the dtype rule, so nothing reorder-sensitive remains
  clamp placement  | exactly the contracted number of V-word clamp heads
                   | (``max`` against V_MIN / ``% V_SPAN``, incl. their
                   | jnp ``jit`` wrappings) per dispatch; every clamp in
                   | the program's mode; no clamp inside a predicated
                   | (`@pl.when` / `lax.cond`) branch — partials must add
                   | unclamped and the single clamp runs after; every
                   | SpikeCheck (``ge``) SSA chain hits a clamp before
                   | reaching a `dot_general`/`psum` accumulation source;
                   | no clamp upstream of a cross-shard ``psum`` (the
                   | AccV2V reduction sums *unclamped* partials)
  bounds           | every ``dynamic_slice`` start and every dynamic
                   | Pallas ``get``/``swap`` row index is provably
                   | in-bounds by interval analysis (event-list gather
                   | indices bounded by the padded fan-in via the
                   | prefix-sum/one-hot decode; mesh row-tile starts
                   | bounded by ``axis_index * rows``)

Violations raise `TraceError` naming the primitive, the eqn's region path
inside the jaxpr, and the backend/surface. The companion `trace_cost`
module walks the same jaxprs into a `TraceCostReport` (MXU MACs, HBM<->
VMEM bytes) whose macro-cycle tally must close exactly against
`isa.count_network_instructions` dense counts.

The clamp-dominance argument has one documented blind spot: dataflow
through Pallas *refs* (`get`/`swap`) is invisible to the SSA walk, so a
ref-mediated accumulate->clamp chain (the event-list kernel) is covered by
the clamp-*count* closure and the no-clamp-in-branch rule rather than the
per-read dominance walk — the walk simply terminates at the ref read.

Entry points: `check_trace(program, backend)` (per-backend `TraceReport`,
memoized by geometry) and the low-level `check_closed_jaxpr(jaxpr,
expect)` that the negative-path tests drive with deliberately broken
kernels. `analysis.validate_program` runs `check_trace` for every int
backend by default; `tools/check_invariants.py --trace` is the CI entry.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis.intervals import AnalysisError, Interval
from repro.core.quant import V_MAX, V_MIN, V_SPAN

#: int backends whose dispatch is an XLA computation we can trace
TRACE_BACKENDS = ("int_ref", "pallas", "pallas_sparse", "pallas_events")
#: int backends that execute on the host (numpy / BitMacro objects) — no
#: jaxpr exists; `check_trace` returns a named skip row for them
HOST_BACKENDS = ("ref_events", "bitmacro")
#: the dispatch surfaces one backend trace covers
SURFACES = ("batch", "step", "megastep", "mesh")
#: abstract mesh extents the mesh surface traces under by default
DEFAULT_MESH_AXES = (("data", 2), ("model", 2))

_CALL_PRIMS = {"jit", "closed_call", "remat2", "custom_jvp_call",
               "custom_vjp_call"}
_RNG_PRIMS = {"threefry2x32", "random_seed", "random_bits", "random_wrap",
              "random_unwrap", "random_fold_in", "random_gamma",
              "rng_uniform", "rng_bit_generator"}
#: primitives a clamp-head call body may consist of (a pure elementwise
#: chain — anything else means the call *contains* a clamp rather than
#: *being* one, e.g. the outer jit'd dispatch itself)
_ELEMENTWISE = {"max", "min", "rem", "add", "sub", "mul", "neg", "sign",
                "convert_element_type", "select_n", "lt", "le", "gt", "ge",
                "eq", "ne", "and", "or", "not", "xor", "broadcast_in_dim",
                "reshape", "squeeze", "expand_dims", "clamp", "div",
                "floor", "integer_pow", "copy"}
#: interval/dominance passthrough primitives (bounds preserved or shrunk)
_PASSTHROUGH = {"convert_element_type", "broadcast_in_dim", "reshape",
                "squeeze", "expand_dims", "slice", "transpose", "copy",
                "rev", "reduce_max", "reduce_min", "stop_gradient",
                "reduce_precision", "abs", "multiple_of"}

_MAX_DEPTH = 64


class TraceError(AnalysisError):
    """A traced dispatch violates the ISA contract (the finding names the
    primitive, its region path in the jaxpr, and the backend/surface)."""


@dataclass(frozen=True)
class TraceCheck:
    """One verified trace property: name, where it held, the numbers."""
    prop: str
    where: str
    detail: str


@dataclass(frozen=True)
class TraceExpectation:
    """What the checker demands of one traced dispatch surface."""
    where: str                     # "backend:surface:call" finding label
    neuron: str = "rmp"
    clamp_mode: str = "saturate"
    n_spiking: int = 1
    mesh_axes: tuple = ()          # (("data", n), ("model", m)) on mesh
    extra_clamps: int = 0          # heads beyond the neuron contract

    @property
    def expected_clamps(self) -> int:
        per = {"if": 1, "lif": 2, "rmp": 2}[self.neuron]
        if self.clamp_mode == "wrap":
            per += 1               # the SpikeCheck comparison itself wraps
        return self.n_spiking * per + self.extra_clamps


@dataclass(frozen=True)
class SurfaceTrace:
    """Checked facts of one traced (surface, call) dispatch."""
    surface: str
    call: str
    clamps: int
    spike_reads: int
    bounds_checked: int
    eqns: int


@dataclass(frozen=True)
class TraceReport:
    backend: str
    surfaces: tuple                # tuple[SurfaceTrace, ...]
    checks: tuple                  # tuple[TraceCheck, ...] all satisfied
    cost: Any = None               # trace_cost.TraceCostReport (batch)


# ---------------------------------------------------------------------------
# jaxpr regions: one (sub)jaxpr + const env + parent linkage
# ---------------------------------------------------------------------------

def _is_literal(atom) -> bool:
    return hasattr(atom, "val") and not hasattr(atom, "count")


def _aval(atom):
    return getattr(atom, "aval", None)


def _aval_dtype(atom):
    av = _aval(atom)
    dt = getattr(av, "dtype", None)
    if dt is None:
        dt = getattr(getattr(av, "inner_aval", None), "dtype", None)
    return dt


def _aval_shape(atom):
    av = _aval(atom)
    shape = getattr(av, "shape", None)
    if shape is None:
        shape = getattr(getattr(av, "inner_aval", None), "shape", None)
    return shape


class _Region:
    """One jaxpr nesting level: local defs, const bindings, the mapping of
    its invars onto parent atoms, and whether it executes predicated."""

    __slots__ = ("jaxpr", "path", "parent", "bindings", "consts",
                 "predicated", "axis_sizes", "defs", "carry_facts")

    def __init__(self, jaxpr, consts, path, parent=None, bindings=None,
                 predicated=False, axis_sizes=None, carry_facts=None):
        self.jaxpr = jaxpr
        self.path = path
        self.parent = parent
        self.bindings = bindings or {}
        self.carry_facts = carry_facts or {}
        self.predicated = predicated
        self.axis_sizes = dict(axis_sizes if axis_sizes is not None
                               else (parent.axis_sizes if parent else {}))
        self.consts = dict(zip(jaxpr.constvars, consts))
        self.defs = {}
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                self.defs[ov] = eqn


def _open(j) -> tuple:
    """(jaxpr, consts) of a ClosedJaxpr or a bare Jaxpr."""
    inner = getattr(j, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner, list(getattr(j, "consts", ()) or ())
    return j, []


def _looks_like_jaxpr(obj) -> bool:
    return (hasattr(obj, "eqns") and hasattr(obj, "invars")) or (
        hasattr(obj, "jaxpr") and hasattr(getattr(obj, "jaxpr"), "eqns"))


def _grid_size(eqn) -> int:
    """Static grid-step count of a pallas_call eqn (1 when unknown)."""
    gm = eqn.params.get("grid_mapping")
    grid = getattr(gm, "grid", None)
    if grid is None:
        grid = eqn.params.get("grid") or ()
    try:
        return int(np.prod([int(g) for g in grid])) if grid else 1
    except (TypeError, ValueError):
        return 1


def _sub_regions(eqn, region) -> list:
    """Child regions of one eqn, with invar bindings where the primitive's
    calling convention is known (version-defensive: unknown primitives that
    carry jaxpr params still get an unbound region, so no eqn is ever
    skipped — checks just lose cross-boundary const facts there)."""
    p = eqn.primitive.name
    params = eqn.params
    out = []
    if p in _CALL_PRIMS or (p.endswith("_call") and p != "pallas_call"
                            and ("jaxpr" in params or "call_jaxpr" in params)):
        body, consts = _open(params.get("jaxpr", params.get("call_jaxpr")))
        name = params.get("name", p)
        out.append(_Region(body, consts, f"{region.path}/{name}", region,
                           dict(zip(body.invars, eqn.invars)),
                           region.predicated))
    elif p == "scan":
        body, consts = _open(params["jaxpr"])
        nc = int(params.get("num_consts", 0))
        ncar = int(params.get("num_carry", 0))
        bind = dict(zip(body.invars[:nc], eqn.invars[:nc]))
        # xs slices: each body slice var is an element of the parent xs —
        # sound for intervals and for upstream walks (subset relation)
        bind.update(zip(body.invars[nc + ncar:], eqn.invars[nc + ncar:]))
        out.append(_Region(body, consts, f"{region.path}/scan", region,
                           bind, region.predicated,
                           carry_facts=_scan_carry_facts(
                               eqn, body, nc, ncar, region)))
    elif p == "while":
        cond, cc = _open(params["cond_jaxpr"])
        body, bc = _open(params["body_jaxpr"])
        cn = int(params.get("cond_nconsts", 0))
        bn = int(params.get("body_nconsts", 0))
        out.append(_Region(cond, cc, f"{region.path}/while.cond", region,
                           dict(zip(cond.invars[:cn], eqn.invars[:cn])),
                           region.predicated))
        # carry vars deliberately stay unbound: binding them to the init
        # values would be wrong from iteration 2 on
        out.append(_Region(body, bc, f"{region.path}/while.body", region,
                           dict(zip(body.invars[:bn],
                                    eqn.invars[cn:cn + bn])),
                           region.predicated))
    elif p == "cond":
        for k, br in enumerate(params.get("branches", ())):
            body, consts = _open(br)
            out.append(_Region(body, consts,
                               f"{region.path}/cond[{k}]", region,
                               dict(zip(body.invars, eqn.invars[1:])),
                               True))
    elif p == "pallas_call":
        body, consts = _open(params["jaxpr"])
        # kernel invars = [*input refs, *output refs, *scratch]; the zip
        # binds exactly the input-ref prefix to the operand arrays
        out.append(_Region(body, consts, f"{region.path}/pallas_call",
                           region, dict(zip(body.invars, eqn.invars)),
                           region.predicated))
    else:
        for key, val in params.items():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for k, v in enumerate(vals):
                if _looks_like_jaxpr(v):
                    body, consts = _open(v)
                    out.append(_Region(body, consts,
                                       f"{region.path}/{p}.{key}[{k}]",
                                       region, None, region.predicated))
    return out


def _scan_carry_facts(eqn, body, nc: int, ncar: int, region) -> dict:
    """Intervals of affine scan carries: a carry initialized to a known
    scalar and advanced by ``add(carry, const)`` (the lowered
    `fori_loop` counter) is bounded over all ``length`` iterations; a
    carry returned unchanged keeps its init value. Keyed by body invar."""
    length = eqn.params.get("length")
    if length is None:
        return {}
    length = int(length)
    defs = {ov: e for e in body.eqns for ov in e.outvars}
    facts = {}
    for j in range(ncar):
        bv = body.invars[nc + j]
        ov = body.outvars[j]
        c0 = _const_scalar(eqn.invars[nc + j], region)
        if c0 is None or isinstance(c0, float):
            continue
        if ov is bv:                      # carry threaded through unchanged
            facts[bv] = Interval(int(c0), int(c0))
            continue
        d = defs.get(ov)
        if d is None or d.primitive.name != "add" or len(d.invars) != 2:
            continue
        a, b = d.invars
        step = None
        if a is bv:
            step = _const_scalar(b, _Region(body, [], ""))
        elif b is bv:
            step = _const_scalar(a, _Region(body, [], ""))
        if step is None or isinstance(step, float):
            continue
        lo = int(c0) + min(0, (length - 1) * int(step))
        hi = int(c0) + max(0, (length - 1) * int(step))
        facts[bv] = Interval(lo, hi)
    return facts


def _walk(region):
    """Yield (eqn, region) for every eqn at every nesting depth."""
    for eqn in region.jaxpr.eqns:
        yield eqn, region
        for sub in _sub_regions(eqn, region):
            yield from _walk(sub)


def root_region(closed_jaxpr, *, axis_sizes: Optional[dict] = None,
                path: str = "") -> _Region:
    """Wrap a traced `ClosedJaxpr` for walking/checking. ``axis_sizes``
    supplies mesh axis extents (``{"model": 4, ...}``) for `axis_index`
    interval facts on traces made under an ``axis_env``; ``path`` labels
    findings."""
    jaxpr, consts = _open(closed_jaxpr)
    return _Region(jaxpr, consts, path, axis_sizes=axis_sizes or {})


# ---------------------------------------------------------------------------
# const propagation (through jit boundaries and elementwise chains)
# ---------------------------------------------------------------------------

_CONST_BINOPS = {
    "add": lambda a, b: a + b, "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b, "max": max, "min": min,
    "eq": lambda a, b: int(a == b), "ne": lambda a, b: int(a != b),
    "lt": lambda a, b: int(a < b), "le": lambda a, b: int(a <= b),
    "gt": lambda a, b: int(a > b), "ge": lambda a, b: int(a >= b),
    "and": lambda a, b: int(bool(a) and bool(b)),
    "or": lambda a, b: int(bool(a) or bool(b)),
}


def _const_scalar(atom, region, depth: int = 0):
    """The python scalar an atom is statically known to hold, evaluated
    through passthroughs, call boundaries, `select_n` and elementwise
    arithmetic/comparisons (jnp's ``remainder`` computes its divisor as
    ``select_n(eq(d, 0), d, 1)`` — head detection needs to see through
    that); None when not statically known."""
    if depth > _MAX_DEPTH:
        return None
    val = None
    if _is_literal(atom):
        val = atom.val
    elif atom in region.consts:
        val = region.consts[atom]
    elif atom in region.bindings and region.parent is not None:
        return _const_scalar(region.bindings[atom], region.parent, depth + 1)
    else:
        eqn = region.defs.get(atom)
        if eqn is None:
            return None
        p = eqn.primitive.name
        if p in ("convert_element_type", "broadcast_in_dim", "reshape",
                 "squeeze", "expand_dims", "copy"):
            return _const_scalar(eqn.invars[0], region, depth + 1)
        if p in _CALL_PRIMS:
            subs = _sub_regions(eqn, region)
            if len(subs) == 1:
                k = list(eqn.outvars).index(atom)
                return _const_scalar(subs[0].jaxpr.outvars[k], subs[0],
                                     depth + 1)
            return None
        if p == "select_n":
            pred = _const_scalar(eqn.invars[0], region, depth + 1)
            if pred is not None and 0 <= int(pred) < len(eqn.invars) - 1:
                return _const_scalar(eqn.invars[1 + int(pred)], region,
                                     depth + 1)
            return None
        if p == "neg":
            a = _const_scalar(eqn.invars[0], region, depth + 1)
            return -a if a is not None else None
        if p == "not":
            a = _const_scalar(eqn.invars[0], region, depth + 1)
            return int(not a) if a is not None else None
        if p in _CONST_BINOPS and len(eqn.invars) == 2:
            a = _const_scalar(eqn.invars[0], region, depth + 1)
            b = _const_scalar(eqn.invars[1], region, depth + 1)
            if a is None or b is None:
                return None
            try:
                return _CONST_BINOPS[p](a, b)
            except (TypeError, ValueError):
                return None
        return None
    try:
        arr = np.asarray(val)
        return arr.reshape(()).item() if arr.size == 1 else None
    except (TypeError, ValueError):
        return None


# ---------------------------------------------------------------------------
# clamp-head classification
# ---------------------------------------------------------------------------

def _bare_clamp_kind(eqn, region) -> Optional[str]:
    """'saturate'/'wrap' when this single eqn is a V-word clamp head: the
    ``max`` against V_MIN (``jnp.clip``'s lower arm — the counted head; the
    paired ``min`` rides along) or the ``rem`` by V_SPAN of the wrap."""
    p = eqn.primitive.name
    if p == "max" and any(_const_scalar(a, region) == V_MIN
                          for a in eqn.invars):
        return "saturate"
    if p == "rem" and len(eqn.invars) == 2 and \
            _const_scalar(eqn.invars[1], region) == V_SPAN:
        return "wrap"
    if p == "clamp":               # direct lax.clamp lowering (version drift)
        lo = _const_scalar(eqn.invars[0], region)
        hi = _const_scalar(eqn.invars[2], region)
        if lo == V_MIN and hi == V_MAX:
            return "saturate"
    return None


def _head_scan(region, kinds: list, depth: int) -> bool:
    """Scan a candidate head body: collect bare clamp patterns, allow
    nested small elementwise calls (``remainder`` wraps a ``_where``
    jit), reject anything non-elementwise. True = body is elementwise."""
    if depth > 4 or len(region.jaxpr.eqns) > 16:
        return False
    for e in region.jaxpr.eqns:
        k = _bare_clamp_kind(e, region)
        if k is not None:
            kinds.append(k)
            continue
        if e.primitive.name in _CALL_PRIMS:
            subs = _sub_regions(e, region)
            if len(subs) != 1 or not _head_scan(subs[0], kinds, depth + 1):
                return False
            continue
        if e.primitive.name not in _ELEMENTWISE:
            return False
    return True


def _clamp_kind(eqn, region) -> Optional[str]:
    """Clamp-head kind of an eqn: a bare head, or a small pure-elementwise
    call (jnp's ``clip``/``remainder`` jit wrappers, nested calls
    allowed) containing exactly one head pattern. A call with control
    flow / dots in its body *contains* clamps but is not itself a head."""
    kind = _bare_clamp_kind(eqn, region)
    if kind is not None:
        return kind
    if eqn.primitive.name not in _CALL_PRIMS:
        return None
    subs = _sub_regions(eqn, region)
    if len(subs) != 1:
        return None
    kinds: list = []
    if not _head_scan(subs[0], kinds, 0):
        return None
    return kinds[0] if len(kinds) == 1 else None


def _collect_clamps(region, out: list, pred: bool) -> None:
    """All clamp heads under ``region`` as (eqn, region, kind,
    predicated); recognized heads are not descended into (their inner
    ``max``/``rem`` would double-count)."""
    for eqn in region.jaxpr.eqns:
        kind = _clamp_kind(eqn, region)
        if kind is not None:
            out.append((eqn, region, kind, pred))
            continue
        for sub in _sub_regions(eqn, region):
            _collect_clamps(sub, out, pred or sub.predicated)


# ---------------------------------------------------------------------------
# interval analysis (the bounds pass)
# ---------------------------------------------------------------------------

def _dtype_interval(atom) -> Optional[Interval]:
    dt = _aval_dtype(atom)
    if dt is None:
        return None
    dt = np.dtype(dt)
    if dt == np.bool_:
        return Interval(0, 1)
    if np.issubdtype(dt, np.integer) and dt.itemsize == 1:
        ii = np.iinfo(dt)
        return Interval(int(ii.min), int(ii.max))
    return None


def _value_interval(val) -> Optional[Interval]:
    try:
        arr = np.asarray(val)
        if arr.dtype == np.bool_:
            arr = arr.astype(np.int32)
        if arr.size == 0 or not np.issubdtype(arr.dtype, np.number):
            return None
        return Interval(int(arr.min()), int(arr.max()))
    except (TypeError, ValueError):
        return None


def _cmp_interval(p: str, a: Optional[Interval], b: Optional[Interval]
                  ) -> Interval:
    """Bool interval of a comparison from its operand intervals."""
    if a is not None and b is not None:
        if p in ("lt", "le"):
            strict = p == "lt"
            if (a.hi < b.lo) or (not strict and a.hi <= b.lo):
                return Interval(1, 1)
            if (a.lo > b.hi) or (strict and a.lo >= b.hi):
                return Interval(0, 0)
        elif p in ("gt", "ge"):
            strict = p == "gt"
            if (a.lo > b.hi) or (not strict and a.lo >= b.hi):
                return Interval(1, 1)
            if (a.hi < b.lo) or (strict and a.hi <= b.lo):
                return Interval(0, 0)
        elif p == "eq" and (a.hi < b.lo or a.lo > b.hi):
            return Interval(0, 0)
        elif p == "ne" and (a.hi < b.lo or a.lo > b.hi):
            return Interval(1, 1)
    return Interval(0, 1)


def _is_triangular_ones(atom, region) -> bool:
    """True when ``atom`` is an iota-vs-iota comparison (a triangular 0/1
    matrix), possibly behind dtype converts."""
    for _ in range(_MAX_DEPTH):
        if _is_literal(atom):
            return False
        if atom in region.bindings and region.parent is not None:
            atom, region = region.bindings[atom], region.parent
            continue
        eqn = region.defs.get(atom)
        if eqn is None:
            return False
        p = eqn.primitive.name
        if p == "convert_element_type":
            atom = eqn.invars[0]
            continue
        if p not in ("le", "lt", "ge", "gt"):
            return False
        dims = []
        for a in eqn.invars:
            d = None if _is_literal(a) else region.defs.get(a)
            if d is None or d.primitive.name not in ("iota",
                                                     "broadcasted_iota"):
                return False
            dims.append(d.params.get("dimension"))
        return dims[0] != dims[1]
    return False


def _chain_has_prefix_sum(atom, region, limit: int = 300) -> bool:
    """True when the def chain of ``atom`` (crossing call boundaries)
    contains a prefix sum — a `dot_general` against a triangular ones
    matrix, the structural certificate of the event-list one-hot
    decode."""
    stack, seen, steps = [(atom, region)], set(), 0
    while stack and steps < limit:
        a, r = stack.pop()
        steps += 1
        if _is_literal(a):
            continue
        key = (id(r), a)
        if key in seen:
            continue
        seen.add(key)
        eqn = r.defs.get(a)
        if eqn is None:
            if a in r.bindings and r.parent is not None:
                stack.append((r.bindings[a], r.parent))
            continue
        p = eqn.primitive.name
        if p == "dot_general" and any(_is_triangular_ones(v, r)
                                      for v in eqn.invars):
            return True
        subs = _sub_regions(eqn, r) if p in _CALL_PRIMS else ()
        if subs:
            k = list(eqn.outvars).index(a)
            stack.append((subs[0].jaxpr.outvars[k], subs[0]))
        else:
            stack.extend((iv, r) for iv in eqn.invars)
    return False


def _onehot_bound(eqn, region, env, depth) -> Optional[Interval]:
    """Interval of ``reduce_sum(select_n(pred, 0, iota-derived))`` when
    ``pred``'s chain contains a prefix-sum comparison — the event-list one-hot
    decode. At most one position matches (the running count of a {0,1}
    raster — the range pass's raster fact — first reaches p+1 exactly
    once), so the sum is bounded by the iota values themselves: the padded
    fan-in, which is the `gather_bounds` kernel contract."""
    op, r, d = eqn.invars[0], region, None
    for _ in range(_MAX_DEPTH):    # unwrap jnp.where's jit and bindings
        if _is_literal(op):
            return None
        if op in r.bindings and r.parent is not None:
            op, r = r.bindings[op], r.parent
            continue
        d = r.defs.get(op)
        if d is None:
            break
        if d.primitive.name in _CALL_PRIMS:
            subs = _sub_regions(d, r)
            if len(subs) == 1:
                op, r = subs[0].jaxpr.outvars[list(d.outvars).index(op)], \
                    subs[0]
                continue
        elif d.primitive.name in ("convert_element_type", "reshape",
                                  "broadcast_in_dim", "squeeze", "copy"):
            op = d.invars[0]
            continue
        break
    if d is None or d.primitive.name != "select_n" or len(d.invars) != 3:
        return None
    pred, case0, case1 = d.invars
    for zero, cand in ((case0, case1), (case1, case0)):
        if _const_scalar(zero, r) == 0 and _chain_has_prefix_sum(pred, r):
            return _ival(cand, r, env, depth + 1)
    return None


def _ival(atom, region, env: dict, depth: int) -> Optional[Interval]:
    """Best-effort interval of an atom's value (None = unknown)."""
    if depth > _MAX_DEPTH:
        return None
    if _is_literal(atom):
        return _value_interval(atom.val)
    key = (id(region), atom)
    if key in env:
        return env[key]
    env[key] = None                # cycle guard
    iv = _ival_raw(atom, region, env, depth)
    env[key] = iv
    return iv


def _ival_raw(atom, region, env, depth) -> Optional[Interval]:
    if atom in region.consts:
        return _value_interval(region.consts[atom])
    if atom in region.bindings and region.parent is not None:
        return _ival(region.bindings[atom], region.parent, env, depth + 1)
    eqn = region.defs.get(atom)
    if eqn is None:                # unbound invar (carry, kernel ref, ...)
        fact = region.carry_facts.get(atom)
        return fact if fact is not None else _dtype_interval(atom)
    p = eqn.primitive.name

    def op(k):
        return _ival(eqn.invars[k], region, env, depth + 1)

    if p in _PASSTHROUGH:
        iv = op(0)
        return iv if iv is not None else _dtype_interval(atom)
    if p == "add":
        a, b = op(0), op(1)
        return a + b if a is not None and b is not None else None
    if p == "sub":
        a, b = op(0), op(1)
        return a - b if a is not None and b is not None else None
    if p == "mul":
        a, b = op(0), op(1)
        if a is None or b is None:
            return None
        prods = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
        return Interval(min(prods), max(prods))
    if p == "neg":
        a = op(0)
        return Interval(-a.hi, -a.lo) if a is not None else None
    if p == "max":
        a, b = op(0), op(1)
        if a is None or b is None:
            return None
        return Interval(max(a.lo, b.lo), max(a.hi, b.hi))
    if p == "min":
        a, b = op(0), op(1)
        if a is None or b is None:
            return None
        return Interval(min(a.lo, b.lo), min(a.hi, b.hi))
    if p == "div":
        d = _const_scalar(eqn.invars[1], region)
        a = op(0)
        if d is None or isinstance(d, float) or d <= 0 or a is None \
                or a.lo < 0:
            return None
        return Interval(a.lo // int(d), a.hi // int(d))
    if p == "rem":
        d = _const_scalar(eqn.invars[1], region)
        if d is None or d == 0:
            return None
        d = abs(int(d))
        a = op(0)
        if a is not None and a.lo >= 0:
            return Interval(0, d - 1)
        return Interval(-(d - 1), d - 1)
    if p == "clamp":
        lo, hi = op(0), op(2)
        if lo is not None and hi is not None:
            return Interval(lo.lo, hi.hi)
        return None
    if p == "select_n":
        pred = op(0)
        cases = eqn.invars[1:]
        if pred is not None and pred.lo == pred.hi and \
                0 <= pred.lo < len(cases):
            return _ival(cases[int(pred.lo)], region, env, depth + 1)
        ivs = [_ival(c, region, env, depth + 1) for c in cases]
        if any(iv is None for iv in ivs):
            return None
        return Interval(min(iv.lo for iv in ivs),
                        max(iv.hi for iv in ivs))
    if p in ("lt", "le", "gt", "ge", "eq", "ne"):
        return _cmp_interval(p, op(0), op(1))
    if p in ("and", "or", "not", "xor"):
        return (Interval(0, 1) if np.dtype(_aval_dtype(atom)) == np.bool_
                else None)
    if p in ("iota", "broadcasted_iota"):
        shape = _aval_shape(atom)
        dim = eqn.params.get("dimension", 0)
        if shape:
            return Interval(0, max(int(shape[int(dim)]) - 1, 0))
        return None
    if p == "axis_index":
        name = str(eqn.params.get("axis_name"))
        n = region.axis_sizes.get(name)
        return Interval(0, int(n) - 1) if n else None
    if p == "reduce_sum":
        onehot = _onehot_bound(eqn, region, env, depth)
        if onehot is not None:
            return onehot
        a = op(0)
        in_shape, out_shape = _aval_shape(eqn.invars[0]), _aval_shape(atom)
        if a is None or in_shape is None:
            return None
        n_in = int(np.prod(in_shape)) if in_shape else 1
        n_out = int(np.prod(out_shape)) if out_shape else 1
        n = max(n_in // max(n_out, 1), 1)
        return Interval(min(a.lo * n, a.lo), max(a.hi * n, a.hi))
    if p == "cumsum":
        a = op(0)
        shape = _aval_shape(atom)
        if a is None or shape is None:
            return None
        n = int(shape[int(eqn.params.get("axis", 0))]) if shape else 1
        return Interval(min(a.lo * n, a.lo), max(a.hi * n, a.hi))
    if p == "psum":
        a = op(0)
        axes = eqn.params.get("axes", ())
        n = 1
        for ax in axes:
            n *= int(region.axis_sizes.get(str(ax), 1))
        if a is None:
            return None
        return Interval(min(a.lo * n, a.lo), max(a.hi * n, a.hi))
    if p in _CALL_PRIMS:
        subs = _sub_regions(eqn, region)
        if len(subs) == 1:
            k = list(eqn.outvars).index(atom)
            return _ival(subs[0].jaxpr.outvars[k], subs[0], env, depth + 1)
    return None


# ---------------------------------------------------------------------------
# the four passes
# ---------------------------------------------------------------------------

def _check_dtypes(root: _Region, expect: TraceExpectation, checks: list
                  ) -> int:
    n = 0
    for eqn, region in _walk(root):
        n += 1
        p = eqn.primitive.name
        if p in _RNG_PRIMS:
            raise TraceError(
                f"determinism: RNG primitive '{p}' at {region.path or '/'}"
                f" — int-domain dispatches must be replay-exact",
                where=expect.where)
        for a in (*eqn.invars, *eqn.outvars):
            dt = _aval_dtype(a)
            if dt is not None and np.issubdtype(np.dtype(dt), np.floating):
                raise TraceError(
                    f"dtype: float {np.dtype(dt).name} aval on primitive "
                    f"'{p}' at {region.path or '/'} — the int domain "
                    "admits no float math (a cast, a float constant, or a "
                    "float reduction leaked in)", where=expect.where)
        if p == "dot_general":
            odt = _aval_dtype(eqn.outvars[0])
            if odt is None or np.dtype(odt) != np.dtype(np.int32):
                raise TraceError(
                    f"dtype: dot_general accumulates in "
                    f"{np.dtype(odt).name if odt is not None else '?'} at "
                    f"{region.path or '/'} — AccW2V must accumulate int32",
                    where=expect.where)
    checks.append(TraceCheck(
        "dtype", expect.where,
        f"{n} eqn(s): no float avals, no RNG primitives, int32 "
        "dot accumulators"))
    return n


def _check_clamps(root: _Region, expect: TraceExpectation, checks: list
                  ) -> int:
    found: list = []
    _collect_clamps(root, found, False)
    for eqn, region, kind, pred in found:
        if pred:
            raise TraceError(
                f"clamp: V-word clamp ('{eqn.primitive.name}') inside a "
                f"predicated branch at {region.path or '/'} — partials "
                "must accumulate unclamped under @pl.when/lax.cond and "
                "the single clamp runs after the predication",
                where=expect.where)
        if kind != expect.clamp_mode:
            raise TraceError(
                f"clamp: {kind} clamp at {region.path or '/'} in a "
                f"{expect.clamp_mode}-mode program — one clamp policy per "
                "program", where=expect.where)
    want = expect.expected_clamps
    if len(found) != want:
        raise TraceError(
            f"clamp: {len(found)} V-word clamp head(s) in the trace, the "
            f"ISA contract requires exactly {want} ({expect.n_spiking} "
            f"spiking layer(s) x {expect.neuron}/{expect.clamp_mode}"
            + (f" + {expect.extra_clamps} extra" if expect.extra_clamps
               else "") + ") — a duplicated or missing clamp changes "
            "11-bit semantics silently", where=expect.where)
    checks.append(TraceCheck(
        "clamp_count", expect.where,
        f"exactly {want} {expect.clamp_mode} clamp head(s), none "
        "predicated"))
    return len(found)


def _upstream(atom, region, *, stop_on_clamp: bool, limit: int = 500):
    """BFS the SSA def chain upstream. Yields (eqn, region) for every
    non-clamp def reached; clamp heads terminate their branch when
    ``stop_on_clamp``. Ref reads (`get`) and loop boundaries terminate
    (documented blind spot — see module docstring)."""
    stack, seen, steps = [(atom, region)], set(), 0
    while stack and steps < limit:
        a, r = stack.pop()
        steps += 1
        if _is_literal(a):
            continue
        key = (id(r), a)
        if key in seen:
            continue
        seen.add(key)
        eqn = r.defs.get(a)
        if eqn is None:
            if a in r.bindings and r.parent is not None:
                stack.append((r.bindings[a], r.parent))
            continue
        if stop_on_clamp and _clamp_kind(eqn, r) is not None:
            continue
        p = eqn.primitive.name
        yield eqn, r
        if p in ("get", "scan", "while", "cond", "pallas_call"):
            continue               # memory / loop boundary: out of SSA scope
        if p in _CALL_PRIMS:
            subs = _sub_regions(eqn, r)
            if len(subs) == 1:
                k = list(eqn.outvars).index(a)
                stack.append((subs[0].jaxpr.outvars[k], subs[0]))
            continue
        stack.extend((iv, r) for iv in eqn.invars)


def _check_dominance(root: _Region, expect: TraceExpectation, checks: list
                     ) -> int:
    """Every SpikeCheck (``ge``) must read a clamped V: its upstream SSA
    chain may not reach a `dot_general` or `psum` without passing a clamp
    head. Symmetrically, no clamp may sit upstream of a cross-shard
    ``psum`` — the AccV2V reduction sums unclamped int32 partials and the
    single clamp composes after the full sum."""
    n_ge = n_psum = 0
    for eqn, region in _walk(root):
        p = eqn.primitive.name
        if p == "ge":
            n_ge += 1
            for d, r in _upstream(eqn.invars[0], region, stop_on_clamp=True):
                if d.primitive.name in ("dot_general", "psum"):
                    raise TraceError(
                        f"clamp: SpikeCheck 'ge' at {region.path or '/'} "
                        f"reads a '{d.primitive.name}' accumulation with "
                        "no V-word clamp in between — on the mesh path "
                        "the clamp must run AFTER the cross-shard psum",
                        where=expect.where)
        elif p == "psum":
            n_psum += 1
            for inv in eqn.invars:
                for d, r in _upstream(inv, region, stop_on_clamp=False):
                    if _clamp_kind(d, r) is not None:
                        raise TraceError(
                            f"clamp: V-word clamp upstream of the "
                            f"cross-shard psum at {region.path or '/'} — "
                            "row-tile partials must reduce UNCLAMPED "
                            "(int32 addition is associative; clamp_v "
                            "composes only after the full AccV2V sum)",
                            where=expect.where)
                    if d.primitive.name == "dot_general":
                        break      # reached the accumulation source
    checks.append(TraceCheck(
        "clamp_dominance", expect.where,
        f"{n_ge} SpikeCheck read(s) dominated by a clamp; "
        f"{n_psum} psum(s) reduce unclamped partials"))
    return n_ge


def _dynamic_get_targets(eqn, base: int) -> Optional[list]:
    """``(dim, size, index_atom)`` for every *dynamic* index of a Pallas
    ``get``/``swap``: the eqn's trailing invars are the flattened dynamic
    leaves of its NDIndexer ``tree`` param, so unflattening recovers which
    ref dim each one indexes. None when the indexer is unreadable."""
    dyn = list(eqn.invars[base:])
    if not dyn:
        return []
    tree = eqn.params.get("tree")
    try:
        indexers = tree.unflatten(dyn)
    except Exception:
        return None
    stack, found = [indexers], []
    while stack:
        node = stack.pop()
        if isinstance(node, (tuple, list)):
            stack.extend(node)
            continue
        indices = getattr(node, "indices", None)
        if indices is None:
            continue
        for d, ix in enumerate(indices):
            if isinstance(ix, (int, np.integer)):
                continue
            start = getattr(ix, "start", None)
            if start is None:              # bare scalar index atom
                found.append((d, 1, ix))
                continue
            if isinstance(start, (int, np.integer)):
                continue                   # static slice
            found.append((d, int(getattr(ix, "size", 1)), start))
    return found if len(found) == len(dyn) else None


def _check_bounds(root: _Region, expect: TraceExpectation, checks: list
                  ) -> int:
    n = 0
    env: dict = {}
    for eqn, region in _walk(root):
        p = eqn.primitive.name
        if p in ("dynamic_slice", "dynamic_update_slice"):
            base = 1 if p == "dynamic_slice" else 2
            starts = eqn.invars[base:]
            shape = _aval_shape(eqn.invars[0])
            sizes = (eqn.params.get("slice_sizes")
                     if p == "dynamic_slice"
                     else _aval_shape(eqn.invars[1]))
            for d, (s, sz) in enumerate(zip(starts, sizes)):
                iv = _ival(s, region, env, 0)
                if iv is None:
                    raise TraceError(
                        f"bounds: cannot bound the dim-{d} start of "
                        f"'{p}' at {region.path or '/'} — index not "
                        "provably in-bounds", where=expect.where)
                if iv.lo < 0 or iv.hi + int(sz) > int(shape[d]):
                    raise TraceError(
                        f"bounds: '{p}' dim-{d} start in [{iv.lo}, "
                        f"{iv.hi}] with size {sz} exceeds operand extent "
                        f"{shape[d]} at {region.path or '/'}",
                        where=expect.where)
                n += 1
        elif p in ("get", "swap"):
            base = 2 if p == "swap" else 1
            if len(eqn.invars) <= base:
                continue           # fully static indexer
            shape = _aval_shape(eqn.invars[0])
            targets = _dynamic_get_targets(eqn, base)
            if targets is None:
                raise TraceError(
                    f"bounds: cannot map the dynamic index operand(s) of "
                    f"'{p}' onto ref dims at {region.path or '/'}",
                    where=expect.where)
            for d, sz, s in targets:
                iv = _ival(s, region, env, 0)
                if iv is None:
                    raise TraceError(
                        f"bounds: cannot bound the dynamic dim-{d} index "
                        f"of '{p}' at {region.path or '/'} — gather row "
                        "not provably inside its weight tile",
                        where=expect.where)
                if iv.lo < 0 or iv.hi + int(sz) > int(shape[d]):
                    raise TraceError(
                        f"bounds: '{p}' dynamic dim-{d} index in "
                        f"[{iv.lo}, {iv.hi}] (+size {sz}) exceeds ref "
                        f"extent {shape[d]} at {region.path or '/'} — an "
                        "event-list gather row would leave its padded "
                        "fan-in tile", where=expect.where)
                n += 1
    checks.append(TraceCheck(
        "bounds", expect.where,
        f"{n} dynamic index/start(s) proven in-bounds by interval "
        "analysis"))
    return n


def check_closed_jaxpr(closed_jaxpr, expect: TraceExpectation,
                       ) -> tuple:
    """Run all four trace passes over one traced dispatch. Returns
    ``(checks, stats)`` where ``stats`` is a `SurfaceTrace`-shaped dict;
    raises `TraceError` (naming primitive + eqn region + ``expect.where``)
    on the first violation. This is the low-level entry the negative-path
    tests drive with deliberately broken kernels."""
    root = root_region(closed_jaxpr, axis_sizes=dict(expect.mesh_axes))
    checks: list = []
    n_eqns = _check_dtypes(root, expect, checks)
    n_clamps = _check_clamps(root, expect, checks)
    n_ge = _check_dominance(root, expect, checks)
    n_bounds = _check_bounds(root, expect, checks)
    return checks, dict(clamps=n_clamps, spike_reads=n_ge,
                        bounds_checked=n_bounds, eqns=n_eqns)


# ---------------------------------------------------------------------------
# program surfaces: trace the real dispatches of one backend
# ---------------------------------------------------------------------------

def _program_calls(program) -> list:
    from repro.analysis.kernel_contracts import _program_calls as pc
    return pc(program)


def _call_params(program, name: str) -> tuple:
    """(thresholds, leaks, readout) of one fused call."""
    if name == "fc_stack":
        stack = program.fc_stack
        return (tuple(int(s.threshold) for s in stack[:-1]),
                tuple(int(s.leak) for s in stack[:-1]), True)
    idx = int(name[name.index("[") + 1:name.index("]")])
    spec = program.int_conv_stack[idx]
    return ((int(spec.threshold),), (int(spec.leak),), False)


def _backend_flags(backend: str, gate_granularity: int,
                   event_crossover: float) -> dict:
    return dict(
        use_pallas=backend != "int_ref",
        use_sparse=backend == "pallas_sparse",
        use_events=backend == "pallas_events",
        gate_granularity=(gate_granularity
                          if backend == "pallas_sparse" else 1),
        event_crossover=event_crossover)


def _trace_surfaces(program, backend: str, surfaces: tuple, *, batch: int,
                    block_b: int, megastep_k: int, mesh_axes: tuple,
                    gate_granularity: int, event_crossover: float) -> list:
    """[(surface, call, closed_jaxpr, TraceExpectation), ...] for every
    requested dispatch surface of ``backend``."""
    from repro.kernels.fused_snn_net.ops import (fused_snn_net,
                                                 mesh_padded_widths,
                                                 mesh_rowpartial_tick)
    flags = _backend_flags(backend, gate_granularity, event_crossover)
    T = int(program.timesteps)
    sds = jax.ShapeDtypeStruct
    out = []
    for name, _names, widths, n_spiking in _program_calls(program):
        ths, lks, readout = _call_params(program, name)
        ws_sds = [sds((widths[i], widths[i + 1]), jnp.int8)
                  for i in range(len(widths) - 1)]
        vi_sds = [sds((batch, w), jnp.int32) for w in widths[1:]]

        def run(spikes, ws, vi=None, _t=ths, _l=lks, _r=readout):
            return fused_snn_net(
                spikes, ws, thresholds=_t, leaks=_l,
                neuron=program.neuron, clamp_mode=program.clamp_mode,
                block_b=block_b, interpret=True, emit_rasters=True,
                readout=_r, v_init=vi, **flags)

        expect_kw = dict(neuron=program.neuron,
                         clamp_mode=program.clamp_mode,
                         n_spiking=n_spiking)
        if "batch" in surfaces:
            j = jax.make_jaxpr(lambda s, w: run(s, w))(
                sds((T, batch, widths[0]), jnp.int8), ws_sds)
            out.append(("batch", name, j, TraceExpectation(
                where=f"{backend}:batch:{name}", **expect_kw)))
        if "step" in surfaces:
            j = jax.make_jaxpr(lambda s, w, v: run(s, w, v))(
                sds((1, batch, widths[0]), jnp.int8), ws_sds, vi_sds)
            out.append(("step", name, j, TraceExpectation(
                where=f"{backend}:step:{name}", **expect_kw)))
        if "megastep" in surfaces:
            if readout:
                # the int megastep tail of `pipeline.stream_megastep`:
                # K-frame fused call resuming v_init + the exact readout
                # trajectory v_init + cumsum(raster @ w_ro)
                def mega(s, w, v):
                    r, vf, _sk = run(s, w, v)
                    ro_in = (r[-1] if len(r) else s).astype(jnp.int32)
                    traj = v[-1][None] + jnp.cumsum(
                        ro_in @ w[-1].astype(jnp.int32), axis=0)
                    return vf, traj
                fn = mega
            else:
                def fn(s, w, v):
                    return run(s, w, v)
            j = jax.make_jaxpr(fn)(
                sds((megastep_k, batch, widths[0]), jnp.int8), ws_sds,
                vi_sds)
            out.append(("megastep", name, j, TraceExpectation(
                where=f"{backend}:megastep:{name}", **expect_kw)))
        if "mesh" in surfaces and mesh_axes:
            sizes = dict(mesh_axes)
            nm = int(sizes.get("model", 1))
            if nm > 1:
                pw = mesh_padded_widths(widths, nm)
                wsl_sds = [sds((pw[i] // nm, pw[i + 1]), jnp.int8)
                           for i in range(len(widths) - 1)]
                vs_sds = [sds((batch, w), jnp.int32) for w in pw[1:]]
                use_events = flags["use_events"]

                def tick(frame, ws_l, vs, _w=widths, _n=n_spiking,
                         _t=ths, _l=lks, _e=use_events):
                    counts = (tuple(jnp.zeros((wi,), jnp.int32)
                                    for wi in _w[:len(ws_l)])
                              if _e else ())
                    return mesh_rowpartial_tick(
                        vs, counts, frame, ws_l, widths=_w, n_spiking=_n,
                        thresholds=_t, leaks=_l, neuron=program.neuron,
                        clamp_mode=program.clamp_mode, use_events=_e)

                j = jax.make_jaxpr(tick, axis_env=list(sizes.items()))(
                    sds((batch, pw[0]), jnp.int32), wsl_sds, vs_sds)
                out.append(("mesh", name, j, TraceExpectation(
                    where=f"{backend}:mesh:{name}",
                    mesh_axes=tuple(sizes.items()), **expect_kw)))
    return out


def _geometry_signature(program, backend, surfaces, batch, block_b,
                        megastep_k, mesh_axes, gate_granularity,
                        event_crossover) -> tuple:
    calls = tuple((name, widths, ns)
                  for name, _ln, widths, ns in _program_calls(program))
    params = tuple((_call_params(program, name)[:2])
                   for name, _ln, _w, _ns in _program_calls(program))
    return (backend, tuple(surfaces), batch, block_b, megastep_k,
            tuple(mesh_axes), gate_granularity, float(event_crossover),
            program.neuron, program.clamp_mode, int(program.timesteps),
            calls, params)


#: geometry-keyed memo — equivalence sweeps re-validate identical
#: geometries hundreds of times; tracing is pure in the signature
_TRACE_CACHE: dict = {}


def check_trace(program, backend: str = "pallas", *,
                surfaces: tuple = SURFACES, batch: Optional[int] = None,
                block_b: int = 8, megastep_k: int = 2,
                mesh: Any = None, gate_granularity: int = 1,
                event_crossover: float = 1.0, with_cost: bool = True,
                use_cache: bool = True) -> TraceReport:
    """Trace every requested dispatch ``surfaces`` of ``program`` on
    ``backend`` and verify the dtype / clamp / bounds / determinism
    contracts; raise `TraceError` naming primitive + eqn + backend on any
    violation. Host backends (`HOST_BACKENDS`) have no jaxpr and return a
    named skip row.

    ``mesh`` is an ``{axis: extent}`` dict or a `jax.sharding.Mesh`
    (default `DEFAULT_MESH_AXES`): the mesh surface traces the
    model-parallel row-partial tick under an abstract ``axis_env`` — no
    devices needed. ``batch`` (default ``block_b``) sizes the traced
    dispatch; ``with_cost`` attaches the `trace_cost.TraceCostReport`
    built from the batch surface. Results are memoized by geometry
    (``use_cache``)."""
    if backend in HOST_BACKENDS:
        return TraceReport(
            backend=backend, surfaces=(), cost=None,
            checks=(TraceCheck(
                "host_backend", backend,
                "host-side executor (numpy/BitMacro) — no XLA dispatch "
                "to trace; covered by the bit-equivalence sweep"),))
    if backend not in TRACE_BACKENDS:
        raise TraceError(
            f"trace: backend {backend!r} has no int-domain trace "
            f"contract; traceable: {sorted(TRACE_BACKENDS)}, host "
            f"(skipped): {sorted(HOST_BACKENDS)}", where=backend)
    if program.domain != "int":
        raise TraceError(
            f"trace: program domain {program.domain!r} — the trace "
            "contract covers int-domain dispatches only", where=backend)
    if batch is None:
        batch = block_b
    if mesh is None:
        mesh_axes = DEFAULT_MESH_AXES if "mesh" in surfaces else ()
    else:
        from repro.analysis.kernel_contracts import _mesh_extents
        mesh_axes = tuple(sorted(_mesh_extents(mesh).items()))
    key = _geometry_signature(program, backend, surfaces, batch, block_b,
                              megastep_k, mesh_axes, gate_granularity,
                              event_crossover) + (bool(with_cost),)
    if use_cache and key in _TRACE_CACHE:
        return _TRACE_CACHE[key]

    traced = _trace_surfaces(
        program, backend, tuple(surfaces), batch=batch, block_b=block_b,
        megastep_k=megastep_k, mesh_axes=mesh_axes,
        gate_granularity=gate_granularity, event_crossover=event_crossover)
    checks: list = []
    stats: list = []
    batch_jaxprs = {}
    for surface, call, closed, expect in traced:
        cs, st = check_closed_jaxpr(closed, expect)
        checks.extend(cs)
        stats.append(SurfaceTrace(surface=surface, call=call, **st))
        if surface == "batch":
            batch_jaxprs[call] = closed
    cost = None
    if with_cost and batch_jaxprs:
        from repro.analysis.trace_cost import build_cost_report
        cost = build_cost_report(program, backend, batch_jaxprs,
                                 batch=batch, block_b=block_b,
                                 checks=checks)
    report = TraceReport(backend=backend, surfaces=tuple(stats),
                         checks=tuple(checks), cost=cost)
    if use_cache:
        _TRACE_CACHE[key] = report
    return report
