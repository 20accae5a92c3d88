"""graftcheck IR utilities: jaxpr-level program auditing.

graftlint (:mod:`.rules`) stops at the AST: it can prove a ``print``
sits inside a traced scope, but not what the compiler actually emits.
The properties that define a distributed trainer — how many collective
bytes a step moves, whether the donated state really aliases, whether a
bf16 hot path silently upcasts — live in the traced program. This
module reads them there, three levels down:

1. **jaxpr** (``jax.make_jaxpr`` on abstract inputs — CPU-safe, no
   FLOPs, no compile): recursive equation walk through ``pjit`` /
   ``scan`` / ``cond`` / ``while`` / ``shard_map`` / ``remat`` /
   custom-derivative sub-jaxprs, with scan trip counts multiplying the
   dynamic cost of their bodies. Collectives (``psum`` & co) appear
   here EXPLICITLY for shard_map-style programs — count + byte volume
   per mesh axis is exact.
2. **lowering** (``fn.lower(...)`` — still no execution): donated
   arguments that the lowered module actually aliases carry
   ``tf.aliasing_output`` attributes in the StableHLO text; a declared
   ``donate_argnums`` the lowering dropped (shape/dtype mismatch, or
   someone deleted the declaration) is visible as a missing alias.
3. **compiled HLO** (``.compile()`` on the CPU mesh — compile only,
   never run): GSPMD-inserted collectives (the TP/FSDP programs, where
   the jaxpr shows only sharding constraints) appear as
   ``all-reduce``/``all-gather``/``reduce-scatter``/``all-to-all`` ops
   in the optimized module; counts and byte volumes are parsed from
   the text.

Fingerprints: a structural digest over the recursive equation outline
(primitive, selected static params, operand/result avals) — committed
per canonical program in ``analysis/fingerprints.json`` so semantic
drift in a hot program fails tier-1 with a readable per-primitive
histogram diff instead of a silent behavior change.

jax is imported at module top: unlike the lint gate this tool exists
to interrogate the tracer. It must still never require an accelerator
— everything here runs on the host platform.
"""

from __future__ import annotations

import hashlib
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
from jax.extend import core as jax_core

_JAXPR_TYPES = (jax_core.Jaxpr, jax_core.ClosedJaxpr)


# collective primitives whose presence/size IS the communication budget
COLLECTIVE_PRIMS = {
    "psum", "pmax", "pmin", "all_gather", "psum_scatter",
    "reduce_scatter", "ppermute", "pshuffle", "all_to_all",
}

# eqn params worth fingerprinting: static semantics, stable reprs (a
# NamedSharding or jaxpr repr would drag device ids / var names in)
_FP_PARAMS = (
    "axes", "axis_name", "axis_index_groups", "length", "num_carry",
    "num_consts", "reverse", "new_dtype", "dimension_numbers",
    "dimensions", "shape", "window_strides", "feature_group_count",
    "direction", "index_dtype", "exact",
)

_F32_UP_SOURCES = ("bfloat16", "float16")


def aval_bytes(aval) -> int:
    """Byte size of a shaped abstract value (0 for non-arrays)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * dtype.itemsize


def abstract(tree):
    """ShapeDtypeStruct twin of an array pytree — audit inputs never
    hold real buffers."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def trace(fn, *args, **kwargs):
    """ClosedJaxpr of ``fn(*args, **kwargs)`` on abstract inputs.

    ``args`` may be arrays or ``ShapeDtypeStruct`` trees; keyword
    arguments are closed over (so jit-static kwargs like the serving
    decode's ``window``/``horizon`` pin one program each)."""
    if kwargs:
        return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return jax.make_jaxpr(fn)(*args)


def _as_jaxpr(obj):
    return obj.jaxpr if hasattr(obj, "jaxpr") else obj


def _sub_jaxprs(eqn) -> List[Tuple[object, int]]:
    """(sub_jaxpr, trip_multiplier) pairs under one equation. A scan
    body's dynamic cost is ``length`` executions; every other nesting
    (pjit, cond branches, while bodies, shard_map, remat, custom_*)
    multiplies by 1 — for while loops that is the STATIC count (trip
    counts are data-dependent; the budget audits what one iteration
    moves)."""
    out: List[Tuple[object, int]] = []
    name = eqn.primitive.name
    for key, val in eqn.params.items():
        if key == "branches":
            out.extend((_as_jaxpr(b), 1) for b in val)
        elif isinstance(val, _JAXPR_TYPES):
            mult = 1
            if name == "scan" and key == "jaxpr":
                mult = int(eqn.params.get("length", 1))
            out.append((_as_jaxpr(val), mult))
        elif isinstance(val, (tuple, list)) and val and all(
                isinstance(v, _JAXPR_TYPES) for v in val):
            out.extend((_as_jaxpr(v), 1) for v in val)
    return out


def iter_eqns(closed, mult: int = 1) -> Iterator[Tuple[object, int]]:
    """Depth-first ``(eqn, trip_multiplier)`` walk of a (Closed)Jaxpr,
    recursing through every sub-jaxpr-carrying equation."""
    jaxpr = _as_jaxpr(closed)
    for eqn in jaxpr.eqns:
        yield eqn, mult
        for sub, m in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, mult * m)


def _axes_of(eqn) -> Tuple[str, ...]:
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def collective_records(closed) -> List[Tuple[str, Tuple[str, ...], int, int]]:
    """Every collective equation in the program (recursively):
    ``(primitive, axes, bytes_per_call, trip_count)``. Bytes are the
    summed operand avals of ONE call — per-shard sizes as the body
    sees them."""
    out = []
    for eqn, mult in iter_eqns(closed):
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            nbytes = sum(aval_bytes(getattr(v, "aval", None))
                         for v in eqn.invars)
            out.append((eqn.primitive.name, _axes_of(eqn), nbytes, mult))
    return out


def collective_budget(closed) -> Dict[str, Dict[str, int]]:
    """The program's jaxpr-level communication budget:
    ``{"psum@data": {"count": N, "bytes": B}, ...}`` with scan trip
    counts multiplied in (count = dynamic calls per program execution,
    bytes = total per-execution volume)."""
    budget: Dict[str, Dict[str, int]] = {}
    for prim, axes, nbytes, mult in collective_records(closed):
        key = f"{prim}@{','.join(axes) or '?'}"
        slot = budget.setdefault(key, {"count": 0, "bytes": 0})
        slot["count"] += mult
        slot["bytes"] += nbytes * mult
    return budget


def psum_sizes(closed) -> List[int]:
    """Per-call byte size of every ``psum`` equation (static list, no
    trip multiplication) — the needle for "exactly one grad-sized
    psum": callers count entries equal to the parameter-tree bytes."""
    return [nbytes for prim, _axes, nbytes, _m in collective_records(closed)
            if prim == "psum"]


def dtype_promotions(closed, min_bytes: int = 0) -> Dict[str, int]:
    """bf16/f16 -> f32 ``convert_element_type`` equations whose result
    DIRECTLY feeds a matmul-class op (``dot_general`` /
    ``conv_general_dilated``) and whose operand is at least
    ``min_bytes`` — the silent-upcast audit. Deliberate f32 islands
    (LayerNorm, softmax) don't feed matmuls and stay out; the programs
    that DO matmul in f32 on purpose (logit paths) pin their count in
    the committed budget, so an unintended new upcast moves the number
    and trips the gate. Returns ``{"count": N, "bytes": B}`` with scan
    trips multiplied in."""
    total = {"count": 0, "bytes": 0}

    def scan_level(jaxpr, mult):
        jaxpr = _as_jaxpr(jaxpr)
        matmul_operands = set()
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("dot_general",
                                      "conv_general_dilated"):
                for v in eqn.invars:
                    matmul_operands.add(id(v))
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "convert_element_type":
                src = getattr(eqn.invars[0], "aval", None)
                dst = eqn.params.get("new_dtype")
                nbytes = aval_bytes(src)
                if (src is not None and dst is not None
                        and str(getattr(src, "dtype", "")) in
                        _F32_UP_SOURCES
                        and str(dst) == "float32"
                        and nbytes >= min_bytes
                        and any(id(o) in matmul_operands
                                for o in eqn.outvars)):
                    total["count"] += mult
                    total["bytes"] += nbytes * mult
            for sub, m in _sub_jaxprs(eqn):
                scan_level(sub, mult * m)

    scan_level(closed, 1)
    return total


# ------------------------------------------------------------ fingerprints

def _aval_str(v) -> str:
    aval = getattr(v, "aval", None)
    if aval is None:
        return repr(getattr(v, "val", v))
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None:
        return str(aval)
    return f"{dtype}[{','.join(str(d) for d in shape)}]"


def outline(closed) -> str:
    """Canonical human-readable structure of the program: one line per
    equation (recursive, indented), primitive + whitelisted static
    params + operand/result avals. Stable across runs (no var names,
    no device ids) — the digest input AND the thing a human diffs when
    a fingerprint moves."""
    lines: List[str] = []

    def emit(jaxpr, depth):
        jaxpr = _as_jaxpr(jaxpr)
        pad = "  " * depth
        for eqn in jaxpr.eqns:
            params = ";".join(
                f"{k}={eqn.params[k]!r}" for k in _FP_PARAMS
                if k in eqn.params)
            ins = ",".join(_aval_str(v) for v in eqn.invars)
            outs = ",".join(_aval_str(v) for v in eqn.outvars)
            lines.append(
                f"{pad}{eqn.primitive.name}[{params}] {ins} -> {outs}")
            for sub, _m in _sub_jaxprs(eqn):
                emit(sub, depth + 1)

    emit(closed, 0)
    return "\n".join(lines)


def op_histogram(closed) -> Dict[str, int]:
    """Static per-primitive equation counts (recursive, NOT trip-
    multiplied — structural, so a scan-length change shows up in the
    digest/params, not as a phantom op-count delta)."""
    hist: Dict[str, int] = {}
    for eqn, _mult in iter_eqns(closed):
        hist[eqn.primitive.name] = hist.get(eqn.primitive.name, 0) + 1
    return hist


def fingerprint(closed) -> Dict[str, object]:
    """``{"digest", "eqns", "ops"}`` for one traced program."""
    text = outline(closed)
    hist = op_histogram(closed)
    return {
        "digest": hashlib.sha256(text.encode()).hexdigest()[:16],
        "eqns": sum(hist.values()),
        "ops": hist,
    }


def diff_histograms(old: Dict[str, int], new: Dict[str, int]) -> str:
    """Readable op-count delta: ``+2 convert_element_type, -1 psum``;
    empty when the histograms agree (a pure reorder/param change)."""
    parts = []
    for prim in sorted(set(old) | set(new)):
        d = new.get(prim, 0) - old.get(prim, 0)
        if d:
            parts.append(f"{'+' if d > 0 else ''}{d} {prim}")
    return ", ".join(parts)


# ------------------------------------------------- lowering / compiled HLO

_ALIAS_ATTRS = ("tf.aliasing_output", "jax.buffer_donor")


def alias_count(lowered_text: str) -> int:
    """Input buffers a lowered module aliases to outputs
    (``tf.aliasing_output`` attrs in the StableHLO text; the
    ``jax.buffer_donor`` spelling counts too on jaxes that emit it).
    Zero with a declared ``donate_argnums`` means the donation was
    dropped — the doubled-HBM bug the donation audit exists for."""
    return sum(lowered_text.count(attr) for attr in _ALIAS_ATTRS)


def donation_aliases(jit_fn, *args, **kwargs) -> int:
    """:func:`alias_count` of ``jit_fn`` lowered on ``args`` —
    lowering only, nothing compiles or runs. (The audit runner lowers
    once and reuses the ``Lowered`` for the HLO compile; this
    convenience wrapper is for tests/one-off probes.)"""
    return alias_count(jit_fn.lower(*args, **kwargs).as_text())


_HLO_COLLECTIVE_RE = re.compile(
    r"=\s*([^=\n]*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(")
_HLO_SHAPE_RE = re.compile(
    r"(pred|bf16|f16|f32|f64|s8|s16|s32|s64|u8|u16|u32|u64|c64|c128)"
    r"\[([0-9,]*)\]")
_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
    "u16": 2, "f32": 4, "s32": 4, "u32": 4, "c64": 8, "f64": 8,
    "s64": 8, "u64": 8, "c128": 16,
}


def _hlo_shape_bytes(type_text: str) -> int:
    total = 0
    for dtype, dims in _HLO_SHAPE_RE.findall(type_text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _HLO_DTYPE_BYTES[dtype]
    return total


def hlo_collectives(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Collective ops in a compiled (post-SPMD-partitioner) HLO module:
    ``{"all-reduce": {"count": N, "bytes": B}, ...}``, bytes from each
    op's result shape. This is where GSPMD-inserted communication —
    invisible at the jaxpr level — becomes countable. Text occurrences
    = static program sites (an op inside an HLO while body counts
    once)."""
    out: Dict[str, Dict[str, int]] = {}
    for m in _HLO_COLLECTIVE_RE.finditer(hlo_text):
        op = m.group(2)
        slot = out.setdefault(op, {"count": 0, "bytes": 0})
        slot["count"] += 1
        slot["bytes"] += _hlo_shape_bytes(m.group(1))
    return out


def hlo_max_allgather_bytes(hlo_text: str) -> int:
    """Largest single all-gather result in the module — the
    replication audit's needle: a 'small' program whose HLO suddenly
    all-gathers a weight-sized array got its sharding dropped."""
    best = 0
    for m in _HLO_COLLECTIVE_RE.finditer(hlo_text):
        if m.group(2) == "all-gather":
            best = max(best, _hlo_shape_bytes(m.group(1)))
    return best
