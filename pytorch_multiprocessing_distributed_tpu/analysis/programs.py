"""graftcheck canonical-program registry and audit runner.

The subsystems that own hot compiled programs register them here via a
module-level ``audit_programs()`` hook (train/step, train/lm,
inference/generate, serving/engine, parallel/collectives, ops/moe).
Each hook returns specs of the shape::

    {"name": str, "min_devices": int, "build": () -> {
        "fn": callable,            # the program (jitted or plain)
        "args": tuple,             # abstract (ShapeDtypeStruct) inputs
        "kwargs": dict,            # jit-static kwargs (closed over)
        "mesh": Mesh | None,       # entered (jax.set_mesh) around
                                   # trace/lower/compile
        "lower_fn": jit fn | None, # enables the donation audit
        "compile": bool,           # enables the HLO collective audit
        "compile_fn": jit fn,      # lowering handle for the HLO audit
                                   # when "fn" is a plain closure
                                   # (default: lower_fn, then fn)
        # ---- inline invariants (checked live, NOT refreshable by
        #      `make check-update` — the hand-written contract):
        "expect_collectives": {..},# exact jaxpr-level budget
        "expect_grad_psums": int,  # psum bytes // params_bytes
        "expect_collective_subset": {..},  # exact count+bytes for
                                   # SELECTED budget keys (graftzero's
                                   # reduce-scatter/all-gather pin)
        "max_psum_bytes": int,     # per-call psum byte cap (pins a
                                   # zero-psum program against a grad-
                                   # sized all-reduce creeping back)
        "params_bytes": int,
        "min_donated": int,        # lowered aliases required
        "require_hlo": (ops,),     # compiled ops that must exist
        "expect_hlo_counts": {..}, # exact compiled-op count pins
        "max_allgather_bytes": int,# replication cap (jaxpr + HLO)
        "dtype_min_bytes": int,    # promotion-audit size floor
    }}

``audit_program`` traces the build on abstract inputs (no FLOPs),
runs the audits from :mod:`.ir`, and returns ``(record, findings)``:
the record is the refreshable snapshot half (fingerprint, budgets —
compared against ``analysis/fingerprints.json`` by :mod:`.check`),
the findings are inline-invariant violations that no snapshot refresh
can launder.
"""

from __future__ import annotations

import contextlib
import importlib
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

from . import ir

# rule table (GC1xx — program-level, disjoint from graftlint's GL1xx)
RULES_GC: Dict[str, str] = {
    "GC100": "program failed to build or trace",
    "GC101": "collective budget drift: count/byte volume per mesh axis "
             "differs from the committed budget",
    "GC102": "donation audit: declared donate_argnums the lowered "
             "module does not alias (state HBM silently doubles)",
    "GC103": "resharding/replication audit: an all-gather exceeds the "
             "program's cap, a required collective is missing, or the "
             "compiled collective set drifted",
    "GC104": "dtype-promotion audit: bf16->f32 upcasts feeding matmuls "
             "differ from the committed count",
    "GC105": "fingerprint drift: the program's structural digest "
             "changed vs analysis/fingerprints.json",
    "GC106": "fingerprint coverage: program has no committed entry "
             "(or a committed entry names no registered program)",
}

# the modules that own canonical programs; each exposes
# audit_programs() (the registration hooks this PR threads through
# the package)
HOOK_MODULES = (
    "pytorch_multiprocessing_distributed_tpu.train.step",
    "pytorch_multiprocessing_distributed_tpu.train.lm",
    "pytorch_multiprocessing_distributed_tpu.inference.generate",
    "pytorch_multiprocessing_distributed_tpu.serving.engine",
    "pytorch_multiprocessing_distributed_tpu.parallel.collectives",
    "pytorch_multiprocessing_distributed_tpu.ops.moe",
)


def audit_tiny_gpt(**overrides):
    """THE tiny-GPT geometry of the LM-family audit programs — one
    copy, imported (lazily) by the train/lm, inference/generate and
    serving/engine hooks, so "the same canonical model audited across
    subsystems" stays true by construction: a geometry change lands in
    every hook's committed fingerprint at once, never in one. bf16 so
    the dtype-promotion audit sees the real mixed-precision convert
    structure; XLA attention so the trace has no Pallas dependency."""
    import jax.numpy as jnp

    from ..models import GPT

    cfg = dict(vocab_size=61, max_seq_len=64, hidden_size=32,
               num_layers=2, num_heads=2, mlp_dim=64, attn_impl="xla",
               dtype=jnp.bfloat16)
    cfg.update(overrides)
    return GPT(**cfg)


@dataclass(frozen=True)
class ProgramSpec:
    name: str
    min_devices: int
    build: Callable[[], dict]
    module: str


@dataclass(frozen=True)
class Finding:
    program: str
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.program}: {self.rule} {self.message}"


def collect(names: Optional[Sequence[str]] = None) -> List[ProgramSpec]:
    """Import every hook module and gather its registered programs
    (optionally filtered to ``names``). Duplicate names are a
    registration bug and raise."""
    specs: List[ProgramSpec] = []
    seen: Dict[str, str] = {}
    for modname in HOOK_MODULES:
        mod = importlib.import_module(modname)
        for entry in mod.audit_programs():
            name = entry["name"]
            if name in seen:
                raise ValueError(
                    f"duplicate audit program {name!r} registered by "
                    f"{modname} and {seen[name]}")
            seen[name] = modname
            specs.append(ProgramSpec(
                name=name,
                min_devices=int(entry.get("min_devices", 1)),
                build=entry["build"],
                module=modname,
            ))
    if names:
        wanted = set(names)
        unknown = wanted - {s.name for s in specs}
        if unknown:
            raise KeyError(
                f"unknown audit program(s) {sorted(unknown)}; known: "
                f"{sorted(s.name for s in specs)}")
        specs = [s for s in specs if s.name in wanted]
    return specs


def _mesh_ctx(mesh):
    return jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()


def audit_program(spec: ProgramSpec
                  ) -> Tuple[Optional[dict], List[Finding]]:
    """Run every applicable audit for one program. Returns the
    snapshot record (None when the build failed) and inline-invariant
    findings."""
    findings: List[Finding] = []

    def add(rule: str, message: str):
        findings.append(Finding(spec.name, rule, message))

    try:
        built = spec.build()
        fn = built["fn"]
        args = tuple(built.get("args", ()))
        kwargs = dict(built.get("kwargs", {}))
        mesh = built.get("mesh")
        with _mesh_ctx(mesh):
            closed = ir.trace(fn, *args, **kwargs)
    except Exception as e:  # noqa: BLE001 — a broken program must
        # fail the gate with its name, not crash the whole check
        add("GC100", f"build/trace failed: {type(e).__name__}: {e}")
        return None, findings

    budget = ir.collective_budget(closed)
    promos = ir.dtype_promotions(
        closed, min_bytes=int(built.get("dtype_min_bytes", 0)))
    record: dict = {
        "fingerprint": ir.fingerprint(closed),
        "collectives": budget,
        "dtype_promotions": promos,
    }

    # ---- inline invariants (live — check-update cannot launder) ----
    expect = built.get("expect_collectives")
    if expect is not None and budget != expect:
        add("GC101",
            f"jaxpr collective budget {budget} != declared {expect}")

    n_grad = built.get("expect_grad_psums")
    if n_grad is not None:
        # whole parameter trees' worth of psum bytes: a ``pmean`` of
        # the gradient tree is ONE equation under some jax versions
        # and one a leaf under others, the same bytes either way (the
        # statistic and metric psums beside it are far below a tree)
        pb = int(built["params_bytes"])
        got = sum(ir.psum_sizes(closed)) // pb
        record["grad_sized_psums"] = got
        if got != n_grad:
            add("GC101",
                f"psums move {got} parameter tree(s) of bytes ({pb} "
                f"each), expected {n_grad} — the gradient all-reduce "
                "contract moved")

    subset = built.get("expect_collective_subset")
    if subset is not None:
        # exact count+bytes pin for SELECTED budget keys (the graftzero
        # reduce-scatter/all-gather contract) without freezing the whole
        # budget dict inline — the rest stays committed/refreshable
        for key, want in subset.items():
            got = budget.get(key)
            if got != want:
                add("GC101",
                    f"collective {key}: traced {got} != declared "
                    f"{want} — the sharded-update exchange moved")

    psum_cap = built.get("max_psum_bytes")
    if psum_cap is not None:
        worst = max(ir.psum_sizes(closed), default=0)
        if worst > int(psum_cap):
            add("GC101",
                f"a psum moves {worst} bytes, over this program's "
                f"{psum_cap}-byte cap — a gradient-sized all-reduce "
                "crept back into a reduce-scatter program")

    cap = built.get("max_allgather_bytes")
    if cap is not None:
        worst = max((b for prim, _ax, b, _m in
                     ir.collective_records(closed)
                     if prim == "all_gather"), default=0)
        if worst > cap:
            add("GC103",
                f"jaxpr all_gather of {worst} bytes exceeds the "
                f"program's replication cap ({cap})")

    lower_fn = built.get("lower_fn")
    lowered = None  # reused by the HLO audit when it targets lower_fn
    if lower_fn is not None:
        try:
            with _mesh_ctx(mesh):
                lowered = lower_fn.lower(*args, **kwargs)
            aliased = ir.alias_count(lowered.as_text())
        except Exception as e:  # noqa: BLE001
            aliased = None
            add("GC102", f"lowering failed: {type(e).__name__}: {e}")
        if aliased is not None:
            record["donation"] = {"aliased": aliased}
            need = built.get("min_donated")
            if need is not None and aliased < int(need):
                add("GC102",
                    f"lowered module aliases {aliased} input "
                    f"buffer(s), expected >= {need} — a declared "
                    "donate_argnums is not reaching the executable")

    # ---- compile: graftmeter cost/memory budget (ALWAYS — every
    # canonical program carries a committed record in
    # analysis/costs.json) + the HLO collective audit (opt-in via
    # "compile"). One executable serves both: the budgeted program and
    # the collective-audited program cannot drift.
    compiled = None
    try:
        from ..utils.compat import (cost_analysis_dict,
                                    memory_analysis_dict)
        from ..utils.compile_cache import lowered_program_analysis

        target = (built.get("compile_fn") or lower_fn or fn)
        with _mesh_ctx(mesh):
            if target is lower_fn and lowered is not None:
                # the donation audit already lowered this exact
                # program — don't pay a second GSPMD lowering
                compiled = lowered.compile()
                cost = cost_analysis_dict(compiled)
                memory = memory_analysis_dict(compiled)
            else:
                if not callable(getattr(target, "lower", None)):
                    # plain closure (the generate-style wrapper):
                    # jit at the audit boundary to get an AOT handle
                    target = jax.jit(target)
                compiled, cost, memory = lowered_program_analysis(
                    target, *args, **kwargs)
    except Exception as e:  # noqa: BLE001 — a program the meter
        # cannot compile must fail the gate named, not crash the check
        add("GM100",
            f"compile for metering failed: {type(e).__name__}: {e}")
        if built.get("compile"):
            add("GC103", f"compile failed: {type(e).__name__}: {e}")
    else:
        from .meter import costs_record

        record["costs"] = costs_record(cost, memory)

    if built.get("compile") and compiled is not None:
        try:
            text = compiled.as_text()
        except Exception as e:  # noqa: BLE001
            add("GC103", f"compile failed: {type(e).__name__}: {e}")
            text = None
        if text is not None:
            hlo = ir.hlo_collectives(text)
            record["hlo_collectives"] = hlo
            for op in built.get("require_hlo", ()):
                if hlo.get(op, {}).get("count", 0) < 1:
                    add("GC103",
                        f"compiled module contains no {op} — the "
                        "partitioner no longer emits this program's "
                        "defining collective (present: "
                        f"{sorted(hlo) or 'none'})")
            for op, n in built.get("expect_hlo_counts", {}).items():
                got = hlo.get(op, {}).get("count", 0)
                if got != n:
                    add("GC103",
                        f"compiled module has {got} {op} op(s), the "
                        f"program's contract pins exactly {n}")
            if cap is not None:
                worst = ir.hlo_max_allgather_bytes(text)
                if worst > cap:
                    add("GC103",
                        f"compiled all-gather of {worst} bytes exceeds "
                        f"the replication cap ({cap}) — an implicit "
                        "full materialization of sharded data")

    return record, findings


def run_audits(names: Optional[Sequence[str]] = None,
               devices: Optional[int] = None
               ) -> Tuple[Dict[str, dict], List[Finding], List[str]]:
    """Audit every registered (or named) program. Returns
    ``(records, findings, skipped)`` — ``skipped`` lists programs the
    process cannot host (fewer devices than ``min_devices``; `make
    check` / tier-1 provide the 8-device CPU mesh)."""
    have = devices if devices is not None else len(jax.devices())
    records: Dict[str, dict] = {}
    findings: List[Finding] = []
    skipped: List[str] = []
    for spec in collect(names):
        if spec.min_devices > have:
            skipped.append(
                f"{spec.name} (needs {spec.min_devices} devices, "
                f"have {have})")
            continue
        record, found = audit_program(spec)
        findings.extend(found)
        if record is not None:
            records[spec.name] = record
    return records, findings, skipped
