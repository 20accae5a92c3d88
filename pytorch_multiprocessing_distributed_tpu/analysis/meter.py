"""graftmeter: static cost/memory model + capacity planner.

graftcheck (PR 5) pins what the canonical programs *are* (structure,
collectives, donation); graftmeter pins what they *cost*: FLOPs, bytes
accessed, arithmetic intensity, and the compiled memory breakdown
(argument/output/temp/generated-code bytes) from XLA's own analyses of
the EXACT lowered executable — the shared
``utils.compile_cache.lowered_program_analysis`` path the bench's MFU
math already reads, so the budgeted program, the benched program and
the audited program are one program.

Three pieces:

- **committed cost budgets** (``analysis/costs.json``): every program
  in the graftcheck registry (``analysis/programs.py``) carries a
  committed ``{flops, bytes_accessed, arithmetic_intensity, memory}``
  record, compared field-by-field by ``make check`` exactly like
  fingerprints — a program that silently grows its temp HBM (lost
  rematerialization, an accidental f32 copy of the cache) fails tier-1
  with a readable "+N MiB temp_bytes" diff naming program and field;
  deliberate changes re-baseline via ``make check-update``.
- **capacity planner** (:func:`plan_capacity`): inverts the HBM ledger
  arithmetic — given a model, a sequence capacity, and a per-chip HBM
  budget, how many KV slots / how large a decode batch actually fit
  beside the parameters. Exact by construction (the same shape x dtype
  products the allocations use), validated against real CPU-backend
  allocation in the tier-1 meter smoke.
- **roofline helpers** (:func:`roofline`): classify a measured point
  as compute- or bandwidth-bound against per-chip peak FLOP/s and HBM
  bandwidth; ``bench.py`` / ``serving_bench.py`` stamp every record
  with the join (achieved FLOP/s, MFU, achieved bytes/s vs the static
  model).

CLI::

    python -m pytorch_multiprocessing_distributed_tpu.analysis.meter
        [--programs NAME ...] [--update] [--json]
    python -m ...analysis.meter --plan gpt_small --s_max 2048 \
        --hbm_gb 16

Rule table (GM — meter-level, disjoint from GL/GC):
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

RULES_GM: Dict[str, str] = {
    "GM100": "program failed to compile for cost/memory metering",
    "GM101": "compute budget drift: FLOPs / bytes-accessed / "
             "arithmetic intensity differ from the committed budget",
    "GM102": "memory budget drift: argument/output/temp/generated-code "
             "bytes differ from the committed budget (temp growth = "
             "lost remat or an accidental resident copy)",
    "GM103": "cost coverage: program has no committed cost entry (or a "
             "committed entry names no registered program)",
}

# a compiled program whose backend exposes no cost/memory model still
# gets a committed entry with explicit nulls — absence must be loud,
# not a skipped comparison
_MEMORY_FIELDS = ("argument_bytes", "output_bytes", "temp_bytes",
                  "alias_bytes", "generated_code_bytes", "peak_bytes")


def costs_record(cost: Optional[dict],
                 memory: Optional[dict]) -> dict:
    """Assemble one program's cost budget from the shared lowering
    path's ``(cost, memory)`` analyses. FLOPs/bytes come from XLA's
    cost model (``flops`` / ``bytes accessed``); intensity is their
    quotient (FLOP per HBM byte — the roofline x-coordinate)."""
    flops = None
    bytes_accessed = None
    if cost:
        f = cost.get("flops")
        b = cost.get("bytes accessed")
        flops = int(f) if f is not None and f >= 0 else None
        bytes_accessed = int(b) if b is not None and b >= 0 else None
    intensity = None
    if flops and bytes_accessed:
        intensity = round(flops / bytes_accessed, 4)
    return {
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "arithmetic_intensity": intensity,
        "memory": ({k: int(memory[k]) for k in _MEMORY_FIELDS}
                   if memory else None),
    }


# ------------------------------------------------ committed budgets

def package_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def default_costs_path() -> str:
    return os.path.join(package_root(), "analysis", "costs.json")


def load_costs(path: Optional[str] = None) -> Dict[str, dict]:
    path = path or default_costs_path()
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as fh:
        return dict(json.load(fh).get("programs", {}))


def write_costs(records: Dict[str, dict], path: Optional[str] = None,
                *, keep: Optional[Dict[str, dict]] = None) -> None:
    """Snapshot ``records`` (merging ``keep`` for programs outside a
    partial-scope run — same discipline as ``check.write_fingerprints``:
    a laptop refresh must not drop entries it could not re-measure)."""
    import jax

    path = path or default_costs_path()
    programs = dict(keep or {})
    programs.update(records)
    payload = {
        "comment": "graftmeter committed cost/memory budgets (FLOPs, "
                   "bytes accessed, arithmetic intensity, compiled "
                   "argument/output/temp/generated-code bytes) per "
                   "canonical program — refresh deliberately via "
                   "`make check-update` and review the diff; temp "
                   "growth here is lost rematerialization or a new "
                   "resident copy in a hot program.",
        "jax": jax.__version__,
        "programs": {k: programs[k] for k in sorted(programs)},
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=2, sort_keys=False)
        fh.write("\n")


def _mib(delta: int) -> str:
    sign = "+" if delta >= 0 else "-"
    return f"{sign}{abs(delta) / (1 << 20):.2f} MiB"


def compare_costs(records: Dict[str, dict],
                  committed: Dict[str, dict], *,
                  full_scope: bool,
                  failed: frozenset = frozenset()) -> List:
    """Field-by-field budget comparison; each drift is a rule-tagged
    finding with the delta spelled out in MiB where bytes are
    involved. Returns ``programs.Finding``s (the check CLI renders
    GM findings beside GC ones)."""
    from .programs import Finding

    findings: List = []
    for name, rec in records.items():
        want = committed.get(name)
        if want is None:
            findings.append(Finding(
                name, "GM103",
                "no committed cost budget — run `make check-update` "
                "and review the new analysis/costs.json entry"))
            continue
        # arithmetic_intensity is DERIVED from flops/bytes — compare
        # the components so one real drift reports once, and flag an
        # intensity-only divergence as the tamper it is (the same
        # discipline GM102 applies to peak_bytes below)
        compute_diffs = [f for f in ("flops", "bytes_accessed")
                         if want.get(f) != rec.get(f)]
        for field in compute_diffs:
            findings.append(Finding(
                name, "GM101",
                f"{field}: committed {want.get(field)} -> traced "
                f"{rec.get(field)}"))
        if (not compute_diffs
                and want.get("arithmetic_intensity")
                != rec.get("arithmetic_intensity")):
            findings.append(Finding(
                name, "GM101",
                f"arithmetic_intensity: committed "
                f"{want.get('arithmetic_intensity')} -> traced "
                f"{rec.get('arithmetic_intensity')} — the derived "
                "field disagrees while flops/bytes match (a tampered "
                "entry)"))
        w_mem, g_mem = want.get("memory"), rec.get("memory")
        if w_mem != g_mem:
            if not w_mem or not g_mem:
                findings.append(Finding(
                    name, "GM102",
                    f"memory budget: committed {w_mem} -> traced "
                    f"{g_mem} (None = the backend lost its memory "
                    "model, or the entry was tampered)"))
            else:
                # peak_bytes is DERIVED from the other five — compare
                # the components so one real drift reports once, and
                # flag a peak-only divergence as the tamper it is
                diffs = [f for f in _MEMORY_FIELDS
                         if f != "peak_bytes"
                         and w_mem.get(f) != g_mem.get(f)]
                for field in diffs:
                    w, g = w_mem.get(field), g_mem.get(field)
                    findings.append(Finding(
                        name, "GM102",
                        f"memory.{field}: committed {w} -> traced "
                        f"{g} ({_mib((g or 0) - (w or 0))} "
                        f"{field.replace('_bytes', '')})"))
                if not diffs:
                    findings.append(Finding(
                        name, "GM102",
                        f"memory.peak_bytes: committed "
                        f"{w_mem.get('peak_bytes')} -> traced "
                        f"{g_mem.get('peak_bytes')} — the derived "
                        "field disagrees while its components match "
                        "(a tampered entry)"))
    if full_scope:
        for name in sorted(set(committed) - set(records) - set(failed)):
            findings.append(Finding(
                name, "GM103",
                "committed cost budget names no registered program — "
                "stale entry; `make check-update` prunes it"))
    return findings


# ------------------------------------------------ capacity planner

def plan_capacity(model, s_max: int, hbm_budget: int, *,
                  params=None, optimizer_moments: int = 0,
                  zero_shards: int = 1,
                  reserved_bytes: int = 0,
                  page_size: Optional[int] = None,
                  length_dist: Optional[Sequence[int]] = None,
                  kv_dtype: str = "model") -> dict:
    """Invert the HBM ledger: how much serving capacity fits a chip.

    Args:
      model: the ``GPT`` to plan for (geometry + dtype).
      s_max: per-slot token capacity (prompt + generated).
      hbm_budget: per-chip HBM bytes available to this workload.
      params: optional real/abstract param tree — its exact bytes are
        used; otherwise the tree is shaped with ``jax.eval_shape``
        (zero FLOPs, no allocation).
      optimizer_moments: moment buffers per parameter the resident
        optimizer keeps (serving: 0; SGD+momentum: 1; Adam/LAMB: 2) —
        each costs another ``params_bytes``.
      zero_shards: graftzero DP degree (``--zero`` on the trainer
        CLIs): optimizer moments are sharded into flat buckets over
        this many ranks, so each chip pays ``shard_bytes`` (the exact
        padded-bucket math of ``parallel.zero.plan_buckets`` — ONE
        copy of the layout, byte-exact vs the real
        :class:`~..parallel.zero.ZeroOptState` allocation) per moment
        instead of ``params_bytes``. The freed ``(N-1)/N`` of the
        optimizer state is exactly what this planner re-spends on
        slots/batch. 1 = replicated (the default).
      reserved_bytes: extra fixed reservation (decode-program temps,
        runtime overhead) charged before slots are counted.
      page_size: PAGED mode (graftpage): plan a
        :class:`~..serving.kv_pages.PagePool` instead of dense slots.
        Adds ``page_bytes`` (the exact per-page shape x dtype product
        the pool allocates — byte-exact against a real allocation, the
        same pin style as the dense planner), ``max_pages`` (pages the
        budget holds BESIDE the scratch page; pass
        ``num_pages=plan["max_pages"] + 1`` to ``PagePool`` and its
        ``hbm_bytes`` matches the planned KV bytes exactly),
        ``pages_per_slot_worst`` and — with ``length_dist`` —
        ``expected_pages_per_request`` / ``expected_resident_requests``.
      length_dist: per-request TOTAL token counts (prompt + generated)
        of the traffic to plan for; paged mode averages their page
        demand to predict resident requests at the budget.
      kv_dtype: ``"model"`` or ``"int8"`` (graftquant) — the pool's
        element layout; int8 charges 1 byte per KV element plus the
        4-byte f32 per-token-per-head scale, the exact bytes the
        quantized ``PagePool`` allocates, so the
        inversion stays byte-exact in BOTH modes (meter smoke pins
        it against a real pool).

    Returns the plan dict: ``params_bytes``, ``opt_state_bytes``,
    ``per_slot_bytes`` (dense worst-case KV + per-slot scalar state —
    the exact bytes a ``PagePool`` at dense parity allocates beside
    its scratch page, validated against a real CPU-backend pool in the
    meter smoke), ``max_slots``,
    ``kv_bytes_at_max`` and ``headroom_bytes`` (what is left after
    params + optimizer + reserved + max_slots slots),
    ``max_generate_batch`` (the one-shot ``generate`` twin: rows of a
    ``[L, B, s_max, H, Dh]`` prefill cache instead of pool slots).
    """
    import jax
    import jax.numpy as jnp

    from ..serving.kv_pages import PagePool

    if hbm_budget <= 0:
        raise ValueError(f"hbm_budget must be > 0, got {hbm_budget}")
    if params is None:
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 1), jnp.int32),
                               train=False))["params"]
    from ..runtime.hbm import tree_nbytes

    params_bytes = tree_nbytes(params)
    if int(zero_shards) > 1:
        # the SAME bucket layout the trainer allocates: per-chip
        # moment cost = the padded flat shard, never an estimate
        from ..parallel.zero import plan_buckets

        per_moment = plan_buckets(params, int(zero_shards)).shard_bytes
    else:
        per_moment = params_bytes
    opt_bytes = int(optimizer_moments) * per_moment
    per_slot = (PagePool.per_slot_kv_bytes(model, s_max, kv_dtype)
                + PagePool.per_slot_state_bytes())
    fixed = params_bytes + opt_bytes + int(reserved_bytes)
    free = hbm_budget - fixed
    max_slots = max(0, free // per_slot)
    per_row = PagePool.per_slot_kv_bytes(model, s_max, kv_dtype)
    plan = {
        "hbm_budget": int(hbm_budget),
        "params_bytes": params_bytes,
        "opt_state_bytes": opt_bytes,
        "reserved_bytes": int(reserved_bytes),
        "per_slot_bytes": per_slot,
        "max_slots": int(max_slots),
        "kv_bytes_at_max": int(max_slots * per_slot),
        "headroom_bytes": int(free - max_slots * per_slot),
        "max_generate_batch": int(max(0, free // per_row)),
        "s_max": int(s_max),
        "zero_shards": int(zero_shards),
        "kv_dtype": kv_dtype,
        "fits": fixed <= hbm_budget,
    }
    if page_size is None:
        return plan
    # ---- paged mode (graftpage): same inversion, page-granular.
    # page_bytes is the ONE shape x dtype product PagePool allocates,
    # so planner == allocator byte-for-byte (pinned in the meter
    # smoke); the scratch page is charged before pages are counted.
    page_bytes = PagePool.page_kv_bytes(model, page_size, kv_dtype)
    max_pages = max(0, (free - page_bytes) // page_bytes)  # - scratch
    plan.update({
        "page_size": int(page_size),
        "page_bytes": int(page_bytes),
        "max_pages": int(max_pages),
        "pages_per_slot_worst": PagePool.pages_for(s_max, page_size),
        "paged_kv_bytes_at_max": int((max_pages + 1) * page_bytes),
    })
    if length_dist:
        demand = [PagePool.pages_for(t, page_size)
                  for t in length_dist]
        expected = sum(demand) / len(demand)
        plan["expected_pages_per_request"] = expected
        plan["expected_resident_requests"] = int(max_pages // expected)
    return plan


# --------------------------------------------------- roofline join

def roofline(flops: Optional[float], bytes_accessed: Optional[float],
             step_seconds: float, peak_flops: Optional[float],
             peak_bw: Optional[float]) -> dict:
    """Measured-vs-model efficiency attribution for one timed program.

    Returns achieved FLOP/s and bytes/s, MFU, the roofline ceiling the
    program's arithmetic intensity allows (``min(peak_flops,
    intensity * peak_bw)``), which resource bounds it, and the
    fraction of that ceiling actually achieved. Null-safe: any missing
    input nulls the dependent outputs (a CPU run or a backend without
    a cost model must never fake an efficiency number)."""
    out = {
        "achieved_flops_per_sec": None,
        "achieved_bytes_per_sec": None,
        "mfu": None,
        "arithmetic_intensity": None,
        "roofline_flops_per_sec": None,
        "roofline_bound": None,
        "roofline_frac": None,
    }
    if not step_seconds or step_seconds <= 0:
        return out
    if flops:
        out["achieved_flops_per_sec"] = flops / step_seconds
    if bytes_accessed:
        out["achieved_bytes_per_sec"] = bytes_accessed / step_seconds
    if flops and bytes_accessed:
        out["arithmetic_intensity"] = round(flops / bytes_accessed, 4)
    if flops and peak_flops:
        out["mfu"] = round(flops / step_seconds / peak_flops, 4)
    if (flops and bytes_accessed and peak_flops and peak_bw):
        ceiling = min(peak_flops, (flops / bytes_accessed) * peak_bw)
        out["roofline_flops_per_sec"] = ceiling
        out["roofline_bound"] = ("compute"
                                 if ceiling >= peak_flops else "memory")
        out["roofline_frac"] = round(flops / step_seconds / ceiling, 4)
    return out


# ------------------------------------------------------------- CLI

def run_meter(names: Optional[Sequence[str]] = None, *,
              update: bool = False,
              costs: Optional[str] = None
              ) -> Tuple[List, Dict[str, dict], List[str]]:
    """Measure the registry (full graftcheck audit pass — builds and
    compiles are shared with the budget audits) and compare/refresh
    ``analysis/costs.json`` ONLY. The ``make check`` gate runs both
    comparisons in one pass through ``check.run_check``; this entry is
    the meter-scoped view."""
    from .programs import run_audits

    path = costs or default_costs_path()
    records, audit_findings, skipped = run_audits(names)
    cost_records = {name: rec["costs"] for name, rec in records.items()
                    if "costs" in rec}
    findings = [f for f in audit_findings
                if f.rule.startswith("GM")]
    failed = frozenset(f.program for f in audit_findings
                       if f.rule in ("GC100", "GM100"))
    committed = load_costs(path)
    if update:
        full = not names and not skipped and not failed
        keep = {} if full else {k: v for k, v in committed.items()
                                if k not in cost_records}
        write_costs(cost_records, path, keep=keep)
        return findings, cost_records, skipped
    findings = findings + compare_costs(
        cost_records, committed,
        full_scope=not names and not skipped, failed=failed)
    return findings, cost_records, skipped


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    import sys

    parser = argparse.ArgumentParser(
        prog="graftmeter",
        description="static cost/memory model per compiled program + "
                    "HBM capacity planner")
    parser.add_argument("--programs", nargs="*", default=None,
                        metavar="NAME",
                        help="measure only these registry programs")
    parser.add_argument("--update", action="store_true",
                        help="refresh analysis/costs.json from the "
                             "current compile and exit")
    parser.add_argument("--json", action="store_true", dest="as_json")
    parser.add_argument("--costs", default=None, metavar="FILE",
                        help="budget file (default: analysis/costs.json)")
    parser.add_argument("--list-rules", action="store_true")
    parser.add_argument("--plan", default=None, metavar="MODEL",
                        help="capacity-plan this models.registry name "
                             "instead of auditing (with --s_max/"
                             "--hbm_gb)")
    parser.add_argument("--s_max", default=2048, type=int)
    parser.add_argument("--hbm_gb", default=16.0, type=float,
                        help="per-chip HBM budget in GiB for --plan")
    parser.add_argument("--page_size", default=None, type=int,
                        help="--plan in PAGED mode: pages-per-chip at "
                             "this page size (graftpage)")
    parser.add_argument("--optimizer_moments", default=0, type=int,
                        help="--plan: resident moment buffers per "
                             "parameter (SGD+momentum 1, LAMB 2)")
    parser.add_argument("--zero_shards", default=1, type=int,
                        help="--plan: graftzero DP degree — moments "
                             "sharded over N ranks cost shard_bytes "
                             "per chip instead of params_bytes")
    parser.add_argument("--kv_dtype", default="model",
                        choices=("model", "int8"),
                        help="--plan: KV-pool element layout — int8 "
                             "(graftquant) charges 1 byte/element + "
                             "the f32 per-token-per-head scale")
    args = parser.parse_args(argv)

    if args.list_rules:
        for rid in sorted(RULES_GM):
            print(f"{rid}  {RULES_GM[rid]}")
        return 0

    if args.plan:
        from ..models import get_model

        model = get_model(args.plan)
        plan = plan_capacity(model, min(args.s_max, model.max_seq_len),
                             int(args.hbm_gb * (1 << 30)),
                             optimizer_moments=args.optimizer_moments,
                             zero_shards=args.zero_shards,
                             page_size=args.page_size,
                             kv_dtype=args.kv_dtype)
        if args.as_json:
            print(json.dumps(plan, indent=2, sort_keys=True))
        else:
            print(f"model={args.plan} s_max={plan['s_max']} "
                  f"budget={plan['hbm_budget'] / (1 << 30):.1f} GiB")
            print(f"  params            "
                  f"{plan['params_bytes'] / (1 << 20):10.1f} MiB")
            if args.optimizer_moments:
                print(f"  optimizer state   "
                      f"{plan['opt_state_bytes'] / (1 << 20):10.1f} MiB"
                      + (f" (zero_shards={plan['zero_shards']})"
                         if args.zero_shards > 1 else ""))
            print(f"  per KV slot       "
                  f"{plan['per_slot_bytes'] / (1 << 20):10.1f} MiB"
                  + (" (int8 + f32 scales)"
                     if args.kv_dtype == "int8" else ""))
            print(f"  max resident slots {plan['max_slots']:9d}")
            print(f"  max generate batch {plan['max_generate_batch']:9d}")
            print(f"  headroom          "
                  f"{plan['headroom_bytes'] / (1 << 20):10.1f} MiB")
            if args.page_size:
                print(f"  per KV page       "
                      f"{plan['page_bytes'] / (1 << 20):10.3f} MiB "
                      f"(page_size={plan['page_size']})")
                print(f"  pages per chip     {plan['max_pages']:9d}")
        return 0

    try:
        findings, records, skipped = run_meter(
            args.programs, update=args.update, costs=args.costs)
    except KeyError as e:
        print(f"graftmeter: {e.args[0]}", file=sys.stderr)
        return 2
    if args.as_json:
        print(json.dumps({
            "findings": [{"program": f.program, "rule": f.rule,
                          "message": f.message} for f in findings],
            "programs": {k: records[k] for k in sorted(records)},
            "skipped": skipped,
            "updated": bool(args.update),
            "ok": not findings,
        }, indent=2))
    else:
        for f in findings:
            print(f.render())
        for s in skipped:
            print(f"graftmeter: skipped {s}", file=sys.stderr)
        verb = "updated" if args.update else "checked"
        if findings:
            print(f"graftmeter: {len(findings)} finding(s) across "
                  f"{len(records)} program(s)")
        else:
            print(f"graftmeter: {verb} {len(records)} program(s), "
                  "clean")
    return 1 if findings else 0


if __name__ == "__main__":
    import sys

    # same platform pinning as analysis.check: the meter compiles on
    # the 8-device CPU mesh, never on a live accelerator
    if "jax" not in sys.modules:  # pragma: no branch
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    sys.exit(main())
