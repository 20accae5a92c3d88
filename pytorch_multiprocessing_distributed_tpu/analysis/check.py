"""graftcheck CLI: ``python -m pytorch_multiprocessing_distributed_tpu.analysis.check``.

The IR-level complement to graftlint: traces the registered canonical
programs (``analysis/programs.py``) abstractly — CPU-safe, no FLOPs —
and enforces two layers of contract:

1. **inline invariants**, declared in code by each registration hook
   (exactly one grad-sized psum in the DP train step, donation reaches
   the lowered module, FSDP emits all-gather + reduce-scatter, ...) —
   live checks that no snapshot refresh can launder;
2. **committed budgets/fingerprints** (``analysis/fingerprints.json``):
   per-program collective budgets (count + bytes per mesh axis),
   dtype-promotion counts, donation alias counts, compiled-HLO
   collective sets, and a structural digest. Any drift fails with a
   readable diff naming the program and rule; deliberate changes are
   re-baselined with ``make check-update`` (and reviewed as a JSON
   diff in the PR);
3. **committed cost/memory budgets** (``analysis/costs.json``,
   graftmeter — GM rules from ``analysis/meter.py``): per-program
   FLOPs, bytes accessed, arithmetic intensity and the compiled
   argument/output/temp/generated-code HBM breakdown, measured off
   the SAME compile as the HLO audit. Temp-HBM growth fails with a
   "+N MiB temp" diff naming program + field.

Workflow::

    make check            # the tier-1 gate
    make check-update     # refresh fingerprints after a reviewed change
    python -m ...analysis.check --programs lm_step_tp --json

Unlike the lint gate this tool imports jax (it exists to interrogate
the tracer) — it pins itself to the host platform before the backend
comes up; tier-1 collection already runs it under ``JAX_PLATFORMS=cpu``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

# pin the host platform before jax is imported (a no-op when the
# caller — pytest's conftest — already did and imported it)
if "jax" not in sys.modules:  # pragma: no branch
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

from . import ir  # noqa: E402
from .programs import Finding, RULES_GC, run_audits  # noqa: E402


def package_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def default_fingerprints_path() -> str:
    return os.path.join(package_root(), "analysis", "fingerprints.json")


def load_fingerprints(path: str) -> Dict[str, dict]:
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as fh:
        return dict(json.load(fh).get("programs", {}))


def write_fingerprints(records: Dict[str, dict], path: str, *,
                       keep: Optional[Dict[str, dict]] = None) -> None:
    """Snapshot ``records`` (merging ``keep`` for programs outside a
    partial-scope or device-limited run — a laptop refresh must not
    drop the TP entries it could not trace)."""
    programs = dict(keep or {})
    programs.update(records)
    payload = {
        "comment": "graftcheck committed budgets/fingerprints — refresh "
                   "deliberately via `make check-update` and review the "
                   "diff; drift here is a semantic change to a hot "
                   "program.",
        "jax": jax.__version__,
        "programs": {k: programs[k] for k in sorted(programs)},
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=2, sort_keys=False)
        fh.write("\n")


def _diff_dict(name: str, rule: str, field: str, want, got,
               out: List[Finding]) -> None:
    if want == got:
        return
    keys = sorted(set(want or {}) | set(got or {}))
    parts = []
    for k in keys:
        w, g = (want or {}).get(k), (got or {}).get(k)
        if w != g:
            parts.append(f"{k}: committed {w} -> traced {g}")
    out.append(Finding(name, rule,
                       f"{field} drift — " + "; ".join(parts)))


def compare(records: Dict[str, dict], committed: Dict[str, dict],
            *, full_scope: bool,
            failed: frozenset = frozenset()) -> List[Finding]:
    """Snapshot comparison: every traced program against its committed
    entry, field by field, each mismatch a rule-tagged finding with
    the delta spelled out."""
    findings: List[Finding] = []
    for name, rec in records.items():
        want = committed.get(name)
        if want is None:
            findings.append(Finding(
                name, "GC106",
                "no committed fingerprint — run `make check-update` "
                "and review the new entry"))
            continue
        got_fp, want_fp = rec["fingerprint"], want.get("fingerprint", {})
        if got_fp["digest"] != want_fp.get("digest"):
            hist_diff = ir.diff_histograms(
                want_fp.get("ops", {}), got_fp["ops"])
            findings.append(Finding(
                name, "GC105",
                "program structure changed: digest "
                f"{want_fp.get('digest')} -> {got_fp['digest']}"
                + (f" (op delta: {hist_diff})" if hist_diff else
                   " (same op mix — shapes/params/order moved)")))
        _diff_dict(name, "GC101", "collective budget",
                   want.get("collectives"), rec.get("collectives"),
                   findings)
        _diff_dict(name, "GC104", "dtype-promotion budget",
                   want.get("dtype_promotions"),
                   rec.get("dtype_promotions"), findings)
        if "donation" in rec or "donation" in want:
            _diff_dict(name, "GC102", "donation aliases",
                       want.get("donation"), rec.get("donation"),
                       findings)
        if "hlo_collectives" in rec or "hlo_collectives" in want:
            _diff_dict(name, "GC103", "compiled (HLO) collectives",
                       want.get("hlo_collectives"),
                       rec.get("hlo_collectives"), findings)
        if "grad_sized_psums" in rec or "grad_sized_psums" in want:
            # presence-or, like the dict fields: the field VANISHING
            # from either side (inline declaration deleted, or the
            # committed entry tampered) must flag, not skip — the
            # invariant is only refresh-proof if its absence is loud
            got_n = rec.get("grad_sized_psums")
            want_n = want.get("grad_sized_psums")
            if got_n != want_n:
                findings.append(Finding(
                    name, "GC101",
                    f"grad-sized psum count: committed {want_n} -> "
                    f"traced {got_n} (None = the declaration/entry is "
                    "gone, which is itself a drift)"))
    if full_scope:
        # programs that FAILED to build (GC100) are registered, not
        # stale — their committed entries are deliberately kept, and a
        # second "stale entry" finding here would send the operator
        # chasing a lost hook that exists
        for name in sorted(set(committed) - set(records) - set(failed)):
            findings.append(Finding(
                name, "GC106",
                "committed fingerprint names no registered program — "
                "stale entry (or a lost registration hook); "
                "`make check-update` prunes it"))
    return findings


def run_check(names: Optional[Sequence[str]] = None, *,
              update: bool = False,
              fingerprints: Optional[str] = None,
              costs: Optional[str] = None
              ) -> Tuple[List[Finding], Dict[str, dict], List[str]]:
    """Library entry (the tier-1 gate calls this in-process): audit,
    compare (or snapshot with ``update``), return
    ``(findings, records, skipped)``. One pass enforces BOTH committed
    files: ``analysis/fingerprints.json`` (structure/collective
    budgets, GC rules) and ``analysis/costs.json`` (graftmeter
    FLOPs/bytes/memory budgets, GM rules) — the audit's one compile
    feeds both, so they can never disagree about which program ran."""
    from . import meter

    path = fingerprints or default_fingerprints_path()
    costs_path = costs or meter.default_costs_path()
    records, findings, skipped = run_audits(names)
    # split each record: "costs" is graftmeter's half, committed and
    # compared separately in costs.json
    fp_records: Dict[str, dict] = {}
    cost_records: Dict[str, dict] = {}
    for name, rec in records.items():
        rec = dict(rec)
        cost_rec = rec.pop("costs", None)
        fp_records[name] = rec
        if cost_rec is not None:
            cost_records[name] = cost_rec
    committed = load_fingerprints(path)
    committed_costs = meter.load_costs(costs_path)
    failed_fp = frozenset(f.program for f in findings
                          if f.rule == "GC100")
    # a GM100 (compile-for-metering failure) program produced no cost
    # record but its committed budget is NOT stale — keep it, like a
    # GC100's fingerprint entry
    failed_costs = frozenset(f.program for f in findings
                             if f.rule in ("GC100", "GM100"))
    if update:
        # prune stale names only on a COMPLETE clean enumeration: a
        # name-filtered, device-limited, or build-failed (GC100 — the
        # program produced no record) run must keep the entries it
        # could not re-trace, or one transient breakage would silently
        # delete a program's committed budget history
        full = not names and not skipped and not failed_fp
        keep = {} if full else {k: v for k, v in committed.items()
                                if k not in fp_records}
        write_fingerprints(fp_records, path, keep=keep)
        full_costs = not names and not skipped and not failed_costs
        keep_costs = ({} if full_costs
                      else {k: v for k, v in committed_costs.items()
                            if k not in cost_records})
        if cost_records or keep_costs != committed_costs:
            # skip the no-op rewrite (nothing measured, nothing pruned)
            meter.write_costs(cost_records, costs_path, keep=keep_costs)
        return findings, records, skipped
    findings = findings + compare(
        fp_records, committed,
        full_scope=not names and not skipped,
        failed=failed_fp)
    findings = findings + meter.compare_costs(
        cost_records, committed_costs,
        full_scope=not names and not skipped,
        failed=failed_costs)
    return findings, records, skipped


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="graftcheck",
        description="jaxpr-level program auditor: collective budgets, "
                    "donation/resharding/dtype audits, golden program "
                    "fingerprints")
    parser.add_argument("--programs", nargs="*", default=None,
                        metavar="NAME",
                        help="audit only these programs")
    parser.add_argument("--update", action="store_true",
                        help="refresh analysis/fingerprints.json from "
                             "the current trace and exit (inline-"
                             "invariant violations still fail)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="machine-readable results on stdout")
    parser.add_argument("--fingerprints", default=None, metavar="FILE",
                        help="fingerprint file (default: "
                             "analysis/fingerprints.json)")
    parser.add_argument("--costs", default=None, metavar="FILE",
                        help="graftmeter cost-budget file (default: "
                             "analysis/costs.json)")
    parser.add_argument("--list", action="store_true", dest="list_only",
                        help="list registered programs and exit")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the GC rule table and exit")
    args = parser.parse_args(argv)

    if args.list_rules:
        from .meter import RULES_GM

        for rid in sorted(RULES_GC):
            print(f"{rid}  {RULES_GC[rid]}")
        for rid in sorted(RULES_GM):
            print(f"{rid}  {RULES_GM[rid]}")
        return 0
    if args.list_only:
        from .programs import collect

        for spec in collect():
            print(f"{spec.name}  ({spec.module}, >= "
                  f"{spec.min_devices} devices)")
        return 0

    try:
        findings, records, skipped = run_check(
            args.programs, update=args.update,
            fingerprints=args.fingerprints, costs=args.costs)
    except KeyError as e:
        print(f"graftcheck: {e.args[0]}", file=sys.stderr)
        return 2

    if args.as_json:
        print(json.dumps({
            "findings": [{"program": f.program, "rule": f.rule,
                          "message": f.message} for f in findings],
            "programs": sorted(records),
            "skipped": skipped,
            "updated": bool(args.update),
            "ok": not findings,
        }, indent=2))
    else:
        for f in findings:
            print(f.render())
        for s in skipped:
            print(f"graftcheck: skipped {s}", file=sys.stderr)
        verb = "updated" if args.update else "checked"
        if findings:
            print(f"graftcheck: {len(findings)} finding(s) across "
                  f"{len(records)} program(s)")
        else:
            print(f"graftcheck: {verb} {len(records)} program(s), "
                  "clean")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
