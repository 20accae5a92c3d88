"""graftlint CLI: ``python -m pytorch_multiprocessing_distributed_tpu.analysis.lint``.

Runs the AST rule engine (:mod:`.rules`) over the package (or explicit
paths), applies per-line suppressions and the committed baseline, and
exits non-zero on any live finding — the tier-1 gate calls this.

Deliberately jax-free: the gate costs milliseconds of ``ast.parse``,
never a backend bring-up, so it runs first in every pipeline.

Suppression (line-scoped, rule-cited — greppable justification):

    x = float(y)  # graftlint: disable=GL101  <reason>
    x = float(y)  # graftlint: disable        (all rules on this line)

Baseline workflow (grandfathering pre-existing findings so the gate can
land red-free and ratchet):

    python -m ...analysis.lint --write-baseline   # snapshot findings
    python -m ...analysis.lint                    # exits 0; NEW findings fail

Baseline entries match on (path, rule, source-line text) — line drift
from unrelated edits doesn't churn the file; editing the offending line
surfaces the finding again (by design: touched code must be clean).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from .rules import RULES, Finding, analyze_files

# rule list = comma-separated GL codes ONLY — anything after is the
# human reason and must not leak into the parsed set ("disable=GL101
# TTFT boundary" suppresses GL101, not the nonexistent rule "GL101 TTFT")
_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*disable(?:=(GL\d{3}(?:\s*,\s*GL\d{3})*))?")

_EXCLUDE_DIRS = {"__pycache__", ".git", "build"}


def package_root() -> str:
    """The pytorch_multiprocessing_distributed_tpu package directory."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def default_baseline_path() -> str:
    return os.path.join(package_root(), "analysis", "baseline.json")


def discover(paths: Sequence[str]) -> List[str]:
    """Expand files/dirs into a sorted .py file list. A path that is
    neither a directory nor an existing .py file raises — a typo'd CI
    invocation must fail loudly, never report 'clean' on nothing."""
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [d for d in dirnames
                               if d not in _EXCLUDE_DIRS]
                for name in sorted(filenames):
                    if name.endswith(".py"):
                        out.append(os.path.join(dirpath, name))
        elif p.endswith(".py") and os.path.isfile(p):
            out.append(p)
        else:
            raise FileNotFoundError(
                f"graftlint: {p!r} is neither a directory nor an "
                "existing .py file")
    return sorted(set(out))


def _lines(path: str, line_cache: Dict[str, List[str]]) -> List[str]:
    lines = line_cache.get(path)
    if lines is None:
        try:
            with open(path, "r", encoding="utf-8") as fh:
                lines = fh.read().splitlines()
        except OSError:
            lines = []
        line_cache[path] = lines
    return lines


def _suppressed(finding: Finding, line_cache: Dict[str, List[str]]) -> bool:
    lines = _lines(finding.path, line_cache)
    if not (0 < finding.line <= len(lines)):
        return False
    m = _SUPPRESS_RE.search(lines[finding.line - 1])
    if not m:
        return False
    if m.group(1) is None:
        return True
    rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
    return finding.rule in rules


def _line_text(finding: Finding, line_cache: Dict[str, List[str]]) -> str:
    lines = _lines(finding.path, line_cache)
    if 0 < finding.line <= len(lines):
        return lines[finding.line - 1].strip()
    return ""


def _rel(path: str, base: str) -> str:
    try:
        return os.path.relpath(os.path.abspath(path), base)
    except ValueError:
        return path


def load_baseline(path: Optional[str]) -> List[dict]:
    if not path or not os.path.exists(path):
        return []
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    return list(data.get("findings", []))


def run_lint(paths: Sequence[str], *, baseline: Optional[str] = None,
             base_dir: Optional[str] = None,
             ) -> Tuple[List[Finding], List[Finding]]:
    """Lint ``paths``; returns ``(live, baselined)`` findings, with
    per-line suppressions already removed from both."""
    base_dir = base_dir or os.path.dirname(package_root())
    files = discover(paths)
    findings = analyze_files(files, package_parent=base_dir)
    line_cache: Dict[str, List[str]] = {}
    findings = [f for f in findings if not _suppressed(f, line_cache)]

    allowance: Dict[Tuple[str, str, str], int] = {}
    for entry in load_baseline(baseline):
        key = (entry.get("path", ""), entry.get("rule", ""),
               entry.get("text", ""))
        allowance[key] = allowance.get(key, 0) + 1
    live: List[Finding] = []
    grandfathered: List[Finding] = []
    for f in findings:
        key = (_rel(f.path, base_dir), f.rule, _line_text(f, line_cache))
        if allowance.get(key, 0) > 0:
            allowance[key] -= 1
            grandfathered.append(f)
        else:
            live.append(f)
    return live, grandfathered


def write_baseline(findings: Sequence[Finding], path: str,
                   base_dir: str, *,
                   keep: Optional[List[dict]] = None) -> None:
    """Snapshot ``findings`` into the baseline file. ``keep`` carries
    pre-existing entries to preserve verbatim (files outside a
    partial-scope run)."""
    line_cache: Dict[str, List[str]] = {}
    payload = {
        "comment": "graftlint grandfathered findings — shrink, never "
                   "grow. Matched on (path, rule, line text): editing a "
                   "baselined line resurfaces its finding.",
        "findings": list(keep or []) + [
            {"path": _rel(f.path, base_dir), "rule": f.rule,
             "line": f.line, "text": _line_text(f, line_cache)}
            for f in findings
        ],
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=2, sort_keys=False)
        fh.write("\n")


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="graftlint",
        description="JAX/TPU jit-hygiene static analysis (AST-only, no "
                    "jax import)")
    parser.add_argument(
        "paths", nargs="*",
        help="files/dirs to lint (default: the package)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="machine-readable findings on stdout")
    parser.add_argument(
        "--baseline", default=None, metavar="FILE",
        help="baseline file (default: analysis/baseline.json when "
             "linting the package; 'none' disables)")
    parser.add_argument(
        "--write-baseline", action="store_true",
        help="snapshot current findings into the baseline and exit 0")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule table and exit")
    args = parser.parse_args(argv)

    if args.list_rules:
        for rid in sorted(RULES):
            print(f"{rid}  {RULES[rid]}")
        return 0

    paths = args.paths or [package_root()]
    base_dir = os.path.dirname(package_root())
    baseline = args.baseline
    if baseline is None:
        baseline = default_baseline_path()
    elif baseline.lower() == "none":
        baseline = None

    try:
        if args.write_baseline:
            target = baseline or default_baseline_path()
            live, grandfathered = run_lint(paths, baseline=None,
                                           base_dir=base_dir)
            # partial-scope runs must not discard grandfathered entries
            # for files OUTSIDE the linted set: merge, don't overwrite
            linted = {_rel(f, base_dir) for f in discover(paths)}
            kept = [e for e in load_baseline(target)
                    if e.get("path", "") not in linted]
            write_baseline(live, target, base_dir, keep=kept)
            print(f"graftlint: baselined {len(live)} finding(s)"
                  + (f" (+{len(kept)} kept outside scope)" if kept
                     else "") + f" -> {target}")
            return 0

        live, grandfathered = run_lint(paths, baseline=baseline,
                                       base_dir=base_dir)
    except FileNotFoundError as e:
        print(e, file=sys.stderr)
        return 2
    if args.as_json:
        print(json.dumps({
            "findings": [
                {"path": _rel(f.path, base_dir), "line": f.line,
                 "col": f.col, "rule": f.rule, "message": f.message}
                for f in live
            ],
            "baselined": len(grandfathered),
            "ok": not live,
        }, indent=2))
    else:
        for f in live:
            print(Finding(_rel(f.path, base_dir), f.line, f.col, f.rule,
                          f.message).render())
        note = (f" ({len(grandfathered)} baselined)"
                if grandfathered else "")
        if live:
            print(f"graftlint: {len(live)} finding(s){note}")
        else:
            print(f"graftlint: clean{note}")
    return 1 if live else 0


if __name__ == "__main__":
    sys.exit(main())
