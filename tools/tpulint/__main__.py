"""tpulint CLI — `python -m tools.tpulint [paths...]`.

Exit status is the gate: 0 = no non-baselined findings, 1 = new
findings (or a syntax error in a scanned file). The machine-readable
report always lands at --report (default: ./lint_report.json).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from .core import (load_baseline, repo_root, run_lint, write_baseline,
                   write_report)
from .rules import RULES


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="tpulint",
        description="AST invariant checkers for paddle_tpu's "
                    "trace-safety/durability/concurrency contracts")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: paddle_tpu, "
                         "tools, bench.py)")
    ap.add_argument("--rule", action="append", default=None,
                    metavar="ID", help="run only these rule ids "
                    "(repeatable)")
    ap.add_argument("--json", action="store_true",
                    help="print the full report as JSON on stdout "
                         "(last line stays machine-parseable either "
                         "way)")
    ap.add_argument("--report", default="lint_report.json",
                    metavar="PATH",
                    help="where to write lint_report.json (default: "
                         "./lint_report.json)")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="baseline file (default: the committed "
                         "tools/tpulint/baseline.json)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from current findings "
                         "(keeps existing justifications; new entries "
                         "are marked UNREVIEWED)")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--root", default=None,
                    help="repo root override (tests lint fixture "
                         "trees)")
    args = ap.parse_args(argv)

    if args.list_rules:
        for r in RULES.values():
            print(f"{r.id}  {r.name}\n    {r.doc}\n")
        return 0

    if args.update_baseline and (args.rule or args.paths):
        # a filtered run sees only a slice of the findings; rewriting
        # from it would silently delete every other rule's entries —
        # and their hand-written justifications
        print("tpulint: --update-baseline requires a FULL run "
              "(no --rule, no explicit paths) — a filtered rewrite "
              "would drop every unseen entry", file=sys.stderr)
        return 2
    if args.update_baseline and args.root and not args.baseline:
        # a foreign-root run over DEFAULT_TARGETS finds (at best)
        # nothing and (at worst) missing-target PARSE rows — writing
        # THAT over the committed baseline deletes every justification
        print("tpulint: --update-baseline with --root needs an "
              "explicit --baseline — refusing to rewrite the "
              "committed tools/tpulint/baseline.json from a foreign "
              "tree", file=sys.stderr)
        return 2

    root = os.path.abspath(args.root) if args.root else repo_root()
    baseline = load_baseline(args.baseline)
    report = run_lint(paths=args.paths or None, rules=args.rule,
                      root=root, baseline=baseline)
    findings = report["_findings_objs"]

    if args.update_baseline:
        path, n, skipped = write_baseline(findings, path=args.baseline,
                                          previous=baseline)
        print(f"baseline rewritten: {path} "
              f"({n} finding(s) grandfathered)")
        if skipped:
            # an honest verdict: these can't be baselined, so the
            # very next gate run still exits 1 — say so now
            print(f"tpulint: {skipped} PARSE/checker-error finding(s) "
                  f"NOT grandfathered — fix them; the gate stays red",
                  file=sys.stderr)
            return 1
        return 0

    write_report(report, args.report)

    if args.json:
        doc = {k: v for k, v in report.items()
               if not k.startswith("_")}
        print(json.dumps(doc, indent=1))
    else:
        for f in findings:
            mark = " [baselined]" if f.baselined else ""
            print(f"{f.path}:{f.line}: {f.rule} {f.message}{mark}")
        for e in report["unused_baseline"]:
            print(f"baseline: UNUSED entry {e['rule']} {e['path']} "
                  f"[{e.get('qualname')}] {e.get('symbol')} — delete "
                  f"it (the debt is paid)")
    # the machine-readable last line
    print(json.dumps({
        "ok": report["non_baselined"] == 0,
        "non_baselined": report["non_baselined"],
        "baselined": report["baselined"],
        "suppressed": report["suppressed"],
        "files_scanned": report["files_scanned"],
        "counts": report["counts"],
        "report": os.path.abspath(args.report),
    }))
    return 0 if report["non_baselined"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
