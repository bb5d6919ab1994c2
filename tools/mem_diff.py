"""mem_diff — compare two memory-ledger snapshots per segment.

"The KV pool grew", "prefix sidecars doubled", "unattributed bytes
are climbing" become CHECKABLE: point this at two ledger snapshot
files (``MemoryLedger.save()`` artifacts — the typed segment tree +
the ground-truth residual) and it reports per-SEGMENT byte deltas as
percent of the baseline — optionally failing on drift thresholds in
BOTH directions so a script can gate on them (the
profile_diff idiom, applied to device memory).

Percent of side A, not absolute bytes: two runs may serve different
models/pool sizes, so each segment's delta is normalized to its own
baseline (``(b - a) / max(a, 1) * 100``). A segment absent from a
side reads as 0 bytes — a brand-new segment on side B reads as a
huge growth and DOES trip a ``>`` gate (that is the point); a
segment that vanished trips a ``<`` gate.

Usage:
  python tools/mem_diff.py old.json new.json
  python tools/mem_diff.py A.json B.json \\
      --fail-on 'segment:kv_pages>+25%' \\
      --fail-on 'segment:unattributed>+50%' \\
      --fail-on 'segment:weights<-10%'

--fail-on SPEC grammar: ``segment:<name>{>|<}{+|-}PCT%`` — <name> a
typed ledger segment (kv_pages, prefix_sidecar, spec_draft_pool,
weights, optimizer_state, grads, activations_peak, other) or one of
the pseudo-segments ``attributed`` / ``unattributed`` / ``total``
(attributed + unattributed). ``>`` fails when B exceeds A by more
than PCT percent of A (leak-like: growing is worse); ``<`` fails
when B undershoots A by more than PCT percent (coverage-like: a
segment that vanished). The sign on PCT is cosmetic.

Vacuity guard: two snapshots whose totals are BOTH zero fail loudly
instead of green-lighting — a gate that compared nothing proved
nothing.

Last stdout line is a JSON report; exit 0 iff no --fail-on tripped.
Stdlib-only (loads memledger straight from its file via
tools/_obs.py — no jax, no package import).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from tools._obs import obs_mod  # noqa: E402

PSEUDO = ("attributed", "unattributed", "total")

_SPEC_RE = re.compile(
    r"^segment:(?P<key>.+?)"
    r"(?P<op>[<>])(?P<sign>[+-]?)(?P<pct>\d+(?:\.\d+)?)%?$")


def parse_spec(s):
    m = _SPEC_RE.match(s.strip())
    if not m:
        raise argparse.ArgumentTypeError(
            f"bad --fail-on spec {s!r} "
            "(grammar: segment:<name>{>|<}{+|-}PCT%)")
    return {"key": m.group("key"), "op": m.group("op"),
            "pct": float(m.group("pct")), "spec": s.strip()}


def load_segments(path):
    """Snapshot file -> {segment: bytes} incl. the pseudo-segments."""
    ml = obs_mod("memledger")
    doc = ml.load_snapshot(path)
    dg = doc.get("digest") or {}
    segs = {str(k): int(v) for k, v in (dg.get("segments")
                                        or {}).items()}
    att = int(dg.get("attributed_bytes") or sum(segs.values()))
    un = int(dg.get("unattributed_bytes") or 0)
    segs["attributed"] = att
    segs["unattributed"] = un
    segs["total"] = att + un
    return segs


def _delta_table(a, b):
    """Per-segment table {seg: {a, b, delta_pct}} — B's bytes as a
    percent change over A's (A==0, B>0 reads as +inf growth: a
    brand-new segment is maximal drift, not division noise). Sorted
    by |delta|."""
    rows = {}
    for key in set(a) | set(b):
        ba, bb = int(a.get(key, 0)), int(b.get(key, 0))
        if ba == 0:
            d = 0.0 if bb == 0 else float("inf")
        else:
            d = (bb - ba) / float(ba) * 100.0
        rows[key] = {"a": ba, "b": bb,
                     "delta_pct": (d if d in (float("inf"),)
                                   else round(d, 4))}
    return dict(sorted(
        rows.items(),
        key=lambda kv: -abs(kv[1]["delta_pct"])
        if kv[1]["delta_pct"] != float("inf") else float("-inf")))


def check_fail_on(rows, specs):
    failures = []
    for spec in specs:
        row = rows.get(spec["key"],
                       {"a": 0, "b": 0, "delta_pct": 0.0})
        d = row["delta_pct"]
        bad = d > spec["pct"] if spec["op"] == ">" \
            else d < -spec["pct"]
        if bad:
            failures.append({"spec": spec["spec"],
                             "key": f"segment:{spec['key']}",
                             "a": row["a"], "b": row["b"],
                             "delta_pct": d})
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="diff two memory-ledger snapshots on per-segment "
                    "byte deltas (percent of baseline)")
    ap.add_argument("a", help="baseline ledger snapshot (.json)")
    ap.add_argument("b", help="candidate ledger snapshot (.json)")
    ap.add_argument("--fail-on", action="append", type=parse_spec,
                    default=[], metavar="segment:NAME{>|<}PCT%",
                    help="byte-drift threshold as percent of the "
                         "baseline segment (repeatable; both "
                         "directions)")
    ap.add_argument("--top", type=int, default=12,
                    help="rows in the human-readable table")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress the human-readable section")
    args = ap.parse_args(argv)

    segs_a = load_segments(args.a)
    segs_b = load_segments(args.b)
    rows = _delta_table(segs_a, segs_b)
    failures = check_fail_on(rows, args.fail_on)
    vacuous = segs_a["total"] == 0 and segs_b["total"] == 0
    if vacuous:
        failures.append({
            "spec": "(vacuity guard)", "key": None, "a": 0, "b": 0,
            "delta_pct": 0.0,
            "error": "both snapshots are empty — nothing was "
                     "compared"})

    report = {"a": args.a, "b": args.b,
              "total_bytes": {"a": segs_a["total"],
                              "b": segs_b["total"]},
              "segments": rows,
              "fail_on": [s["spec"] for s in args.fail_on],
              "failures": failures, "vacuous": vacuous,
              "ok": not failures}

    if not args.quiet:
        for key, r in list(rows.items())[:args.top]:
            print(f"  segment {key}: {r['a']} -> {r['b']} B "
                  f"({r['delta_pct']:+}%)", file=sys.stderr)
        for f in failures:
            print(f"  FAIL {f['spec']}: {f.get('key')} "
                  f"{f.get('a')} -> {f.get('b')} "
                  f"({f.get('delta_pct'):+}%)", file=sys.stderr)
    print(json.dumps(report, default=str))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
