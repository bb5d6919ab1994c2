"""profile_diff — compare two folded continuous-profile snapshots.

"The decode phase got cheaper" or "fingerprinting no longer dominates
placement" become CHECKABLE: point this at two folded-profile files
(``ContinuousProfiler.save()`` artifacts — one ``stack weight`` line
per collapsed stack, ``phase:decode;mod.fn;... N``) and it reports
per-PHASE and per-leaf-FRAME wall-share deltas in absolute percentage
points — optionally failing on drift thresholds so a script can
gate on them (the metrics_diff idiom, applied to profiles).

Shares, not raw sample counts: the two runs may have sampled at
different rates or for different durations, so each side is first
normalized to shares of its own total weight. A delta of ``+5%`` means
the phase/frame absorbs five percentage points MORE of the host's
sampled wall time than it did in the baseline.

Usage:
  python tools/profile_diff.py old.folded new.folded
  python tools/profile_diff.py A.folded B.folded \\
      --fail-on 'phase:decode>+5%' \\
      --fail-on 'frame:paddle_tpu.nlp.serving._prefill_full>+3%'

--fail-on SPEC grammar: ``{phase|frame}:<key>{>|<}{+|-}PCT%`` —
``phase:`` gates a serving-phase share, ``frame:`` a leaf-frame share;
``>`` fails when B's share exceeds A's by more than PCT percentage
points (hot-path-like: growing is worse), ``<`` fails when B's share
UNDERSHOOTS A's by more than PCT points (coverage-like: a phase that
vanished). The sign on PCT is cosmetic (``>+5%`` == ``>5%``). A key
absent from a side reads as share 0.0 — a brand-new hot frame DOES
trip a ``>`` gate (that is the point).

Vacuity guard: two EMPTY profiles (zero total weight on both sides)
fail loudly instead of green-lighting — a gate that compared nothing
proved nothing.

Last stdout line is a JSON report; exit 0 iff no --fail-on tripped.
Stdlib-only (loads contprof straight from its file via tools/_obs.py
— no jax, no package import).
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

_SPEC_RE = re.compile(
    r"^(?P<kind>phase|frame):(?P<key>.+?)"
    r"(?P<op>[<>])(?P<sign>[+-]?)(?P<pct>\d+(?:\.\d+)?)%?$")


def parse_spec(s):
    m = _SPEC_RE.match(s.strip())
    if not m:
        raise argparse.ArgumentTypeError(
            f"bad --fail-on spec {s!r} "
            "(grammar: {phase|frame}:<key>{>|<}{+|-}PCT%)")
    return {"kind": m.group("kind"), "key": m.group("key"),
            "op": m.group("op"), "pct": float(m.group("pct")),
            "spec": s.strip()}


def _shares(path):
    cp = obs_mod("contprof")
    folded = cp.load_folded(path)
    phases, frames = cp.fold_shares(folded)
    return folded, phases, frames


def _delta_table(a, b):
    """Per-key share table: {key: {a, b, delta_pp}} with shares and
    the delta in absolute percentage points, sorted by |delta|."""
    rows = {}
    for key in set(a) | set(b):
        sa, sb = a.get(key, 0.0), b.get(key, 0.0)
        rows[key] = {"a": round(sa, 6), "b": round(sb, 6),
                     "delta_pp": round((sb - sa) * 100.0, 4)}
    return dict(sorted(rows.items(),
                       key=lambda kv: -abs(kv[1]["delta_pp"])))


def check_fail_on(phase_rows, frame_rows, specs):
    failures = []
    for spec in specs:
        rows = phase_rows if spec["kind"] == "phase" else frame_rows
        row = rows.get(spec["key"],
                       {"a": 0.0, "b": 0.0, "delta_pp": 0.0})
        d = row["delta_pp"]
        bad = d > spec["pct"] if spec["op"] == ">" else d < -spec["pct"]
        if bad:
            failures.append({"spec": spec["spec"],
                             "key": f"{spec['kind']}:{spec['key']}",
                             "a": row["a"], "b": row["b"],
                             "delta_pp": d})
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="diff two folded continuous-profile files on "
                    "per-phase / per-frame wall-share deltas")
    ap.add_argument("a", help="baseline folded profile")
    ap.add_argument("b", help="candidate folded profile")
    ap.add_argument("--fail-on", action="append", type=parse_spec,
                    default=[], metavar="{phase|frame}:KEY{>|<}PCT%",
                    help="share-drift threshold in absolute "
                         "percentage points (repeatable)")
    ap.add_argument("--top", type=int, default=12,
                    help="rows in the human-readable tables")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress the human-readable section")
    args = ap.parse_args(argv)

    folded_a, phases_a, frames_a = _shares(args.a)
    folded_b, phases_b, frames_b = _shares(args.b)
    total_a = sum(folded_a.values())
    total_b = sum(folded_b.values())

    phase_rows = _delta_table(phases_a, phases_b)
    frame_rows = _delta_table(frames_a, frames_b)
    failures = check_fail_on(phase_rows, frame_rows, args.fail_on)
    vacuous = total_a == 0 and total_b == 0
    if vacuous:
        failures.append({
            "spec": "(vacuity guard)", "key": None, "a": 0, "b": 0,
            "delta_pp": 0.0,
            "error": "both profiles are empty — nothing was compared"})

    report = {"a": args.a, "b": args.b,
              "total_weight": {"a": total_a, "b": total_b},
              "phases": phase_rows,
              "frames": dict(list(frame_rows.items())[:64]),
              "fail_on": [s["spec"] for s in args.fail_on],
              "failures": failures, "vacuous": vacuous,
              "ok": not failures}

    if not args.quiet:
        for key, r in list(phase_rows.items())[:args.top]:
            print(f"  phase {key}: {r['a']:.3f} -> {r['b']:.3f} "
                  f"({r['delta_pp']:+.2f}pp)", file=sys.stderr)
        for key, r in list(frame_rows.items())[:args.top]:
            print(f"  frame {key}: {r['a']:.3f} -> {r['b']:.3f} "
                  f"({r['delta_pp']:+.2f}pp)", file=sys.stderr)
        for f in failures:
            print(f"  FAIL {f['spec']}: {f.get('key')} "
                  f"{f.get('a')} -> {f.get('b')} "
                  f"({f.get('delta_pp'):+}pp)", file=sys.stderr)
    print(json.dumps(report, default=str))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
