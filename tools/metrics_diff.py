"""metrics_diff — compare two metrics.json snapshots.

Claims like "decode p99 held under 2 ms" or "zero extra retraces"
become CHECKABLE: point this at two `metrics.json` artifacts (registry
snapshots, ``MetricsRegistry.dump``) and it reports counter deltas, histogram quantile shifts (p50/p99/mean,
rebuilt from the snapshot's buckets with the registry's own
interpolation), and series added/removed between the runs — optionally
failing on regression thresholds so a script can gate on them.

Usage:
  python tools/metrics_diff.py old/metrics.json new/metrics.json
  python tools/metrics_diff.py A.json B.json \
      --fail-on 'serve_decode_token_seconds:p99>10%' \
      --fail-on 'recompile_unexpected_retraces_total:value>0%'

History mode — ONE archive, any two points in time: with
``--history <snapshot>`` (a HistoryStore save, e.g.
``tools/history_smoke.py``'s ``history_snapshot.json``) the A/B
snapshots are RECONSTRUCTED from the archive's rings at ``--at t0``
and ``--vs t1`` instead of read from two files, so a single history
archive supports the gate at any two instants:

  python tools/metrics_diff.py --history history_snapshot.json \
      --at +0 --vs -0 --fail-on 'fleet_anomaly_fired_total>0%'

``--at``/``--vs`` take epoch seconds, or ``+S`` (S seconds after the
archive's first sample) / ``-S`` (S seconds before its last).

--fail-on SPEC grammar: ``name[:stat]{>|<}PCT%`` — `name` matches a
series key exactly or every series of that metric name; `stat` is
``value`` (counter/gauge, the default) or ``p50``/``p99``/``mean``/
``count`` (histogram, default p50); ``>`` fails when B exceeds A by
more than PCT percent (latency-like: bigger is worse), ``<`` fails
when B undershoots A by more than PCT (throughput-like: smaller is
worse). A series missing from either side never trips a threshold (it
shows up under added/removed instead). PCT may be 0 ("any increase").

Last stdout line is a JSON report; exit 0 iff no --fail-on tripped.
Stdlib-only (loads the registry module straight from its file via
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

_SPEC_RE = re.compile(
    r"^(?P<name>[^:<>]+?)(?::(?P<stat>value|count|mean|p\d{1,2}))?"
    r"(?P<op>[<>])(?P<pct>\d+(?:\.\d+)?)%?$")


def parse_spec(s):
    m = _SPEC_RE.match(s.strip())
    if not m:
        raise argparse.ArgumentTypeError(
            f"bad --fail-on spec {s!r} (grammar: name[:stat]{{>|<}}PCT%)")
    return {"name": m.group("name"), "stat": m.group("stat"),
            "op": m.group("op"), "pct": float(m.group("pct")),
            "spec": s.strip()}


def load_snapshot(path):
    with open(path) as f:
        doc = json.load(f)
    metrics = doc.get("metrics")
    if not isinstance(metrics, dict):
        raise ValueError(f"{path}: no 'metrics' map — not a registry "
                         "snapshot/dump")
    return doc


def _hist_stats(entry):
    """Rebuild a Histogram from its snapshot and read the rollup stats
    with the registry's own quantile interpolation."""
    H = obs_mod("metrics").Histogram
    h = H(entry["name"], buckets=entry["bounds"])
    h.merge(entry)
    if not h.count:
        return {"count": 0}
    return {"count": h.count, "mean": h.mean(),
            "p50": h.quantile(0.5), "p99": h.quantile(0.99),
            "min": h.min, "max": h.max}


def _pct(a, b):
    if a is None or b is None:
        return None
    if a == 0:
        return None if b == 0 else float("inf")
    return (b - a) / abs(a) * 100.0


def _round(v, n=4):
    if v is None:
        return None
    if v in (float("inf"), float("-inf")):
        return None  # JSON-safe; the raw a/b values tell the story
    return round(v, n)


def diff(a_doc, b_doc):
    a, b = a_doc["metrics"], b_doc["metrics"]
    report = {"counters": {}, "gauges": {}, "histograms": {},
              "added": sorted(set(b) - set(a)),
              "removed": sorted(set(a) - set(b))}
    for key in sorted(set(a) & set(b)):
        ea, eb = a[key], b[key]
        if ea["type"] != eb["type"]:
            report.setdefault("type_changed", []).append(key)
            continue
        if ea["type"] in ("counter", "gauge"):
            row = {"a": ea["value"], "b": eb["value"],
                   "delta": eb["value"] - ea["value"],
                   "pct": _round(_pct(ea["value"], eb["value"]), 2)}
            bucket = ("counters" if ea["type"] == "counter"
                      else "gauges")
            report[bucket][key] = row
        else:
            try:
                sa, sb = _hist_stats(ea), _hist_stats(eb)
            except (KeyError, ValueError) as e:
                report.setdefault("unreadable", []).append(
                    f"{key}: {e}")
                continue
            row = {"a": {k: _round(v, 6) for k, v in sa.items()},
                   "b": {k: _round(v, 6) for k, v in sb.items()}}
            for stat in ("mean", "p50", "p99"):
                row[f"{stat}_shift_pct"] = _round(
                    _pct(sa.get(stat), sb.get(stat)), 2)
            report["histograms"][key] = row
    return report


def _series_stat(doc, key, stat):
    entry = doc["metrics"].get(key)
    if entry is None:
        return None
    if entry["type"] in ("counter", "gauge"):
        return entry["value"] if stat in (None, "value") else None
    stat = stat or "p50"
    if stat in ("count", "mean"):
        return _hist_stats(entry).get(stat)
    m = re.match(r"p(\d{1,2})$", stat)
    if m:
        H = obs_mod("metrics").Histogram
        h = H(entry["name"], buckets=entry["bounds"])
        h.merge(entry)
        return h.quantile(int(m.group(1)) / 100.0) if h.count else None
    return None


def check_fail_on(a_doc, b_doc, specs):
    """Evaluate each spec against every matching series present in
    BOTH snapshots; returns the list of failures."""
    failures = []
    for spec in specs:
        keys = [k for k in a_doc["metrics"]
                if k in b_doc["metrics"]
                and (k == spec["name"]
                     or a_doc["metrics"][k]["name"] == spec["name"])]
        for key in keys:
            va = _series_stat(a_doc, key, spec["stat"])
            vb = _series_stat(b_doc, key, spec["stat"])
            if va is None or vb is None:
                continue
            lim = spec["pct"] / 100.0
            if spec["op"] == ">":
                bad = vb > va + abs(va) * lim if va else vb > va
            else:
                bad = vb < va - abs(va) * lim if va else vb < va
            if bad:
                failures.append({
                    "spec": spec["spec"], "series": key,
                    "a": _round(va, 6), "b": _round(vb, 6),
                    "shift_pct": _round(_pct(va, vb), 2)})
    return failures


def _resolve_t(spec, first, last):
    """--at/--vs grammar: absolute epoch seconds, or +S from the
    archive's first sample / -S from its last."""
    s = str(spec).strip()
    if s.startswith("+"):
        return first + float(s[1:])
    if s.startswith("-"):
        return last - float(s[1:])
    return float(s)


def load_history_pair(path, at, vs):
    """(a_doc, b_doc) reconstructed from a HistoryStore snapshot at
    two instants — the history plane's registry_snapshot_at."""
    HistoryStore = obs_mod("history").HistoryStore
    store = HistoryStore.load(path)
    first, last = store.span()
    if first is None:
        raise ValueError(f"{path}: empty/unreadable history snapshot")
    t0 = _resolve_t(at, first, last)
    t1 = _resolve_t(vs, first, last)
    return store.registry_snapshot_at(t0), \
        store.registry_snapshot_at(t1), t0, t1


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="diff two metrics.json registry snapshots, or one "
                    "history archive at two points in time")
    ap.add_argument("a", nargs="?", help="baseline metrics.json")
    ap.add_argument("b", nargs="?", help="candidate metrics.json")
    ap.add_argument("--history", default=None, metavar="SNAPSHOT",
                    help="HistoryStore snapshot to reconstruct both "
                         "sides from (with --at/--vs)")
    ap.add_argument("--at", default=None, metavar="T0",
                    help="history baseline instant (epoch s, +S from "
                         "first sample, -S from last)")
    ap.add_argument("--vs", default=None, metavar="T1",
                    help="history candidate instant (same grammar)")
    ap.add_argument("--fail-on", action="append", type=parse_spec,
                    default=[], metavar="name[:stat]{>|<}PCT%",
                    help="regression threshold (repeatable)")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress the human-readable section")
    args = ap.parse_args(argv)

    if args.history is not None:
        if args.at is None or args.vs is None:
            ap.error("--history requires --at and --vs")
        a_doc, b_doc, t0, t1 = load_history_pair(
            args.history, args.at, args.vs)
        a_name = f"{args.history}@{t0:.3f}"
        b_name = f"{args.history}@{t1:.3f}"
    else:
        if not args.a or not args.b:
            ap.error("need two snapshot paths (or --history "
                     "--at --vs)")
        a_doc, b_doc = load_snapshot(args.a), load_snapshot(args.b)
        a_name, b_name = args.a, args.b
    report = diff(a_doc, b_doc)
    failures = check_fail_on(a_doc, b_doc, args.fail_on)
    report.update({"a": a_name, "b": b_name,
                   "fail_on": [s["spec"] for s in args.fail_on],
                   "failures": failures, "ok": not failures})

    if not args.quiet:
        changed = [(k, r) for k, r in report["counters"].items()
                   if r["delta"]]
        for k, r in changed[:40]:
            print(f"  counter {k}: {r['a']} -> {r['b']} "
                  f"({r['delta']:+})", file=sys.stderr)
        for k, r in list(report["histograms"].items())[:40]:
            if r.get("p99_shift_pct") is not None:
                print(f"  hist {k}: p50 {r['a'].get('p50')} -> "
                      f"{r['b'].get('p50')}, p99 {r['a'].get('p99')} "
                      f"-> {r['b'].get('p99')} "
                      f"({r['p99_shift_pct']:+}%)", file=sys.stderr)
        for k in report["added"][:20]:
            print(f"  added   {k}", file=sys.stderr)
        for k in report["removed"][:20]:
            print(f"  removed {k}", file=sys.stderr)
        for f in failures:
            print(f"  FAIL {f['spec']}: {f['series']} {f['a']} -> "
                  f"{f['b']} ({f['shift_pct']}%)", file=sys.stderr)
    print(json.dumps(report, default=str))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
