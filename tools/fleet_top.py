"""fleet_top — htop for the serving fleet.

A terminal live view over a running FleetRouter's observability
endpoints: one frame per interval showing the fleet headline (request
rate, delivered tok/s, TTFT/queue-wait p99 from the history plane),
SLO burn alerts + anomaly-sentinel excursions, the per-replica table
(state, incarnation, queue/running, free pages, scrape age, boot
path aot/traced + wall clock), the
AUTOSCALER panel (controller state + size bounds, degraded/brownout
level with the clamped tenants, last decision + reason, per-replica
role incl. booting/retiring members), the per-tenant heavy-hitter
table (space-saving sketch: weight, tokens in/out, KV-page-seconds,
the error bound) and the recent-resolved request table (rid, status,
ttft/e2e, traffic-archive locator).

Live mode reads ``/healthz`` + ``/history`` + ``/tenants`` +
``/requests`` off the router exporter
(``FleetRouter.serve_metrics``):

  python tools/fleet_top.py --url http://127.0.0.1:9101
  python tools/fleet_top.py --url ... --once        # one frame, exit

Offline mode (``--snapshot <dir>``) renders the SAME frame from a
post-mortem triage dir — ``tools/history_smoke.py``'s artifacts, or
anything holding a ``history_snapshot.json`` (HistoryStore save) and
optionally ``tenants.json`` / ``health.json``:

  python tools/fleet_top.py --snapshot /path/to/triage_dir

Stdlib-only (urllib + the standalone-loadable observability modules
via tools/_obs.py); plain ANSI clear-screen, no curses.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from tools._obs import obs_mod  # noqa: E402

WINDOW_S = 30.0


def _get(url, timeout=3.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _fmt(v, unit="", nd=3):
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.{nd}f}{unit}"
    return f"{v}{unit}"


def _fmt_bytes(n):
    """1536 -> '1.5K', 3<<30 -> '3.0G' (the HEADROOM column's unit)."""
    try:
        n = float(n)
    except (TypeError, ValueError):
        return "-"
    for suffix in ("B", "K", "M", "G", "T"):
        if abs(n) < 1024.0 or suffix == "T":
            return f"{n:.1f}{suffix}" if suffix != "B" \
                else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}T"


def collect_live(base):
    """One frame's data off a live router exporter."""
    health = _get(base + "/healthz")
    try:
        tenants = _get(base + "/tenants")
    except Exception:  # noqa: BLE001 — tenancy may be off
        tenants = None
    try:
        requests = _get(base + "/requests")
    except Exception:  # noqa: BLE001 — pre-capture routers lack it
        requests = None

    def roll(series, op, **kw):
        from urllib.parse import quote
        try:
            q = "&".join([f"series={quote(series, safe='')}",
                          f"op={op}",
                          f"window={kw.get('window', WINDOW_S)}"]
                         + ([f"q={kw['q']}"] if "q" in kw else []))
            return _get(f"{base}/history?{q}").get("value")
        except Exception:  # noqa: BLE001 — history may be off
            return None

    return {
        "ts": time.time(), "source": base, "health": health,
        "tenants": tenants, "requests": requests,
        "rates": {
            "req_s": roll("fleet_requests_total{status=\"ok\"}",
                          "rate"),
            "tok_s": roll("fleet_tokens_out_total", "rate"),
            "ttft_p99_s": roll("fleet_ttft_seconds", "quantile",
                               q=0.99),
            "queue_p99_s": roll("fleet_placement_wait_seconds",
                                "quantile", q=0.99)}}


def collect_snapshot(directory):
    """The same frame from a triage dir (offline post-mortem mode)."""
    HistoryStore = obs_mod("history").HistoryStore
    store = HistoryStore.load(
        os.path.join(directory, "history_snapshot.json"))
    _first, last = store.span()

    def read_json(name):
        try:
            with open(os.path.join(directory, name)) as f:
                return json.load(f)
        except (OSError, json.JSONDecodeError):
            return None

    def roll(series, op, q=None):
        if last is None:
            return None
        if op == "rate":
            return store.rate(series, WINDOW_S, now=last)
        return store.quantile_over_time(series, q, WINDOW_S, now=last)

    return {
        "ts": last, "source": directory,
        "health": read_json("health.json"),
        "tenants": read_json("tenants.json"),
        "requests": read_json("requests.json"),
        "rates": {
            "req_s": roll("fleet_requests_total{status=\"ok\"}",
                          "rate"),
            "tok_s": roll("fleet_tokens_out_total", "rate"),
            "ttft_p99_s": roll("fleet_ttft_seconds", "quantile",
                               q=0.99),
            "queue_p99_s": roll("fleet_placement_wait_seconds",
                                "quantile", q=0.99)}}


def render(frame):
    """One frame -> text (pure; pinned by tests/test_history.py)."""
    out = []
    r = frame.get("rates") or {}
    out.append(f"fleet_top  {time.strftime('%H:%M:%S', time.localtime(frame.get('ts') or 0))}"
               f"  src={frame.get('source')}")
    out.append(
        f"  req/s {_fmt(r.get('req_s'), nd=1)}"
        f"  tok/s {_fmt(r.get('tok_s'), nd=1)}"
        f"  ttft p99 {_fmt(r.get('ttft_p99_s'), 's')}"
        f"  queue p99 {_fmt(r.get('queue_p99_s'), 's')}"
        f"  (over {WINDOW_S:g}s)")
    h = frame.get("health")
    if h:
        slo = h.get("slo") or {}
        anom = h.get("anomaly") or {}
        alerting = list(slo.get("alerting") or []) \
            + [f"anomaly:{n}" for n in (anom.get("alerting") or [])]
        out.append(f"  queue={h.get('queue_depth')} "
                   f"pending={h.get('pending')} "
                   f"lost={h.get('lost') or []} "
                   f"alerts={alerting or 'none'}")
        reps = h.get("replicas") or {}
        if reps:
            # HOST% (r22): 100*(1 - idle share) from the replica's
            # continuous-profiler heartbeat digest — how much of the
            # host's sampled wall time was real serving work; "-" for
            # replicas with no profiler armed
            prof = (h.get("profile") or {}).get("replicas") or {}
            # MEM%/HEADROOM (r23): device-memory used ratio and
            # forecast free bytes from the replica's memory-ledger
            # heartbeat digest; "-" for replicas with no ledger armed
            # (or capacity-blind backends)
            mem = (h.get("mem") or {}).get("replicas") or {}
            out.append("  REPLICA     STATE     INC  Q/R    FREE_PG "
                       "SCRAPE_AGE  BOOT         HOST%  MEM%   "
                       "HEADROOM  FLAGS")
            for name in sorted(reps):
                row = reps[name]
                flags = "".join(
                    f for f, on in (("L", row.get("lost")),
                                    ("Q", row.get("quarantined")))
                    if on) or "-"
                # boot path + wall clock (r21): aot = restored from a
                # serving artifact, traced = full trace + compile;
                # pre-artifact replicas carry no boot dict at all
                bi = row.get("boot") or {}
                boot = "-" if not bi.get("mode") else (
                    f"{bi['mode']}"
                    + ("" if bi.get("boot_s") is None
                       else f" {float(bi['boot_s']):.1f}s"))
                hp = (prof.get(name) or {}).get("host_pct")
                host = "-" if hp is None else f"{float(hp):.1f}"
                mrow = mem.get(name) or {}
                mr = mrow.get("used_ratio")
                memp = "-" if mr is None else f"{100.0 * float(mr):.1f}"
                hr = mrow.get("headroom_bytes")
                head = "-" if hr is None else _fmt_bytes(hr)
                if mrow.get("residual_alarm"):
                    flags = (flags.replace("-", "") or "") + "M" \
                        if flags != "-" else "M"
                out.append(
                    f"  {name:<11} {str(row.get('state')):<9} "
                    f"{str(row.get('incarnation')):<4} "
                    f"{_fmt(row.get('queued'))}/"
                    f"{_fmt(row.get('running')):<4} "
                    f"{_fmt(row.get('free_pages')):<7} "
                    f"{_fmt(row.get('scrape_age_s'), 's'):<11} "
                    f"{boot:<12} {host:<6} {memp:<6} "
                    f"{head:<9} {flags}")
    if h:
        asc = h.get("autoscale")
        ov = h.get("overload") or {}
        if asc or ov.get("degraded") or ov.get("brownout_level"):
            bits = []
            if asc:
                bits.append(
                    f"state={asc.get('state')} "
                    f"size={asc.get('replicas')} "
                    f"[{asc.get('min')}..{asc.get('max')}]")
            bits.append(
                f"degraded={'yes' if ov.get('degraded') else 'no'} "
                f"brownout=L{ov.get('brownout_level') or 0}")
            if ov.get("clamped_tenants"):
                bits.append(
                    f"clamped={','.join(ov['clamped_tenants'])}")
            out.append("  AUTOSCALER  " + "  ".join(bits))
            last = (asc or {}).get("last_decision")
            if last:
                detail = " ".join(
                    f"{k}={v}" for k, v in sorted(last.items())
                    if k not in ("event", "t") and v is not None)
                out.append(f"    last: {last.get('event')} "
                           f"{detail}".rstrip())
            reps = h.get("replicas") or {}
            if asc and reps:
                roles = []
                for name in sorted(reps):
                    role = "retiring" if name == asc.get("retiring") \
                        else str(reps[name].get("state"))
                    roles.append(f"{name}={role}")
                if asc.get("booting"):
                    roles.append(f"{asc['booting']}=booting")
                out.append("    ROLE  " + " ".join(roles))
    t = frame.get("tenants")
    if t:
        out.append(
            f"  TENANTS tracked={t.get('tracked')}/"
            f"{t.get('capacity')} evictions={t.get('evictions')} "
            f"err_bound={t.get('error_bound')} "
            f"totals: in={t['totals']['tokens_in']} "
            f"out={t['totals']['tokens_out']} "
            f"kv_page_s={_fmt(t['totals']['kv_page_s'], nd=1)}")
        out.append("  TENANT        WEIGHT  TOK_IN  TOK_OUT "
                   "QWAIT_S  KV_PG_S  PFX_HIT  SPEC_ACC  ERR")
        for row in (t.get("tenants") or [])[:16]:
            # page-level prefix hit rate (r19): the share of this
            # tenant's shareable prompt pages served from cache
            ppg = row.get("prefix_pages") or 0
            pfx = "-" if not ppg else \
                f"{100.0 * (row.get('prefix_hit_pages') or 0) / ppg:.0f}%"
            # speculative acceptance rate (r20): the share of this
            # tenant's draft tokens the target model confirmed
            spp = row.get("spec_proposed") or 0
            spc = "-" if not spp else \
                f"{100.0 * (row.get('spec_accepted') or 0) / spp:.0f}%"
            out.append(
                f"  {row['tenant']:<13} {row['weight']:<7} "
                f"{row['tokens_in']:<7} {row['tokens_out']:<8}"
                f"{_fmt(row['queue_wait_s'], nd=2):<9}"
                f"{_fmt(row['kv_page_s'], nd=2):<9}"
                f"{pfx:<9}{spc:<10}{row['err']}")
    rq = frame.get("requests")
    if rq and rq.get("requests"):
        cap = rq.get("capture") or {}
        out.append(
            "  RECENT REQUESTS"
            + (f"  (capture: {cap.get('dir')}"
               f" @ sample={cap.get('sample')})" if cap else ""))
        out.append("  RID    TENANT        STATUS     TTFT_S   E2E_S"
                   "    REPLICA  ARCHIVE")
        for row in (rq.get("requests") or [])[-8:]:
            arch = row.get("archive") or {}
            loc = (f"{arch.get('segment')}@{arch.get('offset')}"
                   if arch else "-")
            out.append(
                f"  {row['rid']:<6} {str(row.get('tenant')):<13} "
                f"{row['status']:<10} "
                f"{_fmt(row.get('ttft_s'), nd=3):<8} "
                f"{_fmt(row.get('e2e_s'), nd=3):<8} "
                f"{str(row.get('replica')):<8} {loc}")
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="terminal live view of a serving fleet")
    ap.add_argument("--url", default=None,
                    help="router exporter base url "
                         "(http://host:port)")
    ap.add_argument("--snapshot", default=None, metavar="DIR",
                    help="offline mode: render from a triage dir "
                         "(history_snapshot.json [+ tenants.json, "
                         "health.json])")
    ap.add_argument("--interval", type=float, default=2.0)
    ap.add_argument("--once", action="store_true",
                    help="print one frame and exit (offline mode "
                         "implies it)")
    args = ap.parse_args(argv)
    if bool(args.url) == bool(args.snapshot):
        ap.error("exactly one of --url / --snapshot")
    if args.snapshot:
        print(render(collect_snapshot(args.snapshot)))
        return 0
    while True:
        frame = collect_live(args.url.rstrip("/"))
        text = render(frame)
        if args.once:
            print(text)
            return 0
        sys.stdout.write("\x1b[2J\x1b[H" + text + "\n")
        sys.stdout.flush()
        time.sleep(args.interval)


if __name__ == "__main__":
    sys.exit(main())
