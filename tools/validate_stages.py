"""Preflight: every campaign stage's command line must parse.

A stage with a bad flag (or a renamed script) would burn budgeted
chip time on an instant failure. This runs each STAGES entry with a
short probe budget: an argparse failure or instant crash is flagged; a
healthy command reaches its TPU check (which fails on a machine
without a chip — the expected PASS signal here). Run after editing the
ladder, on a machine WITHOUT the chip (with one this would spend chip
time): python tools/validate_stages.py
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tpu_campaign import OUT, REPO, STAGES  # noqa: E402

_BUDGET_S = 120
_INSTANT_S = 3.0  # a real stage spends longer than this just importing

# stages the current round's measurement plan depends on: a rename or
# accidental drop in tpu_campaign.STAGES must fail preflight loudly
REQUIRED_STAGES = {
    "probe", "bench_full", "bench_gpt13b_scan_cce",
    # static invariant sweep — tpulint over the shipping source
    # (CPU-only, runs before chaos_smoke — ISSUE 13)
    "staticcheck",
    # round-7 serving + llama rungs
    "bench_serve_gpt", "bench_serve_llama", "bench_serve_flashk",
    "bench_llama",
    # round-8 resilience drill (CPU-only, seeded — ISSUE 3)
    "chaos_smoke",
    # round-9 observability drill (CPU-only — ISSUE 4)
    "telemetry_smoke",
    # fleet failover/drain/hedge/shed chaos drill (CPU-only — ISSUE 6)
    "fleet_chaos_smoke",
    # router write-ahead-journal durability drill (CPU-only — ISSUE 9)
    "fleet_recovery_smoke",
    # process-isolated replicas + self-healing supervisor drill
    # (CPU-only, real subprocesses — ISSUE 10)
    "fleet_supervisor_smoke",
    # telemetry-history / tenancy / anomaly-sentinel drill + the
    # two-instant history gate (CPU-only — ISSUE 11)
    "history_smoke",
    # traffic capture & deterministic replay drill: committed-wave
    # golden replay + verdict-gate both-directions proof (CPU-only —
    # ISSUE 12)
    "replay_smoke",
    # elastic autoscaling drill: burst → scale-out → recovery →
    # scale-in with no lost rid + bounded SLO breach (CPU-only —
    # ISSUE 15)
    "autoscale_smoke",
    # copy-on-write prefix-cache drill: shared-prefix wave token-exact
    # ON vs OFF, hit rate over floor, ON TTFT p50 strictly better,
    # zero new traces (CPU-only — ISSUE 16)
    "prefix_cache_smoke",
    # speculative-decoding drill: long-decode wave token-exact ON vs
    # OFF, acceptance over floor, ON decode tok/s strictly above OFF,
    # zero new traces (CPU-only — ISSUE 20)
    "spec_smoke",
    # AOT serving-artifact boot probe: artifact boot token-exact vs
    # traced control, zero fallbacks, strictly faster (ISSUE 21)
    "aot_boot",
    # continuous-profiling drill: profiler-armed wave with frozen
    # compile counts, phase attribution live, overhead under the 1%
    # cap, and the profile_diff gate proven both directions (CPU-only
    # — ISSUE 22)
    "profile_smoke",
    # device-memory ledger drill: ledger-armed wave with frozen
    # compile counts, typed-segment conservation within 1%, the
    # residual alarm + mem_diff gate proven both directions via an
    # injected untracked leak (CPU-only — HBM ledger round)
    "mem_smoke",
}


def _emits_metrics(cmd):
    """Stages built on bench.py workers or telemetry_smoke write
    telemetry.jsonl + metrics.json into campaign_out/telemetry/<stage>;
    the fleet chaos pytest stage exports its merged fleet registry the
    same way (conftest session fixture — the canary gate's input);
    other bare tools (fusion_audit, step_anatomy) do not."""
    return any(os.path.basename(str(a)) in ("bench.py",
                                            "telemetry_smoke.py",
                                            "history_smoke.py",
                                            "replay_smoke.py",
                                            "autoscale_smoke.py",
                                            "prefix_cache_smoke.py",
                                            "spec_smoke.py",
                                            "profile_smoke.py",
                                            "mem_smoke.py",
                                            "aot_boot_probe.py",
                                            "test_fleet_serving.py",
                                            "test_fleet_recovery.py",
                                            "test_fleet_proc.py")
               for a in cmd)


def check_completed_stage_metrics():
    """Every COMPLETED stage of the live campaign summary that is
    expected to emit run telemetry must have left a parseable
    metrics.json — a stage that measured but exported nothing is a
    silent observability regression. Returns (problems, checked):
    the list of problems plus how many stages were actually
    inspected (0 when there is nothing eligible to validate)."""
    path = os.path.join(OUT, "summary.json")
    try:
        with open(path) as f:
            summary = json.load(f)
    except (OSError, json.JSONDecodeError):
        return [], 0   # no live campaign to validate
    if not summary.get("_telemetry"):
        # summary predates the telemetry subsystem: its stages never
        # wrote metrics.json — historical artifacts are not a regression
        return [], 0
    by_name = {s[0]: s[1] for s in STAGES}
    problems = []
    checked = 0
    for name, row in summary.items():
        if name.startswith("_") or not isinstance(row, dict) \
                or not row.get("ok"):
            continue
        cmd = by_name.get(name)
        if cmd is None or not _emits_metrics(cmd):
            continue
        checked += 1
        mpath = os.path.join(OUT, "telemetry", name, "metrics.json")
        try:
            with open(mpath) as f:
                doc = json.load(f)
            if not isinstance(doc.get("metrics"), dict):
                problems.append(
                    f"{name}: {mpath} parses but has no 'metrics' map")
        except OSError:
            problems.append(f"{name}: completed but left no "
                            f"metrics.json at {mpath}")
        except json.JSONDecodeError as e:
            problems.append(f"{name}: unparseable metrics.json ({e})")
    return problems, checked


# chaos-family stages: each drives at least one flight-recorder
# trigger (guard rollback, router crash/recovery), so a completed run
# must have left parseable flight dump(s) in its telemetry dir (the
# dumps land there because the campaign exports BENCH_TELEMETRY_DIR
# per stage — flightrec's dump-dir fallback)
FLIGHT_STAGES = {"chaos_smoke", "telemetry_smoke",
                 "fleet_recovery_smoke", "fleet_supervisor_smoke",
                 "history_smoke", "autoscale_smoke",
                 # the anomaly-evidence path end-to-end: its dump
                 # carries the live profile (ISSUE 22)
                 "profile_smoke",
                 # likewise: its dump carries the live segment tree
                 # (HBM ledger round)
                 "mem_smoke"}


def check_flight_dumps():
    """Completed chaos-family stages of a _flightrec-marked campaign
    summary must have left at least one parseable flight_*.json whose
    ring actually holds records — a chaos stage that tripped the guard
    but dumped nothing (or dumped garbage) is a silent loss of the
    post-mortem path. Returns (problems, checked)."""
    path = os.path.join(OUT, "summary.json")
    try:
        with open(path) as f:
            summary = json.load(f)
    except (OSError, json.JSONDecodeError):
        return [], 0
    if not summary.get("_flightrec"):
        return [], 0   # pre-flight-recorder archive: nothing to hold
    problems = []
    checked = 0
    for name in sorted(FLIGHT_STAGES):
        row = summary.get(name)
        if not isinstance(row, dict) or not row.get("ok"):
            continue
        checked += 1
        tdir = os.path.join(OUT, "telemetry", name)
        try:
            dumps = sorted(f for f in os.listdir(tdir)
                           if f.startswith("flight_")
                           and f.endswith(".json"))
        except OSError:
            dumps = []
        if not dumps:
            problems.append(f"{name}: completed but left no "
                            f"flight_*.json under {tdir}")
            continue
        for fn in dumps:
            fp = os.path.join(tdir, fn)
            try:
                with open(fp) as f:
                    doc = json.load(f)
                if not isinstance(doc.get("records"), list) \
                        or not doc.get("reason"):
                    problems.append(f"{name}: {fn} parses but has no "
                                    "records ring / reason")
            except (OSError, json.JSONDecodeError) as e:
                problems.append(f"{name}: unparseable flight dump "
                                f"{fn} ({e})")
    return problems, checked


def check_canary_verdict():
    """A _fleet_canary-marked campaign whose fleet_chaos_smoke stage
    completed must have left the metrics_diff gate's verdict file
    (telemetry/fleet_chaos_smoke/canary_verdict.json, parseable, with
    an 'ok' flag) — a gate that silently never ran would let a
    failover/shed regression ship as a green campaign. Returns
    (problems, checked)."""
    path = os.path.join(OUT, "summary.json")
    try:
        with open(path) as f:
            summary = json.load(f)
    except (OSError, json.JSONDecodeError):
        return [], 0
    if not summary.get("_fleet_canary"):
        return [], 0   # pre-gate archive: nothing to hold it to
    row = summary.get("fleet_chaos_smoke")
    if not isinstance(row, dict) or row.get("rc") is None:
        return [], 0   # stage never ran
    vpath = os.path.join(OUT, "telemetry", "fleet_chaos_smoke",
                         "canary_verdict.json")
    # the gate runs only on a completed stage; a failed stage leaves
    # no verdict and is already red on its own
    if not row.get("ok") and not row.get("canary"):
        return [], 0
    try:
        with open(vpath) as f:
            verdict = json.load(f)
    except OSError:
        return [f"fleet_chaos_smoke: completed but the canary gate "
                f"left no verdict at {vpath}"], 1
    except json.JSONDecodeError as e:
        return [f"fleet_chaos_smoke: unparseable canary verdict "
                f"({e})"], 1
    if "ok" not in verdict:
        return [f"fleet_chaos_smoke: canary verdict {vpath} has no "
                "'ok' flag"], 1
    return [], 1


def check_history_verdict():
    """A _history_gate-marked campaign whose history_smoke stage
    completed must have left the two-instant history gate's verdict
    (telemetry/history_smoke/history_verdict.json, parseable, with an
    'ok' flag) — a silently-skipped gate would let a sentinel
    regression ship as a green campaign. Returns (problems, checked)."""
    path = os.path.join(OUT, "summary.json")
    try:
        with open(path) as f:
            summary = json.load(f)
    except (OSError, json.JSONDecodeError):
        return [], 0
    if not summary.get("_history_gate"):
        return [], 0   # pre-gate archive
    row = summary.get("history_smoke")
    if not isinstance(row, dict) or row.get("rc") is None:
        return [], 0   # stage never ran
    if not row.get("ok") and not row.get("history_gate"):
        return [], 0   # failed on its own; no verdict expected
    vpath = os.path.join(OUT, "telemetry", "history_smoke",
                         "history_verdict.json")
    try:
        with open(vpath) as f:
            verdict = json.load(f)
    except OSError:
        return [f"history_smoke: completed but the history gate left "
                f"no verdict at {vpath}"], 1
    except json.JSONDecodeError as e:
        return [f"history_smoke: unparseable history verdict ({e})"], 1
    if "ok" not in verdict:
        return [f"history_smoke: history verdict {vpath} has no "
                "'ok' flag"], 1
    return [], 1


def check_lint_report():
    """A completed staticcheck stage must have left a parseable
    lint_report.json with non_baselined == 0 in its telemetry dir —
    a lint stage that 'passed' without a report (or with unreported
    new findings) would let a contract violation ship as a green
    campaign. Returns (problems, checked)."""
    path = os.path.join(OUT, "summary.json")
    try:
        with open(path) as f:
            summary = json.load(f)
    except (OSError, json.JSONDecodeError):
        return [], 0
    row = summary.get("staticcheck")
    if not isinstance(row, dict) or not row.get("ok"):
        return [], 0   # never ran, or already red on its own
    rpath = os.path.join(OUT, "telemetry", "staticcheck",
                         "lint_report.json")
    try:
        with open(rpath) as f:
            report = json.load(f)
    except OSError:
        return [f"staticcheck: completed but left no lint report at "
                f"{rpath}"], 1
    except json.JSONDecodeError as e:
        return [f"staticcheck: unparseable lint_report.json ({e})"], 1
    nb = report.get("non_baselined")
    if not isinstance(nb, int):
        return [f"staticcheck: lint report {rpath} has no "
                "'non_baselined' count"], 1
    if nb != 0:
        return [f"staticcheck: {nb} non-baselined finding(s) in a "
                f"stage marked ok — the gate was bypassed"], 1
    return [], 1


def _child_pgids(pid):
    """Process groups of `pid`'s direct children: bench.py stages
    start their workers with start_new_session=True, so killpg on the
    stage's own group does NOT reach them — collect their groups before
    killing. (Workers also self-limit via the 5s probe budget; this
    sweep just avoids leaving them to that.)"""
    pgids = set()
    try:
        for entry in os.listdir("/proc"):
            if not entry.isdigit():
                continue
            try:
                with open(f"/proc/{entry}/stat") as f:
                    fields = f.read().rsplit(")", 1)[1].split()
                ppid, pgrp = int(fields[1]), int(fields[2])
            except (OSError, IndexError, ValueError):
                continue
            if ppid == pid:
                pgids.add(pgrp)
    except OSError:
        pass
    return pgids


def _run_stage(cmd, env):
    t0 = time.monotonic()
    proc = subprocess.Popen(cmd, cwd=REPO, env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=_BUDGET_S)
        return proc.returncode, err, time.monotonic() - t0, False
    except subprocess.TimeoutExpired:
        groups = _child_pgids(proc.pid) | {proc.pid}
        for pg in groups:
            try:
                os.killpg(pg, signal.SIGKILL)
            except (ProcessLookupError, PermissionError, OSError):
                pass
        proc.kill()
        proc.wait()
        return None, "", time.monotonic() - t0, True


def main():
    missing = REQUIRED_STAGES - {s[0] for s in STAGES}
    if missing:
        print(f"MISSING REQUIRED STAGES: {sorted(missing)}")
        return 1
    metric_problems, metrics_checked = check_completed_stage_metrics()
    flight_problems, flights_checked = check_flight_dumps()
    canary_problems, canary_checked = check_canary_verdict()
    history_problems, history_checked = check_history_verdict()
    lint_problems, lint_checked = check_lint_report()
    metric_problems += flight_problems + canary_problems \
        + history_problems + lint_problems
    metrics_checked += flights_checked + canary_checked \
        + history_checked + lint_checked
    for p in metric_problems:
        print(f"  metrics: SUSPECT ({p})", flush=True)
    tmp = tempfile.mkdtemp(prefix="stage_preflight_")
    env = dict(os.environ)
    env.update({"BENCH_PROBE_TIMEOUT": "5", "BENCH_WORK_TIMEOUT": "5",
                "CAMPAIGN_CHILD": "1"})
    bad = []
    for name, cmd, _timeout, env_extra in STAGES:
        e = dict(env)
        e.update(env_extra)
        # a stage that COMPLETES must not clobber real campaign
        # artifacts with preflight junk — point any --out at a temp
        # dir, and the telemetry finalize (which MERGES into an
        # existing metrics.json) at preflight-private dirs so it can
        # never pollute or double-count real campaign telemetry
        e["BENCH_CAMPAIGN_DIR"] = os.path.join(tmp, "campaign_out")
        e["BENCH_TELEMETRY_DIR"] = os.path.join(tmp, "telemetry", name)
        cmd = list(cmd)
        for i, a in enumerate(cmd):
            if a == "--out" and i + 1 < len(cmd):
                cmd[i + 1] = os.path.join(tmp,
                                          os.path.basename(cmd[i + 1]))
        rc, err, dt, timed_out = _run_stage(cmd, e)
        if timed_out:
            print(f"  {name}: ran past preflight budget (OK — command "
                  "parsed, killed group)", flush=True)
            continue
        argparse_fail = "usage:" in err and (
            "unrecognized" in err or "invalid" in err or "error:" in err)
        # slow nonzero exits are the EXPECTED no-chip outcome (bench
        # probe rc=2, a tool's "needs a TPU" exit); a fast nonzero exit is
        # a launch failure (typo'd script, SyntaxError, ImportError)
        instant_crash = rc != 0 and dt < _INSTANT_S
        if argparse_fail or instant_crash:
            tail = err.strip().splitlines()[-1] if err.strip() else ""
            bad.append((name, f"rc={rc} after {dt:.1f}s: {tail}"))
            print(f"  {name}: SUSPECT ({bad[-1][1]})", flush=True)
        else:
            print(f"  {name}: ok (rc={rc} in {dt:.1f}s)", flush=True)
    if bad or metric_problems:
        print("\nBROKEN/SUSPECT STAGES:")
        for name, line in bad:
            print(f"  {name}: {line}")
        for p in metric_problems:
            print(f"  metrics: {p}")
        return 1
    # claim the metrics verification ONLY when stages were actually
    # inspected — a pre-telemetry archive (or no summary) is skipped,
    # not validated
    print(f"\nall {len(STAGES)} stage command lines parse"
          + (f"; {metrics_checked} completed stages all exported "
             "metrics.json" if metrics_checked else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
