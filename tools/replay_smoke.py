"""replay_smoke — CPU drill for the traffic-capture & deterministic-replay
plane, and the generator (``--write-golden``) of the fixture
tools/golden/replay_wave.json that tests/test_fleet_replay.py reads.

Shape (seeded, CPU-only, no chip time spent):

1. **wave-drift guard**: regenerate the seeded 20-request synthetic
   wave (``fleet_replay.synth_wave``) and assert its spec fields
   (prompts, arrival offsets, tenants, priorities) equal the
   committed golden ``tools/golden/replay_wave.json`` — a silently
   drifted generator would invalidate every cross-round comparison;
2. **capture**: a 2-replica in-process fleet with capture armed
   drives the committed wave open-loop; the archive must hold all 20
   requests, resolve-complete, zero torn drops, zero
   capture<->trace-sampling divergences, and the fleet's compile
   counts stay frozen with capture on;
3. **committed-archive golden replay**: replay the COMMITTED archive
   (which carries the tokens recorded at golden-write time) in
   golden mode — token-exact per rid, zero new XLA traces. Timing
   gates are disabled here (the committed latencies were recorded on
   the golden-write box); tokens and compile counts are what the
   committed golden pins;
4. **clean-wave gate proof**: replay THIS run's live capture in
   golden mode with the default gates — per-hop attribution share
   deltas must land within 5% and the latency ratios inside their
   limits (vacuity-guarded: the verdict must actually have compared
   tokens and hops);
5. **regression gate proof**: replay the live capture again with an
   injected per-round replica slowdown (``replica_slow`` — the
   mid-wave latency regression) — the SAME gate spec MUST trip (a
   gate that never fires is not a gate);
6. artifacts into ``<tempdir>/paddle_tpu_replay_smoke`` (the verdict's
   ``out_dir``): ``metrics.json`` (capture
   fleet registry incl. the ``fleet_capture_*`` series + recompile
   report), ``replay_verdict.json`` (clean),
   ``replay_verdict_regression.json``, and the capture archive dir.

Regenerate the committed golden with ``--write-golden`` (captures the
wave on THIS box and stores spec + resolved tokens + sampling meta).
Last stdout line is a JSON verdict; exit 0 only when every check
holds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

GOLDEN = os.path.join(REPO, "tools", "golden", "replay_wave.json")
WAVE_SEED = 12
WAVE_N = 20

# the spec fields the drift guard pins (resolve fields — tokens,
# latencies — are measurements, not spec)
SPEC_FIELDS = ("rid", "arrival_s", "tenant", "priority",
               "deadline_ms", "prompt", "max_new", "eos")

NO_TIMING_GATES = {"e2e_p99_ratio": None, "ttft_p99_ratio": None,
                   "hop_share_delta": None}


def _wave():
    import fleet_replay as fr
    return fr.synth_wave(WAVE_SEED, WAVE_N, burst=4,
                         burst_gap_s=0.05)


def _spec(entries):
    return [{k: e.get(k) for k in SPEC_FIELDS} for e in entries]


def _capture_run(wave, out_dir):
    """Drive `wave` through a capture-armed fleet; returns
    (archive_entries, registry, checks_fragment)."""
    import fleet_replay as fr
    from paddle_tpu.observability.trace import report_all
    from paddle_tpu.observability.trafficrec import load_archive

    cap_dir = os.path.join(out_dir, "capture")
    router, engines, frozen = fr.build_fleet(wave,
                                             capture_dir=cap_dir)
    checks = {}
    try:
        _res, _wall, _map = fr.replay(router, wave, timeout_s=120.0)
        reg = router.registry
        checks["capture_all_requests"] = int(reg.get(
            "fleet_capture_requests_total").value) == len(wave)
        checks["capture_no_trace_missing"] = int(reg.get(
            "fleet_capture_trace_missing_total").value) == 0
        checks["capture_no_errors"] = int(reg.get(
            "fleet_capture_errors_total").value) == 0
        checks["capture_compiles_frozen"] = (
            [e.compile_counts() for e in engines] == frozen
            and router.compile_report()["unexpected_retraces"] == 0)
        reg.dump(os.path.join(out_dir, "metrics.json"),
                 extra={"recompile_report": report_all(),
                        "stage": "replay_smoke"})
    finally:
        router.close()
        for e in engines:
            e.close()
    entries, _meta, stats = load_archive(cap_dir)
    checks["archive_complete"] = (
        len(entries) == len(wave) and stats["unresolved"] == 0
        and stats["torn_drops"] == 0)
    return entries, stats, checks


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--write-golden", action="store_true",
                    help="capture the seeded wave on THIS box and "
                         "save it as the committed golden")
    args = ap.parse_args(argv)

    out_dir = os.path.join(tempfile.gettempdir(),
                           "paddle_tpu_replay_smoke")
    os.makedirs(out_dir, exist_ok=True)
    os.environ.setdefault("PADDLE_TPU_FLIGHT_DIR", out_dir)

    import fleet_replay as fr
    from paddle_tpu.resilience import faults

    wave = _wave()

    if args.write_golden:
        entries, stats, checks = _capture_run(wave, out_dir)
        ok = all(checks.values())
        if ok:
            # wave_spec = the GENERATED schedule (the drift guard's
            # reference); entries = the CAPTURED archive (measured
            # arrival offsets + resolved tokens — golden replay input).
            # Through io/atomic: a ctrl-C mid-regen must cost this
            # regen, never the committed golden the tests replay
            # against.
            from paddle_tpu.io import atomic
            atomic.atomic_replace(
                GOLDEN,
                json.dumps({"format": 1,
                            "seed": WAVE_SEED, "n": WAVE_N,
                            "wave_spec": _spec(wave),
                            "entries": entries}, indent=1) + "\n")
        print(json.dumps({"ok": ok, "wrote_golden": GOLDEN if ok
                          else None, "checks": checks}))
        return 0 if ok else 1

    checks = {}

    # -- 1. wave-drift guard ----------------------------------------------
    try:
        with open(GOLDEN) as f:
            golden = json.load(f)
        committed = golden.get("entries") or []
        spec = golden.get("wave_spec") or []
    except (OSError, json.JSONDecodeError):
        committed, spec = [], []
    checks["wave_matches_committed_golden"] = bool(spec) and \
        _spec(wave) == spec

    # -- 2. capture the wave live -----------------------------------------
    live, stats, cap_checks = _capture_run(wave, out_dir)
    checks.update(cap_checks)

    # -- 3. committed-archive golden replay (token-exact, no new
    # traces; timing gates off — committed latencies are another
    # box's measurements) --------------------------------------------------
    if committed:
        v_gold, _ = fr.run_replay(
            committed, out_dir=os.path.join(out_dir, "committed"),
            golden=True, gates=NO_TIMING_GATES)
        checks["committed_golden_token_exact"] = bool(
            v_gold["golden"]["token_exact"]
            and v_gold["golden"]["compared"] == WAVE_N)
        checks["committed_golden_zero_new_traces"] = (
            v_gold["golden"]["compile_frozen"]
            and v_gold["golden"]["new_traces"] == 0
            and v_gold["golden"]["unexpected_retraces"] == 0)
        checks["committed_golden_ok"] = bool(v_gold["ok"])
    else:
        checks["committed_golden_token_exact"] = False
        checks["committed_golden_zero_new_traces"] = False
        checks["committed_golden_ok"] = False

    # -- 4. clean-wave gate proof (default gates incl. the 5%
    # per-hop attribution bar) ---------------------------------------------
    v_clean, _ = fr.run_replay(
        live, out_dir=os.path.join(out_dir, "clean"), golden=True)
    with open(os.path.join(out_dir, "replay_verdict.json"), "w") as f:
        json.dump(v_clean, f, indent=1)
    checks["clean_replay_ok"] = bool(v_clean["ok"])
    # vacuity guards: the clean pass must have genuinely compared
    checks["clean_replay_compared"] = (
        v_clean["golden"]["compared"] == WAVE_N
        and len(v_clean["attribution"]["hops"]) > 0)
    checks["clean_hop_deltas_within_5pct"] = (
        len(v_clean["attribution"]["hops"]) > 0
        and v_clean["attribution"]["max_share_delta"] <= 0.05)

    # -- 5. regression gate proof -----------------------------------------
    def arm():
        for name in ("r0", "r1"):
            faults.inject("replica_slow", count=10_000,
                          seconds=0.05, replica=name)

    try:
        v_reg, _ = fr.run_replay(
            live, out_dir=os.path.join(out_dir, "regression"),
            faults_arm=arm)
    finally:
        faults.clear()
    with open(os.path.join(out_dir,
                           "replay_verdict_regression.json"),
              "w") as f:
        json.dump(v_reg, f, indent=1)
    checks["regression_trips_gate"] = (not v_reg["ok"]) and any(
        f.get("gate") in ("e2e_p99_ratio", "ttft_p99_ratio")
        for f in v_reg["failures"])

    ok = all(checks.values())
    print(json.dumps({
        "ok": ok, "checks": checks,
        "clean_max_hop_delta":
            v_clean["attribution"]["max_share_delta"],
        "clean_ratios": v_clean["slo"]["ratios"],
        "regression_failures": v_reg["failures"],
        "out_dir": out_dir}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
