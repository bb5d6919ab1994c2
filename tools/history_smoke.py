"""history_smoke — CPU drill for the telemetry history plane, per-tenant
accounting and the anomaly sentinel, and the generator (``--write-golden``)
of the fixture tools/golden/history_clean_wave.json that
tests/test_fleet_top.py reads.

Shape (seeded, CPU-only, no chip time spent):

1. build a 2-replica in-process fleet with the history plane, tenancy
   and the sentinel armed; warm every prefill bucket and FREEZE the
   compile counts;
2. **clean golden wave**: tenant-tagged traffic in steady pulses —
   the sentinel learns its bands and must stay QUIET (zero
   ``fleet_anomaly_fired_total``); the clean-wave history is what the
   committed golden archive (tools/golden/history_clean_wave.json,
   regenerate with ``--write-golden``) holds, and this run REPLAYS
   the sentinel over that committed golden asserting zero firings —
   band drift that starts alarming on known-good history fails here;
3. **regression wave**: the same traffic with an injected per-round
   replica slowdown (``replica_slow`` on every replica — the
   mid-wave latency regression). The sentinel MUST fire (TTFT p99 /
   queue-wait / decode-tok/s excursion) and leave a parseable
   ``flight_fleet_anomaly*.json``;
4. invariants, asserted hard: per-tenant token totals sum EXACTLY to
   the fleet counters (space-saving sketch conservation), and compile
   counts are FROZEN across both waves with accounting on;
5. artifacts into ``<tempdir>/paddle_tpu_history_smoke`` (the
   verdict's ``out_dir``): ``metrics.json`` (fleet registry +
   recompile report), ``history_snapshot.json`` (the torn-tolerant
   archive), ``tenants.json``, ``health.json``, ``marks.json``
   ({"t0","t_clean","t_end"} epoch marks). ``tools/metrics_diff.py
   --history --at --vs`` over the archive proves the two-instant gate:
   the clean span shows no ``fleet_anomaly_*`` increase, the
   regression span trips it.

Last stdout line is a JSON verdict; exit 0 only when every assertion
holds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

GOLDEN = os.path.join(REPO, "tools", "golden",
                      "history_clean_wave.json")
NEW_TOK = 8
SCRAPE_S = 0.05

# band knobs shared by the live run and the committed-golden replay
# (one source of truth: quiet/fire claims must test the SAME detector)
SENTINEL_KW = dict(warmup=10, min_consecutive=3, z=5.0, rel_floor=0.5)


def _signals():
    from paddle_tpu.observability.sentinel import default_signals
    # 1s windows over a 0.05s scrape cadence: ~20 samples per window
    return [dict(s, window_s=1.0) for s in default_signals()]


def _build_fleet():
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    from paddle_tpu.nlp.serving import ServingEngine
    from paddle_tpu.serving_fleet import FleetRouter, InprocReplica

    paddle.seed(0)
    model = GPTForCausalLM(_resolve_config("gpt-tiny"))
    model.eval()
    engines = []
    for _ in range(2):
        eng = ServingEngine(model, max_slots=2, page_size=16,
                            max_seq_len=64, steps_per_dispatch=4)
        # warm every bucket the waves can land in, then reset the
        # measurement window
        eng.generate([np.arange(5, dtype=np.int32),
                      np.arange(17, dtype=np.int32)], max_new_tokens=4)
        eng.reset_counters()
        engines.append(eng)
    frozen = [e.compile_counts() for e in engines]
    reps = [InprocReplica(f"r{i}", e) for i, e in enumerate(engines)]
    router = FleetRouter(
        reps, history=True, history_interval_s=SCRAPE_S,
        sentinel=True,
        sentinel_kw=dict(SENTINEL_KW, signals=_signals()))
    return router, engines, frozen


def _wave(router, rng, *, pulses, per_pulse, pulse_gap_s, tenants):
    """Steady tenant-tagged pulses; drains between pulses so the
    cadence (and so every latency signal) is reproducible."""
    import numpy as np
    for pulse in range(pulses):
        rids = []
        for i in range(per_pulse):
            n = int(rng.integers(4, 22))
            prompt = rng.integers(0, 256, (n,)).astype(np.int32)
            rids.append(router.submit(
                prompt, NEW_TOK,
                tenant=tenants[(pulse + i) % len(tenants)]))
        t_end = time.monotonic() + 30.0
        results = []
        while len(results) < len(rids):
            results += router.step()
            router.results()
            if time.monotonic() > t_end:
                raise RuntimeError("wave did not drain in 30s")
            time.sleep(0.002)
        # idle gap: the history plane keeps scraping (the sentinel's
        # bands need BETWEEN-pulse samples too)
        t_gap = time.monotonic() + pulse_gap_s
        while time.monotonic() < t_gap:
            router.step()
            time.sleep(0.01)
        yield results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--write-golden", action="store_true",
                    help="save the clean wave's history archive as "
                         "the committed golden and exit")
    ap.add_argument("--pulses", type=int, default=24)
    args = ap.parse_args(argv)

    out_dir = os.path.join(tempfile.gettempdir(),
                           "paddle_tpu_history_smoke")
    os.makedirs(out_dir, exist_ok=True)
    # flight dumps (fleet_anomaly) land next to the other artifacts
    os.environ.setdefault("PADDLE_TPU_FLIGHT_DIR", out_dir)

    import numpy as np
    from paddle_tpu.observability.history import HistoryStore
    from paddle_tpu.observability.sentinel import AnomalySentinel
    from paddle_tpu.observability.trace import report_all
    from paddle_tpu.resilience import faults

    checks = {}
    router, engines, frozen = _build_fleet()
    # t0 marks AFTER the first history scrape: the clean-span gate
    # (--at t0 --vs t_clean) needs the fleet_anomaly_* series present
    # at BOTH instants — a pre-boot t0 reconstructs an empty snapshot
    # and check_fail_on would skip every series, making the gate
    # vacuous instead of proving the clean span quiet
    while router.history.scrapes == 0:
        router.step()
        time.sleep(0.01)
    t0 = time.time()
    rng = np.random.default_rng(0)
    tenants = [f"tenant-{i}" for i in range(4)]
    all_results = []

    # -- clean golden wave: sentinel learns, must stay quiet ---------------
    for res in _wave(router, rng, pulses=args.pulses, per_pulse=4,
                     pulse_gap_s=0.08, tenants=tenants):
        all_results += res
    fired_clean = router.sentinel.fired_total
    checks["clean_wave_quiet"] = fired_clean == 0
    t_clean = time.time()

    if args.write_golden:
        router.history.save(GOLDEN)
        print(json.dumps({"ok": True, "wrote_golden": GOLDEN,
                          "fired_clean": fired_clean}))
        router.close()
        return 0 if fired_clean == 0 else 1

    # -- committed-golden replay: the archived clean wave must also
    # read quiet under TODAY's detector (band-drift guard)
    if os.path.exists(GOLDEN):
        golden_firings = AnomalySentinel.replay(
            HistoryStore.load(GOLDEN), signals=_signals(),
            **SENTINEL_KW)
        checks["golden_replay_quiet"] = not golden_firings
    else:
        checks["golden_replay_quiet"] = False

    # -- regression wave: injected mid-wave latency cliff ------------------
    for name in ("r0", "r1"):
        faults.inject("replica_slow", count=10_000,
                      seconds=0.06, replica=name)
    try:
        for res in _wave(router, rng, pulses=8, per_pulse=4,
                         pulse_gap_s=0.08, tenants=tenants):
            all_results += res
    finally:
        faults.clear()
    t_end = time.time()

    fired = router.sentinel.fired_total
    checks["sentinel_fired_on_regression"] = fired > fired_clean
    alerting = sorted(
        {f for st in [router.sentinel.state()] for f, r in st.items()
         if r.get("alert")})

    # the fleet_anomaly flight dump must exist and parse
    dumps = sorted(f for f in os.listdir(out_dir)
                   if f.startswith("flight_fleet_anomaly")
                   and f.endswith(".json"))
    parsed = False
    for fn in dumps:
        try:
            with open(os.path.join(out_dir, fn)) as f:
                doc = json.load(f)
            parsed = bool(doc.get("reason") == "fleet_anomaly"
                          and doc.get("signal"))
        except (OSError, json.JSONDecodeError):
            parsed = False
        if parsed:
            break
    checks["anomaly_flight_dump_parseable"] = parsed

    # -- tenancy: per-tenant token totals sum EXACTLY to fleet totals ------
    rep = router.tenants.report()
    fleet_out = int(router.registry.get("fleet_tokens_out_total").value)
    fleet_in = int(router.registry.get("fleet_tokens_in_total").value)
    res_out = sum(len(r["tokens"]) for r in all_results)
    sketch_out = sum(t["tokens_out"] for t in rep["tenants"])
    sketch_in = sum(t["tokens_in"] for t in rep["tenants"])
    checks["tenant_tokens_out_exact"] = (
        sketch_out == rep["totals"]["tokens_out"] == fleet_out
        == res_out)
    checks["tenant_tokens_in_exact"] = (
        sketch_in == rep["totals"]["tokens_in"] == fleet_in)
    checks["tenant_kv_page_seconds_nonzero"] = \
        rep["totals"]["kv_page_s"] > 0

    # -- zero new recompiles with accounting on ----------------------------
    checks["compile_counts_frozen"] = all(
        engines[i].compile_counts() == frozen[i]
        for i in range(len(engines))) and \
        router.compile_report()["unexpected_retraces"] == 0

    # -- artifacts ---------------------------------------------------------
    router.history.save(os.path.join(out_dir, "history_snapshot.json"))
    with open(os.path.join(out_dir, "marks.json"), "w") as f:
        json.dump({"t0": t0, "t_clean": t_clean, "t_end": t_end}, f)
    with open(os.path.join(out_dir, "tenants.json"), "w") as f:
        json.dump(rep, f, indent=1)
    with open(os.path.join(out_dir, "health.json"), "w") as f:
        json.dump(router.health(), f, indent=1)
    router.registry.dump(os.path.join(out_dir, "metrics.json"),
                         extra={"recompile_report": report_all(),
                                "stage": "history_smoke"})
    router.close()
    for e in engines:
        e.close()

    ok = all(checks.values())
    print(json.dumps({"ok": ok, "checks": checks,
                      "anomaly_fired": fired,
                      "alerting": alerting,
                      "requests": len(all_results),
                      "tokens_out": res_out,
                      "out_dir": out_dir}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
