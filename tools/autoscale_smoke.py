"""autoscale_smoke — the campaign's CPU drill for elastic fleet
autoscaling (ISSUE 15).

Shape (seeded, CPU-only, no chip time spent):

1. build a ONE-replica in-process fleet (journaled, history plane on,
   tight TTFT/e2e SLOs with sub-second burn windows) plus a
   FleetAutoscaler whose ``spawn_fn`` builds warmed replicas up to
   ``max_replicas``;
2. **burst wave**: the base replica is pinned slow (``replica_slow``
   — the saturation seam) while a seeded burst arrives open-loop.
   TTFT burn fires the multi-window alert → the autoscaler spawns a
   replica, holds it at the warm-boot gate, and adopts it only on a
   ``serving`` + ``warmed`` heartbeat;
3. **recovery**: the wave drains, the burn windows clear, budgets
   recover and the fleet runs idle for the hold — the autoscaler
   retires capacity (hedge-safe drain → ``remove_replica``) back to
   ``min_replicas``;
4. invariants, asserted hard: NO LOST RID (every submitted request
   resolves exactly once), every ok result TOKEN-EXACT vs an
   uninterrupted single-engine golden (scale events never corrupt a
   stream), bounded SLO breach (ok fraction over the whole drill),
   compile counts FROZEN — the base engine from warmup, spawned
   engines from their adoption snapshot (a new replica takes traffic
   with zero new steady-state traces), zero unexpected retraces,
   ZERO flaps, ``scale_out``+``scale_in`` records in the journal
   (``reconcile()["autoscale"]``), parseable
   ``flight_fleet_scale_out``/``flight_fleet_scale_in`` dumps, and —
   r21 — the alert-to-serving latency bar: the base replica's traced
   boot exports an AOT serving artifact, every autoscaler spawn boots
   off it (``mode=aot``, counted in ``fleet_boots_total``), and every
   AOT boot wall beats the traced-boot control measured on the same
   drill;
5. artifacts into $BENCH_TELEMETRY_DIR: ``metrics.json`` (fleet
   registry + recompile report — the validate_stages contract),
   ``health.json``, ``autoscale_events.json``, the journal dir and
   the flight dumps.

Last stdout line is a JSON verdict; exit 0 only when every assertion
holds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

NEW_TOK = 8
WAVE_LENS = (5, 12, 17, 9, 12, 5, 17, 12, 9, 5, 12, 17,
             5, 9, 12, 17, 5, 12, 9, 17, 9, 5, 17, 12)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--timeout", type=float, default=120.0)
    args = ap.parse_args(argv)

    out_dir = os.environ.get("BENCH_TELEMETRY_DIR") or os.path.join(
        REPO, "campaign_out", "telemetry", "autoscale_smoke")
    os.makedirs(out_dir, exist_ok=True)
    # scale-event flight dumps land next to the other artifacts
    os.environ.setdefault("PADDLE_TPU_FLIGHT_DIR", out_dir)

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    from paddle_tpu.nlp.serving import ServingEngine
    from paddle_tpu.observability.slo import SLObjective
    from paddle_tpu.observability.trace import report_all
    from paddle_tpu.resilience import faults
    from paddle_tpu.serving_fleet import FleetAutoscaler, \
        FleetRouter, InprocReplica
    from paddle_tpu.serving_fleet.journal import reconcile, replay

    paddle.seed(0)
    model = GPTForCausalLM(_resolve_config("gpt-tiny"))
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 200, (int(n),)).astype(np.int32)
               for n in WAVE_LENS]

    # uninterrupted single-engine golden: greedy decoding makes every
    # scale-event stream comparable token for token
    g = ServingEngine(model, max_slots=2, page_size=16, max_seq_len=64,
                      steps_per_dispatch=4)
    refs = g.generate(prompts, max_new_tokens=NEW_TOK)
    g.close()

    engines = []
    boots = []   # (wall_s, boot_info) per built engine — the AOT-vs-
    #              traced alert-to-serving latency assertion's data
    store = os.path.join(out_dir, "aot_store")

    def build_engine(aot=False):
        eng = ServingEngine(model, max_slots=2, page_size=16,
                            max_seq_len=64, steps_per_dispatch=4)
        t = time.monotonic()
        if aot:
            # the r21 scale-out spawn path: restore serialized
            # programs from the artifact e0's traced boot exported
            warm_boot(eng, buckets=sorted(set(WAVE_LENS)),
                      artifact_dir=store)
        else:
            eng.warmup(buckets=sorted(set(WAVE_LENS)), decode=True)
        boots.append((time.monotonic() - t, dict(eng.boot_info)))
        engines.append(eng)
        return eng

    from paddle_tpu.jit.serving_artifact import export_artifact, \
        warm_boot

    # traced-boot CONTROL: e0 pays the full trace+compile wall, then
    # exports the artifact every autoscaler spawn boots from
    e0 = build_engine(aot=False)
    traced_boot_s = boots[0][0]
    export_artifact(e0, store)
    frozen0 = e0.compile_counts()
    slos = (SLObjective("ttft", "latency", target=0.99,
                        threshold_s=0.05),
            SLObjective("e2e", "latency", target=0.99, threshold_s=2.0),
            SLObjective("availability", "availability", target=0.999))
    windows = ({"short_s": 0.5, "long_s": 2.0, "burn": 1.0},)
    jdir = os.path.join(out_dir, "journal")
    router = FleetRouter(
        [InprocReplica("r0", e0)], slos=slos, slo_windows=windows,
        history=True, history_interval_s=0.05, journal_dir=jdir,
        overload_target_ms=5000.0)
    asc = FleetAutoscaler(
        router, lambda i: InprocReplica(f"as{i}",
                                        build_engine(aot=True)),
        min_replicas=1, max_replicas=3,
        scale_out_cooldown_s=0.5, scale_in_cooldown_s=0.5,
        recovery_hold_s=0.75, boot_timeout_s=60.0,
        flap_window_s=0.05)

    # saturate the base replica for the first ~2s of the wave only —
    # the recovery half of the drill needs the fleet fast again
    faults.inject("replica_slow", replica="r0", count=50, seconds=0.04)

    checks = {}
    events, results = [], []
    rids = []
    max_size = 1
    t0 = time.monotonic()
    t_end = t0 + float(args.timeout)
    nxt = 0
    try:
        while time.monotonic() < t_end:
            now = time.monotonic() - t0
            while nxt < len(prompts) and now > nxt * 0.01:
                rids.append(router.submit(prompts[nxt], NEW_TOK))
                nxt += 1
            router.step()
            events += asc.poll()
            results += router.results()
            max_size = max(max_size, len(router.replicas))
            if nxt >= len(prompts) and len(results) >= len(prompts) \
                    and asc.state == "steady" \
                    and len(router.replicas) <= asc.min_replicas \
                    and any(e[0] == "scaled_in" for e in events):
                break
            time.sleep(0.002)
    finally:
        faults.clear()

    by_rid = {r["id"]: r for r in results}
    checks["no_lost_rid_exactly_once"] = (
        sorted(by_rid) == sorted(rids)
        and len(results) == len(rids))
    ok_n = sum(1 for r in results if r["status"] == "ok")
    checks["bounded_slo_breach"] = ok_n >= int(0.8 * len(rids))
    checks["ok_results_token_exact"] = all(
        by_rid[rid]["tokens"] == refs[i]
        for i, rid in enumerate(rids)
        if rid in by_rid and by_rid[rid]["status"] == "ok") and ok_n > 0
    checks["scaled_out_then_in"] = (
        any(e[0] == "scaled_out" for e in events)
        and any(e[0] == "scaled_in" for e in events)
        and max_size > 1 and len(router.replicas) == 1)
    checks["zero_flaps"] = int(router.registry.get(
        "fleet_autoscale_flaps_total").value) == 0
    # frozen compiles: the base engine vs its warmup snapshot; every
    # ADOPTED spawned engine vs its adoption snapshot (a boot-failed
    # spawn never took traffic and is exempt)
    spawned_ok = all(
        rep.engine.compile_counts() == fz
        for rep, fz in asc.spawned if fz is not None)
    checks["compile_counts_frozen"] = (
        e0.compile_counts() == frozen0 and spawned_ok
        and router.compile_report()["unexpected_retraces"] == 0)

    # r21 alert-to-serving latency, asserted HARD: every autoscaler
    # spawn must have booted off the AOT artifact (mode=aot, counted
    # in fleet_boots_total{mode="aot"}) and every such boot must beat
    # the traced-boot control wall measured on the SAME drill
    aot_boots = [w for w, bi in boots[1:] if bi.get("mode") == "aot"]
    checks["spawns_booted_aot"] = (
        len(aot_boots) == len(boots) - 1 and len(boots) > 1)
    mb = router.registry.get("fleet_boots_total", labels={"mode": "aot"})
    checks["fleet_boots_aot_counted"] = (
        mb is not None and int(mb.value) >= len(
            [1 for _rep, fz in asc.spawned if fz is not None]) > 0)
    checks["aot_boot_beats_traced"] = bool(aot_boots) and (
        max(aot_boots) < traced_boot_s)

    # journal: the scale decisions must be durable + reconcilable
    try:
        records, _stats = replay(jdir)
        state = reconcile(records)
        kinds = {r.get("kind") for r in state["autoscale"]}
        checks["journal_scale_records"] = {"scale_out",
                                           "scale_in"} <= kinds
    except Exception:  # noqa: BLE001 — an unreadable journal fails
        checks["journal_scale_records"] = False

    def _dump_ok(prefix):
        for fn in sorted(os.listdir(out_dir)):
            if fn.startswith(f"flight_{prefix}") \
                    and fn.endswith(".json"):
                try:
                    with open(os.path.join(out_dir, fn)) as f:
                        doc = json.load(f)
                    if doc.get("reason") == prefix \
                            and isinstance(doc.get("records"), list):
                        return True
                except (OSError, json.JSONDecodeError):
                    pass
        return False

    checks["scale_flight_dumps_parseable"] = (
        _dump_ok("fleet_scale_out") and _dump_ok("fleet_scale_in"))

    # artifacts
    with open(os.path.join(out_dir, "health.json"), "w") as f:
        json.dump(router.health(), f, indent=1)
    with open(os.path.join(out_dir, "autoscale_events.json"),
              "w") as f:
        json.dump({"events": [list(e) for e in events],
                   "decisions": asc.health()["decisions"]}, f,
                  indent=1)
    router.registry.dump(os.path.join(out_dir, "metrics.json"),
                         extra={"recompile_report": report_all(),
                                "stage": "autoscale_smoke"})
    router.close()
    for e in engines:
        e.close()

    ok = all(bool(v) for v in checks.values())
    print(json.dumps({"ok": ok, "checks": checks,
                      "requests": len(rids), "ok_results": ok_n,
                      "max_fleet_size": max_size,
                      "traced_boot_s": round(traced_boot_s, 3),
                      "aot_boot_s": [round(w, 3) for w in aot_boots],
                      "events": [list(e) for e in events],
                      "out_dir": out_dir}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
