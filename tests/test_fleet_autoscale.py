"""Elastic fleet autoscaling + adaptive overload control (ISSUE 15).

Pins the contracts (docs/robustness.md "Elastic autoscaling &
overload control"):

- FleetAutoscaler: scale OUT on multi-window SLO burn / standing
  overload with a warm-boot adoption gate (a newcomer takes traffic
  only after a ``serving``+``warmed`` heartbeat, with zero new
  steady-state traces), scale IN on recovered budget + idle hold
  (hysteresis + per-direction cooldowns), drain → remove with zero
  lost or duplicated requests — token-exact, exactly-once by rid;
- adaptive overload control in FleetRouter: CoDel-style sojourn
  admission (head-of-line wait over target for a full interval sheds
  fail-fast in the tenant-fair order), the brownout ladder clamping
  the heaviest tenants' decode budgets first, ``degraded`` honestly
  visible in health();
- satellite regressions: a hedge leg on a retiring replica is
  cancelled before membership removal (never burns a draining slot
  into the stale-leg guard); a ``retiring`` replica is exempt from
  the supervisor's kill/respawn and half-open-trial paths
  (exactly-one-owner); autoscale decisions are journaled and
  recoverable across a router crash mid-scale-event; and
  ``tools/fleet_replay.py --knob autoscale.<param>`` scores a policy
  offline.

`pytest -m chaos` selects the chaos classes.
"""
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
from paddle_tpu.nlp.serving import ServingEngine
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.observability.slo import SLObjective
from paddle_tpu.resilience import faults, preemption
from paddle_tpu.serving_fleet import (
    FleetAutoscaler, FleetRouter, FleetSupervisor, InprocReplica,
    RouterCrash)
from paddle_tpu.serving_fleet.journal import reconcile, replay

from test_fleet_proc import StubReplica, StubRouter

NEW_TOK = 8
WAVE_LENS = (5, 12, 17, 9, 12, 5, 17, 12, 9, 5, 12, 17,
             5, 9, 12, 17, 5, 12, 9, 17)

# tight SLOs + sub-second burn windows: the drills must see an alert
# within a CPU test's budget (SLOTracker semantics are pinned by
# test_fleet_tracing; here they are just the scale-out trigger)
SLOS = (SLObjective("ttft", "latency", target=0.99, threshold_s=0.05),
        SLObjective("e2e", "latency", target=0.99, threshold_s=2.0),
        SLObjective("availability", "availability", target=0.999))
# short 0.5s: alerts clear fast after recovery (alert = short AND
# long burning). long 8s: doubles as the SLI horizon, so the drill's
# end-of-run accounting assertions still see every event
WINDOWS = ({"short_s": 0.5, "long_s": 8.0, "burn": 1.0},)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    preemption.clear()
    yield
    faults.clear()
    preemption.clear()


@pytest.fixture(scope="module")
def gpt_model():
    paddle.seed(0)
    m = GPTForCausalLM(_resolve_config("gpt-tiny"))
    m.eval()
    return m


def _prompts(lens, vocab=200, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, (n,)).astype(np.int32)
            for n in lens]


@pytest.fixture(scope="module")
def wave(gpt_model):
    """(prompts, golden) — golden from an uninterrupted single
    engine: the token-exactness reference across scale events."""
    prompts = _prompts(WAVE_LENS)
    eng = ServingEngine(gpt_model, max_slots=2, page_size=16,
                        max_seq_len=64, steps_per_dispatch=4)
    refs = eng.generate(prompts, max_new_tokens=NEW_TOK)
    eng.close()
    return prompts, refs


def _engine(model, **kw):
    d = dict(max_slots=2, page_size=16, max_seq_len=64,
             steps_per_dispatch=4)
    d.update(kw)
    eng = ServingEngine(model, **d)
    eng.warmup(buckets=sorted(set(WAVE_LENS)), decode=True)
    return eng


def _counter(reg, name, **labels):
    c = reg.get(name, labels or None)
    return 0 if c is None else int(c.value)


def _elastic_fleet(model, router_kw=None,
                   autoscale_kw=None, n=1):
    """One-replica-plus-autoscaler fleet; spawn_fn builds warmed
    engines (appended to `engines` for cleanup)."""
    engines = []

    def build():
        eng = _engine(model)
        engines.append(eng)
        return eng

    reps = [InprocReplica(f"r{i}", build()) for i in range(n)]
    frozen = [e.compile_counts() for e in engines]
    rkw = dict(slos=SLOS, slo_windows=WINDOWS, history=True,
               history_interval_s=0.05)
    rkw.update(router_kw or {})
    router = FleetRouter(reps, **rkw)
    akw = dict(min_replicas=n, max_replicas=3,
               scale_out_cooldown_s=0.4, scale_in_cooldown_s=0.4,
               recovery_hold_s=0.6, boot_timeout_s=60.0,
               flap_window_s=0.05)
    akw.update(autoscale_kw or {})
    asc = FleetAutoscaler(router, lambda i: InprocReplica(
        f"as{i}", build()), **akw)
    return router, asc, engines, frozen


def _close(router, engines):
    router.close()
    for e in engines:
        e.close()


def _drive(router, asc, cond, timeout=60.0, results=None,
           events=None):
    deadline = time.monotonic() + timeout
    while not cond():
        router.step()
        ev = asc.poll()
        if events is not None:
            events.extend(ev)
        if results is not None:
            results.extend(router.results())
        assert time.monotonic() < deadline, "drill made no progress"
        time.sleep(0.002)


# -- adaptive overload control (router layer) ---------------------------


class TestOverloadControl:
    def test_sojourn_shed_tenant_fair_and_degraded_visible(
            self, gpt_model):
        """Standing head-of-line sojourn over target -> degraded;
        queued requests past the target shed fail-fast, heaviest
        tenant first within a priority band; degraded clears after
        the storm."""
        eng = _engine(gpt_model, max_slots=1)
        rep = InprocReplica("r0", eng)
        router = FleetRouter(
            [rep], slos=False, replica_queue_limit=1,
            overload_target_ms=80.0, overload_interval_s=0.08,
            brownout_step_s=60.0)
        try:
            # whale is pre-accounted heavy: the shed order must hit
            # it first inside the same priority band
            router.tenants.account("whale", tokens_in=10_000,
                                   requests=1)
            with faults.scenario(
                    ("replica_slow", {"replica": "r0", "count": 1000,
                                      "seconds": 0.05})):
                prompts = _prompts((5, 5, 5, 5, 5, 5), seed=3)
                rids = []
                for i, p in enumerate(prompts):
                    tenant = "whale" if i % 2 == 0 else "minnow"
                    rids.append(router.submit(p, NEW_TOK,
                                              tenant=tenant))
                res = []
                deadline = time.monotonic() + 30
                saw_degraded = False
                while len(res) < len(rids):
                    router.step()
                    saw_degraded = saw_degraded or router.degraded
                    res += router.results()
                    assert time.monotonic() < deadline
                    time.sleep(0.002)
            assert saw_degraded, "overload never became visible"
            assert router.health()["overload"]["target_s"] == 0.08
            shed = [r for r in res if r["status"] == "shed"]
            assert shed, "sojourn controller never shed"
            assert _counter(router.registry,
                            "fleet_overload_sheds_total") == len(shed)
            # tenant fairness: no minnow request sheds while a whale
            # request that was ALSO past the target stayed queued —
            # within the shed set, whales resolve before minnows
            shed_tenants = [r["tenant"] for r in shed]
            first_minnow = shed_tenants.index("minnow") \
                if "minnow" in shed_tenants else len(shed_tenants)
            assert all(t == "whale"
                       for t in shed_tenants[:first_minnow])
            # recovery: queue drained -> degraded clears
            deadline = time.monotonic() + 10
            while router.degraded:
                router.step()
                assert time.monotonic() < deadline
                time.sleep(0.002)
            assert router.health()["overload"]["degraded"] is False
        finally:
            _close(router, [eng])

    def test_brownout_clamps_heaviest_tenant_first(self, gpt_model):
        """The ladder climbs while degraded and DECAYS one rung per
        step after recovery (hysteresis): inside that decay window
        the heaviest tenant's decode budget is still clamped — its
        request resolves with exactly brownout_max_new tokens while a
        light tenant keeps the full budget."""
        eng = _engine(gpt_model, max_slots=1)
        rep = InprocReplica("r0", eng)
        router = FleetRouter(
            [rep], slos=False, replica_queue_limit=1,
            overload_target_ms=60.0, overload_interval_s=0.06,
            brownout_max_new=2, brownout_levels=1,
            brownout_step_s=2.0)
        try:
            router.tenants.account("whale", tokens_in=10_000,
                                   requests=1)
            prompts = _prompts((5, 5, 5, 5, 5, 5, 5, 5), seed=4)
            with faults.scenario(
                    ("replica_slow", {"replica": "r0", "count": 40,
                                      "seconds": 0.05})):
                # saturate with enough filler that the head-of-line
                # wait stands past the interval -> degraded + level 1
                for p in prompts[:6]:
                    router.submit(p, NEW_TOK, priority=1)
                deadline = time.monotonic() + 30
                while router._brownout_level < 1:
                    router.step()
                    assert time.monotonic() < deadline
                    time.sleep(0.002)
                h = router.health()["overload"]
                assert h["brownout_level"] == 1
                assert h["clamped_tenants"] == ["whale"]
                # let the storm clear (sheds + drain) — the ladder
                # holds its rung for brownout_step_s after recovery
                deadline = time.monotonic() + 30
                while router.degraded or router._queue \
                        or router._outstanding().get("r0"):
                    router.step()
                    router.results()
                    assert time.monotonic() < deadline
                    time.sleep(0.002)
            assert router._brownout_level == 1, \
                "the ladder must decay with hysteresis, not a cliff"
            # inside the decay window: whale clamped, minnow not
            whale = router.submit(prompts[6], NEW_TOK,
                                  tenant="whale")
            minnow = router.submit(prompts[7], NEW_TOK,
                                   tenant="minnow")
            res = {}
            deadline = time.monotonic() + 30
            while not {whale, minnow} <= set(res):
                router.step()
                res.update({r["id"]: r for r in router.results()})
                assert time.monotonic() < deadline
                time.sleep(0.002)
            assert res[whale]["status"] == "ok"
            assert res[minnow]["status"] == "ok"
            assert len(res[whale]["tokens"]) == 2, \
                "whale budget not clamped to brownout_max_new"
            assert len(res[minnow]["tokens"]) == NEW_TOK, \
                "light tenant must keep its full budget"
            assert _counter(router.registry,
                            "fleet_brownout_clamped_total",
                            tenant="whale") == 1
            assert _counter(router.registry,
                            "fleet_brownout_clamped_total",
                            tenant="minnow") == 0
            # ladder fully decays once the step elapses
            deadline = time.monotonic() + 10
            while router._brownout_level > 0:
                router.step()
                assert time.monotonic() < deadline
                time.sleep(0.002)
            assert router.health()["overload"]["brownout_level"] == 0
        finally:
            _close(router, [eng])


# -- satellite 1: scale-in vs hedging race ------------------------------


class TestHedgeScaleInRace:
    def test_retire_cancels_inflight_hedge_leg(self, gpt_model, wave):
        """A hedge leg parked on the retiring replica is cancelled
        BEFORE the drain/removal — the primary resolves the request
        exactly once, no failover is counted for the hedge leg, and
        the replica removes cleanly."""
        prompts, refs = wave
        engines = [_engine(gpt_model) for _ in range(2)]
        reps = [InprocReplica(f"r{i}", e)
                for i, e in enumerate(engines)]
        router = FleetRouter(reps, slos=False, hedge_after_ms=30,
                             replica_queue_limit=4)
        try:
            # keep BOTH replicas slow so the hedge fires and both
            # legs are genuinely in flight at retire time
            with faults.scenario(
                    ("replica_slow", {"replica": "r0", "count": 1000,
                                      "seconds": 0.03}),
                    ("replica_slow", {"replica": "r1", "count": 1000,
                                      "seconds": 0.03})):
                rid = router.submit(prompts[0], NEW_TOK)
                deadline = time.monotonic() + 30
                p = router._pending[rid]
                while p.hedge is None:
                    router.step()
                    assert time.monotonic() < deadline, \
                        "hedge never fired"
                    time.sleep(0.002)
                victim = p.hedge
                primary = p.replica
                router.retire(victim)
                # the hedge leg is gone from the request state NOW —
                # nothing left to burn a draining slot
                assert p.hedge is None
                res = router.run_to_completion(timeout_s=60)
            assert [r["id"] for r in res] == [rid]
            assert res[0]["status"] == "ok"
            assert res[0]["tokens"] == refs[0]
            assert res[0]["replica"] == primary
            assert _counter(router.registry, "fleet_failovers_total",
                            replica=victim, reason="removed") == 0
            # the victim drains and removes cleanly
            deadline = time.monotonic() + 10
            while router.replicas[victim].alive:
                router.step()
                assert time.monotonic() < deadline
                time.sleep(0.002)
            router.remove_replica(victim)
            assert victim not in router.replicas
        finally:
            _close(router, engines)


# -- satellites 2+3: supervisor ownership -------------------------------


class TestSupervisorRetiring:
    def _sup(self, reps, **kw):
        router = StubRouter(reps)
        d = dict(seed=3, breaker_threshold=3, breaker_window_s=60.0,
                 breaker_cooldown_s=100.0, boot_timeout_s=5.0)
        d.update(kw)
        return FleetSupervisor(router, **d), router

    def test_retiring_replica_death_is_not_a_crash(self):
        """A retiring replica's death must NOT schedule a respawn —
        today's bug: watch() would resurrect a replica the autoscaler
        is scaling in."""
        rep = StubReplica("r0")
        sup, router = self._sup([rep])
        assert sup.mark_retiring("r0") == "serving"
        rep.die()
        assert sup.poll(now=1000.0) == []
        assert sup.poll(now=2000.0) == []
        assert rep.rejoins == 0
        h = sup.health()
        assert h["replicas"]["r0"]["phase"] == "retiring"
        assert h["retiring"] == ["r0"]
        # removal purges the state
        del router.replicas["r0"]
        sup.poll(now=3000.0)
        assert "r0" not in sup.health()["replicas"]

    def test_retiring_exempt_from_hb_timeout_kill(self):
        """The supervisor-side wedge detector must not kill a
        retiring replica that (expectedly) stopped heartbeating."""
        class StaleReplica(StubReplica):
            def scrape(self):
                snap = super().scrape()
                if snap:
                    snap["ts"] = 0.0   # ancient heartbeat
                return snap

        rep = StaleReplica("r0")
        sup, _router = self._sup([rep], heartbeat_timeout_s=1.0)
        sup.mark_retiring("r0")
        assert sup.poll(now=5000.0) == []
        assert rep.kills == 0 and rep.alive
        # control: without the mark the same staleness is a wedge
        rep2 = StaleReplica("r1")
        sup2, _ = self._sup([rep2], heartbeat_timeout_s=1.0)
        ev = sup2.poll(now=5000.0)
        assert ("r1", "down") in ev and rep2.kills == 1

    def test_half_open_trial_races_scale_in_exactly_one_owner(self):
        """Satellite 3: quarantined -> cooldown -> the half-open
        trial would fire, but the autoscaler retired the replica
        first — the supervisor must not re-arm/trial-boot it, and a
        retired NAME is never respawned."""
        rep = StubReplica("rbad", fail_incs=set(range(2, 50)))
        sup, router = self._sup([rep], breaker_threshold=1,
                                breaker_cooldown_s=10.0)
        t = 1000.0
        rep.die()
        ev = sup.poll(now=t)
        assert ("rbad", "quarantined") in ev
        assert rep.quarantined is True
        rejoins0 = rep.rejoins
        # the autoscaler claims ownership DURING the cooldown
        assert sup.mark_retiring("rbad") == "quarantined"
        assert rep.quarantined is False  # honest health: retiring,
        #                                   not phantom-quarantined
        # past the cooldown: no rearm, no trial boot
        assert sup.poll(now=t + 60.0) == []
        assert rep.rejoins == rejoins0
        assert sup.health()["replicas"]["rbad"]["phase"] == "retiring"
        # the router removes the name: purged, still never respawned
        del router.replicas["rbad"]
        assert sup.poll(now=t + 120.0) == []
        assert "rbad" not in sup.health()["replicas"]
        assert rep.rejoins == rejoins0


# -- autoscaler units ---------------------------------------------------


class TestAutoscalerUnits:
    def _stub_asc(self, monkeypatch=None, **kw):
        reps = [StubReplica("r0")]
        router = StubRouter(reps)
        router._lost = set()
        d = dict(registry=router.registry)
        d.update(kw)
        return FleetAutoscaler(router, lambda i: StubReplica(
            f"as{i}"), **d)

    def test_env_knob_defaults(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_AUTOSCALE_MIN", "2")
        monkeypatch.setenv("PADDLE_TPU_AUTOSCALE_MAX", "5")
        monkeypatch.setenv("PADDLE_TPU_AUTOSCALE_COOLDOWN_S", "7.5")
        monkeypatch.setenv("PADDLE_TPU_AUTOSCALE_HOLD_S", "9.0")
        asc = self._stub_asc()
        assert (asc.min_replicas, asc.max_replicas) == (2, 5)
        assert asc.scale_out_cooldown_s == 7.5
        assert asc.scale_in_cooldown_s == 22.5   # 3x by default
        assert asc.recovery_hold_s == 9.0
        # explicit args beat the env
        asc2 = self._stub_asc(min_replicas=1, max_replicas=3,
                              scale_out_cooldown_s=1.0,
                              recovery_hold_s=2.0)
        assert (asc2.min_replicas, asc2.max_replicas) == (1, 3)
        assert asc2.scale_out_cooldown_s == 1.0

    def test_bounds_validated(self):
        with pytest.raises(ValueError, match="min_replicas"):
            self._stub_asc(min_replicas=4, max_replicas=2)

    def test_flap_counter(self):
        asc = self._stub_asc(flap_window_s=10.0)
        reg = asc.registry
        assert _counter(reg, "fleet_autoscale_flaps_total") == 0
        asc._last_in_at = 100.0
        assert asc._flap_check(105.0, "out") is True
        assert _counter(reg, "fleet_autoscale_flaps_total") == 1
        assert asc._flap_check(200.0, "out") is False
        asc._last_out_at = 200.0
        assert asc._flap_check(205.0, "in") is True
        assert _counter(reg, "fleet_autoscale_flaps_total") == 2

    def test_boot_gate_and_timeout(self, gpt_model):
        """A spawned replica is adopted only on a serving+warmed
        heartbeat; an unwarmed one that never warms is killed at the
        boot deadline and the fleet is untouched."""
        eng = _engine(gpt_model)
        router = FleetRouter([InprocReplica("r0", eng)], slos=False)
        cold = []

        def spawn(i):
            e = ServingEngine(gpt_model, max_slots=2, page_size=16,
                              max_seq_len=64, steps_per_dispatch=4)
            cold.append(e)       # deliberately NOT warmed
            return InprocReplica(f"as{i}", e)

        asc = FleetAutoscaler(router, spawn, min_replicas=1,
                              max_replicas=2, boot_timeout_s=5.0,
                              scale_out_cooldown_s=0.0)
        try:
            t = time.monotonic()
            asc._start_scale_out(t, "slo_burn:test", [])
            assert asc.state == "booting"
            # heartbeats flow but warmed stays False -> no adoption
            deadline = time.monotonic() + 5
            while not asc._pending_rep.scrape():
                assert time.monotonic() < deadline
                time.sleep(0.01)
            assert asc.poll() == []
            assert asc.state == "booting"
            assert len(router.replicas) == 1
            # past the deadline: killed + counted, fleet untouched
            ev = asc.poll(now=t + 10.0)
            assert ev == [("boot_failed", "as0")]
            assert asc.state == "steady"
            assert len(router.replicas) == 1
            assert _counter(router.registry,
                            "fleet_autoscale_events_total",
                            direction="out",
                            reason="boot_timeout") == 1
            assert router.health()["autoscale"]["state"] == "steady"
        finally:
            _close(router, [eng] + cold)


# -- the elastic chaos drill --------------------------------------------


@pytest.mark.chaos
class TestElasticChaos:
    def test_burst_scaleout_recovery_scalein_token_exact(
            self, gpt_model, wave, tmp_path):
        """The acceptance drill: a seeded burst against a pinned-slow
        single replica fires the TTFT burn alert -> scale-out through
        the warm-boot gate (the newcomer takes traffic with zero new
        steady-state traces) -> the wave drains, budget recovers ->
        scale-in (hedge-safe drain -> remove, token-exact,
        exactly-once by rid vs the uninterrupted golden); decisions
        journaled; no SLO-accounting gap; zero flaps."""
        prompts, refs = wave
        jdir = os.path.join(str(tmp_path), "journal")
        router, asc, engines, frozen = _elastic_fleet(
            gpt_model, router_kw={"journal_dir": jdir,
                                  "overload_target_ms": 5000.0})
        try:
            faults.inject("replica_slow", replica="r0", count=50,
                          seconds=0.04)
            rids, results, events = [], [], []
            avail_snap = None
            t0 = time.monotonic()
            nxt = 0

            def done():
                return (nxt >= len(prompts)
                        and len(results) >= len(prompts)
                        and asc.state == "steady"
                        and len(router.replicas) == 1
                        and any(e[0] == "scaled_in" for e in events))

            deadline = time.monotonic() + 120
            while not done():
                now = time.monotonic() - t0
                while nxt < len(prompts) and now > nxt * 0.01:
                    rids.append(router.submit(prompts[nxt], NEW_TOK))
                    nxt += 1
                router.step()
                events += asc.poll()
                results += router.results()
                if avail_snap is None \
                        and len(results) >= len(prompts):
                    # accounting checked the moment the wave is fully
                    # resolved — the sliding SLO windows forget by
                    # design once events age past the horizon
                    avail_snap = router.slo.evaluate()["availability"]
                assert time.monotonic() < deadline, \
                    f"drill stalled: {events}, {len(results)}"
                time.sleep(0.002)
            faults.clear()
            # exactly-once, token-exact, nothing lost
            ids = [r["id"] for r in results]
            assert sorted(ids) == sorted(rids)
            assert len(ids) == len(set(ids))
            by_id = {r["id"]: r for r in results}
            for i, rid in enumerate(rids):
                assert by_id[rid]["status"] == "ok", by_id[rid]
                assert by_id[rid]["tokens"] == refs[i], \
                    f"rid {rid} not token-exact across scale events"
            # a scale-out passed the boot gate and TOOK TRAFFIC
            assert any(e[0] == "scaled_out" for e in events)
            spawned_names = [rep.name for rep, _fz in asc.spawned]
            assert spawned_names
            assert any(
                _counter(router.registry, "fleet_routed_total",
                         replica=n) > 0 for n in spawned_names), \
                "no spawned replica ever took traffic"
            # zero new steady-state traces: base engine vs warmup
            # snapshot, spawned engines vs their adoption snapshot
            assert engines[0].compile_counts() == frozen[0]
            for rep, fz in asc.spawned:
                assert fz is not None
                assert rep.engine.compile_counts() == fz, \
                    f"{rep.name} traced after its warm-boot gate"
            assert router.compile_report()["unexpected_retraces"] == 0
            # no SLO-accounting gap: every resolve across the scale
            # events was counted exactly once as ok (the registry is
            # the cumulative ledger; the sliding SLO windows forget
            # by design) and the availability objective never saw a
            # bad event
            assert avail_snap is not None
            assert avail_snap["bad"] == 0
            assert avail_snap["events"] > 0
            assert _counter(router.registry, "fleet_requests_total",
                            status="ok") == len(rids)
            for st in ("shed", "expired", "cancelled", "failed"):
                assert _counter(router.registry,
                                "fleet_requests_total",
                                status=st) == 0
            # decisions journaled + reconcilable
            records, _stats = replay(jdir)
            state = reconcile(records)
            kinds = [r["kind"] for r in state["autoscale"]]
            assert "scale_out" in kinds and "scale_in" in kinds
            # the controller never flapped
            assert _counter(router.registry,
                            "fleet_autoscale_flaps_total") == 0
        finally:
            faults.clear()
            _close(router, engines)

    def test_router_crash_mid_scale_event_recovers(
            self, gpt_model, wave, tmp_path):
        """Kill the router right after a scale-out was journaled and
        executed: the successor re-adopts the (now larger) fleet from
        the journal + live replicas, every request resolves exactly
        once token-exact, and the scale records survive replay."""
        prompts, refs = wave
        jdir = os.path.join(str(tmp_path), "journal")
        router, asc, engines, frozen = _elastic_fleet(
            gpt_model, router_kw={"journal_dir": jdir,
                                  "overload_target_ms": 5000.0})
        pre = []
        try:
            faults.inject("replica_slow", replica="r0", count=80,
                          seconds=0.04)
            rids = [router.submit(p, NEW_TOK) for p in prompts]
            events = []
            _drive(router, asc,
                   lambda: any(e[0] == "scaled_out" for e in events),
                   timeout=60.0, results=pre, events=events)
            # crash the control plane mid-scale-event (replicas live)
            faults.inject("router_crash")
            with pytest.raises(RouterCrash):
                deadline = time.monotonic() + 30
                while True:
                    router.step()
                    pre.extend(router.results())
                    assert time.monotonic() < deadline
            faults.clear()
            reps = list(router.replicas.values())
            r2 = FleetRouter.recover(jdir, reps, slos=SLOS,
                                     slo_windows=WINDOWS,
                                     overload_target_ms=5000.0)
            try:
                post = r2.run_to_completion(timeout_s=120)
                got = pre + post
                ids = [r["id"] for r in got]
                assert sorted(ids) == sorted(rids), \
                    "requests lost across the crash mid-scale-event"
                assert len(ids) == len(set(ids))
                by_id = {r["id"]: r for r in got}
                for i, rid in enumerate(rids):
                    assert by_id[rid]["status"] == "ok"
                    assert by_id[rid]["tokens"] == refs[i]
                # the journal still tells the scale story
                records, _stats = replay(jdir)
                state = reconcile(records)
                assert any(r["kind"] == "scale_out"
                           for r in state["autoscale"])
                assert r2.compile_report()[
                    "unexpected_retraces"] == 0
            finally:
                r2.close()
        finally:
            faults.clear()
            _close(router, engines)

    def test_replay_knob_scores_autoscale_policy(
            self, gpt_model, tmp_path):
        """tools/fleet_replay.py --knob autoscale.<param> arms an
        autoscaler over the replay fleet and the verdict scores the
        policy (events, flaps, final size) — the offline what-if
        loop."""
        import tools.fleet_replay as fr

        wave_entries = fr.synth_wave(7, 12, burst=6,
                                     burst_gap_s=0.02)
        knobs = ["autoscale.max_replicas=2",
                 "autoscale.min_replicas=1",
                 "autoscale.scale_out_cooldown_s=0.3",
                 "autoscale.recovery_hold_s=0.5",
                 "autoscale.flap_window_s=0.05",
                 "overload_target_ms=100",
                 "overload_interval_s=0.1"]
        verdict, _rep = fr.run_replay(
            wave_entries, out_dir=str(tmp_path), knob_pairs=knobs,
            replicas=1, timeout_s=120.0,
            faults_arm=lambda: faults.inject(
                "replica_slow", replica="r0", count=60,
                seconds=0.05))
        assert verdict["autoscale"] is not None
        assert verdict["autoscale"]["replicas_final"] >= 1
        evs = [e["event"] for e in verdict["autoscale"]["events"]]
        assert "scale_out_started" in evs, \
            f"policy never scaled under saturation: {evs}"
        assert isinstance(verdict["autoscale"]["flaps"], int)
        # the knob pairs are recorded in the verdict for provenance
        assert verdict["knobs"]["pairs"] == knobs
