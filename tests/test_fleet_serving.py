"""Fault-tolerant serving fleet (paddle_tpu/serving_fleet/).

Pins the fleet contracts (docs/robustness.md "Fleet serving"):

- engine lifecycle: explicit serving|draining|closed state, clear
  closed-engine errors, idempotent drain-then-close that never
  wedges, in-flight export;
- crash-mid-decode failover: every request still completes TOKEN-
  EXACT vs a single-replica golden — the completed prefix recovered
  off the carcass is deduped (continuation resubmit), never replayed;
- graceful drain under load: in-flight finishes token-exactly on the
  draining replica, queued work bounces and re-places; rejoin reuses
  the same engine so the whole cycle costs zero recompiles;
- hedging: a slow primary gets a duplicate, the first finisher wins,
  the loser is cancelled, the client sees exactly one result;
- priority load shedding under full-fleet saturation;
- fleet-wide compile counts FROZEN through a crash/drain/rejoin wave
  (zero unexpected retraces — the zero-recompile contract at fleet
  scale).

Everything drills deterministically on CPU via resilience.faults
(replica_crash / replica_wedge / replica_slow / scrape_timeout /
flaky_transport, payload-targeted by replica name). `pytest -m chaos`
selects the chaos classes.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
from paddle_tpu.nlp.serving import ServingEngine
from paddle_tpu.resilience import backoff_schedule, faults
from paddle_tpu.resilience.retry import TransientError, \
    call_with_retries
from paddle_tpu.serving_fleet import FleetRouter, InprocReplica

NEW_TOK = 10


@pytest.fixture(scope="module")
def gpt_model():
    paddle.seed(0)
    m = GPTForCausalLM(_resolve_config("gpt-tiny"))
    m.eval()
    return m


def _prompts(lens, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,)).astype(np.int32) for n in lens]


# prompt lengths straddle pages and pow2 buckets; max_new keeps every
# continuation (prompt + recovered prefix) inside the warmed buckets
WAVE_LENS = (5, 12, 17, 9, 21, 14)


@pytest.fixture(scope="module")
def wave(gpt_model):
    """(prompts, golden) — golden from a fresh single replica."""
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
    return ServingEngine(model, **d)


def _warm(eng):
    """Warm every prefill bucket the wave (and any failover
    continuation: prompt ≤ 21 + delivered ≤ 10 → bucket 32) can land
    in, then reset the measurement window — placement scores read the
    queue-wait p99, and warmup noise would skew the spread."""
    eng.generate(_prompts((5, 17), seed=7), max_new_tokens=4)
    eng.reset_counters()


def _fleet(model, n=3, router_kw=None, **engine_kw):
    engines = [_engine(model, **engine_kw) for _ in range(n)]
    for e in engines:
        _warm(e)
    frozen = [e.compile_counts() for e in engines]
    reps = [InprocReplica(f"r{i}", e) for i, e in enumerate(engines)]
    router = FleetRouter(reps, **(router_kw or {}))
    return router, reps, engines, frozen


def _counter(reg, name, **labels):
    c = reg.get(name, labels or None)
    return 0 if c is None else int(c.value)


def _assert_frozen(engines, frozen, router):
    for i, eng in enumerate(engines):
        assert eng.compile_counts() == frozen[i], \
            f"replica {i} compiled something mid-wave"
    assert router.compile_report()["unexpected_retraces"] == 0


# -- engine lifecycle (satellites: state field, drain, closed errors) ----


class TestEngineLifecycle:
    def test_state_field_and_closed_errors(self, gpt_model):
        eng = _engine(gpt_model)
        assert eng.state == "serving"
        assert eng.health()["state"] == "serving"
        eng.drain()
        assert eng.state == "draining"
        assert eng.health()["state"] == "draining"
        with pytest.raises(RuntimeError, match="draining"):
            eng.submit(np.ones(4, np.int32), 4)
        eng.resume()
        assert eng.state == "serving"
        eng.close()
        assert eng.state == "closed"
        assert eng.health()["state"] == "closed"
        with pytest.raises(RuntimeError, match="closed"):
            eng.submit(np.ones(4, np.int32), 4)
        with pytest.raises(RuntimeError, match="closed"):
            eng.step()
        with pytest.raises(RuntimeError, match="closed"):
            eng.drain()
        eng.close()  # idempotent

    def test_draining_completes_inflight_token_exact(self, gpt_model,
                                                     wave):
        """A draining replica stops admitting but finishes in-flight
        work token-exactly; queued requests come back CANCELLED."""
        prompts, refs = wave
        eng = _engine(gpt_model, max_slots=1)
        rids = [eng.submit(p, NEW_TOK) for p in prompts[:3]]
        done = eng.step()          # admits rid0 only (1 slot)
        results = list(done) + eng.drain_to_completion()
        by_id = {r["id"]: r for r in results}
        assert by_id[rids[0]]["status"] == "ok"
        assert by_id[rids[0]]["tokens"] == refs[0], \
            "in-flight request must finish token-exactly under drain"
        for rid in rids[1:]:
            assert by_id[rid]["status"] == "cancelled"
            assert by_id[rid]["tokens"] == []
        assert eng.idle
        eng.close()

    def test_close_releases_everything_never_wedges(self, gpt_model):
        eng = _engine(gpt_model, max_slots=1)
        free0 = eng.free_page_count
        for p in _prompts((5, 9, 12)):
            eng.submit(p, NEW_TOK)
        eng.step()                 # one in flight, two queued
        eng.close()                # impatient close: cancel everything
        assert eng.state == "closed"
        assert eng.free_page_count == free0, "pages must be released"
        eng.close()                # idempotent

    def test_export_inflight(self, gpt_model):
        eng = _engine(gpt_model, max_slots=1)
        rids = [eng.submit(p, NEW_TOK) for p in _prompts((5, 9))]
        eng.step()
        ents = {e["rid"]: e for e in eng.export_inflight()}
        assert set(ents) == set(rids)
        running = ents[rids[0]]
        assert not running["queued"] and len(running["tokens"]) >= 1
        queued = ents[rids[1]]
        assert queued["queued"] and queued["tokens"] == []
        assert queued["max_new_tokens"] == NEW_TOK
        eng.close()


# -- retry jitter (satellite) --------------------------------------------


class TestRetryJitter:
    def test_default_schedule_unchanged(self):
        assert backoff_schedule(3, base_delay=0.05, max_delay=2.0) \
            == [0.05, 0.1, 0.2]

    def test_seeded_jitter_deterministic_and_desynchronized(self):
        a1 = backoff_schedule(4, jitter=0.5, jitter_seed=1)
        a2 = backoff_schedule(4, jitter=0.5, jitter_seed=1)
        b = backoff_schedule(4, jitter=0.5, jitter_seed=2)
        assert a1 == a2, "same seed must replay bit-identically"
        assert a1 != b, "different seeds must de-synchronize"
        base = backoff_schedule(4)
        for d, d0 in zip(a1, base):
            assert d0 <= d <= d0 * 1.5, "jitter stretches, never shrinks"

    def test_call_with_retries_sleeps_the_seeded_schedule(
            self, monkeypatch):
        slept = []
        monkeypatch.setattr(time, "sleep", slept.append)
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] <= 2:
                raise TransientError("UNAVAILABLE: injected")
            return "ok"

        assert call_with_retries(flaky, retries=3, base_delay=0.01,
                                 jitter=0.5, jitter_seed=3) == "ok"
        assert slept == backoff_schedule(3, base_delay=0.01, jitter=0.5,
                                         jitter_seed=3)[:2]


# -- fault targeting (fleet fault kinds) ---------------------------------


class TestFaultTargeting:
    def test_payload_pinned_fault_only_fires_for_its_target(self):
        with faults.scenario(("replica_crash", {"replica": "r1"})):
            assert faults.pull("replica_crash", 1,
                               match={"replica": "r0"}) is None
            assert faults.pull("replica_crash", 1,
                               match={"replica": "r1"}) is not None
            assert faults.pull("replica_crash", 2,
                               match={"replica": "r1"}) is None

    def test_unpinned_fault_matches_any_target(self):
        with faults.scenario("replica_slow"):
            assert faults.pull("replica_slow", 1,
                               match={"replica": "anything"}) is not None


# -- chaos suite ---------------------------------------------------------


@pytest.mark.chaos
class TestFleetChaos:
    def test_crash_mid_decode_failover_token_exact(self, gpt_model,
                                                   wave):
        """THE acceptance drill: a clean 3-replica wave is token-exact
        and actually spreads; then a seeded replica_crash mid-decode —
        every request still completes token-exact (recovered prefix
        deduped), compile counts stay frozen, and the crashed replica
        rejoins without a single new trace."""
        prompts, refs = wave
        router, reps, engines, frozen = _fleet(gpt_model)
        try:
            # clean wave first: parity + health-routed spread
            assert router.generate(prompts, max_new_tokens=NEW_TOK) \
                == refs
            routed = [_counter(router.registry, "fleet_routed_total",
                               replica=f"r{i}") for i in range(3)]
            assert sum(routed) == len(prompts)
            assert sum(1 for n in routed if n) >= 2, routed
            _assert_frozen(engines, frozen, router)
            with faults.scenario(("replica_crash", {"replica": "r1"})):
                outs = router.generate(prompts, max_new_tokens=NEW_TOK)
                fired = faults.fired_log()
            assert outs == refs, "failover must be token-exact"
            assert [k for k, _ in fired] == ["replica_crash"]
            assert reps[1].state == "dead"
            failovers = sum(
                _counter(router.registry, "fleet_failovers_total",
                         replica="r1", reason=reason)
                for reason in ("crash", "wedge"))
            assert failovers >= 1, \
                "the crashed replica held work that was failed over"
            _assert_frozen(engines, frozen, router)
            # no request was lost or duplicated across both waves
            assert _counter(router.registry, "fleet_requests_total",
                            status="ok") == 2 * len(prompts)
            # rejoin the corpse: same engine, zero new traces
            router.rejoin("r1")
            assert router.generate(prompts[:3],
                                   max_new_tokens=NEW_TOK) == refs[:3]
            _assert_frozen(engines, frozen, router)
        finally:
            router.close()

    def test_wedge_failover(self, gpt_model, wave):
        """A wedged (silent, not dead) replica is detected by scrape
        staleness, killed, and its work recovered token-exactly."""
        prompts, refs = wave
        router, reps, engines, frozen = _fleet(
            gpt_model, n=2, router_kw={"wedge_timeout_s": 0.2})
        try:
            with faults.scenario(
                    ("replica_wedge", {"replica": "r0",
                                       "seconds": 30.0})):
                outs = router.generate(prompts, max_new_tokens=NEW_TOK)
            assert outs == refs
            assert reps[0].state == "dead"
            assert sum(_counter(router.registry,
                                "fleet_failovers_total",
                                replica="r0", reason=reason)
                       for reason in ("wedge", "crash")) >= 1
            _assert_frozen(engines, frozen, router)
        finally:
            router.close()

    def test_drain_under_load_and_rejoin(self, gpt_model, wave):
        """Drain a busy replica: its in-flight requests finish token-
        exactly, its queued work bounces and re-places, nothing is
        lost; rejoin costs zero recompiles."""
        prompts, refs = wave
        router, reps, engines, frozen = _fleet(
            gpt_model, n=2, max_slots=1,
            router_kw={"replica_queue_limit": 3})
        try:
            # keep r0 slow so it still has a backlog when the drain
            # lands (deterministic bounce)
            with faults.scenario(
                    ("replica_slow", {"replica": "r0", "count": 1000,
                                      "seconds": 0.02})):
                rids = [router.submit(p, NEW_TOK) for p in prompts]
                deadline = time.monotonic() + 30
                while not any(p.replica == "r0" and p.placed_at
                              for p in router._pending.values()):
                    router.step()
                    assert time.monotonic() < deadline
                    time.sleep(0.002)
                router.drain("r0")
                res = {r["id"]: r for r in router.run_to_completion()}
            assert [res[i]["tokens"] for i in rids] == refs, \
                "drain must lose nothing and stay token-exact"
            deadline = time.monotonic() + 10
            while reps[0].alive and time.monotonic() < deadline:
                time.sleep(0.01)
            assert reps[0].state == "drained"
            _assert_frozen(engines, frozen, router)
            router.rejoin("r0")
            assert router.generate(prompts[:2],
                                   max_new_tokens=NEW_TOK) == refs[:2]
            assert reps[0].state == "serving"
            _assert_frozen(engines, frozen, router)
        finally:
            router.close()

    def test_bounced_work_replaces_onto_rejoined_replica(
            self, gpt_model, wave):
        """A drained fleet-of-one: bounced work can only re-place onto
        the SAME replica after rejoin — the new incarnation must not
        drop the rid as a duplicate delivery (the idempotency ledger
        resets across incarnations)."""
        prompts, refs = wave
        router, reps, engines, frozen = _fleet(
            gpt_model, n=1, max_slots=1,
            router_kw={"replica_queue_limit": 3})
        try:
            deadline = time.monotonic() + 60
            with faults.scenario(
                    ("replica_slow", {"replica": "r0", "count": 1000,
                                      "seconds": 0.02})):
                rids = [router.submit(p, NEW_TOK)
                        for p in prompts[:3]]
                while not any(p.placed_at
                              for p in router._pending.values()):
                    router.step()
                    assert time.monotonic() < deadline
                    time.sleep(0.002)
                router.drain("r0")
                while reps[0].alive:
                    router.step()
                    assert time.monotonic() < deadline
                    time.sleep(0.005)
            assert reps[0].state == "drained"
            router.rejoin("r0")
            res = {x["id"]: x for x in router.run_to_completion()}
            assert [res[i]["tokens"] for i in rids] == refs[:3]
            assert all(res[i]["status"] == "ok" for i in rids)
            _assert_frozen(engines, frozen, router)
        finally:
            router.close()

    def test_hedging_cancels_the_loser(self, gpt_model, wave):
        """A slow primary gets hedged; the hedge wins, the loser is
        cancelled, the client sees exactly one token-exact result."""
        prompts, refs = wave
        router, reps, engines, frozen = _fleet(
            gpt_model, n=2,
            router_kw={"hedge_after_ms": 60, "wedge_timeout_s": 30.0})
        try:
            with faults.scenario(
                    ("replica_slow", {"replica": "r0", "count": 1000,
                                      "seconds": 0.05})):
                router.submit(prompts[0], NEW_TOK)
                (result,) = router.run_to_completion()
            assert result["tokens"] == refs[0]
            assert result["hedged"] and result["replica"] == "r1"
            assert _counter(router.registry, "fleet_hedges_total") == 1
            assert _counter(router.registry, "fleet_hedge_wins_total",
                            by="hedge") == 1
            assert _counter(router.registry, "fleet_requests_total",
                            status="ok") == 1
            _assert_frozen(engines, frozen, router)
        finally:
            router.close()

    def test_shed_by_priority_under_saturation(self, gpt_model, wave):
        """Full-fleet saturation: the global queue overflows and the
        LOWEST-priority requests are shed; every high-priority request
        completes."""
        prompts, refs = wave
        router, reps, engines, frozen = _fleet(
            gpt_model, n=1, max_slots=1,
            router_kw={"max_queue": 2, "replica_queue_limit": 2})
        try:
            prios = [0, 5, 0, 5, 0, 5]
            rids = [router.submit(prompts[i], NEW_TOK, priority=pr)
                    for i, pr in enumerate(prios)]
            res = {r["id"]: r for r in router.run_to_completion()}
            shed = [rid for rid in rids
                    if res[rid]["status"] == "shed"]
            ok = [rid for rid in rids if res[rid]["status"] == "ok"]
            assert len(shed) == 2 and len(ok) == 4
            assert all(prios[rid] == 0 for rid in shed), \
                "only priority-0 work may be shed"
            for rid in ok:
                assert res[rid]["tokens"] == refs[rid]
            assert _counter(router.registry,
                            "fleet_shed_total") == len(shed)
            _assert_frozen(engines, frozen, router)
        finally:
            router.close()

    def test_flaky_transport_and_scrape_timeouts(self, gpt_model,
                                                 wave):
        """Transport blips (lost sends AND lost acks) plus scrape
        timeouts: retries + rid idempotency absorb everything, the
        client sees each result exactly once."""
        prompts, refs = wave
        router, reps, engines, frozen = _fleet(gpt_model, n=2)
        try:
            with faults.scenario(
                    ("flaky_transport", {"replica": "r0", "count": 2}),
                    ("flaky_transport", {"replica": "r0", "count": 2,
                                         "after": 1}),
                    ("scrape_timeout", {"replica": "r1", "count": 2})):
                outs = router.generate(prompts, max_new_tokens=NEW_TOK)
            assert outs == refs
            retries = sum(c.stats.retries
                          for c in router._clients.values())
            assert retries >= 3, "the flaky seam must have fired"
            assert _counter(router.registry,
                            "fleet_scrape_errors_total") >= 1
            assert _counter(router.registry, "fleet_requests_total",
                            status="ok") == len(prompts)
            _assert_frozen(engines, frozen, router)
        finally:
            router.close()

    def test_preemption_drains_the_fleet(self, gpt_model, wave):
        """A process-level preemption notice (the resilience seam)
        drains every replica gracefully; after clear + rejoin the
        fleet serves again with zero new traces."""
        from paddle_tpu.resilience import preemption
        prompts, refs = wave
        router, reps, engines, frozen = _fleet(gpt_model, n=2)
        try:
            assert router.generate(prompts[:2],
                                   max_new_tokens=NEW_TOK) == refs[:2]
            preemption.request()
            deadline = time.monotonic() + 10
            while any(rp.alive for rp in reps) \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            assert all(rp.state == "drained" for rp in reps)
            preemption.clear()
            for rp in reps:
                router.rejoin(rp.name)
            assert router.generate(prompts[:2],
                                   max_new_tokens=NEW_TOK) == refs[:2]
            _assert_frozen(engines, frozen, router)
        finally:
            preemption.clear()
            router.close()

    def test_router_metrics_endpoint(self, gpt_model, wave):
        """The router is itself a scrape target: /metrics serves the
        fleet registry, /healthz the fleet health snapshot."""
        import json
        from urllib.request import urlopen
        prompts, refs = wave
        router, reps, engines, frozen = _fleet(gpt_model, n=2)
        exp = router.serve_metrics(port=0)
        try:
            assert router.generate(prompts[:3],
                                   max_new_tokens=NEW_TOK) == refs[:3]
            text = urlopen(f"{exp.url}/metrics",
                           timeout=5).read().decode()
            assert "fleet_routed_total" in text
            assert "fleet_placement_wait_seconds_bucket" in text
            health = json.loads(urlopen(f"{exp.url}/healthz",
                                        timeout=5).read().decode())
            assert set(health["replicas"]) == {"r0", "r1"}
            assert health["replicas"]["r0"]["state"] == "serving"
            assert health["compile_report"]["unexpected_retraces"] == 0
        finally:
            router.close()

    def test_idempotent_submit_dedup(self, gpt_model, wave):
        """Double-delivered submit commands (the ack-lost retry case)
        produce exactly one engine request and one result. The result
        plane is at-least-once: the single result is RE-returned by
        every poll until acked (so a crashed router's successor can
        re-harvest it), then retired for good."""
        prompts, refs = wave
        eng = _engine(gpt_model)
        _warm(eng)
        rep = InprocReplica("r0", eng)
        try:
            rep.enqueue(("submit", 0, list(prompts[0]), NEW_TOK,
                         None, 0))
            rep.enqueue(("submit", 0, list(prompts[0]), NEW_TOK,
                         None, 0))  # duplicate delivery
            deadline = time.monotonic() + 30
            got = []
            while len(got) < 1 and time.monotonic() < deadline:
                got.extend(rep.pop_results())
                time.sleep(0.005)
            time.sleep(0.05)
            got.extend(rep.pop_results())
            # ONE distinct engine result, however many times polled
            assert len({r["_rseq"] for r in got}) == 1, got
            assert {r["id"] for r in got} == {0}
            assert got[0]["tokens"] == refs[0]
            # ack retires it; later polls are empty
            rep.ack([got[0]["_rseq"]])
            rep.ack([got[0]["_rseq"]])  # idempotent
            assert rep.pop_results() == []
        finally:
            rep.kill()
            eng.close()
