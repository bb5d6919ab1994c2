"""Durable fleet state: router crash recovery drills.

Pins the round-13 contracts (docs/robustness.md "Router durability &
recovery"): a FleetRouter journaling to a write-ahead log can die at
ANY control round — crash seam (``router_crash``), SIGTERM preemption,
torn journal write, transient disk errors — and a successor built by
``FleetRouter.recover(journal_dir, replicas)``:

- re-adopts the still-live replicas (scrape + retained result plane +
  carcass export_inflight) with ZERO new compiles on their engines;
- continuation-resubmits every unresolved request with the journaled
  delivered prefix deduped — the combined pre-crash + post-recovery
  output is TOKEN-EXACT vs an uninterrupted single-router golden;
- delivers every result EXACTLY ONCE across the crash (no rid
  resolved twice, restored unpopped results re-delivered once,
  retired rids never resurrected).

`pytest -m chaos` selects the chaos classes.
"""
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
from paddle_tpu.nlp.serving import ServingEngine
from paddle_tpu.resilience import faults, preemption
from paddle_tpu.serving_fleet import (
    FleetRouter, InprocReplica, JournalError, RouterCrash)
from paddle_tpu.serving_fleet.journal import JournalCrash, reconcile, \
    replay

NEW_TOK = 10
WAVE_LENS = (5, 12, 17, 9, 21, 14)


@pytest.fixture(autouse=True)
def _clean_faults_and_preemption():
    """The crash drills arm global faults outside scenario() blocks
    (the router must die OUTSIDE a with-body to mimic a process
    crash) — never leak them, or a preemption flag, into the next
    test."""
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


def _prompts(lens, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,)).astype(np.int32) for n in lens]


@pytest.fixture(scope="module")
def wave(gpt_model):
    """(prompts, golden) — golden from an uninterrupted single
    replica, the token-exactness reference for every drill."""
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
    eng.generate(_prompts((5, 17), seed=7), max_new_tokens=4)
    eng.reset_counters()


def _fleet(model, tmp_path, n=3, router_kw=None, replica_kw=None,
           **engine_kw):
    engines = [_engine(model, **engine_kw) for _ in range(n)]
    for e in engines:
        _warm(e)
    frozen = [e.compile_counts() for e in engines]
    reps = [InprocReplica(f"r{i}", e, **(replica_kw or {}))
            for i, e in enumerate(engines)]
    jdir = os.path.join(tmp_path, "journal")
    router = FleetRouter(reps, journal_dir=jdir, **(router_kw or {}))
    return router, reps, engines, frozen, jdir


def _drive_until(router, cond, timeout=60.0, results=None):
    """Step the router until cond() or a crash propagates."""
    deadline = time.monotonic() + timeout
    while not cond():
        router.step()
        if results is not None:
            results.extend(router.results())
        assert time.monotonic() < deadline, "drill made no progress"
        time.sleep(0.002)


def _crash(router, results):
    """Arm the crash seam and step until the router dies mid-round,
    exactly like a process crash: NO close(), the replicas keep
    running under a dead control plane."""
    faults.inject("router_crash")
    with pytest.raises(RouterCrash):
        deadline = time.monotonic() + 30
        while True:
            router.step()
            results.extend(router.results())
            assert time.monotonic() < deadline
    assert not faults.armed("router_crash")


def _assert_exactly_once_token_exact(rids, refs, pre, post,
                                     statuses=("ok",)):
    got = pre + post
    ids = [r["id"] for r in got]
    assert len(ids) == len(set(ids)), \
        f"a rid was delivered twice across the crash: {sorted(ids)}"
    assert sorted(ids) == sorted(rids), \
        f"requests lost across the crash: {sorted(set(rids) - set(ids))}"
    by_id = {r["id"]: r for r in got}
    for i, rid in enumerate(rids):
        assert by_id[rid]["status"] in statuses, by_id[rid]
        assert by_id[rid]["tokens"] == refs[i], \
            f"rid {rid} not token-exact across the crash"


def _assert_frozen(engines, frozen, router):
    for i, eng in enumerate(engines):
        assert eng.compile_counts() == frozen[i], \
            f"replica {i} compiled something across the recovery"
    assert router.compile_report()["unexpected_retraces"] == 0


def _ok_total(*routers):
    total = 0
    for r in routers:
        c = r.registry.get("fleet_requests_total", {"status": "ok"})
        total += 0 if c is None else int(c.value)
    return total


# -- journal-at-the-router units (no crash needed) -----------------------


class TestRouterJournalUnits:
    def test_submit_rejected_when_admission_append_fails(
            self, gpt_model, wave, tmp_path):
        """Write-ahead admission: a submit whose `accepted` record
        cannot be made durable raises — the caller KNOWS the request
        was never accepted, and the fleet state stays consistent."""
        prompts, refs = wave
        router, reps, engines, frozen, jdir = _fleet(
            gpt_model, tmp_path, n=1)
        try:
            with faults.scenario(("journal_io_error", {"step": 2})):
                rid0 = router.submit(prompts[0], NEW_TOK)
                with pytest.raises(JournalError):
                    router.submit(prompts[1], NEW_TOK)
                rid2 = router.submit(prompts[2], NEW_TOK)
            res = {r["id"]: r for r in router.run_to_completion()}
            assert sorted(res) == [rid0, rid2]
            assert res[rid0]["tokens"] == refs[0]
            assert res[rid2]["tokens"] == refs[2]
            # the rejected rid was journaled nowhere and never ran
            st = reconcile(replay(jdir)[0])
            assert 1 not in st["requests"] and 1 not in st["retired"]
        finally:
            router.close()

    def test_results_withheld_until_retirement_is_durable(
            self, gpt_model, wave, tmp_path):
        """A transient disk failure on the `retired` append WITHHOLDS
        the pop (returns []) instead of handing over results whose
        retirement is not durable — handing them over un-retired
        would re-deliver them after a crash."""
        prompts, refs = wave
        router, reps, engines, frozen, jdir = _fleet(
            gpt_model, tmp_path, n=1)
        try:
            rids = [router.submit(p, NEW_TOK) for p in prompts[:2]]
            deadline = time.monotonic() + 60
            while any(not p.done for p in router._pending.values()):
                router.step()
                assert time.monotonic() < deadline
                time.sleep(0.002)
            faults.inject("journal_io_error")   # next append fails
            assert router.results() == [], \
                "un-retired results must be withheld"
            faults.clear()
            res = {r["id"]: r for r in router.results()}
            assert sorted(res) == rids
            assert [res[i]["tokens"] for i in rids] == refs[:2]
            assert router.results() == []
            st = reconcile(replay(jdir)[0])
            assert st["retired"] == set(rids)
        finally:
            router.close()

    def test_lifecycle_is_journaled_and_retired(self, gpt_model, wave,
                                                tmp_path):
        prompts, refs = wave
        router, reps, engines, frozen, jdir = _fleet(
            gpt_model, tmp_path, n=2)
        try:
            rids = [router.submit(p, NEW_TOK) for p in prompts[:3]]
            router.run_to_completion()
            st = reconcile(replay(jdir)[0])
            assert st["retired"] == set(rids)
            assert st["requests"] == {}
            assert st["next_rid"] == max(rids) + 1
            reg = router.registry
            assert reg.get("fleet_journal_appends_total").value > 0
            assert reg.get("fleet_journal_fsyncs_total").value > 0
            assert reg.get("fleet_journal_bytes_total").value > 0
        finally:
            router.close()


# -- chaos drills --------------------------------------------------------


@pytest.mark.chaos
class TestRouterRecoveryChaos:
    def test_router_crash_recovery_token_exact_exactly_once(
            self, gpt_model, wave, tmp_path, monkeypatch):
        """THE acceptance drill: kill the router mid-wave with results
        already delivered, some resolved-but-unpopped, some mid-decode
        on live replicas, some still queued. The successor re-adopts
        the SAME replicas and the combined output is token-exact and
        exactly-once, with frozen compile counts and a parseable
        fleet_router_recovery flight dump."""
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        prompts, refs = wave
        router, reps, engines, frozen, jdir = _fleet(
            gpt_model, tmp_path)
        faults.clear()
        pre = []
        rids = [router.submit(p, NEW_TOK) for p in prompts[:4]]
        # progress until ≥2 results reached the client, then accept
        # two MORE requests the dead router can never place — the
        # crash provably lands mid-wave: delivered + in-flight +
        # journaled-but-never-placed, all at once
        _drive_until(router, lambda: len(pre) >= 2, results=pre)
        rids += [router.submit(p, NEW_TOK) for p in prompts[4:]]
        _crash(router, pre)
        assert any(not p.done for p in router._pending.values()), \
            "drill must crash with work still in flight"
        r2 = FleetRouter.recover(jdir, reps)
        try:
            post = r2.run_to_completion(timeout_s=90)
            _assert_exactly_once_token_exact(rids, refs, pre, post)
            _assert_frozen(engines, frozen, r2)
            # no resolution was double-counted fleet-wide either
            assert _ok_total(router, r2) == len(prompts)
            reg = r2.registry
            assert reg.get(
                "fleet_journal_replay_records_total").value > 0
            assert reg.get(
                "fleet_journal_recovered_requests_total").value > 0
            dumps = [f for f in os.listdir(tmp_path)
                     if f.startswith("flight_fleet_router_recovery")]
            assert dumps, "recovery must leave a flight record"
            doc = json.load(open(os.path.join(tmp_path, dumps[0])))
            assert doc["reason"] == "fleet_router_recovery"
            assert doc["replay"]["replay_records"] > 0
            assert doc["reinstated"], "dump must name the survivors"
        finally:
            r2.close()

    def test_sigterm_preemption_seals_journal_and_recovers(
            self, gpt_model, wave, tmp_path):
        """Process-level SIGTERM: the replicas drain through the
        preemption seam (round-11 behavior) and the router now ALSO
        seals the journal — so the next incarnation recovers the
        bounced backlog instead of inheriting a torn tail. In-flight
        work finishes token-exactly on the draining replicas; queued
        work bounces, is journaled with its delivered watermark, and
        completes after recovery."""
        prompts, refs = wave
        router, reps, engines, frozen, jdir = _fleet(
            gpt_model, tmp_path, n=2, max_slots=1,
            router_kw={"replica_queue_limit": 3})
        pre = []
        try:
            rids = [router.submit(p, NEW_TOK) for p in prompts]
            _drive_until(
                router,
                lambda: any(p.placed_at
                            for p in router._pending.values()),
                results=pre)
            preemption.request()
            # grace window: replicas drain; router seals + keeps
            # collecting until every worker parked
            _drive_until(
                router,
                lambda: all(not rp.alive for rp in reps),
                results=pre, timeout=90)
            assert router._journal.sealed, \
                "preemption must seal the journal, not just drain"
            for _ in range(3):          # settle the last bounces
                router.step()
                pre.extend(router.results())
            assert replay(jdir)[1]["sealed"]
            assert all(rp.state == "drained" for rp in reps)
        finally:
            preemption.clear()
        # successor: rejoin the parked replicas, finish the backlog
        r2 = FleetRouter.recover(jdir, reps)
        try:
            post = r2.run_to_completion(timeout_s=90)
            _assert_exactly_once_token_exact(rids, refs, pre, post)
            _assert_frozen(engines, frozen, r2)
            assert all(rp.state == "serving" for rp in reps)
        finally:
            r2.close()

    def test_torn_write_crash_recovery(self, gpt_model, wave,
                                       tmp_path):
        """journal_torn_write mid-wave: the append tears and the
        router dies AT that write (JournalCrash). Replay drops
        exactly the torn record; the successor reconciles the rest
        against the live replicas — still token-exact, still
        exactly-once."""
        prompts, refs = wave
        router, reps, engines, frozen, jdir = _fleet(
            gpt_model, tmp_path)
        pre = []
        rids = [router.submit(p, NEW_TOK) for p in prompts]
        # appends 1-6 are the admissions; tear a mid-wave lifecycle
        # record (placed/delivered/resolved — whichever lands 10th)
        faults.clear()
        faults.inject("journal_torn_write", step=10)
        with pytest.raises(JournalCrash):
            deadline = time.monotonic() + 60
            while True:
                router.step()
                pre.extend(router.results())
                assert time.monotonic() < deadline
                time.sleep(0.002)
        faults.clear()
        stats = replay(jdir)[1]
        assert stats["torn_tail_drops"] == 1
        r2 = FleetRouter.recover(jdir, reps)
        try:
            post = r2.run_to_completion(timeout_s=90)
            _assert_exactly_once_token_exact(rids, refs, pre, post)
            _assert_frozen(engines, frozen, r2)
            assert _ok_total(router, r2) == len(prompts)
            assert r2.registry.get(
                "fleet_journal_torn_tail_drops_total").value == 1
        finally:
            r2.close()

    def test_io_error_faults_then_crash_recovery(self, gpt_model,
                                                 wave, tmp_path):
        """Transient disk errors on lifecycle appends: the live
        router parks them in the retry backlog (results stay unacked
        at their replicas until durable) and keeps serving; a crash
        on top still recovers token-exact and exactly-once."""
        prompts, refs = wave
        router, reps, engines, frozen, jdir = _fleet(
            gpt_model, tmp_path)
        pre = []
        rids = [router.submit(p, NEW_TOK) for p in prompts[:4]]
        # admissions are appends 1-4; the storm window [6, 8) lands on
        # placement/lifecycle records — the live router must absorb
        # both failures (retry backlog) and keep serving
        faults.clear()
        faults.inject("journal_io_error", step=6, count=2)
        _drive_until(router, lambda: len(pre) >= 2, results=pre)
        assert router.registry.get(
            "fleet_journal_errors_total").value == 2
        rids += [router.submit(p, NEW_TOK) for p in prompts[4:]]
        _crash(router, pre)
        r2 = FleetRouter.recover(jdir, reps)
        try:
            post = r2.run_to_completion(timeout_s=90)
            _assert_exactly_once_token_exact(rids, refs, pre, post)
            _assert_frozen(engines, frozen, r2)
            assert _ok_total(router, r2) == len(prompts)
        finally:
            r2.close()

    def test_drain_backlog_race_with_router_kill(self, gpt_model,
                                                 wave, tmp_path):
        """Satellite: drain_to_completion under a pinned replica_slow
        seam racing a router kill. r0 is slow and draining with a
        backlog; the router dies mid-drain. Recovery must NOT
        double-place the drained backlog — every rid resolves exactly
        once, token-exact."""
        prompts, refs = wave
        router, reps, engines, frozen, jdir = _fleet(
            gpt_model, tmp_path, n=2, max_slots=1,
            router_kw={"replica_queue_limit": 3})
        pre = []
        with faults.scenario(
                ("replica_slow", {"replica": "r0", "count": 1000,
                                  "seconds": 0.02})):
            rids = [router.submit(p, NEW_TOK) for p in prompts]
            _drive_until(
                router,
                lambda: any(p.replica == "r0" and p.placed_at
                            for p in router._pending.values()),
                results=pre)
            router.drain("r0")
            # let the drain begin bouncing/finishing, then kill the
            # router in the middle of the re-placement churn
            _drive_until(
                router,
                lambda: (not reps[0].alive
                         or router.registry.get(
                             "fleet_requeued_total").value > 0),
                results=pre, timeout=90)
            _crash(router, pre)
        r2 = FleetRouter.recover(jdir, reps)
        try:
            post = r2.run_to_completion(timeout_s=120)
            _assert_exactly_once_token_exact(rids, refs, pre, post)
            _assert_frozen(engines, frozen, r2)
            assert _ok_total(router, r2) == len(prompts)
        finally:
            r2.close()

    def test_cancel_intent_survives_router_crash(self, gpt_model,
                                                 wave, tmp_path):
        """A client cancel journaled before the crash is honored by
        the successor: the request resolves cancelled with its
        partial tokens instead of being resurrected into a full
        decode the client never wanted."""
        prompts, refs = wave
        router, reps, engines, frozen, jdir = _fleet(
            gpt_model, tmp_path, n=2)
        pre = []
        rids = [router.submit(p, NEW_TOK) for p in prompts[:3]]
        _drive_until(
            router,
            lambda: any(p.placed_at and not p.done
                        for p in router._pending.values()),
            results=pre)
        victim = next(rid for rid in rids
                      if router._pending[rid].placed_at
                      and not router._pending[rid].done)
        # keep the victim's replica slow so the cancel provably races
        # ahead of completion, then cancel and crash immediately
        faults.inject("replica_slow",
                      replica=router._pending[victim].replica,
                      count=1000, seconds=0.02)
        router.cancel(victim)
        _crash(router, pre)
        faults.clear()
        r2 = FleetRouter.recover(jdir, reps)
        try:
            post = r2.run_to_completion(timeout_s=90)
            allres = {r["id"]: r for r in pre + post}
            assert sorted(allres) == sorted(rids)
            assert len(pre) + len(post) == len(rids)
            assert allres[victim]["status"] == "cancelled", \
                "recovery must honor the journaled cancel"
            got = allres[victim]["tokens"]
            assert got == refs[rids.index(victim)][:len(got)], \
                "cancelled partials must still be a golden prefix"
            for rid in rids:
                if rid != victim:
                    assert allres[rid]["status"] == "ok"
                    assert allres[rid]["tokens"] \
                        == refs[rids.index(rid)]
            _assert_frozen(engines, frozen, r2)
        finally:
            r2.close()

    def test_recovery_restores_unpopped_results_exactly_once(
            self, gpt_model, wave, tmp_path):
        """Results resolved before the crash but never popped are
        journaled: the successor re-delivers them ONCE, and rids the
        dead router already handed out (journaled `retired`) are
        never resurrected."""
        prompts, refs = wave
        router, reps, engines, frozen, jdir = _fleet(
            gpt_model, tmp_path, n=2)
        rids = [router.submit(p, NEW_TOK) for p in prompts[:4]]
        # resolve everything, pop HALF (journals their retirement)
        deadline = time.monotonic() + 60
        while any(not p.done for p in router._pending.values()):
            router.step()
            assert time.monotonic() < deadline
            time.sleep(0.002)
        popped = router.results()     # all four delivered + retired
        assert sorted(r["id"] for r in popped) == rids
        # submit two more; resolve them; crash BEFORE popping
        rids2 = [router.submit(p, NEW_TOK) for p in prompts[4:6]]
        deadline = time.monotonic() + 60
        while any(not p.done for p in router._pending.values()):
            router.step()
            assert time.monotonic() < deadline
            time.sleep(0.002)
        pre = []
        _crash(router, pre)
        assert not pre, "nothing was popped after the second wave"
        r2 = FleetRouter.recover(jdir, reps)
        try:
            post = r2.run_to_completion(timeout_s=60)
            # exactly the unpopped wave comes back — once
            assert sorted(r["id"] for r in post) == rids2
            by_id = {r["id"]: r for r in post}
            for i, rid in enumerate(rids2):
                assert by_id[rid]["tokens"] == refs[4 + i]
            # popping again yields nothing (retired stays retired)
            assert r2.results() == []
            _assert_frozen(engines, frozen, r2)
        finally:
            r2.close()
