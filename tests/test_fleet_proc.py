"""Process-isolated replicas + self-healing supervisor
(paddle_tpu/serving_fleet/proc.py, proc_child.py, supervisor.py).

Pins the round-14 contracts (docs/robustness.md "Process
supervision"):

- wire framing: the pipe protocol shares the journal's length-prefix
  + crc32 discipline; the FUZZ ladder truncates / garbles a frame at
  every byte offset and asserts the reader never crashes, never
  duplicates, never misparses — at most the torn frame is lost;
- supervisor state machine: seeded-backoff respawn scheduling
  (deterministic per (seed, name)), the crash-loop breaker ladder
  (trip → quarantine → cooldown → half-open trial), boot-gate
  timeouts — all drilled against stub replicas with injected clocks,
  so the policy logic is testable in milliseconds;
- ServingEngine.warmup(): pre-traced buckets + decode, counted once,
  zero new traces on the first real wave, token-exact parity with an
  unwarmed engine;
- incarnation stamping: a respawned same-name replica's stale-leg
  results are rejected uniformly; journaled placements carry the
  incarnation and recovery treats a bumped incarnation as a fresh
  engine;
- REAL-process chaos (pytest -m chaos; the slow-marked drills run
  with PADDLE_TPU_RUN_SLOW=1): a ServingEngine subprocess SIGKILLed
  mid-decode fails over token-exactly, the supervisor respawns it
  with a warm boot and health-gates it back into rotation under
  frozen compile counts; a persistent exit-at-boot seed trips the
  breaker instead of respawning forever; SIGTERM drains the child
  token-exactly and releases its metrics port.
"""
import json
import os
import signal
import time
import zlib

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
from paddle_tpu.nlp.serving import ServingEngine
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.resilience.retry import backoff_schedule
from paddle_tpu.serving_fleet import (
    FleetRouter, FleetSupervisor, FrameReader, InprocReplica, Journal,
    ProcReplica)
from paddle_tpu.serving_fleet.journal import _frame

NEW_TOK = 10
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUPPORT = os.path.join(REPO, "tests", "fleet_proc_support.py")


@pytest.fixture(scope="module")
def gpt_model():
    paddle.seed(0)
    m = GPTForCausalLM(_resolve_config("gpt-tiny"))
    m.eval()
    return m


def _prompts(lens, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,)).astype(np.int32) for n in lens]


WAVE_LENS = (5, 12, 17, 9, 21, 14)


@pytest.fixture(scope="module")
def wave(gpt_model):
    """(prompts, golden) — golden from a fresh single replica; the
    subprocess replicas build the SAME seeded model, so token-exact
    means cross-process token-exact."""
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


def _proc_spec(**kw):
    spec = {"builder": {"path": SUPPORT, "fn": "build_engine"},
            "kwargs": {}, "warmup": [5, 17], "sys_path": [REPO],
            "force_cpu": True, "heartbeat_s": 0.02, "poll_s": 0.002}
    spec.update(kw)
    return spec


def _counter(reg, name, **labels):
    c = reg.get(name, labels or None)
    return 0 if c is None else int(c.value)


# -- wire framing fuzz (satellite) ----------------------------------------


class TestFrameReaderFuzz:
    RECS = [{"t": "hb", "replica": "r0", "queued": 0, "ts": 1.5},
            {"t": "result", "res": {"id": 3, "tokens": [1, 2, 3],
                                    "status": "ok"}},
            {"t": "progress", "rid": 4, "tokens": [9]},
            {"t": "submit", "rid": 5, "prompt": [7] * 40,
             "max_new": 8, "eos": None, "priority": 0},
            {"t": "bye", "state": "drained"}]

    def _stream(self):
        return b"".join(_frame(r) for r in self.RECS)

    def test_truncate_at_every_offset_then_resume(self):
        """A frame cut at ANY byte is held (not dropped) and completes
        when the rest arrives — no loss, no duplicate, no misparse."""
        stream = self._stream()
        for cut in range(len(stream) + 1):
            fr = FrameReader()
            got = fr.feed(stream[:cut]) + fr.feed(stream[cut:])
            assert got == self.RECS, cut
            assert fr.dropped == 0, cut

    def test_kill_mid_write_drops_only_the_torn_frame(self):
        """Feed ONLY a truncated prefix (the SIGKILL-mid-write shape):
        every fully-delivered frame parses, the torn one never
        surfaces as a record, nothing raises."""
        stream = self._stream()
        bounds = []
        off = 0
        for r in self.RECS:
            off += len(_frame(r))
            bounds.append(off)
        for cut in range(len(stream) + 1):
            fr = FrameReader()
            got = fr.feed(stream[:cut])
            n_complete = sum(1 for b in bounds if b <= cut)
            assert got == self.RECS[:n_complete], cut
            assert fr.dropped == 0, cut   # torn tail HELD, not dropped

    def test_garbage_between_frames_resyncs(self):
        """Newline-terminated garbage (a stray library print, a
        corrupted line) is dropped and counted; every real frame
        still parses exactly once."""
        frames = [_frame(r) for r in self.RECS]
        for i in range(len(frames) + 1):
            noise = b"Traceback (most recent call last):\n"
            stream = b"".join(frames[:i]) + noise + b"".join(frames[i:])
            fr = FrameReader()
            got = fr.feed(stream)
            assert got == self.RECS, i
            assert fr.dropped == 1, i

    def test_corrupted_frame_byte_never_misparses(self):
        """Flip one byte inside a frame's payload: the crc rejects the
        line (dropped), every other frame survives."""
        frames = [_frame(r) for r in self.RECS]
        victim = bytearray(frames[2])
        victim[25] ^= 0xFF
        stream = b"".join(frames[:2]) + bytes(victim) \
            + b"".join(frames[3:])
        fr = FrameReader()
        got = fr.feed(stream)
        assert got == self.RECS[:2] + self.RECS[3:]
        assert fr.dropped == 1

    def test_byte_at_a_time_feed(self):
        stream = self._stream()
        fr = FrameReader()
        got = []
        for i in range(len(stream)):
            got.extend(fr.feed(stream[i:i + 1]))
        assert got == self.RECS and fr.dropped == 0


# -- supervisor policy units (stub replicas, injected clock) --------------


class StubReplica:
    """Lifecycle-only replica stand-in: the supervisor's state machine
    is pure policy, testable without engines or processes."""

    def __init__(self, name, fail_incs=(), slow_incs=()):
        self.name = name
        self.incarnation = 1
        self.alive = True
        self.state = "serving"
        self.fail_incs = set(fail_incs)   # incarnations that exit at boot
        self.slow_incs = set(slow_incs)   # incarnations that never hb
        self.rejoins = 0
        self.kills = 0
        self.ops = []

    def die(self):
        self.alive = False
        self.state = "dead"

    def rejoin(self):
        self.rejoins += 1
        self.incarnation += 1
        if self.incarnation in self.fail_incs:
            self.alive = False
            self.state = "dead"
            return
        self.alive = True
        self.state = "booting" if self.incarnation in self.slow_incs \
            else "serving"

    def kill(self, *a, **k):
        self.kills += 1
        self.alive = False
        self.state = "dead"

    def drain(self):
        self.state = "drained"
        self.alive = False

    def scrape(self):
        if self.alive and self.state == "serving":
            return {"replica": self.name, "state": "serving",
                    "warmed": True, "incarnation": self.incarnation,
                    "ts": time.monotonic(), "queued": 0, "running": 0,
                    "free_pages": 8, "queue_wait_p99_s": 0.0}
        return {}

    def enqueue(self, op):
        self.ops.append(tuple(op))

    def pop_results(self):
        return []

    def ack(self, seqs):
        pass

    def export_inflight(self):
        return []

    def compile_counts(self):
        return {}

    def unexpected_retraces(self):
        return 0


class StubRouter:
    def __init__(self, reps):
        self.replicas = {r.name: r for r in reps}
        self.registry = MetricsRegistry()
        self.reinstated = []

    def reinstate(self, name):
        self.reinstated.append(name)

    def step(self):
        return []


class TestSupervisorBreaker:
    def _sup(self, reps, **kw):
        router = StubRouter(reps)
        d = dict(seed=3, breaker_threshold=3, breaker_window_s=60.0,
                 breaker_cooldown_s=100.0, boot_timeout_s=5.0)
        d.update(kw)
        return FleetSupervisor(router, **d), router

    def test_respawn_follows_the_seeded_backoff(self):
        rep = StubReplica("r0")
        sup, router = self._sup([rep])
        t = 1000.0
        rep.die()
        ev = sup.poll(now=t)
        assert ("r0", "down") in ev and ("r0", "respawn_scheduled") in ev
        d1 = sup.backoff_delays("r0", 1)[0]
        # not due yet: nothing happens
        assert sup.poll(now=t + d1 * 0.5) == []
        assert rep.rejoins == 0
        ev = sup.poll(now=t + d1 + 1e-9)
        assert ev == [("r0", "boot_started")] and rep.rejoins == 1
        # healthy heartbeat gates it back in
        ev = sup.poll(now=t + d1 + 0.01)
        assert ev == [("r0", "respawned")]
        assert router.reinstated == ["r0"]
        assert _counter(sup.registry, "fleet_respawns_total",
                        replica="r0") == 1
        assert sup.health()["replicas"]["r0"]["phase"] == "serving"

    def test_crash_loop_trips_quarantines_and_rearms(self):
        rep = StubReplica("rbad", fail_incs=set(range(2, 50)))
        sup, router = self._sup([rep])
        t = 2000.0
        rep.die()
        sup.poll(now=t)                       # down 1 -> backoff
        trips = 0
        for k in range(1, 10):
            if sup.health()["replicas"]["rbad"]["phase"] \
                    == "quarantined":
                break
            delay = sup.backoff_delays("rbad", k)[k - 1]
            t += delay + 1e-6
            sup.poll(now=t)                   # boot attempt (exits)
            ev = sup.poll(now=t)              # exit-at-boot detected
            trips += 1
        h = sup.health()
        assert h["replicas"]["rbad"]["phase"] == "quarantined"
        assert h["quarantined"] == ["rbad"]
        # threshold 3: the initial crash + 2 failed boots
        assert rep.rejoins == 2
        assert _counter(sup.registry, "fleet_crash_loops_total",
                        replica="rbad") == 1
        assert _counter(sup.registry, "fleet_boot_failures_total",
                        replica="rbad", reason="exit_at_boot") == 2
        assert rep.quarantined is True
        assert sup.registry.get("fleet_replicas_quarantined").value == 1
        # quarantine holds: no respawn attempts during the cooldown
        sup.poll(now=t + 50.0)
        assert rep.rejoins == 2
        # cooldown over: half-open trial; a failure re-trips IMMEDIATELY
        ev = sup.poll(now=t + 101.0)
        assert ("rbad", "rearmed") in ev
        sup.poll(now=t + 101.1)               # trial boot (exits)
        ev = sup.poll(now=t + 101.2)
        assert ("rbad", "quarantined") in ev
        assert rep.rejoins == 3
        assert _counter(sup.registry, "fleet_crash_loops_total",
                        replica="rbad") == 2
        # a healthy half-open trial re-arms for good
        rep.fail_incs.clear()
        ev = sup.poll(now=t + 203.0)
        assert ("rbad", "rearmed") in ev
        sup.poll(now=t + 203.1)               # trial boot (healthy)
        ev = sup.poll(now=t + 203.2)
        assert ("rbad", "respawned") in ev
        assert sup.health()["replicas"]["rbad"]["phase"] == "serving"
        assert rep.quarantined is False

    def test_slow_boot_past_the_gate_is_killed_and_counted(self):
        rep = StubReplica("r0", slow_incs={2})
        sup, router = self._sup([rep], boot_timeout_s=5.0)
        t = 3000.0
        rep.die()
        sup.poll(now=t)
        d1 = sup.backoff_delays("r0", 1)[0]
        sup.poll(now=t + d1 + 1e-6)           # boot inc 2 (never hb)
        assert rep.rejoins == 1
        assert sup.poll(now=t + d1 + 4.0) == []   # still inside gate
        ev = sup.poll(now=t + d1 + 5.1)       # past the gate: killed
        assert ("r0", "down") in ev and rep.kills == 1
        assert _counter(sup.registry, "fleet_boot_failures_total",
                        replica="r0", reason="boot_timeout") == 1
        # next attempt (inc 3) boots clean
        d2 = sup.backoff_delays("r0", 2)[1]
        sup.poll(now=t + d1 + 5.1 + d2 + 1e-6)
        ev = sup.poll(now=t + d1 + 5.1 + d2 + 0.01)
        assert ("r0", "respawned") in ev
        boot_h = sup.registry.get("fleet_boot_seconds")
        assert boot_h is not None and boot_h.count >= 1

    def test_drained_replicas_are_left_alone(self):
        rep = StubReplica("r0")
        sup, router = self._sup([rep])
        rep.drain()
        assert sup.poll(now=500.0) == []
        assert rep.rejoins == 0


class TestBackoffDeterminism:
    def test_schedule_is_a_pure_function_of_seed_and_name(self):
        r = StubRouter([StubReplica("r0"), StubReplica("r1")])
        a = FleetSupervisor(r, seed=11)
        b = FleetSupervisor(r, seed=11)
        c = FleetSupervisor(r, seed=12)
        assert a.backoff_delays("r0", 5) == b.backoff_delays("r0", 5), \
            "same (seed, name) must replay bit-identically"
        assert a.backoff_delays("r0", 5) != a.backoff_delays("r1", 5), \
            "different replicas must de-synchronize"
        assert a.backoff_delays("r0", 5) != c.backoff_delays("r0", 5)

    def test_schedule_is_the_documented_retry_ladder(self):
        r = StubRouter([StubReplica("r0")])
        sup = FleetSupervisor(r, seed=7, backoff_base_s=0.1,
                              backoff_max_s=1.0, backoff_jitter=0.5)
        seed = zlib.crc32(b"7:r0") & 0xFFFFFFFF
        assert sup.backoff_delays("r0", 4) == backoff_schedule(
            4, base_delay=0.1, max_delay=1.0, jitter=0.5,
            jitter_seed=seed)
        base = backoff_schedule(4, base_delay=0.1, max_delay=1.0)
        for d, d0 in zip(sup.backoff_delays("r0", 4), base):
            assert d0 <= d <= d0 * 1.5


# -- warmup (satellite) ---------------------------------------------------


class TestWarmup:
    def test_warmed_engine_serves_first_wave_with_zero_new_traces(
            self, gpt_model, wave):
        prompts, refs = wave
        eng = _engine(gpt_model)
        assert not eng.warmed
        warmed = eng.warmup(buckets=(5, 17))
        assert warmed == [16, 32]
        assert eng.warmed and eng.health()["warmed"]
        assert eng.health()["warmed_buckets"] == [16, 32]
        frozen = eng.compile_counts()
        assert frozen == {"prefill_16": 1, "prefill_32": 1,
                          "tail_prefill_16": 1,
                          "tail_prefill_32": 1, "decode": 1}
        # the first REAL wave: token-exact parity with the unwarmed
        # golden AND zero new traces (the TTFT cliff is gone — no
        # compile inside any request's latency)
        assert eng.generate(prompts, max_new_tokens=NEW_TOK) == refs
        assert eng.compile_counts() == frozen, \
            "a warmed engine must not trace on its first wave"
        assert eng.tracer.unexpected_retraces() == 0
        # idempotent: re-warming is free
        assert eng.warmup(buckets=(16, 32)) == []
        assert eng.compile_counts() == frozen
        eng.close()

    def test_warmup_requires_idle_and_open(self, gpt_model):
        eng = _engine(gpt_model)
        eng.submit(np.ones(4, np.int32), 4)
        with pytest.raises(RuntimeError, match="idle"):
            eng.warmup(buckets=(8,))
        eng.run_to_completion()
        eng.close()
        with pytest.raises(RuntimeError, match="closed"):
            eng.warmup(buckets=(8,))


# -- incarnation stamping (satellite) -------------------------------------


class TestIncarnationGuard:
    def test_handle_rejects_stale_incarnation_uniformly(self):
        router = FleetRouter([StubReplica("r0")])
        for status in ("ok", "cancelled", "bounced", "expired"):
            rid = router.submit([1, 2, 3], 4)
            p = router._pending[rid]
            p.replica = "r0"
            p.leg_base["r0"] = 0
            p.leg_inc["r0"] = 2
            router._handle({"id": rid, "tokens": [9], "status": status,
                            "replica": "r0", "incarnation": 1})
            assert not p.done and p.delivered == [], \
                f"stale-incarnation {status} must be dropped"
            router._handle({"id": rid, "tokens": [9, 8],
                            "status": "ok", "replica": "r0",
                            "incarnation": 2})
            assert p.done and router._done[rid]["tokens"] == [9, 8]
            router.results()

    def test_unstamped_results_keep_working(self):
        """Back-compat: a transport that predates the contract (no
        incarnation field) still resolves."""
        router = FleetRouter([StubReplica("r0")])
        rid = router.submit([1, 2], 4)
        p = router._pending[rid]
        p.replica = "r0"
        p.leg_inc["r0"] = 3
        router._handle({"id": rid, "tokens": [5], "status": "ok",
                        "replica": "r0"})
        assert p.done

    def test_inproc_results_stamped_with_accept_incarnation(
            self, gpt_model, wave):
        prompts, refs = wave
        eng = _engine(gpt_model)
        rep = InprocReplica("r0", eng)
        try:
            assert rep.incarnation == 1
            rep.enqueue(("submit", 0, list(prompts[0]), NEW_TOK,
                         None, 0))
            deadline = time.monotonic() + 60
            got = []
            while not got and time.monotonic() < deadline:
                got = rep.pop_results()
                time.sleep(0.005)
            assert got and got[0]["incarnation"] == 1
            rep.ack([r["_rseq"] for r in got])
            rep.kill()
            rep.rejoin()
            assert rep.incarnation == 2
            rep.enqueue(("submit", 1, list(prompts[1]), NEW_TOK,
                         None, 0))
            got = []
            deadline = time.monotonic() + 60
            while not got and time.monotonic() < deadline:
                got = rep.pop_results()
                time.sleep(0.005)
            assert got and got[0]["incarnation"] == 2
            assert got[0]["tokens"] == refs[1]
        finally:
            rep.kill()
            eng.close()

    def test_journal_placed_carries_incarnation(self, tmp_path):
        from paddle_tpu.serving_fleet.journal import reconcile, replay
        j = Journal(os.path.join(tmp_path, "wal"))
        j.append("accepted", rid=0, prompt=[1, 2], max_new=4, eos=None,
                 priority=0, deadline_epoch=None, submitted_epoch=None)
        j.append("placed", rid=0, replica="r0", prefix=0, incarnation=3)
        st = reconcile(replay(j.dir)[0])
        assert st["requests"][0]["placed_incarnation"] == 3
        j.append("failover", rid=0, replica="r0", reason="crash",
                 incarnation=3)
        st = reconcile(replay(j.dir)[0])
        assert st["requests"][0]["placed_incarnation"] is None
        j.close()

    def test_recovery_treats_newer_incarnation_as_fresh_engine(
            self, tmp_path):
        """A rid journaled onto r0@inc1: if r0 has respawned (inc 2)
        by recovery time, the old leg is GONE — the successor must
        re-queue the rid, not trust 'still running there'; with the
        incarnation unchanged, the idempotent continuation-resubmit
        goes back to r0."""
        def build_journal(d):
            j = Journal(d)
            j.append("accepted", rid=0, prompt=[1, 2], max_new=4,
                     eos=None, priority=0, deadline_epoch=None,
                     submitted_epoch=None)
            j.append("placed", rid=0, replica="r0", prefix=0,
                     incarnation=1)
            j.close()

        # same incarnation: continuation-resubmitted to r0
        d1 = os.path.join(tmp_path, "same")
        build_journal(d1)
        rep = StubReplica("r0")
        router = FleetRouter.recover(d1, [rep])
        assert [op[0] for op in rep.ops] == ["submit"]
        assert rep.ops[0][1] == 0
        assert router._pending[0].replica == "r0"
        router.close()

        # bumped incarnation: fresh engine — re-queued, nothing sent
        d2 = os.path.join(tmp_path, "bumped")
        build_journal(d2)
        rep2 = StubReplica("r0")
        rep2.incarnation = 2
        router2 = FleetRouter.recover(d2, [rep2])
        assert rep2.ops == [], \
            "a respawned replica must not be treated as still running"
        assert 0 in router2._queue
        router2.close()


# -- router dynamic membership --------------------------------------------


class TestRouterMembership:
    def test_adopt_and_remove(self):
        r0, r1 = StubReplica("r0"), StubReplica("r1")
        router = FleetRouter([r0])
        router.adopt_replica(r1)
        assert set(router.replicas) == {"r0", "r1"}
        with pytest.raises(ValueError, match="already"):
            router.adopt_replica(StubReplica("r1"))
        with pytest.raises(RuntimeError, match="drain"):
            router.remove_replica("r1")
        r1.drain()
        router.remove_replica("r1")
        assert set(router.replicas) == {"r0"}
        with pytest.raises(KeyError):
            router.reinstate("r1")
        router.close()

    def test_reinstate_clears_lost_without_respawning(self):
        rep = StubReplica("r0")
        router = FleetRouter([rep])
        router._lost.add("r0")
        router._last_scrape["r0"] = {"ts": 0.0}
        router.reinstate("r0")
        assert "r0" not in router._lost
        assert "r0" not in router._last_scrape
        assert rep.rejoins == 0, \
            "reinstate must not respawn (the supervisor already did)"
        router.close()


# -- real-subprocess chaos drills ----------------------------------------


def _wait_for(cond, timeout=180.0, step=None, msg="condition"):
    deadline = time.monotonic() + timeout
    while not cond():
        if step is not None:
            step()
        assert time.monotonic() < deadline, f"timed out: {msg}"
        time.sleep(0.01)


def _poll_one(rep, timeout=120.0):
    """Poll the replica's result plane until something lands."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = rep.pop_results()
        if got:
            return got
        time.sleep(0.01)
    raise AssertionError("no result within the deadline")


@pytest.mark.chaos
class TestProcReplicaSmoke:
    def test_boot_serve_sigkill_respawn_token_exact(self, wave,
                                                    tmp_path):
        """Tier-1's one real subprocess drill: boot → warm hello →
        token-exact serve → SIGKILL → death detected → respawn →
        token-exact serve under the fresh incarnation's frozen
        counts."""
        prompts, refs = wave
        rep = ProcReplica("p0", _proc_spec(),
                          flight_dir=str(tmp_path))
        try:
            _wait_for(lambda: rep.state == "serving", 180, msg="boot")
            snap = rep.scrape()
            assert snap["warmed"] and snap["incarnation"] == 1
            frozen = rep.compile_counts()
            assert frozen == {"prefill_16": 1, "prefill_32": 1,
                              "tail_prefill_16": 1,
                              "tail_prefill_32": 1,
                              "decode": 1}, \
                "warm boot must pre-trace exactly the spec'd programs"
            rep.enqueue(("submit", 0, list(prompts[0]), NEW_TOK,
                         None, 0))
            got = _poll_one(rep)
            assert got[0]["tokens"] == refs[0]
            assert got[0]["status"] == "ok"
            assert got[0]["incarnation"] == 1
            rep.ack([r["_rseq"] for r in got])
            # the compile counts FROZE through the wave (the
            # zero-recompile contract, heartbeat-scraped; decode
            # produces max_new - 1 tokens — prefill emits the first)
            _wait_for(lambda: rep.scrape().get("decode_tokens", 0)
                      >= NEW_TOK - 1, 60, msg="hb")
            assert rep.compile_counts() == frozen
            assert rep.unexpected_retraces() == 0
            # the real thing: SIGKILL, no seam
            os.kill(rep.pid, signal.SIGKILL)
            _wait_for(lambda: not rep.alive and rep.state == "dead",
                      60, msg="death detection")
            assert rep.error == "killed" or "exit" in rep.error
            rep.respawn()
            assert rep.incarnation == 2
            _wait_for(lambda: rep.state == "serving", 180,
                      msg="respawn boot")
            assert rep.scrape()["incarnation"] == 2
            rep.enqueue(("submit", 1, list(prompts[1]), NEW_TOK,
                         None, 0))
            got2 = _poll_one(rep)
            assert got2[0]["tokens"] == refs[1]
            assert got2[0]["incarnation"] == 2
            # fresh incarnation, fresh-but-frozen compile budget
            assert rep.compile_counts() == frozen
        finally:
            rep.kill()


@pytest.mark.chaos
@pytest.mark.slow
class TestProcFleetChaos:
    """THE acceptance drills — real processes, real signals. Slow
    (several subprocess boots each): PADDLE_TPU_RUN_SLOW=1 runs
    them."""

    def _fleet(self, tmp_path, n=2, sup_kw=None, **rep_kw):
        reps = [ProcReplica(f"p{i}", _proc_spec(),
                            flight_dir=str(tmp_path), **rep_kw)
                for i in range(n)]
        router = FleetRouter(reps, wedge_timeout_s=60.0)
        d = dict(seed=7, boot_timeout_s=180.0, breaker_threshold=3,
                 breaker_window_s=60.0, breaker_cooldown_s=600.0,
                 backoff_base_s=0.05, backoff_max_s=0.5)
        d.update(sup_kw or {})
        sup = FleetSupervisor(router, **d)
        return router, sup, reps

    def test_sigkill_mid_decode_failover_respawn_warm_rejoin(
            self, wave, tmp_path):
        from paddle_tpu.observability import flightrec
        prompts, refs = wave
        router, sup, reps = self._fleet(tmp_path)
        victim = reps[1]
        try:
            _wait_for(lambda: all(r.state == "serving" for r in reps),
                      300, msg="fleet boot")
            # wave 1: clean, token-exact, spread across both
            assert router.generate(prompts, max_new_tokens=NEW_TOK) \
                == refs
            routed0 = [_counter(router.registry, "fleet_routed_total",
                                replica=f"p{i}") for i in range(2)]
            assert sum(routed0) == len(prompts)
            assert all(n > 0 for n in routed0), routed0
            # wave 2: SIGKILL p1 once its decode is provably moving
            # (the parent mirror streams partial tokens)
            rids = [router.submit(p, NEW_TOK) for p in prompts]
            _wait_for(lambda: any(e["tokens"] for e in
                                  victim.export_inflight()),
                      120, step=lambda: (router.step(), sup.poll()),
                      msg="victim mid-decode")
            dumps0 = len(flightrec.get_recorder().dumps)
            os.kill(victim.pid, signal.SIGKILL)
            res = {}

            def drain():
                router.step()
                sup.poll()
                for r in router.results():
                    res[r["id"]] = r
                return len(res) == len(rids)

            _wait_for(lambda: drain(), 300, msg="wave 2 completion")
            assert [res[i]["tokens"] for i in rids] == refs, \
                "failover must be token-exact vs the uninterrupted " \
                "golden"
            assert all(res[i]["status"] == "ok" for i in rids)
            assert len(res) == len(rids), "exactly-once by rid"
            assert sum(_counter(router.registry,
                                "fleet_failovers_total",
                                replica="p1", reason=r)
                       for r in ("crash", "wedge")) >= 1
            # the failover left a flight dump
            new_dumps = flightrec.get_recorder().dumps[dumps0:]
            assert any("fleet_failover" in p for p in new_dumps)
            # supervisor: respawn + warm boot + health-gated rejoin
            sup.watch(lambda: victim.state == "serving"
                      and victim.incarnation == 2
                      and sup.health()["replicas"]["p1"]["phase"]
                      == "serving", timeout_s=300)
            assert _counter(router.registry, "fleet_respawns_total",
                            replica="p1") == 1
            snap = victim.scrape()
            assert snap["warmed"] and snap["incarnation"] == 2
            frozen = victim.compile_counts()
            assert frozen == {"prefill_16": 1, "prefill_32": 1,
                              "tail_prefill_16": 1,
                              "tail_prefill_32": 1, "decode": 1}
            # wave 3: the respawned replica takes real traffic with
            # ZERO steady-state recompiles after its warm boot
            rids3 = [router.submit(p, NEW_TOK) for p in prompts]
            res3 = {}

            def drain3():
                router.step()
                sup.poll()
                for r in router.results():
                    res3[r["id"]] = r
                return len(res3) == len(rids3)

            _wait_for(lambda: drain3(), 300, msg="wave 3 completion")
            assert [res3[i]["tokens"] for i in rids3] == refs
            assert _counter(router.registry, "fleet_routed_total",
                            replica="p1") > routed0[1], \
                "the rejoined replica must actually take traffic"
            _wait_for(lambda: victim.scrape().get("round", 0) > 0, 60,
                      msg="fresh hb")
            assert victim.compile_counts() == frozen, \
                "zero steady-state recompiles after warm-boot"
            assert victim.unexpected_retraces() == 0
            assert router.compile_report()["unexpected_retraces"] == 0
        finally:
            router.close()

    def test_persistent_boot_failure_trips_the_breaker(self, wave,
                                                       tmp_path):
        """Exit-at-boot for every respawn (incarnations 2+): the
        breaker must quarantine instead of respawning forever, fleet
        health must degrade honestly, and the healthy replica keeps
        serving."""
        from paddle_tpu.observability import flightrec
        prompts, refs = wave
        reps = [ProcReplica("p0", _proc_spec(),
                            flight_dir=str(tmp_path)),
                ProcReplica("pbad", _proc_spec(),
                            flight_dir=str(tmp_path),
                            child_faults="replica_exit_at_boot@2x99")]
        router = FleetRouter(reps, wedge_timeout_s=60.0)
        sup = FleetSupervisor(router, seed=7, boot_timeout_s=60.0,
                              breaker_threshold=3,
                              breaker_window_s=120.0,
                              breaker_cooldown_s=600.0,
                              backoff_base_s=0.05, backoff_max_s=0.2)
        try:
            _wait_for(lambda: all(r.state == "serving" for r in reps),
                      300, msg="fleet boot")
            dumps0 = len(flightrec.get_recorder().dumps)
            os.kill(reps[1].pid, signal.SIGKILL)
            sup.watch(lambda: sup.health()["replicas"]["pbad"]["phase"]
                      == "quarantined", timeout_s=300)
            assert _counter(router.registry, "fleet_crash_loops_total",
                            replica="pbad") == 1
            assert _counter(
                sup.registry, "fleet_boot_failures_total",
                replica="pbad", reason="exit_at_boot") >= 2
            assert sup.registry.get(
                "fleet_replicas_quarantined").value == 1
            # honest degradation: quarantine is visible fleet-wide
            assert router.health()["replicas"]["pbad"]["quarantined"]
            assert sup.health()["quarantined"] == ["pbad"]
            new_dumps = flightrec.get_recorder().dumps[dumps0:]
            assert any("fleet_crash_loop" in p for p in new_dumps), \
                "the breaker trip must leave a postmortem"
            # no more respawns while quarantined
            inc = reps[1].incarnation
            for _ in range(20):
                router.step()
                sup.poll()
                time.sleep(0.01)
            assert reps[1].incarnation == inc
            # the healthy half of the fleet still serves, token-exact
            res = {}
            rids = [router.submit(p, NEW_TOK) for p in prompts[:3]]

            def drain():
                router.step()
                sup.poll()
                for r in router.results():
                    res[r["id"]] = r
                return len(res) == len(rids)

            _wait_for(lambda: drain(), 300, msg="degraded wave")
            assert [res[i]["tokens"] for i in rids] == refs[:3]
        finally:
            router.close()

    def test_sigterm_drains_child_token_exact_and_releases_port(
            self, wave, tmp_path):
        """Subprocess hygiene: SIGTERM → the child finishes in-flight
        work token-exactly, emits everything, exits 0 with state
        'drained', and releases its /metrics port; per-incarnation
        artifact dirs keep the carcass's post-mortem safe from the
        next incarnation."""
        from urllib.request import urlopen
        prompts, refs = wave
        # slow_step (an ENGINE seam, armed inside the child) stretches
        # each decode dispatch so the SIGTERM provably lands mid-decode
        rep = ProcReplica(
            "p0", _proc_spec(metrics_port=0, heartbeat_s=0.01),
            flight_dir=str(tmp_path),
            child_faults="slow_step@1x1000:seconds=0.1")
        try:
            _wait_for(lambda: rep.state == "serving", 300, msg="boot")
            _wait_for(lambda: rep.scrape().get("metrics_port"), 60,
                      msg="exporter port on the heartbeat")
            port = rep.scrape()["metrics_port"]
            health = json.loads(urlopen(
                f"http://127.0.0.1:{port}/healthz",
                timeout=5).read().decode())
            assert health["state"] == "serving" and health["warmed"]
            rep.enqueue(("submit", 0, list(prompts[4]), NEW_TOK,
                         None, 0))
            _wait_for(lambda: any(e["tokens"] for e in
                                  rep.export_inflight()), 120,
                      msg="mid-decode")
            os.kill(rep.pid, signal.SIGTERM)
            _wait_for(lambda: rep.state == "drained", 120,
                      msg="drain")
            assert rep._proc.returncode == 0, "a drain is a CLEAN exit"
            got = rep.pop_results()
            assert [r["id"] for r in got] == [0]
            assert got[0]["tokens"] == refs[4], \
                "in-flight work must finish token-exactly under " \
                "SIGTERM"
            # port released on exit
            with pytest.raises(Exception):
                urlopen(f"http://127.0.0.1:{port}/healthz", timeout=2)
            # per-incarnation artifact dir + stderr log exist
            assert os.path.isdir(os.path.join(tmp_path, "p0-inc001"))
            assert os.path.exists(os.path.join(
                tmp_path, "p0-inc001.stderr.log"))
            # a respawn writes NEW per-incarnation paths — the carcass
            # post-mortem is never clobbered
            rep.respawn()
            _wait_for(lambda: rep.state == "serving", 300,
                      msg="respawn")
            assert os.path.isdir(os.path.join(tmp_path, "p0-inc002"))
        finally:
            rep.kill()

    def test_slow_boot_past_the_gate_is_killed_then_recovers(
            self, wave, tmp_path):
        """replica_slow_boot makes incarnation 2 hang pre-import past
        the boot gate: the supervisor kills it, counts a boot_timeout
        failure, and the NEXT attempt (fault exhausted) boots clean
        and rejoins."""
        prompts, refs = wave
        # the injected hang (300s) must dwarf the gate, and the gate
        # (40s) must still tolerate a REAL warm boot on a loaded box
        reps = [ProcReplica("p0", _proc_spec(),
                            flight_dir=str(tmp_path),
                            child_faults="replica_slow_boot@2:"
                                         "seconds=300")]
        router = FleetRouter(reps, wedge_timeout_s=60.0)
        sup = FleetSupervisor(router, seed=5, boot_timeout_s=40.0,
                              breaker_threshold=4,
                              breaker_window_s=300.0,
                              backoff_base_s=0.05, backoff_max_s=0.2)
        try:
            _wait_for(lambda: reps[0].state == "serving", 300,
                      msg="boot")
            os.kill(reps[0].pid, signal.SIGKILL)
            sup.watch(lambda: _counter(
                sup.registry, "fleet_boot_failures_total",
                replica="p0", reason="boot_timeout") >= 1,
                timeout_s=120)
            sup.watch(lambda: _counter(
                router.registry, "fleet_respawns_total",
                replica="p0") == 1, timeout_s=300)
            assert reps[0].state == "serving"
            assert reps[0].incarnation == 3
            # and the recovered fleet serves token-exact
            res = {}
            rids = [router.submit(p, NEW_TOK) for p in prompts[:2]]

            def drain():
                router.step()
                sup.poll()
                for r in router.results():
                    res[r["id"]] = r
                return len(res) == len(rids)

            _wait_for(lambda: drain(), 300, msg="post-recovery wave")
            assert [res[i]["tokens"] for i in rids] == refs[:2]
        finally:
            router.close()
