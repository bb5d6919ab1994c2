"""Resilience chaos suite (ISSUE 3): every failure-handling behavior
in the stack, drilled deterministically via paddle_tpu.resilience.

- fault registry semantics (pinning, counts, env grammar, scenarios)
- TrainGuard: NaN-storm skip + rollback with loss continuity vs an
  uninjected run with those steps skipped (acceptance criterion),
  GradScaler composition, transient-dispatch retry
- preemption: SIGTERM at a step boundary -> finalized checkpoint ->
  loss-exact resume
- CheckpointManager crash-safe finalize: torn writes and corrupt dirs
  are skipped, never crashed on
- ServingEngine degradation: deadlines, cancel, reject/evict admission
  policies, injected page exhaustion, watchdog wedge detection —
  with compile_counts() frozen after warmup (zero-recompile survives
  chaos)

Runs as part of tier-1 and standalone: pytest -m chaos (seeded, CPU).
"""
import os
import signal
import time

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.amp import GradScaler
from paddle_tpu.hapi.engine import Engine
from paddle_tpu.io.checkpoint import CheckpointManager
from paddle_tpu.resilience import (TrainGuard, Watchdog, faults,
                                   preemption)
from paddle_tpu.resilience.retry import (RetryStats, TransientError,
                                         call_with_retries, is_transient)

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _clean_slate():
    faults.clear()
    preemption.clear()
    yield
    faults.clear()
    preemption.clear()
    preemption.uninstall()


# -- fault registry -------------------------------------------------------

class TestFaultRegistry:
    def test_pull_consumes_and_pins(self):
        faults.inject("nan_grads", step=5)
        assert faults.pull("nan_grads", 4) is None
        assert faults.pull("nan_grads", 5) == {}
        assert faults.pull("nan_grads", 5) is None, "count=1 exhausted"

    def test_unpinned_fires_count_times(self):
        faults.inject("slow_step", count=2, seconds=0.0)
        assert faults.pull("slow_step", 1) is not None
        assert faults.pull("slow_step", 9) is not None
        assert faults.pull("slow_step", 10) is None
        assert faults.fired_log() == [("slow_step", 1), ("slow_step", 9)]

    def test_env_grammar(self, monkeypatch):
        monkeypatch.setenv(
            "PADDLE_TPU_FAULTS",
            "nan_grads@10x3, sigterm@25, slow_step@5:seconds=0.5,"
            "page_exhaustion")   # bare kind CONTAINING 'x': no suffix
        faults.clear()
        faults.load_env(force=True)
        # @10x3 is a STORM: consecutive steps 10-12, as a train loop
        # consults them — not 3 firings at one step
        assert faults.pull("nan_grads", 10) == {}
        assert faults.pull("nan_grads", 11) == {}
        assert faults.pull("nan_grads", 12) == {}
        assert faults.pull("nan_grads", 13) is None
        assert faults.pull("sigterm", 25) == {}
        assert faults.pull("slow_step", 5) == {"seconds": 0.5}
        assert faults.pull("sigterm", 25) is None
        assert faults.pull("page_exhaustion", 1) == {}

    def test_scenario_restores_registry(self):
        outer = faults.inject("nan_grads", step=99)
        with faults.scenario(("dispatch_error", {"count": 1})):
            assert faults.armed("dispatch_error")
            assert not faults.armed("nan_grads")
        assert not faults.armed("dispatch_error")
        assert faults.armed("nan_grads") and outer.fired == 0

    def test_nan_scale_seam(self):
        assert faults.nan_scale(1) == 1.0
        faults.inject("nan_grads", step=2)
        assert np.isnan(faults.nan_scale(2))


# -- retry ----------------------------------------------------------------

class TestRetry:
    def test_transient_grammar(self):
        assert is_transient(TransientError("boom"))
        assert is_transient(RuntimeError("RESOURCE_EXHAUSTED: oom"))
        assert is_transient(RuntimeError("backend UNAVAILABLE"))
        assert not is_transient(RuntimeError("shape mismatch"))
        assert not is_transient(ValueError("RESOURCE_EXHAUSTED"))

    def test_retries_then_succeeds(self):
        calls = []
        stats = RetryStats()

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise TransientError("RESOURCE_EXHAUSTED: injected")
            return "ok"

        assert call_with_retries(flaky, retries=3, base_delay=0.001,
                                 stats=stats) == "ok"
        assert len(calls) == 3 and stats.retries == 2

    def test_gives_up_and_reraises(self):
        stats = RetryStats()
        with pytest.raises(TransientError):
            call_with_retries(
                lambda: (_ for _ in ()).throw(TransientError("x")),
                retries=1, base_delay=0.001, stats=stats)
        assert stats.gave_up == 1

    def test_non_transient_propagates_immediately(self):
        calls = []

        def bad():
            calls.append(1)
            raise ValueError("nope")

        with pytest.raises(ValueError):
            call_with_retries(bad, retries=5, base_delay=0.001)
        assert len(calls) == 1


# -- train guard ----------------------------------------------------------

def _make_engine(guard=None, seed=0):
    paddle.seed(seed)
    net = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.Tanh(),
                               paddle.nn.Linear(16, 4))
    opt = paddle.optimizer.AdamW(1e-2, parameters=net.parameters())
    return Engine(net, loss=paddle.nn.CrossEntropyLoss(), optimizer=opt,
                  guard=guard)


def _batches(n, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal((8, 8)).astype("float32"),
             rng.integers(0, 4, (8,)).astype("int64")) for _ in range(n)]


class TestTrainGuard:
    BAD = (5, 6, 7)  # 1-indexed steps hit by the injected NaN storm

    def test_nan_storm_skip_rollback_loss_continuity(self):
        """Acceptance criterion: under a 3-consecutive-bad-step NaN
        storm at step K the guard skips/rolls back and the surviving
        loss curve matches an uninjected run that never saw those
        batches (same params, moments, bias-correction count)."""
        batches = _batches(12)
        golden_eng = _make_engine()
        golden = [float(np.asarray(golden_eng.train_batch([x], [y])[0]))
                  for i, (x, y) in enumerate(batches)
                  if i + 1 not in self.BAD]

        guard = TrainGuard(snapshot_every=1, rollback_after=3)
        eng = _make_engine(guard)
        # the storm form: one fault covering steps 5-7
        faults.inject("nan_grads", step=self.BAD[0], count=len(self.BAD))
        observed = [float(np.asarray(eng.train_batch([x], [y])[0]))
                    for (x, y) in batches]
        bad_losses = [observed[s - 1] for s in self.BAD]
        good_losses = [l for i, l in enumerate(observed)
                       if i + 1 not in self.BAD]
        assert all(np.isnan(v) for v in bad_losses), \
            "the injected steps must OBSERVE the NaN loss"
        np.testing.assert_allclose(good_losses, golden, rtol=1e-5,
                                   atol=1e-7)
        assert guard.skipped_steps == 3
        assert guard.rollbacks == 1, \
            "3 consecutive bad steps == rollback_after must roll back"
        assert guard.good_steps == 9

    def test_rollback_restores_update_counter(self):
        guard = TrainGuard(snapshot_every=1, rollback_after=1)
        eng = _make_engine(guard)
        (x, y), = _batches(1)
        eng.train_batch([x], [y])
        opt_step_before = eng._opt_step
        faults.inject("nan_grads", step=2)
        eng.train_batch([x], [y])
        assert eng._opt_step == opt_step_before, \
            "a skipped step must not advance Adam's bias correction"
        assert guard.rollbacks == 1

    def test_dispatch_error_retried(self):
        guard = TrainGuard(snapshot_every=10, retries=2,
                           retry_base_delay=0.001)
        eng = _make_engine(guard)
        (x, y), = _batches(1)
        faults.inject("dispatch_error", count=2)
        loss, _ = eng.train_batch([x], [y])
        assert np.isfinite(float(np.asarray(loss)))
        assert guard.retry_stats.retries == 2
        assert not faults.armed("dispatch_error")

    def test_retry_budget_exhausted_raises(self):
        guard = TrainGuard(retries=1, retry_base_delay=0.001)
        eng = _make_engine(guard)
        (x, y), = _batches(1)
        faults.inject("dispatch_error", count=5)
        with pytest.raises(TransientError):
            eng.train_batch([x], [y])
        assert guard.retry_stats.gave_up == 1

    def test_scaler_composition(self):
        """GradScaler rides the guarded step: found-inf drops the
        dynamic scale in-step and the host counters track it."""
        scaler = GradScaler(init_loss_scaling=1024.0,
                            incr_every_n_steps=10_000)
        guard = TrainGuard(snapshot_every=5, rollback_after=5,
                           scaler=scaler)
        eng = _make_engine(guard)
        faults.inject("nan_grads", step=2)
        for x, y in _batches(4, seed=3):
            eng.train_batch([x], [y])
        assert scaler.found_inf_count == 1
        assert scaler.skip_count == 1
        assert float(np.asarray(eng._scaler_state["scale"])) == 512.0

    def test_rollback_restores_lr_schedule(self):
        """A rollback that rewinds opt_step must rewind the LR
        scheduler with it — and the resulting loss curve must still
        match the skip-equivalent golden run UNDER A SCHEDULE (the
        review finding: constant-LR tests could not see this)."""
        def build(guard=None):
            paddle.seed(0)
            net = paddle.nn.Linear(8, 4)
            model = paddle.Model(net)
            sched = paddle.optimizer.lr.StepDecay(0.05, step_size=2,
                                                  gamma=0.5)
            model.prepare(
                paddle.optimizer.AdamW(sched,
                                       parameters=net.parameters()),
                paddle.nn.CrossEntropyLoss(), guard=guard)
            return model, sched

        rng = np.random.default_rng(7)
        X = rng.standard_normal((48, 8)).astype("float32")
        Y = rng.integers(0, 4, (48,)).astype("int64")
        bad = (3, 4, 5)   # 1-indexed steps of the storm
        keep = [i for i in range(12) if i + 1 not in bad]
        Xg = np.concatenate([X[i * 4:(i + 1) * 4] for i in keep])
        Yg = np.concatenate([Y[i * 4:(i + 1) * 4] for i in keep])

        golden_model, golden_sched = build()
        gl = []

        class G(paddle.callbacks.Callback):
            def on_train_batch_end(self, s, logs=None):
                gl.append(float(logs["loss"][0]))

        golden_model.fit(paddle.io.TensorDataset([Xg, Yg]), epochs=1,
                         batch_size=4, verbose=0, shuffle=False,
                         callbacks=[G()])

        guard = TrainGuard(snapshot_every=1, rollback_after=3)
        model, sched = build(guard)
        il = []

        class R(paddle.callbacks.Callback):
            def on_train_batch_end(self, s, logs=None):
                il.append(float(logs["loss"][0]))

        faults.inject("nan_grads", step=bad[0], count=len(bad))
        model.fit(paddle.io.TensorDataset([X, Y]), epochs=1,
                  batch_size=4, verbose=0, shuffle=False,
                  callbacks=[R()])
        assert guard.rollbacks == 1
        survived = [l for i, l in enumerate(il) if i + 1 not in bad]
        np.testing.assert_allclose(survived, gl, rtol=1e-5, atol=1e-7)
        # schedule position tracks APPLIED updates on both runs
        assert float(sched()) == float(golden_sched())

    def test_guard_refuses_accumulation_paths(self):
        eng = _make_engine(TrainGuard())
        (x, y), = _batches(1)
        with pytest.raises(ValueError, match="TrainGuard"):
            eng.train_batch_accum([x], [y], apply_update=True)
        with pytest.raises(ValueError, match="TrainGuard"):
            eng.train_batch_multi([x[None]], [y[None]])

    def test_guard_swap_resets_scaler_state(self):
        """A new guard's scaler must start from ITS init scale, not
        inherit the previous scaler's decayed in-step state."""
        s1 = GradScaler(init_loss_scaling=1024.0)
        eng = _make_engine(TrainGuard(scaler=s1, snapshot_every=10))
        (x, y), = _batches(1)
        faults.inject("nan_grads", step=1)
        eng.train_batch([x], [y])            # found-inf: 1024 -> 512
        assert float(np.asarray(eng._scaler_state["scale"])) == 512.0
        s2 = GradScaler(init_loss_scaling=256.0)
        eng.guard = TrainGuard(scaler=s2, snapshot_every=10)
        eng.train_batch([x], [y])
        assert float(np.asarray(eng._scaler_state["scale"])) == 256.0

    def test_detach_via_assignment(self):
        """engine.guard = None (the error messages' advice) must drop
        the guarded executable, not feed it plain-signature args."""
        eng = _make_engine(TrainGuard(snapshot_every=10))
        (x, y), = _batches(1)
        eng.train_batch([x], [y])          # compiles the guarded step
        eng.guard = None
        loss, _ = eng.train_batch([x], [y])  # plain step, fresh build
        assert np.isfinite(float(np.asarray(loss)))
        eng.guard = TrainGuard()             # and back
        loss, _ = eng.train_batch([x], [y])
        assert np.isfinite(float(np.asarray(loss)))

    def test_eager_unscale_then_step_divides_once(self):
        """Explicit unscale_() -> step() (the standard AMP pattern for
        gradient clipping between the two) must divide by the loss
        scale exactly ONCE — step() used to re-unscale."""
        paddle.seed(0)
        net = paddle.nn.Linear(4, 4)
        opt = paddle.optimizer.SGD(learning_rate=1.0,
                                   parameters=net.parameters())
        scaler = GradScaler(init_loss_scaling=1024.0)
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        loss = scaler.scale(net(x).sum())
        loss.backward()
        w0 = np.array(net.weight.numpy())
        scaler.unscale_(opt)
        g = np.array(net.weight._grad_value)   # unscaled exactly once
        scaler.step(opt)
        w1 = np.array(net.weight.numpy())
        np.testing.assert_allclose(w0 - w1, g, rtol=1e-5,
                                   err_msg="step() re-unscaled grads")
        assert scaler.skip_count == 0

    def test_fit_logs_guard_scalars(self):
        """hapi fit() surfaces skip/found-inf counters in batch logs
        (the satellite mirroring criterion.last_mlm_overflow)."""
        paddle.seed(0)
        net = paddle.nn.Linear(8, 4)
        model = paddle.Model(net)
        scaler = GradScaler(init_loss_scaling=256.0)
        model.prepare(
            paddle.optimizer.AdamW(1e-2, parameters=net.parameters()),
            paddle.nn.CrossEntropyLoss(),
            guard=TrainGuard(snapshot_every=2, rollback_after=4,
                             scaler=scaler))
        rng = np.random.default_rng(0)
        X = rng.standard_normal((16, 8)).astype("float32")
        Y = rng.integers(0, 4, (16,)).astype("int64")
        ds = paddle.io.TensorDataset([X, Y])
        seen = {}

        class Rec(paddle.callbacks.Callback):
            def on_train_batch_end(self, step, logs=None):
                seen.update(logs or {})

        faults.inject("nan_grads", step=2)
        model.fit(ds, epochs=1, batch_size=4, verbose=0, shuffle=False,
                  callbacks=[Rec()])
        assert seen["skipped"] == 1
        assert seen["found_inf"] == 1
        assert seen["rollbacks"] == 0


# -- preemption -----------------------------------------------------------

def _fit_run(ckdir, total_steps, seed=0, resume=False, sigterm_at=None,
             losses=None):
    """One fit 'process': deterministic per-step batches; optionally a
    sigterm fault armed at an engine step; optionally resumes from the
    manager first. Returns (model, manager, callback)."""
    paddle.seed(seed)
    net = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.Tanh(),
                               paddle.nn.Linear(16, 4))
    model = paddle.Model(net)
    sched = paddle.optimizer.lr.StepDecay(0.05, step_size=3, gamma=0.5)
    model.prepare(paddle.optimizer.AdamW(sched,
                                         parameters=net.parameters()),
                  paddle.nn.CrossEntropyLoss())
    mgr = CheckpointManager(ckdir, keep_max=3)
    start = 0
    if resume:
        restored = preemption.restore_training_state(model, mgr)
        assert restored is not None, "nothing to resume from"
        start = restored

    rng = np.random.default_rng(42)
    all_b = [(rng.standard_normal((8, 8)).astype("float32"),
              rng.integers(0, 4, (8,)).astype("int64"))
             for _ in range(total_steps)]
    X = np.stack([b[0] for b in all_b[start:]]).reshape(-1, 8)
    Y = np.stack([b[1] for b in all_b[start:]]).reshape(-1)
    ds = paddle.io.TensorDataset([X, Y])

    class Rec(paddle.callbacks.Callback):
        def on_train_batch_end(self, step, logs=None):
            l = logs["loss"]
            losses[start + step + 1] = float(
                l[0] if isinstance(l, (list, tuple)) else l)

    cb = paddle.callbacks.PreemptionCheckpoint(mgr)
    if sigterm_at is not None:
        faults.inject("sigterm", step=sigterm_at)
    model.fit(ds, epochs=1, batch_size=8, verbose=0, shuffle=False,
              callbacks=[Rec(), cb])
    return model, mgr, cb


class TestPreemption:
    def test_flag_mechanics(self):
        assert not preemption.requested()
        preemption.request()
        assert preemption.requested()
        preemption.clear()
        assert not preemption.requested()

    def test_sigterm_checkpoint_and_exact_resume(self, tmp_path):
        """Acceptance criterion: a SIGTERM-injected run checkpoints at
        the step boundary (finalized) and resumes loss-exact."""
        TOTAL, KILL = 10, 6
        golden = {}
        _fit_run(str(tmp_path / "gold"), TOTAL, losses=golden)
        assert len(golden) == TOTAL

        victim = {}
        _, mgr, cb = _fit_run(str(tmp_path / "ck"), TOTAL,
                              sigterm_at=KILL, losses=victim)
        assert cb.preempted and cb.saved_step == KILL
        assert max(victim) == KILL, "fit must stop at the boundary"
        assert mgr.is_finalized(KILL), "preemption save must finalize"
        # pre-kill curve identical to golden
        for s in range(1, KILL + 1):
            np.testing.assert_allclose(victim[s], golden[s], rtol=1e-6)

        # note: NO manual preemption.clear() — restore_training_state
        # resets the sticky flag itself (the documented resume recipe
        # must work in-process too)
        resumed = {}
        _fit_run(str(tmp_path / "ck"), TOTAL, resume=True,
                 losses=resumed)
        assert min(resumed) == KILL + 1 and max(resumed) == TOTAL
        for s in sorted(resumed):
            np.testing.assert_allclose(
                resumed[s], golden[s], rtol=1e-6, atol=1e-8,
                err_msg=f"resume diverged at step {s}")

    def test_real_signal_sets_flag(self):
        preemption.install()
        signal.raise_signal(signal.SIGTERM)
        assert preemption.requested()

    def test_sigint_does_not_raise_keyboardinterrupt(self):
        """Python's default SIGINT handler must NOT be chained — a
        KeyboardInterrupt mid-step is the unclean unwind this module
        replaces with a boundary checkpoint."""
        preemption.install()
        signal.raise_signal(signal.SIGINT)   # would raise if chained
        assert preemption.requested()


# -- checkpoint finalize --------------------------------------------------

class TestCheckpointFinalize:
    def _st(self, v):
        return {"w": jnp.full((4,), float(v)), "step": int(v)}

    def test_torn_write_skipped(self, tmp_path):
        mgr = CheckpointManager(tmp_path / "ck", keep_max=5)
        mgr.save(1, self._st(1))
        mgr.save(2, self._st(2))
        faults.inject("torn_ckpt", step=3)
        mgr.save(3, self._st(3))
        assert not mgr.is_finalized(3) and mgr.is_finalized(2)
        assert mgr.latest_step() == 2
        assert mgr.restore()["step"] == 2
        assert mgr.finalized_steps() == [1, 2]

    def test_corrupt_finalized_falls_back(self, tmp_path):
        mgr = CheckpointManager(tmp_path / "ck", keep_max=5)
        mgr.save(1, self._st(1))
        mgr.save(2, self._st(2))
        with open(os.path.join(mgr._step_dir(2), "state.pdparams"),
                  "wb") as f:
            f.write(b"not a checkpoint")
        with pytest.warns(UserWarning, match="unreadable"):
            st = mgr.restore()
        assert st["step"] == 1

    def test_explicit_step_still_raises(self, tmp_path):
        mgr = CheckpointManager(tmp_path / "ck")
        mgr.save(1, self._st(1))
        with open(os.path.join(mgr._step_dir(1), "state.pdparams"),
                  "wb") as f:
            f.write(b"junk")
        with pytest.raises(Exception):
            mgr.restore(step=1)

    def test_best_requires_finalized(self, tmp_path):
        mgr = CheckpointManager(tmp_path / "ck", keep_max=5)
        mgr.save(1, self._st(1), metric=0.5)
        faults.inject("torn_ckpt", step=2)
        mgr.save(2, self._st(2), metric=0.9)   # torn best candidate
        assert mgr.best_step() is None or mgr.is_finalized(
            mgr.best_step())
        mgr.save(3, self._st(3), metric=0.7)
        assert mgr.restore(best=False)["step"] == 3

    def test_empty_dir_returns_none(self, tmp_path):
        mgr = CheckpointManager(tmp_path / "ck")
        assert mgr.restore() is None and mgr.latest_step() is None

    def test_legacy_premarker_checkpoints_still_restore(self, tmp_path):
        """Dirs written by the pre-marker CheckpointManager (no
        COMPLETE file, format-1 index) were finalized by the old
        atomic-rename contract — an upgrade must keep them
        restorable."""
        import json
        mgr = CheckpointManager(tmp_path / "ck", keep_max=5)
        mgr.save(1, self._st(1))
        mgr.save(2, self._st(2))
        # rewrite history: strip markers + the format field
        for s in (1, 2):
            os.remove(os.path.join(mgr._step_dir(s), "COMPLETE"))
        with open(mgr._index_path()) as f:
            idx = json.load(f)
        idx.pop("format"), idx.pop("legacy_steps")
        with open(mgr._index_path(), "w") as f:
            json.dump(idx, f)
        mgr2 = CheckpointManager(tmp_path / "ck", keep_max=5)
        assert mgr2.latest_step() == 2
        assert mgr2.restore()["step"] == 2
        # new saves coexist and torn detection still works on them
        faults.inject("torn_ckpt", step=3)
        mgr2.save(3, self._st(3))
        assert mgr2.latest_step() == 2

    def test_torn_saves_never_age_out_finalized(self, tmp_path):
        """Retention counts finalized checkpoints only: a burst of
        torn saves must not crowd every restorable dir out of the
        keep_max window."""
        mgr = CheckpointManager(tmp_path / "ck", keep_max=2)
        mgr.save(1, self._st(1))
        mgr.save(2, self._st(2))
        for s in (3, 4, 5):
            faults.inject("torn_ckpt", step=s)
            mgr.save(s, self._st(s))
        assert mgr.finalized_steps() == [1, 2]
        assert mgr.restore()["step"] == 2


# -- watchdog -------------------------------------------------------------

class TestWatchdog:
    def test_flags_overrun_and_recovers(self):
        wd = Watchdog(timeout_s=0.01, poll_s=0.005)
        wedges = []
        wd.on_wedge = lambda op, dt: wedges.append((op, dt))
        wd.begin("decode")
        time.sleep(0.03)
        assert wd.check(), "op past timeout must read as wedged"
        assert wd.wedged and wd.wedge_count == 1
        assert wd.check() and wd.wedge_count == 1, \
            "one wedge event per in-flight op"
        wd.end()
        assert not wd.wedged, "a returned op clears the live flag"
        assert wedges and wedges[0][0] == "decode"
        h = wd.health()
        assert h["wedge_count"] == 1 and h["inflight_op"] is None

    def test_fast_op_never_flags(self):
        wd = Watchdog(timeout_s=5.0)
        with wd.watch("prefill"):
            pass
        assert not wd.check() and wd.wedge_count == 0


# -- serving chaos --------------------------------------------------------

@pytest.fixture(scope="module")
def gpt_model():
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    paddle.seed(0)
    m = GPTForCausalLM(_resolve_config("gpt-tiny"))
    m.eval()
    return m


@pytest.fixture(scope="module")
def serve_eng(gpt_model):
    """ONE engine for the whole chaos class (compiles once): the
    degradation knobs under test — admission_policy, deadlines,
    cancels, faults — are host-side state, so tests flip them between
    (fully drained) waves instead of paying a fresh engine's traces."""
    from paddle_tpu.nlp.serving import ServingEngine
    eng = ServingEngine(gpt_model, max_slots=2, page_size=16,
                        max_seq_len=48, num_pages=5,
                        steps_per_dispatch=2, watchdog_timeout=0.05)
    yield eng
    eng.close()
    assert eng._watchdog is None, "close() must stop the watchdog"


@pytest.fixture(autouse=True)
def _drained(request):
    """Every serving test must leave the shared engine empty."""
    yield
    if "serve_eng" in request.fixturenames:
        eng = request.getfixturevalue("serve_eng")
        eng.admission_policy = "wait"
        assert not eng._queue and all(s is None for s in eng._slots)
        assert eng.free_page_count == eng.num_pages - 1


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, (n,)).astype(np.int32)


class TestServingChaos:
    def test_deadline_expiry_and_cancel(self, serve_eng):
        eng = serve_eng
        ok_r = eng.submit(_prompt(5), max_new_tokens=6)
        dead = eng.submit(_prompt(7, 1), max_new_tokens=6,
                          deadline_ms=0)
        time.sleep(0.002)
        res = {r["id"]: r for r in eng.run_to_completion()}
        assert res[dead]["status"] == "expired"
        assert res[dead]["tokens"] == []
        assert res[ok_r]["status"] == "ok"
        assert len(res[ok_r]["tokens"]) == 6

        # cancel a RUNNING request: partial tokens, pages recycled
        free0 = eng.free_page_count
        a = eng.submit(_prompt(5), max_new_tokens=12)
        b = eng.submit(_prompt(6, 2), max_new_tokens=12)
        eng.step()
        assert eng.cancel(b)
        assert not eng.cancel(12345), "unknown rid -> False"
        res = {r["id"]: r for r in eng.run_to_completion()}
        assert res[b]["status"] == "cancelled"
        assert 0 < len(res[b]["tokens"]) < 12
        assert res[a]["status"] == "ok" and len(res[a]["tokens"]) == 12
        assert eng.free_page_count == free0, "cancel leaked pages"

    def test_submit_rejects_impossible_request(self, gpt_model):
        """Satellite: a prompt needing more pages than the pool can
        EVER hold must fail fast, not wedge the admission queue.
        (Engine construction traces nothing, so this stays cheap.)"""
        from paddle_tpu.nlp.serving import ServingEngine
        eng = ServingEngine(gpt_model, max_slots=2, page_size=16,
                            max_seq_len=64, num_pages=3)
        with pytest.raises(ValueError, match="wedge"):
            eng.submit(_prompt(40), max_new_tokens=10)
        # boundary: exactly pool-sized request queues fine
        eng.submit(_prompt(20), max_new_tokens=10)
        assert eng.health()["queued"] == 1

    def test_reject_policy_under_injected_exhaustion(self, serve_eng):
        eng = serve_eng
        eng.admission_policy = "reject"
        faults.inject("page_exhaustion", count=100)
        rid = eng.submit(_prompt(5), max_new_tokens=6)
        res = {r["id"]: r for r in eng.run_to_completion()}
        faults.clear()
        assert res[rid]["status"] == "rejected"
        assert eng.health()["status_counts"]["rejected"] == 1
        # exhaustion cleared: the engine serves again
        rid2 = eng.submit(_prompt(5), max_new_tokens=6)
        res = {r["id"]: r for r in eng.run_to_completion()}
        assert res[rid2]["status"] == "ok"

    def test_evict_lowest_priority(self, serve_eng):
        eng = serve_eng
        eng.admission_policy = "evict"
        lo = eng.submit(_prompt(5), max_new_tokens=20, priority=0)
        mid = eng.submit(_prompt(6, 5), max_new_tokens=20, priority=1)
        eng.step()
        hi = eng.submit(_prompt(5, 6), max_new_tokens=8, priority=5)
        res = {r["id"]: r for r in eng.run_to_completion()}
        assert res[lo]["status"] == "evicted"
        assert 0 < len(res[lo]["tokens"]) < 20, "partial result kept"
        assert res[hi]["status"] == "ok" and len(res[hi]["tokens"]) == 8
        assert res[mid]["status"] == "ok"
        assert eng.free_page_count == 4, "eviction leaked pages"
        # equal priority never evicts: both complete via back-pressure
        a = eng.submit(_prompt(5, 8), max_new_tokens=6, priority=3)
        b = eng.submit(_prompt(6, 9), max_new_tokens=6, priority=3)
        res = {r["id"]: r for r in eng.run_to_completion()}
        assert res[a]["status"] == res[b]["status"] == "ok"

    def test_chaos_wave_zero_recompile(self, serve_eng):
        """Acceptance criterion: a chaos wave (slow step, transient
        dispatch errors, injected page exhaustion, a cancel, a
        deadline) completes every non-expired request with
        compile_counts() UNCHANGED after warmup — degradation is pure
        host-side scheduling."""
        eng = serve_eng
        ref = eng.generate([_prompt(5), _prompt(9, 7)],
                           max_new_tokens=6)           # warmup wave
        frozen = eng.compile_counts()
        wedges0 = eng.health()["watchdog"]["wedge_count"]

        faults.inject("slow_step", seconds=0.25)
        faults.inject("dispatch_error", count=2)
        faults.inject("page_exhaustion", count=2)
        r1 = eng.submit(_prompt(5), max_new_tokens=6)   # same bucket
        r2 = eng.submit(_prompt(9, 7), max_new_tokens=6)
        r3 = eng.submit(_prompt(6, 8), max_new_tokens=12)
        r4 = eng.submit(_prompt(7, 9), max_new_tokens=6,
                        deadline_ms=0)                  # will expire
        early = eng.step()   # r4 may already expire this round
        eng.cancel(r3)
        res = {r["id"]: r
               for r in early + eng.run_to_completion()}
        faults.clear()

        assert res[r1]["status"] == "ok" and res[r1]["tokens"] == ref[0]
        assert res[r2]["status"] == "ok" and res[r2]["tokens"] == ref[1]
        assert res[r3]["status"] == "cancelled"
        assert res[r4]["status"] == "expired"
        assert eng.compile_counts() == frozen, \
            "chaos must not trigger a single new trace"
        h = eng.health()
        assert h["dispatch_retries"] == 2
        assert h["watchdog"]["wedge_count"] > wedges0, \
            "the injected stall must register as a wedge"
        assert h["running"] == 0 and h["queued"] == 0

    def test_health_snapshot_shape(self, gpt_model, serve_eng):
        from paddle_tpu.nlp.serving import ServingEngine
        eng = ServingEngine(gpt_model, max_slots=2, page_size=16,
                            max_seq_len=48)   # traces nothing unused
        eng.submit(_prompt(5), max_new_tokens=4)
        h = eng.health()
        assert h["queued"] == 1 and h["running"] == 0
        assert h["free_pages"] == h["total_pages"]
        for k in ("rounds", "decode_dispatches", "status_counts",
                  "compile_counts", "admission_policy"):
            assert k in h
        assert "watchdog" not in h, "no watchdog armed -> no section"
        # the shared (armed) engine carries the section + ok counts
        h2 = serve_eng.health()
        assert "watchdog" in h2
        assert h2["status_counts"]["ok"] >= 1
        # drain the queued request cheaply: cancel resolves host-side
        eng.cancel(0)
        assert eng.step()[0]["status"] == "cancelled"
