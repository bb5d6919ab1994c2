"""Observability subsystem (ISSUE 4): metrics registry, recompile
tracer, structured run telemetry, and their wiring into hapi / serving
/ dataloader / profiler.

Pins the contracts docs/observability.md documents:
- histogram bucket math (log-spaced 1-2-5 ladder, count-weighted
  observe, bucket-interpolated quantiles) and snapshot MERGE;
- Prometheus-text and JSON export golden strings;
- RecompileTracer: an intentional shape change is a trace with a fresh
  signature (expected), re-tracing a seen signature is UNEXPECTED, and
  a zero-recompile serve wave records nothing after warmup;
- TelemetryCallback: skip/rollback counts consistent with TrainGuard
  under an injected NaN storm (resilience.faults seams);
- TelemetryLogger JSONL rotation + torn-line-tolerant summarize();
- ServingEngine health()/reset_counters() uniform reset through the
  registry (the retry/watchdog-survives-reset divergence, fixed).
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability.metrics import (Counter, Gauge, Histogram,
                                              MetricsRegistry,
                                              default_time_buckets,
                                              get_registry)
from paddle_tpu.observability.telemetry import (TelemetryCallback,
                                                TelemetryLogger)
from paddle_tpu.observability.trace import RecompileTracer, report_all
from paddle_tpu.resilience import TrainGuard, faults


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


# -- histogram math -------------------------------------------------------

class TestHistogram:
    def test_default_buckets_are_125_ladder(self):
        b = default_time_buckets(-2, 0)
        assert b == (0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0, 2.0, 5.0)
        assert list(b) == sorted(b)

    def test_observe_bucketing_and_overflow(self):
        h = Histogram("h", buckets=(1.0, 2.0, 5.0))
        for v in (0.5, 1.0, 1.5, 4.0, 100.0):
            h.observe(v)
        # counts: (..1], (1..2], (2..5], overflow
        assert h.counts == [2, 1, 1, 1]
        assert h.count == 5
        assert h.min == 0.5 and h.max == 100.0
        assert h.sum == pytest.approx(107.0)

    def test_count_weighted_observe(self):
        h = Histogram("h", buckets=(1.0, 10.0))
        h.observe(0.25, count=8)   # a K-token dispatch in O(1)
        assert h.count == 8
        assert h.counts == [8, 0, 0]
        assert h.sum == pytest.approx(2.0)
        assert h.mean() == pytest.approx(0.25)

    def test_quantiles_interpolate_within_min_max(self):
        h = Histogram("h", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 1.6, 3.0):
            h.observe(v)
        assert h.quantile(0.0) >= h.min
        assert h.quantile(1.0) == pytest.approx(h.max)
        p50 = h.quantile(0.5)
        assert 1.0 <= p50 <= 2.0, "median sits in the (1,2] bucket"
        assert Histogram("e").quantile(0.5) is None

    def test_merge_adds_buckets_and_tracks_extrema(self):
        a = Histogram("h", buckets=(1.0, 2.0))
        b = Histogram("h", buckets=(1.0, 2.0))
        a.observe(0.5)
        b.observe(1.5)
        b.observe(9.0)
        a.merge(b.snapshot())
        assert a.count == 3
        assert a.counts == [1, 1, 1]
        assert a.min == 0.5 and a.max == 9.0
        assert a.sum == pytest.approx(11.0)

    def test_merge_rejects_mismatched_bounds(self):
        a = Histogram("h", buckets=(1.0, 2.0))
        b = Histogram("h", buckets=(1.0, 2.0, 4.0))
        with pytest.raises(ValueError, match="mismatched bucket"):
            a.merge(b.snapshot())


# -- registry: series identity, merge, reset ------------------------------

class TestRegistry:
    def test_series_identity_and_type_guard(self):
        reg = MetricsRegistry()
        c1 = reg.counter("req", labels={"status": "ok"})
        c2 = reg.counter("req", labels={"status": "ok"})
        c3 = reg.counter("req", labels={"status": "bad"})
        assert c1 is c2 and c1 is not c3
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("req", labels={"status": "ok"})

    def test_merge_counters_add_gauges_last_win(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.counter("n").inc(2)
        b.counter("n").inc(3)
        a.gauge("g").set(1.0)
        b.gauge("g").set(7.0)
        b.histogram("h", buckets=(1.0,)).observe(0.5)
        a.merge(b.snapshot())
        assert a.counter("n").value == 5
        assert a.gauge("g").value == 7.0
        assert a.get("h").count == 1

    def test_reset_zeroes_in_place(self):
        reg = MetricsRegistry()
        c = reg.counter("n")
        h = reg.histogram("h", buckets=(1.0,))
        c.inc(4)
        h.observe(0.5)
        reg.reset()
        assert c.value == 0, "the held handle must stay live"
        assert h.count == 0 and h.min is None

    def test_concurrent_scrape_during_registration(self):
        # a scrape thread iterating the registry while the main thread
        # lazily registers new series must not crash with "dictionary
        # changed size during iteration"
        import threading
        reg = MetricsRegistry()
        stop = threading.Event()
        errs = []

        def scrape():
            while not stop.is_set():
                try:
                    reg.to_prometheus()
                    reg.snapshot()
                    reg.names()
                except Exception as e:  # pragma: no cover
                    errs.append(e)
                    return

        t = threading.Thread(target=scrape)
        t.start()
        try:
            for i in range(300):
                reg.counter("c", labels={"i": str(i)}).inc()
                reg.histogram("h", labels={"i": str(i)}).observe(0.1)
        finally:
            stop.set()
            t.join()
        assert not errs, errs

    def test_dump_is_parseable_with_extra(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("n").inc()
        p = reg.dump(str(tmp_path / "metrics.json"),
                     extra={"recompile_report": {"unexpected": 0}})
        doc = json.loads(open(p).read())
        assert doc["metrics"]["n"]["value"] == 1
        assert doc["recompile_report"] == {"unexpected": 0}


# -- export golden strings ------------------------------------------------

class TestExports:
    def _golden_registry(self):
        reg = MetricsRegistry()
        reg.counter("requests_total", help="served requests",
                    labels={"status": "ok"}).inc(3)
        reg.gauge("free_pages").set(5)
        h = reg.histogram("latency_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5, count=2)
        return reg

    def test_prometheus_golden(self):
        text = self._golden_registry().to_prometheus()
        assert text == (
            "# TYPE free_pages gauge\n"
            "free_pages 5\n"
            "# TYPE latency_seconds histogram\n"
            'latency_seconds_bucket{le="0.1"} 1\n'
            'latency_seconds_bucket{le="1.0"} 3\n'
            'latency_seconds_bucket{le="+Inf"} 3\n'
            "latency_seconds_sum 1.05\n"
            "latency_seconds_count 3\n"
            "# HELP requests_total served requests\n"
            "# TYPE requests_total counter\n"
            'requests_total{status="ok"} 3\n')

    def test_json_golden_roundtrip(self):
        doc = json.loads(self._golden_registry().to_json())
        m = doc["metrics"]
        assert m['requests_total{status="ok"}'] == {
            "name": "requests_total", "labels": {"status": "ok"},
            "type": "counter", "value": 3}
        assert m["latency_seconds"]["counts"] == [1, 2, 0]
        assert m["latency_seconds"]["sum"] == pytest.approx(1.05)
        fresh = MetricsRegistry()
        fresh.merge(doc)   # a dumped snapshot is a mergeable snapshot
        assert fresh.get("free_pages").value == 5


# -- recompile tracer -----------------------------------------------------

class TestRecompileTracer:
    def test_trace_once_then_silent(self):
        import jax.numpy as jnp
        reg = MetricsRegistry()
        tr = RecompileTracer(name="t", registry=reg)
        f = tr.jit("add", lambda x: x + 1)
        for _ in range(3):
            f(jnp.zeros((4,)))
        assert tr.counts() == {"add": 1}
        assert tr.unexpected_retraces() == 0
        [e] = tr.events()
        assert e["site"] == "add" and not e["unexpected"]
        assert "[4]" in e["signature"] and "float" in e["signature"]
        assert reg.counter("recompile_traces_total",
                           labels={"tracer": "t",
                                   "site": "add"}).value == 1

    def test_shape_change_is_expected_new_signature(self):
        import jax.numpy as jnp
        tr = RecompileTracer(name="t")
        f = tr.jit("add", lambda x: x + 1)
        f(jnp.zeros((4,)))
        f(jnp.zeros((8,)))   # intentional retrace: NEW signature
        assert tr.counts()["add"] == 2
        assert tr.unexpected_retraces() == 0
        rep = tr.report()
        assert rep["sites"]["add"] == {"traces": 2, "signatures": 2,
                                       "unexpected_retraces": 0}

    def test_seen_signature_retrace_is_unexpected(self):
        import jax.numpy as jnp
        tr = RecompileTracer(name="t", registry=MetricsRegistry())
        f = tr.jit("add", lambda x: x + 1)
        f(jnp.zeros((4,)))
        # drop THIS function's compiled program (the cliff), without
        # jax.clear_caches() nuking other tests' warm programs
        f.jitted.clear_cache()
        f(jnp.zeros((4,)))
        assert tr.counts()["add"] == 2
        assert tr.unexpected_retraces() == 1
        assert [e["unexpected"] for e in tr.events()] == [False, True]

    def test_report_all_merges_live_tracers(self):
        import jax.numpy as jnp
        tr = RecompileTracer(name="zz-report-all-test")
        tr.jit("f", lambda x: x * 2)(jnp.ones(()))
        rep = report_all()
        names = [t["tracer"] for t in rep["tracers"]]
        assert "zz-report-all-test" in names

    def test_traced_call_gets_a_staged_record(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.observability import trace

        def body(a):
            # jits traced inside the program's trace: their trace events
            # are part of its trace, not added to it again
            for i in range(8):
                a = jax.jit(lambda x, i=i: jnp.tanh(x @ x) + i)(a)
            return a

        tr = RecompileTracer(name="t")
        f = tr.jit("mm", body)
        a = jnp.ones((32, 32), jnp.float32)
        f(a)
        [e] = tr.events()
        assert e["kind"] == "program" and e["parent"] is None
        st = e["stages"]
        parts = ("trace_s", "lower_s", "backend_s", "first_run_s")
        assert all(st[k] >= 0 for k in parts + ("cache_retrieval_s",))
        assert st["trace_s"] > 0 and st["lower_s"] > 0
        assert sum(st[k] for k in parts) <= e["t1"] - e["t0"] + 1e-3
        assert st["cache_retrieval_s"] <= st["backend_s"] + 1e-6
        assert e["kernel_places"] == {}
        # a call that does not trace adds no record and hears nothing
        # from jax: the listeners fire only while a program is built
        heard = len(trace._local.reported)
        f(a)
        assert tr.events() == [e]
        assert len(trace._local.reported) == heard

    def test_kernel_places_count_trace_sites(self):
        """One place per pallas_call that entered the trace: twice in a
        loop unrolled twice, once in a scan of two."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas._common import pallas_call

        def body(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        def kern(x):
            shape = jax.ShapeDtypeStruct(x.shape, x.dtype)
            return pallas_call(body, name="double", out_shape=shape)(x)

        def unrolled(x):
            for _ in range(2):
                x = kern(x)
            return x

        def scanned(x):
            return jax.lax.scan(lambda c, _: (kern(c), None), x, None,
                                length=2)[0]

        tr = RecompileTracer(name="t")
        x = jnp.ones((8, 128), jnp.float32)
        out = tr.jit("unrolled", unrolled)(x)
        assert float(out[0, 0]) == float(tr.jit("scanned", scanned)(x)[0, 0])
        places = {e["site"]: e["kernel_places"] for e in tr.events()}
        assert places == {"unrolled": {"double": 2}, "scanned": {"double": 1}}

    def test_nested_site_has_its_own_record(self):
        import jax.numpy as jnp
        tr = RecompileTracer(name="t")
        inner = tr.jit("inner", lambda x: x * 3.0, introspect=False)
        tr.jit("outer", lambda x: inner(x) + 1.0)(jnp.ones((4,)))
        inner_ev, outer_ev = tr.events()
        assert (inner_ev["site"], inner_ev["parent"]) == ("inner", "outer")
        assert (outer_ev["site"], outer_ev["parent"]) == ("outer", None)
        # the inner trace runs inside the outer one and is part of it
        assert outer_ev["stages"]["trace_s"] >= \
            inner_ev["stages"]["trace_s"] > 0
        assert inner_ev["introspect"] is None
        assert outer_ev["introspect"] is not None

    def test_serve_warmup_records_each_program_under_warmup(self):
        from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
        from paddle_tpu.nlp.serving import ServingEngine
        paddle.seed(0)
        model = GPTForCausalLM(_resolve_config("gpt-tiny"))
        eng = ServingEngine(model, max_slots=2, page_size=16,
                            max_seq_len=48, steps_per_dispatch=2,
                            prefix_cache=False)
        eng.warmup(buckets=(5, 17))
        evs = eng.tracer.events()
        programs = [e for e in evs if e["kind"] == "program"]
        assert sorted(e["site"] for e in programs) == sorted(
            eng.compile_counts())
        assert {e["parent"] for e in programs} == {"warmup"}
        assert all(e["t1"] > e["t0"] and e["introspect"] is not None
                   for e in programs)
        [phase] = [e for e in evs if e["kind"] == "phase"]
        assert (phase["site"], phase["parent"]) == ("warmup", None)
        assert phase["t0"] <= min(e["t0"] for e in programs)
        assert phase["t1"] >= max(e["t1"] for e in programs)
        assert phase["wall_s"] == phase["t1"] - phase["t0"]

    def test_serve_wave_traces_warmup_only(self, tmp_path):
        """The acceptance shape: a zero-recompile serve wave records
        warmup traces and NOTHING after — and the instrumentation
        itself (histograms, health snapshots) induces no retrace."""
        from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
        from paddle_tpu.nlp.serving import ServingEngine
        paddle.seed(0)
        model = GPTForCausalLM(_resolve_config("gpt-tiny"))
        reg = MetricsRegistry()
        eng = ServingEngine(model, max_slots=2, page_size=16,
                            max_seq_len=48, steps_per_dispatch=2,
                            registry=reg)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 256, (6,)) for _ in range(4)]
        eng.generate(prompts, max_new_tokens=4)      # warmup wave
        events_after_warmup = len(eng.tracer.events())
        eng.reset_counters()
        eng.generate(prompts, max_new_tokens=4)      # steady wave
        eng.health()
        assert len(eng.tracer.events()) == events_after_warmup, \
            "steady-state wave must record zero trace events"
        assert eng.tracer.unexpected_retraces() == 0
        assert reg.get("serve_ttft_seconds").count == 4
        assert reg.get("serve_decode_token_seconds").count > 0


# -- telemetry logger: JSONL + rotation -----------------------------------

class TestTelemetryLogger:
    def test_emit_and_summarize(self, tmp_path):
        lg = TelemetryLogger(str(tmp_path))
        lg.emit("train_step", step=1, loss=2.0)
        lg.emit("train_step", step=2, loss=1.0)
        lg.emit("serve_request", ttft_ms=5.0)
        s = lg.summarize()
        assert s["records"] == 3
        st = s["by_kind"]["train_step"]["fields"]["loss"]
        assert st == {"min": 1.0, "max": 2.0, "last": 1.0, "mean": 1.5}
        lg.close()

    def test_rotation_keeps_bounded_files(self, tmp_path):
        lg = TelemetryLogger(str(tmp_path), rotate_bytes=200,
                             max_rotated=2)
        for i in range(50):
            lg.emit("r", i=i, pad="x" * 40)
        assert lg.rotations >= 3
        lg.flush()
        files = lg.files()
        assert [os.path.basename(f) for f in files] == [
            "telemetry.jsonl.2", "telemetry.jsonl.1",
            "telemetry.jsonl"]
        recs = list(lg.iter_records())
        assert recs, "retained files must still parse"
        # newest record survives; the oldest rotated out
        assert recs[-1]["i"] == 49
        assert recs[0]["i"] > 0
        lg.close()

    def test_nan_loss_emits_valid_json(self, tmp_path):
        """A NaN loss (the storm the guard records) must land as RFC
        JSON (null), never a bare NaN token jq/JS consumers reject."""
        lg = TelemetryLogger(str(tmp_path))
        lg.emit("train_step", loss=float("nan"), step_time_s=0.1,
                nested={"g": float("inf")})
        lg.close()
        raw = open(lg.path).read()
        assert "NaN" not in raw and "Infinity" not in raw
        rec = json.loads(raw.splitlines()[0])
        assert rec["loss"] is None and rec["nested"]["g"] is None
        assert rec["step_time_s"] == 0.1

    def test_nan_gauge_dumps_valid_json(self, tmp_path):
        reg = MetricsRegistry()
        reg.gauge("train_loss").set(float("nan"))
        reg.counter("ok_total").inc(2)
        path = reg.dump(str(tmp_path / "metrics.json"))
        raw = open(path).read()
        assert "NaN" not in raw
        doc = json.loads(raw)
        assert doc["metrics"]["train_loss"]["value"] is None
        assert reg.to_json()  # parseable too
        assert "NaN" not in reg.to_json()

    def test_torn_line_does_not_kill_rollup(self, tmp_path):
        lg = TelemetryLogger(str(tmp_path))
        lg.emit("r", i=1)
        lg.flush()
        with open(lg.path, "a") as f:
            f.write('{"kind": "r", "i": 2')   # torn crash write
        assert lg.summarize()["records"] == 1
        lg.close()


# -- TelemetryCallback under a NaN storm ----------------------------------

class TestTelemetryCallback:
    def _fit(self, tmp_path, registry, storm=None):
        paddle.seed(0)
        net = paddle.nn.Linear(8, 4)
        model = paddle.Model(net)
        guard = TrainGuard(snapshot_every=1, rollback_after=3)
        model.prepare(
            paddle.optimizer.AdamW(1e-2, parameters=net.parameters()),
            paddle.nn.CrossEntropyLoss(), guard=guard)
        rng = np.random.default_rng(0)
        X = rng.standard_normal((32, 8)).astype("float32")
        Y = rng.integers(0, 4, (32,)).astype("int64")
        cb = TelemetryCallback(run_dir=str(tmp_path), registry=registry)
        if storm:
            faults.inject("nan_grads", step=storm[0], count=storm[1])
        model.fit(paddle.io.TensorDataset([X, Y]), epochs=1,
                  batch_size=4, verbose=0, shuffle=False,
                  callbacks=[cb])
        return guard, cb

    def test_storm_counts_match_guard(self, tmp_path):
        reg = MetricsRegistry()
        guard, cb = self._fit(tmp_path, reg, storm=(3, 3))
        assert guard.skipped_steps == 3
        assert guard.rollbacks == 1
        assert reg.counter("train_skipped_steps_total").value == 3
        assert reg.counter("train_rollbacks_total").value == 1
        assert reg.counter("train_steps_total").value == 8
        assert reg.get("train_step_seconds").count == 8
        assert reg.gauge("train_loss").value > 0
        assert reg.gauge("train_samples_per_s").value > 0
        assert reg.gauge("train_grad_norm").value >= 0
        # JSONL records carry the same story, step by step
        recs = [r for r in cb.logger.iter_records()
                if r["kind"] == "train_step"]
        assert len(recs) == 8
        assert [r["outcome"] for r in recs] == (
            ["ok", "ok", "skipped", "skipped", "rolled_back",
             "ok", "ok", "ok"])
        assert recs[-1]["skipped"] == 3 and recs[-1]["rollbacks"] == 1
        end = [r for r in cb.logger.iter_records()
               if r["kind"] == "train_end"]
        assert end and end[0]["skipped_steps"] == 3

    def test_clean_run_exports_zero_counters(self, tmp_path):
        """A clean run exports the guard counters AT ZERO — absent
        metrics are indistinguishable from broken wiring."""
        reg = MetricsRegistry()
        guard, cb = self._fit(tmp_path, reg)
        assert reg.counter("train_skipped_steps_total").value == 0
        assert reg.counter("train_rollbacks_total").value == 0
        assert cb.metrics_path and os.path.exists(cb.metrics_path)
        doc = json.load(open(cb.metrics_path))
        assert "recompile_report" in doc
        # scope to THIS fit's engine: report_all() spans every tracer
        # the process ever made, including other tests' deliberate
        # retraces (tracers register strongly — see trace.py)
        assert cb.model._engine.tracer.unexpected_retraces() == 0

    def test_second_fit_does_not_recount_history(self, tmp_path):
        """Guard/scaler totals are lifetime-absolute on the guard; a
        second fit() on the same model must baseline them at
        train_begin and diff only ITS OWN skips into the registry."""
        reg = MetricsRegistry()
        paddle.seed(0)
        net = paddle.nn.Linear(8, 4)
        model = paddle.Model(net)
        guard = TrainGuard(snapshot_every=1, rollback_after=3)
        model.prepare(
            paddle.optimizer.AdamW(1e-2, parameters=net.parameters()),
            paddle.nn.CrossEntropyLoss(), guard=guard)
        rng = np.random.default_rng(0)
        X = rng.standard_normal((32, 8)).astype("float32")
        Y = rng.integers(0, 4, (32,)).astype("int64")
        ds = paddle.io.TensorDataset([X, Y])
        faults.inject("nan_grads", step=3, count=3)
        model.fit(ds, epochs=1, batch_size=4, verbose=0, shuffle=False,
                  callbacks=[TelemetryCallback(run_dir=str(tmp_path),
                                               registry=reg)])
        assert guard.skipped_steps == 3
        assert reg.counter("train_skipped_steps_total").value == 3
        # clean second fit: fresh callback, same guard + registry —
        # the counters must NOT double to 6/2
        model.fit(ds, epochs=1, batch_size=4, verbose=0, shuffle=False,
                  callbacks=[TelemetryCallback(run_dir=str(tmp_path),
                                               registry=reg)])
        assert guard.skipped_steps == 3
        assert reg.counter("train_skipped_steps_total").value == 3
        assert reg.counter("train_rollbacks_total").value == 1
        assert reg.counter("train_steps_total").value == 16

    def test_grad_norm_is_opt_in(self, tmp_path):
        """A bare Engine (no TelemetryCallback) must not pay the
        in-step grad-norm reduction: last_grad_norm stays None and the
        compiled step matches pre-telemetry baselines. With the
        callback attached, the same step exports a real norm."""
        paddle.seed(0)
        net = paddle.nn.Linear(8, 4)
        model = paddle.Model(net)
        model.prepare(
            paddle.optimizer.AdamW(1e-2, parameters=net.parameters()),
            paddle.nn.CrossEntropyLoss())
        eng = model._engine
        assert not eng.collect_grad_norm
        x = np.zeros((4, 8), dtype="float32")
        y = np.zeros((4,), dtype="int64")
        model.train_batch([x], [y])
        assert eng.last_grad_norm is None

        reg = MetricsRegistry()
        guard, cb = self._fit(tmp_path, reg)
        recs = [r for r in cb.logger.iter_records()
                if r["kind"] == "train_step"]
        assert all(r.get("grad_norm") is not None for r in recs)
        assert cb.model._engine.collect_grad_norm

    def test_grad_norm_cleared_on_accum_and_multi_paths(self):
        """train_batch_accum / train_batch_multi compute no global
        grad norm; they must CLEAR last_grad_norm so a later telemetry
        read never reports a stale fused-step value as current."""
        paddle.seed(0)
        net = paddle.nn.Linear(8, 4)
        model = paddle.Model(net)
        model.prepare(
            paddle.optimizer.AdamW(1e-2, parameters=net.parameters()),
            paddle.nn.CrossEntropyLoss())
        eng = model._engine
        eng.enable_grad_norm()
        x = np.zeros((4, 8), dtype="float32")
        y = np.zeros((4,), dtype="int64")
        model.train_batch([x], [y])
        assert eng.last_grad_norm is not None
        eng.train_batch_accum([x], [y], apply_update=True)
        assert eng.last_grad_norm is None

        model.train_batch([x], [y])
        assert eng.last_grad_norm is not None
        xs = np.stack([x, x])
        ys = np.stack([y, y])
        eng.train_batch_multi([xs], [ys])
        assert eng.last_grad_norm is None

    def test_dataloader_batch_wait_lands_in_global_registry(self):
        from paddle_tpu.io import DataLoader, TensorDataset
        reg = get_registry()
        train = {"role": "train"}
        before = reg.get("dataloader_batches_total", labels=train)
        before = before.value if before else 0
        X = np.zeros((8, 3), "float32")
        n = sum(1 for _ in DataLoader(TensorDataset([X]), batch_size=2))
        assert n == 4
        assert reg.counter("dataloader_batches_total",
                           labels=train).value == before + 4
        assert reg.get("dataloader_batch_wait_seconds",
                       labels=train).count >= 4

    def test_dataloader_role_label_separates_eval_from_train(self):
        # eval/predict loaders must not pollute the train batch-wait
        # series (the input-bound-run diagnostic)
        from paddle_tpu.io import DataLoader, TensorDataset
        reg = get_registry()
        train = reg.counter("dataloader_batches_total",
                            labels={"role": "train"}).value
        X = np.zeros((6, 3), "float32")
        loader = DataLoader(TensorDataset([X]), batch_size=2)
        loader._obs_role = "eval"
        assert sum(1 for _ in loader) == 3
        assert reg.counter("dataloader_batches_total",
                           labels={"role": "eval"}).value >= 3
        assert reg.counter("dataloader_batches_total",
                           labels={"role": "train"}).value == train


# -- serving reset/health uniformity (the ISSUE 4 divergence fix) ---------

class TestServeResetUniformity:
    @pytest.fixture(scope="class")
    def engine(self):
        from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
        from paddle_tpu.nlp.serving import ServingEngine
        paddle.seed(0)
        model = GPTForCausalLM(_resolve_config("gpt-tiny"))
        eng = ServingEngine(model, max_slots=2, page_size=16,
                            max_seq_len=48, steps_per_dispatch=2,
                            dispatch_retries=2,
                            registry=MetricsRegistry())
        yield eng
        eng.close()

    def test_reset_clears_retry_and_status_fields(self, engine):
        rng = np.random.default_rng(0)
        faults.inject("dispatch_error", count=1)
        engine.generate([rng.integers(0, 256, (6,))], max_new_tokens=4)
        h = engine.health()
        assert h["dispatch_retries"] == 1
        assert h["status_counts"]["ok"] == 1
        assert h["deadline_misses"] == 0
        engine.reset_counters()
        h2 = engine.health()
        assert h2["dispatch_retries"] == 0, \
            "retry count must not survive reset_counters()"
        assert h2["status_counts"]["ok"] == 0
        assert h2["decode_tokens"] == 0
        # live state (pages, queue) is NOT a counter: still truthful
        assert h2["free_pages"] == engine.free_page_count

    def test_counters_resume_after_reset(self, engine):
        rng = np.random.default_rng(1)
        engine.generate([rng.integers(0, 256, (6,))], max_new_tokens=4)
        h = engine.health()
        assert h["status_counts"]["ok"] == 1
        assert h["page_occupancy"] == 0.0, "drained pool reads empty"


class TestServeRegistryIsolation:
    def test_default_registries_are_per_engine(self):
        """Two engines with the default registry must not alias each
        other's serve_* series: counts stay per-engine and one
        engine's reset cannot zero a sibling's window."""
        from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
        from paddle_tpu.nlp.serving import ServingEngine
        from paddle_tpu.observability.metrics import get_registry
        paddle.seed(0)
        model = GPTForCausalLM(_resolve_config("gpt-tiny"))
        a = ServingEngine(model, max_slots=1, page_size=16,
                          max_seq_len=48, steps_per_dispatch=2)
        b = ServingEngine(model, max_slots=1, page_size=16,
                          max_seq_len=48, steps_per_dispatch=2)
        try:
            assert a.registry is not b.registry
            assert a.registry is not get_registry()
            rng = np.random.default_rng(0)
            a.generate([rng.integers(0, 256, (6,))], max_new_tokens=4)
            assert a.health()["status_counts"]["ok"] == 1
            assert b.health()["status_counts"]["ok"] == 0
            b.reset_counters()
            assert a.health()["status_counts"]["ok"] == 1, \
                "a sibling's reset_counters() must not zero this engine"
        finally:
            a.close()
            b.close()

    def test_closed_tracer_report_retained(self):
        """close() deregisters the tracer (no unbounded growth across
        engine reloads) but its site aggregates stay in report_all."""
        from paddle_tpu.observability.trace import (RecompileTracer,
                                                    all_tracers,
                                                    report_all)
        tr = RecompileTracer(name="retired", registry=MetricsRegistry())
        f = tr.jit("square", lambda x: x * x)
        f(np.arange(4.0, dtype=np.float32))
        tr.close()
        assert tr not in all_tracers()
        tr.close()  # idempotent
        mine = [t for t in report_all()["tracers"]
                if t["tracer"] == "retired"]
        assert len(mine) == 1 and mine[0]["closed"]
        assert mine[0]["sites"]["square"]["traces"] == 1
        assert mine[0]["events"] == []

    def test_closed_aggregate_never_evicts(self):
        """An unexpected retrace recorded by an early engine must
        survive ANY number of later tracer retirements — closed
        tracers fold into a cumulative per-name rollup, not a bounded
        list that silently evicts the one fact the report exists to
        keep."""
        import jax.numpy as jnp
        from paddle_tpu.observability.trace import (RecompileTracer,
                                                    report_all)
        early = RecompileTracer(name="agg-victim")
        f = early.jit("hot", lambda x: x + 1)
        f(jnp.zeros((4,)))
        f.jitted.clear_cache()
        f(jnp.zeros((4,)))
        early.close()
        for _ in range(70):   # > the old deque's maxlen of 64
            tr = RecompileTracer(name="agg-churn")
            tr.jit("g", lambda x: x * 2)(jnp.ones(()))
            tr.close()
        rep = report_all()
        victim = [t for t in rep["tracers"]
                  if t["tracer"] == "agg-victim"]
        assert len(victim) == 1 and victim[0]["closed"]
        assert victim[0]["unexpected_retraces"] == 1
        churn = [t for t in rep["tracers"]
                 if t["tracer"] == "agg-churn"]
        assert len(churn) == 1, "same-name closes fold into ONE row"
        assert churn[0]["closed_tracers"] == 70
        assert churn[0]["sites"]["g"]["traces"] == 70
        assert rep["unexpected_retraces"] >= 1

    def test_engine_gc_retires_tracer(self):
        """Engines register tracers STRONGLY (reports outlive
        the engine) — so a collected Engine must retire its tracer or
        repeated construction grows the live set forever."""
        import gc
        from paddle_tpu.observability.trace import all_tracers
        net = paddle.nn.Linear(4, 2)
        model = paddle.Model(net)
        model.prepare(
            paddle.optimizer.AdamW(1e-2, parameters=net.parameters()),
            paddle.nn.CrossEntropyLoss())
        tr = model._engine.tracer
        assert tr in all_tracers()
        del model, net
        gc.collect()
        assert tr not in all_tracers()


# -- profiler bridge ------------------------------------------------------

class TestProfilerBridge:
    def test_record_event_lands_in_registry(self):
        import jax.numpy as jnp
        from paddle_tpu.profiler import Profiler, RecordEvent
        reg = MetricsRegistry()
        p = Profiler(registry=reg).start()
        with p.record_event("region_a"):
            float(jnp.ones((4,)).sum())
        with RecordEvent("region_b", p):
            pass
        p.step()
        p.stop()
        for region in ("region_a", "region_b", "train_step"):
            h = reg.get("profiler_region_seconds",
                        {"region": region})
            assert h is not None and h.count == 1, region

    def test_registry_false_disables_bridge(self):
        from paddle_tpu.profiler import Profiler
        p = Profiler(registry=False).start()
        with p.record_event("quiet", sync=False):
            pass
        p.stop()
        assert p.registry is None

    def test_export_chrome_tracing_copies_artifacts(self, tmp_path):
        from paddle_tpu.profiler import export_chrome_tracing

        class FakeProf:
            trace_dir = str(tmp_path / "trace")
        run = tmp_path / "trace" / "plugins" / "profile" / "run1"
        run.mkdir(parents=True)
        (run / "host.trace.json.gz").write_bytes(b"x")
        (run / "host.xplane.pb").write_bytes(b"y")
        (run / "notes.txt").write_bytes(b"ignored")
        out = tmp_path / "export"
        cb = export_chrome_tracing(str(out), worker_name="w0")
        prof = FakeProf()
        cb(prof)
        names = sorted(os.listdir(out))
        assert names == ["w0.host.trace.json.gz", "w0.host.xplane.pb"]
        assert prof._export_dir == str(out)
        assert len(prof._exported) == 2

    def test_export_disambiguates_same_named_runs(self, tmp_path):
        """Two profiling runs under one trace_dir with same-named
        artifacts must BOTH survive the flat export (the colliding
        copy carries its source subpath in the name)."""
        from paddle_tpu.profiler import export_chrome_tracing

        class FakeProf:
            trace_dir = str(tmp_path / "trace")
        for run in ("run1", "run2"):
            d = tmp_path / "trace" / "plugins" / "profile" / run
            d.mkdir(parents=True)
            (d / "host.xplane.pb").write_bytes(run.encode())
        out = tmp_path / "export"
        prof = FakeProf()
        export_chrome_tracing(str(out))(prof)
        assert len(prof._exported) == 2
        payloads = {open(p, "rb").read() for p in prof._exported}
        assert payloads == {b"run1", b"run2"}


# =========================================================================
# Round-10 deep-introspection layer (ISSUE 5): compiled-cost capture,
# live exporter, span timelines, crash flight recorder.
# =========================================================================

from paddle_tpu.observability import (exporter as obs_exporter,  # noqa: E402
                                      flightrec, introspect)
from paddle_tpu.observability.spans import (SpanRecorder,  # noqa: E402
                                            export_chrome)


@pytest.fixture(autouse=True)
def _clean_introspection(monkeypatch, tmp_path):
    """Introspection + flight state are process-global; isolate each
    test and point stray dumps at a tmp dir."""
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path / "flight"))
    introspect.clear()
    flightrec.get_recorder().clear()
    yield
    introspect.clear()
    flightrec.get_recorder().clear()


class TestIntrospect:
    def test_normalize_cost(self):
        # a dict; CPU builds may omit keys or return None
        full = introspect.normalize_cost(
            {"flops": 10.0, "bytes accessed": 5.0})
        assert full == {"flops": 10.0, "bytes_accessed": 5.0,
                        "transcendentals": None}
        assert introspect.normalize_cost({"flops": 3})["flops"] == 3.0
        assert introspect.normalize_cost({}) == {
            "flops": None, "bytes_accessed": None,
            "transcendentals": None}
        assert introspect.normalize_cost(None) is None
        assert introspect.normalize_cost("bogus") is None

    def test_resolve_peak_env_override_beats_table(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "123e9")
        peak, src = introspect.resolve_peak_flops()
        assert peak == 123e9 and src == "env:PADDLE_TPU_PEAK_FLOPS"

    def test_resolve_peak_table_by_device_kind(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)
        peak, src = introspect.resolve_peak_flops("TPU v5 lite")
        assert peak == 197e12 and src.startswith("table:")
        peak, src = introspect.resolve_peak_flops("TPU v4")
        assert peak == 275e12
        peak, src = introspect.resolve_peak_flops("Quantum9000")
        assert peak is None and "unknown-device-kind" in src
        # no catch-all: a v5 string the table does not name is unknown,
        # never silently the v5p peak
        peak, src = introspect.resolve_peak_flops("TPU v5")
        assert peak is None and "unknown-device-kind" in src

    def test_resolve_peak_null_on_cpu_without_override(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)
        peak, src = introspect.resolve_peak_flops()   # CPU backend
        assert peak is None and src == "no-table:cpu"

    def test_measured_mfu_null_honesty(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)
        assert introspect.measured_mfu(None, 0.1) is None
        assert introspect.measured_mfu(1e9, 0) is None
        assert introspect.measured_mfu(1e9, 0.1) is None  # no peak
        assert introspect.measured_mfu(1e9, 0.1, peak=1e12) == \
            pytest.approx(0.01)

    def test_capture_rides_the_tracer_without_recompile_noise(self):
        """A traced site is introspected exactly once per compile, the
        AOT replay never bumps trace counters, and the capture carries
        real non-zero FLOPs on CPU."""
        import jax.numpy as jnp
        reg = MetricsRegistry()
        tr = RecompileTracer(name="intro_t", registry=reg)
        f = tr.jit("mm", lambda a, b: jnp.dot(a, b) + 1.0)
        a = jnp.ones((16, 16), jnp.float32)
        for _ in range(3):
            f(a, a)
        assert tr._counts["mm"] == 1          # replay stayed silent
        assert tr.unexpected_retraces() == 0
        e = introspect.site_cost("mm", tracer="intro_t")
        assert e is not None and e["captures"] == 1
        if e["flops"] is not None:            # key present on this jax
            assert e["flops"] >= 2 * 16 * 16 * 16
        # registry gauge published under (tracer, site) labels
        g = reg.get("xla_cost_flops",
                    labels={"tracer": "intro_t", "site": "mm"})
        assert (g is None) == (e["flops"] is None)
        rep = introspect.cost_report()
        assert "intro_t/mm" in rep["sites"]
        tr.close()

    def test_replay_stages_sit_under_introspect(self):
        """The AOT replay is timed apart from the call, under
        `introspect`, and its re-trace counts no kernel place."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas._common import pallas_call

        def body(x_ref, o_ref):
            o_ref[...] = x_ref[...] + 1.0

        def f(x):
            shape = jax.ShapeDtypeStruct(x.shape, x.dtype)
            return pallas_call(body, name="inc", out_shape=shape)(x) * 2.0

        tr = RecompileTracer(name="intro_stages")
        with tr.phase("boot"):
            tr.jit("inc", f)(jnp.ones((8, 128), jnp.float32))
        e, phase = tr.events()
        rep = e["introspect"]
        assert set(rep) == {"trace_s", "lower_s", "backend_s",
                            "cache_retrieval_s", "cache_hit", "wall_s"}
        assert all(rep[k] >= 0 for k in rep if k != "cache_hit")
        assert rep["trace_s"] + rep["lower_s"] + rep["backend_s"] <= \
            rep["wall_s"] + 1e-3
        assert e["t1"] <= phase["t1"]
        assert introspect.site_cost("inc", tracer="intro_stages") is not None
        assert e["kernel_places"] == phase["kernel_places"] == {"inc": 1}
        tr.close()

    def test_compile_budget_skips_with_reason(self):
        out = introspect.capture_site("t", "slow_site", None, (), {},
                                      wall_s=1e9)
        assert out is None
        assert "budget" in introspect.cost_report()["skipped"]["t/slow_site"]

    def test_env_kill_switch(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_INTROSPECT", "0")
        assert not introspect.enabled()
        assert introspect.capture_site("t", "s", None, (), {}) is None
        assert introspect.cost_report()["sites"] == {}

    def test_broken_aot_records_reason_not_raise(self):
        class Boom:
            def lower(self, *a, **k):
                raise RuntimeError("no AOT here")
        out = introspect.capture_site("t", "broken", Boom(), (), {})
        assert out is None
        skipped = introspect.cost_report()["skipped"]
        assert "RuntimeError" in skipped["t/broken"]


def _parse_prom(text):
    """Prometheus text -> {series_key: float} (comments skipped)."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, _, val = line.rpartition(" ")
        out[key] = float(val)
    return out


class TestExporter:
    def test_endpoints_roundtrip(self):
        import urllib.error
        import urllib.request
        reg = MetricsRegistry()
        reg.counter("c").inc(7)
        reg.histogram("h", buckets=(1.0,)).observe(0.5)
        ex = obs_exporter.MetricsExporter(
            registry=reg, health_fn=lambda: {"queue": 3},
            report_fn=lambda: {"extra_section": True})
        try:
            txt = urllib.request.urlopen(
                ex.url + "/metrics", timeout=10).read().decode()
            assert txt == reg.to_prometheus()
            h = json.load(urllib.request.urlopen(ex.url + "/healthz",
                                                 timeout=10))
            assert h["status"] == "ok" and h["queue"] == 3
            r = json.load(urllib.request.urlopen(ex.url + "/report",
                                                 timeout=10))
            assert "recompile_report" in r and "cost_report" in r
            assert r["extra_section"] is True
            try:
                urllib.request.urlopen(ex.url + "/nope", timeout=10)
                assert False, "404 expected"
            except urllib.error.HTTPError as e:
                assert e.code == 404
                assert "endpoints" in json.load(e)
        finally:
            ex.close()

    def test_close_releases_port_for_immediate_rebind(self):
        reg = MetricsRegistry()
        ex1 = obs_exporter.MetricsExporter(registry=reg)
        port = ex1.port
        ex1.close()
        ex2 = obs_exporter.MetricsExporter(registry=reg, port=port)
        assert ex2.port == port
        ex2.close()

    def test_double_close_is_idempotent(self):
        ex = obs_exporter.MetricsExporter(registry=MetricsRegistry())
        ex.close()
        ex.close()   # second close: no error, no hang
        with obs_exporter.MetricsExporter(
                registry=MetricsRegistry()) as ex2:
            pass
        ex2.close()  # context exit already closed it

    def test_scrape_after_close_refused(self):
        import urllib.error
        import urllib.request
        ex = obs_exporter.MetricsExporter(registry=MetricsRegistry())
        url = ex.url
        ex.close()
        with pytest.raises((urllib.error.URLError, OSError)):
            urllib.request.urlopen(url + "/metrics", timeout=2)


class TestServeObservability:
    """One 2-request serve wave, scraped live from a second thread:
    pins the span-timeline golden AND the no-torn-histogram scrape
    contract in a single compile."""

    @pytest.fixture(scope="class")
    def wave(self):
        import threading
        import urllib.request
        import paddle_tpu as paddle
        from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
        from paddle_tpu.nlp.serving import ServingEngine

        paddle.seed(0)
        model = GPTForCausalLM(_resolve_config("gpt-tiny",
                                               num_attention_heads=1))
        eng = ServingEngine(model, max_slots=2, page_size=8,
                            max_seq_len=32, steps_per_dispatch=2)
        ex = eng.serve_metrics(port=0)
        rng = np.random.default_rng(0)
        rids = [eng.submit(rng.integers(
            0, model.config.vocab_size, (5 + i,)), max_new_tokens=4)
            for i in range(2)]
        scrapes, stop = [], threading.Event()

        def scraper():
            while not stop.is_set():
                try:
                    scrapes.append(urllib.request.urlopen(
                        ex.url + "/metrics", timeout=10).read().decode())
                except OSError:
                    pass
        t = threading.Thread(target=scraper, daemon=True)
        t.start()
        finished = []
        rounds = 0
        while eng._queue or any(s is not None for s in eng._slots):
            finished.extend(eng.step())
            rounds += 1
            assert rounds < 500
        stop.set()
        t.join(timeout=5)
        final = urllib.request.urlopen(
            ex.url + "/metrics", timeout=10).read().decode()
        data = {"eng": eng, "exporter": ex, "rids": rids,
                "finished": finished, "scrapes": scrapes,
                "final": final,
                "events": eng.spans.events(),
                "prom": eng.registry.to_prometheus()}
        yield data
        eng.close()

    def test_wave_completed_ok(self, wave):
        assert {r["id"] for r in wave["finished"]} == set(wave["rids"])
        assert all(r["status"] == "ok" for r in wave["finished"])

    def test_span_timeline_golden(self, wave):
        """The host-scheduling story for a 2-request wave: each request
        lane tells queue_wait -> prefill_<bucket> -> finish, the shared
        decode lane carries batched dispatches, sched releases pages."""
        by_lane = {}
        for ev in wave["events"]:
            by_lane.setdefault(ev["tid"], []).append(ev)
        for rid in wave["rids"]:
            lane = by_lane[f"req{rid}"]
            names = [e["name"] for e in lane]
            assert names[0] == "queue_wait"
            assert names[1].startswith("prefill_")
            assert names[-1] == "finish"
            assert lane[-1]["args"]["status"] == "ok"
            # spans on one lane are time-ordered
            ts = [e["ts"] for e in lane]
            assert ts == sorted(ts)
        decode = by_lane.get("decode", [])
        assert decode and all(e["name"] == "decode" for e in decode)
        assert sum(e["args"]["tokens"] for e in decode) > 0
        sched = by_lane.get("sched", [])
        assert len([e for e in sched
                    if e["name"] == "release_pages"]) == 2

    def test_chrome_export_merges_lanes(self, wave, tmp_path):
        rec2 = SpanRecorder(name="other")
        rec2.add("x", rec2.now())
        path = export_chrome(str(tmp_path / "tl.json"),
                             [wave["eng"].spans, rec2])
        doc = json.load(open(path))
        evs = doc["traceEvents"]
        pids = {e["pid"] for e in evs}
        assert pids == {1, 2}
        names = {e["args"]["name"] for e in evs
                 if e["name"] == "process_name"}
        assert names == {"serving", "other"}
        # integer tids + thread_name metadata for every named lane
        assert all(isinstance(e["tid"], int) for e in evs)
        lanes = {e["args"]["name"] for e in evs
                 if e["name"] == "thread_name" and e["pid"] == 1}
        assert {"decode", "sched"} <= lanes

    def test_concurrent_scrapes_never_torn(self, wave):
        """Every mid-wave scrape is internally consistent: for each
        histogram, the +Inf bucket equals its _count — a torn read
        (count bumped, bucket not yet) would break this."""
        assert wave["scrapes"], "scraper thread never landed a scrape"
        for txt in wave["scrapes"]:
            vals = _parse_prom(txt)
            counts = {k: v for k, v in vals.items()
                      if k.endswith("_count") and "{" not in k}
            for ck, cv in counts.items():
                base = ck[:-len("_count")]
                inf_key = base + '_bucket{le="+Inf"}'
                if inf_key in vals:
                    assert vals[inf_key] == cv, (ck, txt[:400])

    def test_final_scrape_matches_registry(self, wave):
        assert wave["final"] == wave["prom"]

    def test_engine_close_shuts_exporter(self, wave):
        import urllib.request
        eng = wave["eng"]
        url = wave["exporter"].url
        eng.close()
        with pytest.raises(OSError):
            urllib.request.urlopen(url + "/metrics", timeout=2)


class TestFlightRecorder:
    def test_ring_keeps_last_n_in_arrival_order(self):
        rec = flightrec.FlightRecorder(capacity=4)
        for i in range(10):
            rec.note("step", i=i)
        got = rec.records()
        assert [r["i"] for r in got] == [6, 7, 8, 9]
        assert [r["seq"] for r in got] == [6, 7, 8, 9]

    def test_dump_parses_and_never_clobbers(self, tmp_path):
        rec = flightrec.FlightRecorder(capacity=8,
                                       run_dir=str(tmp_path))
        rec.note("step", loss=float("nan"), i=1)
        p1 = rec.dump("boom", extra={"x": 1})
        p2 = rec.dump("boom")
        assert p1 != p2 and os.path.basename(p1) == "flight_boom.json"
        doc = json.load(open(p1))
        assert doc["reason"] == "boom" and doc["x"] == 1
        assert doc["records"][0]["loss"] is None   # NaN -> null
        assert isinstance(doc.get("registry"), dict)
        assert rec.dumps == [p1, p2]

    def test_reason_sanitized_into_filename(self, tmp_path):
        rec = flightrec.FlightRecorder(run_dir=str(tmp_path))
        p = rec.dump("we/ird reason!")
        assert os.path.basename(p) == "flight_we_ird_reason_.json"

    def test_dump_failure_returns_none(self):
        rec = flightrec.FlightRecorder(
            run_dir="/dev/null/not_a_dir")
        assert rec.dump("x") is None   # never raises

    def test_env_dir_resolution(self, tmp_path, monkeypatch):
        d = tmp_path / "env_dir"
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(d))
        rec = flightrec.FlightRecorder()
        p = rec.dump("envtest")
        assert p is not None and os.path.dirname(p) == str(d)

    def test_serve_step_exception_dumps(self, tmp_path, monkeypatch):
        import paddle_tpu as paddle
        from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
        from paddle_tpu.nlp.serving import ServingEngine
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        paddle.seed(0)
        model = GPTForCausalLM(_resolve_config("gpt-tiny",
                                               num_attention_heads=1))
        eng = ServingEngine(model, max_slots=1, page_size=8,
                            max_seq_len=32)
        monkeypatch.setattr(
            eng, "_step_impl",
            lambda: (_ for _ in ()).throw(RuntimeError("kaboom")))
        with pytest.raises(RuntimeError, match="kaboom"):
            eng.step()
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight_serve_exception")]
        assert len(dumps) == 1
        doc = json.load(open(tmp_path / dumps[0]))
        assert "kaboom" in doc["error"]
        eng.close()

    def test_fit_exception_dumps(self, tmp_path, monkeypatch):
        import paddle_tpu as paddle
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        paddle.seed(0)
        net = paddle.nn.Linear(4, 2)
        model = paddle.Model(net)
        model.prepare(paddle.optimizer.AdamW(
            1e-2, parameters=net.parameters()),
            paddle.nn.CrossEntropyLoss())
        X = np.zeros((8, 4), "float32")
        Y = np.zeros((8,), "int64")

        class BoomCB:
            def __getattr__(self, name):
                return lambda *a, **k: None

            def on_train_batch_end(self, step, logs=None):
                if step == 1:
                    raise RuntimeError("cb boom")
        with pytest.raises(RuntimeError, match="cb boom"):
            model.fit(paddle.io.TensorDataset([X, Y]), epochs=1,
                      batch_size=4, verbose=0, shuffle=False,
                      callbacks=[BoomCB()])
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight_fit_exception")]
        assert len(dumps) == 1
        doc = json.load(open(tmp_path / dumps[0]))
        assert "cb boom" in doc["error"]

    def test_guard_rollback_dump_contains_storm_records(
            self, tmp_path, monkeypatch):
        """The acceptance shape: a guard-tripping run leaves a
        parseable flight_rollback.json whose ring holds the rollback
        window's own guard_step records."""
        import paddle_tpu as paddle
        from paddle_tpu.resilience import TrainGuard
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        paddle.seed(0)
        net = paddle.nn.Linear(8, 4)
        model = paddle.Model(net)
        guard = TrainGuard(snapshot_every=1, rollback_after=3)
        model.prepare(paddle.optimizer.AdamW(
            1e-2, parameters=net.parameters()),
            paddle.nn.CrossEntropyLoss(), guard=guard)
        rng = np.random.default_rng(0)
        X = rng.standard_normal((24, 8)).astype("float32")
        Y = rng.integers(0, 4, (24,)).astype("int64")
        faults.inject("nan_grads", step=2, count=3)
        model.fit(paddle.io.TensorDataset([X, Y]), epochs=1,
                  batch_size=4, verbose=0, shuffle=False)
        assert guard.rollbacks == 1
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight_rollback")]
        assert len(dumps) == 1
        doc = json.load(open(tmp_path / dumps[0]))
        bad = [r for r in doc["records"]
               if r["kind"] == "guard_step" and not r["ok"]]
        assert len(bad) == 3            # the storm's own records
        assert bad[-1]["outcome"] == "rolled_back"
        assert doc["guard"]["rollbacks"] == 1
        assert any(r["kind"] == "guard_rollback"
                   for r in doc["records"])


class TestSpanRecorder:
    def test_bounded_ring_and_clear(self):
        rec = SpanRecorder(maxlen=3)
        for i in range(5):
            rec.instant(f"i{i}")
        assert [e["name"] for e in rec.events()] == ["i2", "i3", "i4"]
        rec.clear()
        assert rec.events() == []

    def test_span_context_manager_and_args(self):
        rec = SpanRecorder()
        with rec.span("work", tid="lane", detail=7):
            pass
        ev = rec.events()[0]
        assert ev["name"] == "work" and ev["ph"] == "X"
        assert ev["args"] == {"detail": 7} and ev["dur"] >= 0

    def test_recorders_share_one_clock(self, tmp_path):
        a, b = SpanRecorder(name="a"), SpanRecorder(name="b")
        t = SpanRecorder.now()
        a.add("first", t, t + 0.001)
        b.add("second", t + 0.002, t + 0.003)
        path = export_chrome(str(tmp_path / "m.json"), [a, b])
        evs = [e for e in json.load(open(path))["traceEvents"]
               if e["ph"] == "X"]
        assert evs[0]["name"] == "first"    # cross-recorder ordering
        assert evs[1]["ts"] > evs[0]["ts"]

    def test_profiler_regions_land_on_span_bridge(self):
        from paddle_tpu.profiler import Profiler, RecordEvent
        prof = Profiler(registry=False)
        with prof.record_event("regionA", sync=False):
            pass
        with RecordEvent("regionB", profiler=prof):
            pass
        names = [e["name"] for e in prof.spans.events()]
        assert names == ["regionA", "regionB"]
        assert all(e["tid"] == "regions"
                   for e in prof.spans.events())


class TestMeasuredMFUGauges:
    def test_callback_publishes_measured_mfu(self, tmp_path,
                                             monkeypatch):
        import paddle_tpu as paddle
        from paddle_tpu.observability.telemetry import TelemetryCallback
        # a small peak so the tiny model's MFU survives the JSONL
        # rounding (the gauges are unrounded either way)
        monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e8")
        paddle.seed(0)
        net = paddle.nn.Linear(8, 4)
        model = paddle.Model(net)
        model.prepare(paddle.optimizer.AdamW(
            1e-2, parameters=net.parameters()),
            paddle.nn.CrossEntropyLoss())
        X = np.random.default_rng(0).standard_normal(
            (16, 8)).astype("float32")
        Y = np.random.default_rng(0).integers(0, 4, (16,)).astype("int64")
        reg = MetricsRegistry()
        cb = TelemetryCallback(run_dir=str(tmp_path), registry=reg,
                               write_metrics=False,
                               flops_per_step=2 * 8 * 4 * 4 * 3)
        model.fit(paddle.io.TensorDataset([X, Y]), epochs=1,
                  batch_size=4, verbose=0, shuffle=False,
                  callbacks=[cb])
        assert reg.get("train_peak_flops").value == 1e8
        m = reg.get("train_mfu_measured")
        assert m is not None and 0 < m.value < 1
        a = reg.get("train_mfu_analytic")
        assert a is not None and 0 < a.value < 1
        # JSONL records carry both legs
        recs = [r for r in cb.logger.iter_records()
                if r["kind"] == "train_step"]
        assert recs and recs[-1]["mfu_measured"] > 0
        # spans export landed next to the jsonl
        assert cb.spans_path and os.path.exists(cb.spans_path)

    def test_mfu_gauges_absent_without_peak(self, tmp_path,
                                            monkeypatch):
        import paddle_tpu as paddle
        from paddle_tpu.observability.telemetry import TelemetryCallback
        monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)
        paddle.seed(0)
        net = paddle.nn.Linear(8, 4)
        model = paddle.Model(net)
        model.prepare(paddle.optimizer.AdamW(
            1e-2, parameters=net.parameters()),
            paddle.nn.CrossEntropyLoss())
        X = np.zeros((8, 8), "float32")
        Y = np.zeros((8,), "int64")
        reg = MetricsRegistry()
        cb = TelemetryCallback(run_dir=str(tmp_path), registry=reg,
                               write_metrics=False)
        model.fit(paddle.io.TensorDataset([X, Y]), epochs=1,
                  batch_size=4, verbose=0, shuffle=False,
                  callbacks=[cb])
        # no resolvable peak on CPU -> honest absence, not a made-up 0
        assert reg.get("train_mfu_measured") is None
        assert reg.get("train_mfu_analytic") is None


class TestMetricsDiffTool:
    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def _dump(self, path, fill):
        reg = MetricsRegistry()
        fill(reg)
        reg.dump(str(path))
        return str(path)

    def _run(self, *argv):
        import subprocess
        import sys as _sys
        return subprocess.run(
            [_sys.executable, "tools/metrics_diff.py", *argv],
            cwd=self.REPO, capture_output=True, text=True, timeout=60)

    def test_diff_reports_deltas_added_removed(self, tmp_path):
        a = self._dump(tmp_path / "a.json", lambda r: (
            r.counter("steps").inc(10), r.gauge("gone").set(1)))
        b = self._dump(tmp_path / "b.json", lambda r: (
            r.counter("steps").inc(13), r.gauge("fresh").set(2)))
        p = self._run(a, b)
        assert p.returncode == 0, p.stderr[-1000:]
        rep = json.loads(p.stdout.strip().splitlines()[-1])
        assert rep["ok"] is True
        assert rep["counters"]["steps"]["delta"] == 3
        assert rep["added"] == ["fresh"] and rep["removed"] == ["gone"]

    def test_fail_on_quantile_regression(self, tmp_path):
        def fast(r):
            h = r.histogram("lat", buckets=(0.001, 0.01, 0.1))
            for _ in range(10):
                h.observe(0.002)

        def slow(r):
            h = r.histogram("lat", buckets=(0.001, 0.01, 0.1))
            for _ in range(10):
                h.observe(0.05)
        a = self._dump(tmp_path / "a.json", fast)
        b = self._dump(tmp_path / "b.json", slow)
        p = self._run(a, b, "--fail-on", "lat:p99>10%")
        assert p.returncode == 1
        rep = json.loads(p.stdout.strip().splitlines()[-1])
        assert not rep["ok"]
        assert rep["failures"][0]["series"] == "lat"
        # reversed direction: improvement passes the same gate
        p = self._run(b, a, "--fail-on", "lat:p99>10%")
        assert p.returncode == 0

    def test_fail_on_counter_increase_and_throughput_drop(
            self, tmp_path):
        a = self._dump(tmp_path / "a.json", lambda r: (
            r.counter("retraces").inc(0), r.gauge("tok_s").set(100)))
        b = self._dump(tmp_path / "b.json", lambda r: (
            r.counter("retraces").inc(1), r.gauge("tok_s").set(80)))
        p = self._run(a, b, "--fail-on", "retraces>0%",
                      "--fail-on", "tok_s<10%")
        assert p.returncode == 1
        rep = json.loads(p.stdout.strip().splitlines()[-1])
        assert {f["series"] for f in rep["failures"]} == \
            {"retraces", "tok_s"}

    def test_bad_spec_is_an_argparse_error(self, tmp_path):
        a = self._dump(tmp_path / "a.json", lambda r: None)
        p = self._run(a, a, "--fail-on", "nonsense")
        assert p.returncode == 2
        assert "grammar" in p.stderr


class TestGuardOutcomeAfterRollback:
    def test_storm_outlasting_rollback_keeps_skipping_one_dump(
            self, tmp_path, monkeypatch):
        """Review regression: a storm LONGER than rollback_after must
        report the post-rollback bad steps as 'skipped' (consecutive
        count restarted) and dump exactly one flight record — not
        re-report 'rolled_back' and re-dump every further bad step."""
        import paddle_tpu as paddle
        from paddle_tpu.resilience import TrainGuard
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        paddle.seed(0)
        net = paddle.nn.Linear(8, 4)
        model = paddle.Model(net)
        guard = TrainGuard(snapshot_every=1, rollback_after=3)
        model.prepare(paddle.optimizer.AdamW(
            1e-2, parameters=net.parameters()),
            paddle.nn.CrossEntropyLoss(), guard=guard)
        rng = np.random.default_rng(0)
        X = rng.standard_normal((32, 8)).astype("float32")
        Y = rng.integers(0, 4, (32,)).astype("int64")
        faults.inject("nan_grads", step=2, count=4)   # 4-step storm
        model.fit(paddle.io.TensorDataset([X, Y]), epochs=1,
                  batch_size=4, verbose=0, shuffle=False)
        assert guard.rollbacks == 1
        assert guard.skipped_steps == 4
        assert guard.last_outcome == "ok"     # recovered after storm
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight_rollback")]
        assert len(dumps) == 1                # ONE dump, not per step
        doc = json.load(open(tmp_path / dumps[0]))
        outcomes = [r["outcome"] for r in doc["records"]
                    if r["kind"] == "guard_step" and not r["ok"]]
        assert outcomes == ["skipped", "skipped", "rolled_back"]
        # the 4th bad step (after the dump) went back to 'skipped'
        ring = flightrec.get_recorder().records()
        post = [r for r in ring if r["kind"] == "guard_step"
                and not r["ok"]][-1]
        assert post["outcome"] == "skipped"
