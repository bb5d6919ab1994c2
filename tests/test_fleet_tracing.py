"""Fleet-wide distributed tracing, latency attribution, SLO burn rate
(paddle_tpu/observability/{dtrace,slo}.py + serving_fleet wiring).

Pins the ISSUE-8 contracts (docs/observability.md "Distributed
tracing & SLOs"):

- every fleet request yields ONE causally-linked span tree covering
  placement wait, transport, and each replica leg's queue/prefill/
  decode — and its hop-by-hop attribution sums to the measured
  end-to-end wall time within tolerance;
- a crash-mid-decode failover keeps BOTH replica legs in the same
  tree (the lost leg annotated ``failover_source``, the continuation
  carrying the prefix-dedup boundary) and still attributes within
  tolerance;
- a hedged request's losing leg stays in the tree as
  ``outcome=cancelled``;
- the cross-replica Perfetto merge is valid traceEvents JSON with a
  router lane, one lane per replica, and monotonic per-lane spans;
- burn-rate alerts fire on an injected deadline-miss storm and clear
  after recovery, scrapeable as ``fleet_slo_*`` gauges;
- the flight recorder dumps on fleet failover / shed storm / router
  exception with the fleet registry + victim trace tree attached;
- store hygiene: eviction drops WHOLE trees (never an interior
  node), emission is suppressed under ``introspecting()``, exports
  stay RFC-valid under NaN/Inf — and fleet compile counts stay
  frozen with tracing enabled.

`pytest -m chaos` selects the chaos classes.
"""
import json
import time
from urllib.request import urlopen

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
from paddle_tpu.nlp.serving import ServingEngine
from paddle_tpu.observability import dtrace as dtrace_mod
from paddle_tpu.observability.dtrace import TraceStore, hop
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.observability.slo import SLObjective, SLOTracker
from paddle_tpu.observability.spans import SpanRecorder
from paddle_tpu.resilience import faults
from paddle_tpu.serving_fleet import FleetRouter, InprocReplica

NEW_TOK = 10
WAVE_LENS = (5, 12, 17, 9)


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


def _fleet(model, n=3, router_kw=None, **engine_kw):
    # fresh global trace store per fleet: the engines record into the
    # process-global store, so the router must share it
    dtrace_mod.get_store().clear()
    engines = [_engine(model, **engine_kw) for _ in range(n)]
    for e in engines:
        _warm(e)
    frozen = [e.compile_counts() for e in engines]
    reps = [InprocReplica(f"r{i}", e) for i, e in enumerate(engines)]
    router = FleetRouter(reps, **(router_kw or {}))
    return router, reps, engines, frozen


def _assert_frozen(engines, frozen, router):
    for i, eng in enumerate(engines):
        assert eng.compile_counts() == frozen[i], \
            f"replica {i} compiled something with tracing on"
    assert router.compile_report()["unexpected_retraces"] == 0


def _legs(report):
    return [h for h in report["attribution"]["hops"]
            if h["name"] == "replica_leg"]


# -- trace store units ---------------------------------------------------


class TestTraceStore:
    def test_whole_tree_eviction_never_orphans(self):
        s = TraceStore(max_traces=3)
        ctxs = []
        for i in range(8):
            ctx = s.new_trace(rid=i)
            leg = s.start_span(ctx, "leg", proc="r0")
            s.add_span(leg, "queue", dtrace_mod.now())
            s.end_span(leg, outcome="ok")
            s.end_span(ctx, outcome="ok")
            ctxs.append(ctx)
        assert len(s.trace_ids()) == 3
        # only the NEWEST whole trees survive; every surviving span's
        # parent is present (no interior-node eviction)
        for tid in s.trace_ids():
            spans = s.spans(tid)
            ids = {sp["id"] for sp in spans}
            assert all(sp["parent"] is None or sp["parent"] in ids
                       for sp in spans)
            assert s.tree(tid)["root"]["name"] == "request"
        assert s.tree(ctxs[0]["trace_id"]) is None  # oldest: whole
        #                                             tree gone

    def test_truncation_drops_new_spans_not_interior_nodes(self):
        s = TraceStore(max_spans_per_trace=3)
        ctx = s.new_trace(rid=1)
        leg = s.start_span(ctx, "leg", proc="r0")
        assert s.add_span(leg, "queue", dtrace_mod.now()) is not None
        # cap reached: new spans are refused, the tree stays intact
        assert s.add_span(leg, "prefill_16", dtrace_mod.now()) is None
        assert s.start_span(ctx, "leg2", proc="r1") is None
        t = s.tree(ctx["trace_id"])
        assert t["truncated"]
        ids = {sp["id"] for sp in s.spans(ctx["trace_id"])}
        assert all(sp["parent"] is None or sp["parent"] in ids
                   for sp in s.spans(ctx["trace_id"]))

    def test_hop_budget_exhausts_to_none(self):
        s = TraceStore()
        ctx = s.new_trace(hops=2)
        h1 = hop(ctx)
        h2 = hop(h1)
        assert h1["hops"] == 1 and h2["hops"] == 0
        assert hop(h2) is None
        assert hop(None) is None

    def test_suppressed_under_introspection(self):
        from paddle_tpu.observability import introspect
        s = TraceStore()
        rec = SpanRecorder()
        introspect._introspecting.on = True
        try:
            assert s.new_trace(rid=1) is None
            assert rec.add("x", dtrace_mod.now()) is None
            assert rec.instant("y") is None
        finally:
            introspect._introspecting.on = False
        assert s.trace_ids() == []
        assert rec.events() == []
        # and emission works again once the flag drops
        assert s.new_trace(rid=1) is not None
        assert rec.add("x", dtrace_mod.now()) is not None

    def test_export_rfc_valid_under_nan_inf(self, tmp_path):
        s = TraceStore()
        ctx = s.new_trace(rid=1)
        s.add_span(ctx, "queue", dtrace_mod.now(),
                   args={"bad": float("nan"), "worse": float("inf")})
        s.end_span(ctx, outcome="ok")
        path = s.export_chrome(str(tmp_path / "t.json"))
        doc = json.load(open(path))  # bare NaN tokens would raise
        assert doc["traceEvents"]

    def test_serial_sum_excludes_only_hedge_losers(self):
        """A client-CANCELLED leg is real serial work and stays in
        hops_sum_s; only hedge_loser-annotated legs (which overlap
        the winner by construction) are excluded."""
        s = TraceStore()
        ctx = s.new_trace(rid=1, t0=100.0)
        a = s.start_span(ctx, "replica_leg", proc="r0", t0=100.0)
        s.end_span(a, t1=102.0, outcome="cancelled")
        b = s.start_span(ctx, "replica_leg", proc="r1", t0=100.5,
                         args={"hedge_loser": True})
        s.end_span(b, t1=101.5, outcome="cancelled")
        s.end_span(ctx, t1=102.0, outcome="cancelled")
        att = s.attribution(ctx["trace_id"])
        assert att["hops_sum_s"] == pytest.approx(2.0)
        assert att["within_tolerance"]

    def test_summaries_one_pass_index(self):
        s = TraceStore()
        ctx = s.new_trace(rid=9, t0=10.0)
        s.end_span(ctx, t1=10.5, outcome="ok")
        (row,) = s.summaries()
        assert row["rid"] == 9 and row["outcome"] == "ok"
        assert row["e2e_s"] == pytest.approx(0.5)
        assert row["spans"] == 1 and not row["truncated"]

    def test_end_span_first_close_wins(self):
        s = TraceStore()
        ctx = s.new_trace(rid=1)
        leg = s.start_span(ctx, "leg", proc="r0")
        s.end_span(leg, outcome="cancelled")
        s.end_span(leg, outcome="ok")  # late result: must not rewrite
        spans = {sp["name"]: sp for sp in s.spans(ctx["trace_id"])}
        assert spans["leg"]["outcome"] == "cancelled"


# -- SLO units -----------------------------------------------------------


class TestSLOTracker:
    def _tracker(self, reg=None):
        return SLOTracker(
            [SLObjective("e2e", "latency", target=0.9, threshold_s=1.0),
             SLObjective("availability", "availability", target=0.9)],
            windows=[{"short_s": 1.0, "long_s": 5.0, "burn": 2.0}],
            registry=reg)

    def test_alert_fires_on_storm_and_clears_after_recovery(self):
        reg = MetricsRegistry()
        tr = self._tracker(reg)
        for i in range(20):
            tr.record_latency("e2e", 5.0, now=10.0 + i * 0.01)
        rep = tr.evaluate(now=10.3)
        assert rep["e2e"]["alert"]
        assert reg.get("fleet_slo_alert", {"slo": "e2e"}).value == 1
        for i in range(50):
            tr.record_latency("e2e", 0.1, now=12.0 + i * 0.01)
        rep = tr.evaluate(now=16.0)  # short window clean -> clears
        assert not rep["e2e"]["alert"]
        assert reg.get("fleet_slo_alert", {"slo": "e2e"}).value == 0

    def test_no_traffic_burns_nothing(self):
        tr = self._tracker()
        rep = tr.evaluate(now=100.0)
        assert rep["e2e"]["sli"] is None
        assert not rep["e2e"]["alert"]

    def test_availability_classification(self):
        tr = self._tracker()
        # all inside the 5s retention horizon at evaluate time
        for i in range(9):
            tr.record_event("availability", good=True,
                            now=55.5 + i * 0.5)
        tr.record_event("availability", good=False, now=59.9)
        rep = tr.evaluate(now=60.0)
        assert rep["availability"]["events"] == 10
        assert rep["availability"]["bad"] == 1
        assert rep["availability"]["sli"] == pytest.approx(0.9)

    def test_validation(self):
        with pytest.raises(ValueError, match="threshold_s"):
            SLObjective("x", "latency")
        with pytest.raises(ValueError, match="latency | availability"):
            SLObjective("x", "nope", threshold_s=1.0)
        with pytest.raises(ValueError, match="duplicate"):
            SLOTracker([SLObjective("a", "availability", target=0.9),
                        SLObjective("a", "availability", target=0.9)])


# -- fleet chaos ---------------------------------------------------------


@pytest.mark.chaos
class TestFleetTracingChaos:
    def test_clean_wave_attribution_and_endpoints(self, gpt_model,
                                                  wave):
        """Every request of a clean wave yields one span tree whose
        hops cover e2e within tolerance; /traces, /report and
        /healthz answer with the new payloads; compile counts stay
        frozen with tracing enabled."""
        prompts, refs = wave
        router, reps, engines, frozen = _fleet(gpt_model, n=2)
        exp = router.serve_metrics(port=0)
        try:
            rids = [router.submit(p, NEW_TOK) for p in prompts]
            res = {r["id"]: r for r in router.run_to_completion()}
            assert [res[i]["tokens"] for i in rids] == refs
            for rid in rids:
                assert res[rid]["trace_id"]
                rep = router.trace_report(rid)
                att = rep["attribution"]
                assert att["within_tolerance"], att
                assert att["e2e_s"] == pytest.approx(
                    res[rid]["age_s"], rel=0.2, abs=0.05)
                names = [h["name"] for h in att["hops"]]
                assert "placement_wait" in names
                legs = _legs(rep)
                assert len(legs) == 1 and legs[0]["outcome"] == "ok"
                kid_names = [k["name"] for k in legs[0]["children"]]
                assert "queue" in kid_names
                assert any(k.startswith("prefill_")
                           for k in kid_names)
                assert "decode" in kid_names
                assert "transport_submit" in kid_names
                # serial hops sum to e2e within the 5% tolerance
                assert abs(att["hops_sum_s"] - att["e2e_s"]) \
                    <= 0.05 * att["e2e_s"] + 0.01
            # live endpoints
            idx = json.loads(urlopen(f"{exp.url}/traces",
                                     timeout=5).read().decode())
            assert {t["rid"] for t in idx["traces"]} >= set(rids)
            one = json.loads(urlopen(f"{exp.url}/traces/{rids[0]}",
                                     timeout=5).read().decode())
            assert one["trace"]["root"]["name"] == "request"
            report = json.loads(urlopen(f"{exp.url}/report",
                                        timeout=5).read().decode())
            assert report["fleet_compile_report"][
                "unexpected_retraces"] == 0
            health = json.loads(urlopen(f"{exp.url}/healthz",
                                        timeout=5).read().decode())
            assert "slo" in health
            metrics = urlopen(f"{exp.url}/metrics",
                              timeout=5).read().decode()
            assert "fleet_slo_alert" in metrics
            _assert_frozen(engines, frozen, router)
        finally:
            router.close()

    def test_crash_failover_one_trace_two_legs(self, gpt_model, wave,
                                               tmp_path, monkeypatch):
        """THE acceptance drill: a crash-mid-decode failover produces
        ONE trace with two causally-linked replica legs (lost leg
        ``failover_source`` with the harvested prefix, continuation
        carrying the prefix-dedup boundary), attribution still sums
        to e2e within tolerance, the merged Perfetto timeline carries
        a router lane + per-replica lanes with monotonic spans, and
        the flight recorder dumped the failover with the victim's
        tree."""
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        from paddle_tpu.observability import flightrec
        flightrec.get_recorder().clear()
        prompts, refs = wave
        router, reps, engines, frozen = _fleet(gpt_model)
        try:
            with faults.scenario(("replica_crash", {"replica": "r1"})):
                rids = [router.submit(p, NEW_TOK) for p in prompts]
                res = {r["id"]: r for r in router.run_to_completion()}
            assert [res[i]["tokens"] for i in rids] == refs
            victims = [rid for rid in rids if res[rid]["failovers"]]
            assert victims, "the crash must have cost someone a leg"
            for rid in victims:
                rep = router.trace_report(rid)
                legs = _legs(rep)
                assert len(legs) >= 2, \
                    "failover must leave both legs in ONE tree"
                lost = [h for h in legs
                        if h["outcome"] == "failover_source"]
                assert lost and lost[0]["proc"] == "r1"
                cont = [h for h in legs if h["args"].get("failover_of")]
                assert cont, "continuation leg must be in the tree"
                for h in cont:
                    assert ("prefix_dedup" in h["args"]) == \
                        (h["args"].get("prefix_tokens", 0) > 0)
                att = rep["attribution"]
                assert att["within_tolerance"], att
                assert abs(att["hops_sum_s"] - att["e2e_s"]) \
                    <= 0.05 * att["e2e_s"] + 0.01
            _assert_frozen(engines, frozen, router)
            # merged Perfetto timeline: router + both replica lanes,
            # valid traceEvents JSON, monotonic per-lane spans
            path = router.export_timeline(str(tmp_path / "fleet.json"))
            doc = json.load(open(path))
            procs = {e["args"]["name"] for e in doc["traceEvents"]
                     if e.get("name") == "process_name"}
            assert "router" in procs
            assert {"r0", "r1"} & procs == {"r0", "r1"}
            lanes = {}
            for e in doc["traceEvents"]:
                if e.get("ph") == "X":
                    assert e["dur"] >= 0
                    lanes.setdefault((e["pid"], e["tid"]),
                                     []).append(e["ts"])
            assert lanes
            for ts in lanes.values():
                assert ts == sorted(ts), "per-lane spans must be " \
                    "time-ordered"
            # flight recorder: the failover dumped with the victim's
            # trace tree + the fleet registry snapshot
            dumps = sorted(tmp_path.glob("flight_fleet_failover*.json"))
            assert dumps, "failover must trigger a flight dump"
            dump = json.load(open(dumps[0]))
            assert dump["reason"] == "fleet_failover"
            assert dump["failover_reason"] == "crash"
            assert dump["replica"] == "r1"
            assert isinstance(dump["fleet_registry"], dict)
            assert dump["victim_trace"]["root"]["name"] == "request"
        finally:
            router.close()

    def test_hedge_loser_leg_cancelled_in_tree(self, gpt_model, wave):
        """The losing hedge leg stays in the trace, annotated
        outcome=cancelled (hedge_loser) — the winner reads ok."""
        prompts, refs = wave
        router, reps, engines, frozen = _fleet(
            gpt_model, n=2,
            router_kw={"hedge_after_ms": 60, "wedge_timeout_s": 30.0})
        try:
            with faults.scenario(
                    ("replica_slow", {"replica": "r0", "count": 1000,
                                      "seconds": 0.05})):
                rid = router.submit(prompts[0], NEW_TOK)
                (result,) = router.run_to_completion()
            assert result["tokens"] == refs[0] and result["hedged"]
            rep = router.trace_report(rid)
            legs = _legs(rep)
            assert len(legs) == 2
            by_outcome = {h["outcome"]: h for h in legs}
            assert by_outcome["cancelled"]["args"].get("hedge_loser")
            assert by_outcome["cancelled"]["proc"] == "r0"
            assert by_outcome["ok"]["proc"] == "r1"
            assert by_outcome["ok"]["args"].get("hedge")
            # the cancelled leg is excluded from the serial sum but
            # counted in interval coverage — tolerance still holds
            assert rep["attribution"]["within_tolerance"]
            _assert_frozen(engines, frozen, router)
        finally:
            router.close()

    def test_burn_alert_fires_on_deadline_storm_and_clears(
            self, gpt_model, wave, tmp_path, monkeypatch):
        """An injected deadline-miss storm lights the availability
        burn alert (gauges + health rollup); clean traffic after the
        short window clears it. Piggybacks the router-exception
        flight-dump check on the same fleet."""
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        prompts, refs = wave
        slos = (SLObjective("availability", "availability",
                            target=0.9),)
        windows = ({"short_s": 0.5, "long_s": 3.0, "burn": 1.0},)
        router, reps, engines, frozen = _fleet(
            gpt_model, n=1,
            router_kw={"slos": slos, "slo_windows": windows})
        try:
            with faults.scenario(
                    ("replica_slow", {"replica": "r0", "count": 1000,
                                      "seconds": 0.05})):
                for p in prompts:
                    router.submit(p, NEW_TOK, deadline_ms=1)
                res = router.run_to_completion()
            assert {r["status"] for r in res} == {"expired"}
            assert router._slo_state["availability"]["alert"]
            assert router.health()["slo"]["alerting"] \
                == ["availability"]
            g = router.registry.get("fleet_slo_alert",
                                    {"slo": "availability"})
            assert g is not None and g.value == 1
            # recovery: wait out the short window, serve clean
            time.sleep(0.6)
            assert router.generate(prompts[:2],
                                   max_new_tokens=NEW_TOK) == refs[:2]
            assert not router._slo_state["availability"]["alert"]
            assert router.health()["slo"]["alerting"] == []
            assert g.value == 0
            _assert_frozen(engines, frozen, router)
            # router-loop exception -> flight dump, then error
            monkeypatch.setattr(
                router, "_hedge",
                lambda: (_ for _ in ()).throw(RuntimeError("boom")))
            with pytest.raises(RuntimeError, match="boom"):
                router.step()
            dumps = sorted(
                tmp_path.glob("flight_fleet_router_exception*.json"))
            assert dumps and json.load(open(dumps[0]))["error"]
        finally:
            router.close()

    def test_shed_storm_flight_dump(self, gpt_model, wave, tmp_path,
                                    monkeypatch):
        """Sheds past the threshold inside the window dump ONE
        shed-storm flight record carrying a victim trace tree."""
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        prompts, refs = wave
        router, reps, engines, frozen = _fleet(
            gpt_model, n=1, max_slots=1,
            router_kw={"max_queue": 1, "replica_queue_limit": 1,
                       "shed_storm_threshold": 2,
                       "shed_storm_window_s": 0.5})
        try:
            rids = [router.submit(p, NEW_TOK)
                    for p in prompts + prompts]
            res = {r["id"]: r for r in router.run_to_completion()}
            shed = [r for r in rids if res[r]["status"] == "shed"]
            assert len(shed) >= 2
            dumps = sorted(
                tmp_path.glob("flight_fleet_shed_storm*.json"))
            assert len(dumps) == 1, "one storm -> one dump"
            doc = json.load(open(dumps[0]))
            assert doc["shed_in_window"] >= 2
            assert doc["victim_trace"]["root"]["args"]["priority"] == 0
            # a shed request's trace still tiles e2e: its router-queue
            # wait is a hop, not unattributed time
            rep = router.trace_report(shed[0])
            att = rep["attribution"]
            assert att["outcome"] == "shed"
            assert any(h["name"] == "router_queue"
                       for h in att["hops"])
            assert att["within_tolerance"], att
            # re-arm: a SECOND storm after the window drains dumps
            # again (regression: the armed flag used to stay down
            # when the next storm's first batch already met the
            # threshold)
            time.sleep(0.6)
            router._note_shed_storm(shed[:2])
            dumps = sorted(
                tmp_path.glob("flight_fleet_shed_storm*.json"))
            assert len(dumps) == 2, "post-drain storm must re-dump"
        finally:
            router.close()
