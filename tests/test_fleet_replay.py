"""Traffic replay harness (tools/fleet_replay.py) + the FleetRouter
capture wiring (ISSUE 12).

Pins the contracts docs/observability.md "Traffic capture & replay"
claims:

- a capture-armed router archives every admitted request and its
  resolve (tokens + per-hop attribution), trace-sampling-coherent:
  captured requests keep their span tree even under an aggressive
  PADDLE_TPU_TRACE_SAMPLE (``fleet_capture_trace_missing_total`` = 0);
- ``/requests`` serves the recent-resolved index with the archive
  locator (rid/tenant/status/ttft/e2e), mirroring /traces;
- the committed ``tools/golden/replay_wave.json`` spec equals the
  generator's output for its seed (the drift guard);
- golden-mode replay of a live capture is token-exact per rid with
  frozen compile counts and zero new traces, and the verdict's gates
  fire in BOTH directions (clean passes, an injected replica_slow
  regression trips) — vacuity-guarded;
- knob overrides parse/apply (router, engine, placement weights).
"""
import json
import os
import sys
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
from paddle_tpu.nlp.serving import ServingEngine
from paddle_tpu.observability.dtrace import TraceStore
from paddle_tpu.resilience import faults
from paddle_tpu.serving_fleet import FleetRouter, InprocReplica

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import fleet_replay as fr  # noqa: E402
import replay_smoke  # noqa: E402

GOLDEN_WAVE = os.path.join(REPO, "tools", "golden",
                           "replay_wave.json")


@pytest.fixture(scope="module")
def gpt_model():
    paddle.seed(0)
    m = GPTForCausalLM(_resolve_config("gpt-tiny"))
    m.eval()
    return m


@pytest.fixture(scope="module")
def wave():
    # single prefill bucket (lens 4..15 -> bucket 16) keeps the
    # warmup small; two bursts exercise the open-loop schedule
    return fr.synth_wave(3, 8, burst=4, burst_gap_s=0.03,
                         prompt_lens=((4, 15, 1.0),), max_new=6)


@pytest.fixture(scope="module")
def captured(gpt_model, wave, tmp_path_factory):
    """One capture run shared by the replay tests: drive the wave
    through a capture-armed 2-replica fleet, return the archive
    entries + the capture registry snapshot."""
    from paddle_tpu.observability.trafficrec import load_archive
    tmp = tmp_path_factory.mktemp("capture")
    cap_dir = str(tmp / "archive")
    router, engines, frozen = fr.build_fleet(
        wave, capture_dir=cap_dir)
    try:
        _res, _wall, _map = fr.replay(router, wave, timeout_s=60.0)
        reg_snap = router.registry.snapshot()
        frozen_after = [e.compile_counts() for e in engines]
    finally:
        router.close()
        for e in engines:
            e.close()
    entries, meta, stats = load_archive(cap_dir)
    return {"entries": entries, "meta": meta, "stats": stats,
            "registry": reg_snap, "frozen": frozen,
            "frozen_after": frozen_after, "dir": cap_dir}


class TestSynthWave:
    def test_deterministic(self):
        a = fr.synth_wave(7, 12)
        b = fr.synth_wave(7, 12)
        assert a == b
        assert fr.synth_wave(8, 12) != a

    def test_shape(self):
        w = fr.synth_wave(1, 9, burst=3, burst_gap_s=0.5,
                          priorities=(0, 1))
        assert [e["rid"] for e in w] == list(range(9))
        # bursty schedule: 3 pulses of 3, 0.5s apart
        assert sorted({e["arrival_s"] for e in w}) == [0.0, 0.5, 1.0]
        assert {e["priority"] for e in w} <= {0, 1}
        assert all(len(e["prompt"]) >= 4 for e in w)

    def test_committed_golden_matches_generator(self):
        """Tier-1 drift guard: the committed replay_wave.json was
        generated by synth_wave(seed) — a silently changed generator
        must fail HERE."""
        with open(GOLDEN_WAVE) as f:
            golden = json.load(f)
        wave = fr.synth_wave(golden["seed"], golden["n"], burst=4,
                             burst_gap_s=0.05)
        assert replay_smoke._spec(wave) == golden["wave_spec"]
        # and the captured entries carry resolved tokens for every rid
        assert len(golden["entries"]) == golden["n"]
        assert all(e["tokens"] for e in golden["entries"]
                   if e["status"] == "ok")


class TestKnobsAndSchedule:
    def test_parse_knobs(self):
        rkw, ekw, w, akw = fr.parse_knobs(
            ["hedge_after_ms=50", "steps_per_dispatch=2",
             "placement.queued=16", "max_queue=8"])
        assert rkw == {"hedge_after_ms": 50, "max_queue": 8}
        assert ekw == {"steps_per_dispatch": 2}
        assert w == {"queued": 16.0}
        assert akw is None

    def test_parse_autoscale_knobs(self):
        rkw, ekw, w, akw = fr.parse_knobs(
            ["autoscale.max_replicas=3",
             "autoscale.scale_out_cooldown_s=0.5"])
        assert (rkw, ekw, w) == ({}, {}, {})
        assert akw == {"max_replicas": 3, "scale_out_cooldown_s": 0.5}
        with pytest.raises(ValueError, match="autoscale params"):
            fr.parse_knobs(["autoscale.typo=1"])

    def test_parse_knobs_unknown_fails(self):
        with pytest.raises(ValueError, match="unknown knob"):
            fr.parse_knobs(["typo_knob=1"])
        with pytest.raises(ValueError, match="k=v"):
            fr.parse_knobs(["justaname"])

    def test_schedule_modes(self):
        entries = [{"arrival_s": 0.0}, {"arrival_s": 1.0},
                   {"arrival_s": 4.0}]
        assert fr.schedule(entries) == [0.0, 1.0, 4.0]
        assert fr.schedule(entries, mode="scaled",
                           time_scale=0.5) == [0.0, 0.5, 2.0]
        assert fr.schedule(entries, mode="rate",
                           rate=2.0) == [0.0, 0.5, 1.0]
        with pytest.raises(ValueError):
            fr.schedule(entries, mode="rate", rate=0)

    def test_placement_weights_validated(self, gpt_model):
        eng = ServingEngine(gpt_model, max_slots=2, page_size=16,
                            max_seq_len=64)
        rep = InprocReplica("w0", eng)
        with pytest.raises(ValueError, match="unknown placement"):
            FleetRouter([rep], placement_weights={"typo": 1.0})
        rep.kill()
        eng.close()


class TestVerdictUnits:
    ENTRY = {"rid": 0, "status": "ok", "tokens": [1, 2],
             "e2e_s": 0.2, "ttft_s": 0.1,
             "hops": [{"name": "replica_leg", "proc": "r0",
                       "dur_s": 0.18, "outcome": "ok"}]}

    def _mk(self, **kw):
        return dict(self.ENTRY, **kw)

    def test_token_mismatch_fails_golden(self):
        v = fr.make_verdict([self._mk()],
                            [self._mk(tokens=[1, 3])], golden=True)
        assert not v["ok"]
        assert any(f["gate"] == "golden" for f in v["failures"])
        assert v["golden"]["mismatched_rids"] == [0]

    def test_golden_vacuity_guard(self):
        # nothing comparable -> failure, not a silent pass
        v = fr.make_verdict([self._mk(status="shed", tokens=None)],
                            [], golden=True)
        assert not v["ok"]
        assert any("vacuous" in str(f.get("reason"))
                   for f in v["failures"])

    def test_ratio_gate_needs_floor_and_ratio(self):
        def hop(d):
            return [{"name": "replica_leg", "proc": "r0",
                     "dur_s": d, "outcome": "ok"}]
        # 3x slower but only +4ms: under the floor, not a regression
        v = fr.make_verdict(
            [self._mk(e2e_s=0.002, ttft_s=0.001, hops=hop(0.0018))],
            [self._mk(e2e_s=0.006, ttft_s=0.003, hops=hop(0.0054))])
        assert v["ok"], v["failures"]
        # 3x slower AND +300ms: trips
        v2 = fr.make_verdict(
            [self._mk(e2e_s=0.2, ttft_s=0.1, hops=hop(0.18))],
            [self._mk(e2e_s=0.6, ttft_s=0.4, hops=hop(0.54))])
        assert not v2["ok"]
        assert {f["gate"] for f in v2["failures"]} >= {
            "e2e_p99_ratio", "ttft_p99_ratio"}

    def test_hop_share_gate(self):
        orig = [self._mk()]
        rep = [self._mk(hops=[
            {"name": "replica_leg", "proc": "r0", "dur_s": 0.1,
             "outcome": "ok"},
            {"name": "placement_wait", "proc": "router",
             "dur_s": 0.08, "outcome": None}])]
        v = fr.make_verdict(orig, rep)
        assert any(f["gate"] == "hop_share_delta"
                   for f in v["failures"])
        row = v["attribution"]["hops"]["placement_wait"]
        assert row["orig_share"] == 0.0
        assert row["replay_share"] > 0.3

    def test_gates_disabled_by_none(self):
        v = fr.make_verdict([self._mk(e2e_s=0.1)],
                            [self._mk(e2e_s=9.9, ttft_s=9.0,
                                      hops=[])],
                            gates={"e2e_p99_ratio": None,
                                   "ttft_p99_ratio": None,
                                   "hop_share_delta": None})
        assert v["ok"], v["failures"]


class TestCaptureEndToEnd:
    def test_archive_complete_and_coherent(self, captured, wave):
        assert len(captured["entries"]) == len(wave)
        assert captured["stats"]["unresolved"] == 0
        assert captured["stats"]["torn_drops"] == 0
        m = captured["registry"]["metrics"]
        assert m["fleet_capture_requests_total"]["value"] == len(wave)
        assert m["fleet_capture_trace_missing_total"]["value"] == 0
        assert m["fleet_capture_errors_total"]["value"] == 0
        # sampling params rode the health plane into the archive meta
        assert "sampling.r0" in captured["meta"]
        assert captured["meta"]["sampling.r0"]["temperature"] == 0.0
        # capture on = still zero new traces
        assert captured["frozen_after"] == captured["frozen"]

    def test_entries_carry_attribution(self, captured):
        for e in captured["entries"]:
            assert e["status"] == "ok"
            assert e["tokens"]
            assert e["e2e_s"] > 0
            names = {h["name"] for h in e["hops"]}
            assert "replica_leg" in names

    def test_golden_replay_token_exact(self, captured, tmp_path):
        v, rep_entries = fr.run_replay(
            captured["entries"], out_dir=str(tmp_path), golden=True)
        assert v["golden"]["token_exact"], v["golden"]
        assert v["golden"]["compared"] == len(captured["entries"])
        assert v["golden"]["compile_frozen"]
        assert v["golden"]["new_traces"] == 0
        assert v["golden"]["unexpected_retraces"] == 0
        assert v["ok"], v["failures"]
        # the replay fleet captured its own archive in the same shape
        assert len(rep_entries) == len(captured["entries"])
        assert v["history"] is not None  # live history cross-check

    def test_regression_replay_trips_gate(self, captured, tmp_path):
        def arm():
            for name in ("r0", "r1"):
                faults.inject("replica_slow", count=10_000,
                              seconds=0.05, replica=name)
        try:
            v, _ = fr.run_replay(captured["entries"],
                                 out_dir=str(tmp_path),
                                 faults_arm=arm)
        finally:
            faults.clear()
        assert not v["ok"]
        assert any(f["gate"] in ("e2e_p99_ratio", "ttft_p99_ratio")
                   for f in v["failures"])

    def test_golden_replay_noncontiguous_rids(self, captured,
                                              tmp_path):
        """A real archive's rids are whatever the capturing router
        minted (prior traffic, rotation, sampling) — golden scoring
        must map the replay fleet's fresh rids back through the
        submit-time rid map, not assume 0..n-1 alignment."""
        shifted = [dict(e, rid=e["rid"] + 1000)
                   for e in captured["entries"]]
        v, rep = fr.run_replay(shifted, out_dir=str(tmp_path),
                               golden=True)
        assert v["golden"]["token_exact"], v["golden"]
        assert v["golden"]["compared"] == len(shifted)
        assert sorted(e["rid"] for e in rep) == sorted(
            e["rid"] for e in shifted)

    def test_knob_override_applies(self, captured, gpt_model):
        rkw, ekw, w, _akw = fr.parse_knobs(
            ["hedge_after_ms=250", "placement.queued=16"])
        router, engines, _ = fr.build_fleet(
            captured["entries"], router_kw=rkw, engine_kw=ekw,
            placement_weights=w)
        try:
            assert router.hedge_after_ms == 250
            assert router.placement_weights["queued"] == 16.0
            assert router.placement_weights["running"] == 2.0
        finally:
            router.close()
            for e in engines:
                e.close()


class TestRequestsEndpointAndSamplingCoherence:
    def test_requests_endpoint(self, gpt_model, tmp_path):
        eng = ServingEngine(gpt_model, max_slots=2, page_size=16,
                            max_seq_len=64, steps_per_dispatch=4)
        rep = InprocReplica("q0", eng)
        router = FleetRouter([rep], capture=str(tmp_path / "cap"))
        exp = router.serve_metrics(port=0)
        try:
            rng = np.random.default_rng(0)
            rids = [router.submit(
                rng.integers(0, 256, (5 + i,)).astype(np.int32), 4,
                tenant="acme") for i in range(3)]
            router.run_to_completion()

            def get(path):
                with urllib.request.urlopen(exp.url + path,
                                            timeout=5) as r:
                    return r.status, json.loads(r.read())

            code, idx = get("/requests")
            assert code == 200
            assert [r["rid"] for r in idx["requests"]] == rids
            row = idx["requests"][0]
            assert row["status"] == "ok"
            assert row["tenant"] == "acme"
            assert row["e2e_s"] > 0
            assert row["archive"]["segment"].startswith("cap-")
            assert isinstance(row["archive"]["offset"], int)
            assert idx["capture"]["dir"] == str(tmp_path / "cap")
            code, one = get(f"/requests/{rids[1]}")
            assert code == 200 and one["rid"] == rids[1]
            try:
                code, _ = get("/requests/9999")
            except urllib.error.HTTPError as e:
                code = e.code
            assert code == 404
            # the index appears in the endpoint catalogue
            try:
                get("/nope")
            except urllib.error.HTTPError as e:
                doc = json.loads(e.read())
                assert "/requests" in doc["endpoints"]
        finally:
            router.close()
            eng.close()

    def test_captured_requests_keep_traces_under_sampling(
            self, gpt_model, tmp_path):
        """Satellite 1: capture<->trace sampling coherence. With an
        aggressive trace head-sampler, every CAPTURED request still
        gets a whole span tree (force-kept), so archived requests
        always carry attribution — zero divergences counted."""
        store = TraceStore(max_traces=64, sample=0.01)
        eng = ServingEngine(gpt_model, max_slots=2, page_size=16,
                            max_seq_len=64, steps_per_dispatch=4)
        rep = InprocReplica("s0", eng)
        router = FleetRouter([rep], capture=str(tmp_path / "cap"),
                             trace_store=store)
        try:
            rng = np.random.default_rng(1)
            rids = [router.submit(
                rng.integers(0, 256, (6,)).astype(np.int32), 4)
                for _ in range(4)]
            router.run_to_completion()
            # every captured request has a tree despite sample=0.01
            for rid in rids:
                assert store.find(rid) is not None
            reg = router.registry
            assert int(reg.get(
                "fleet_capture_trace_missing_total").value) == 0
            assert int(reg.get(
                "fleet_capture_requests_total").value) == len(rids)
        finally:
            router.close()
            eng.close()
        from paddle_tpu.observability.trafficrec import load_archive
        entries, _, _ = load_archive(str(tmp_path / "cap"))
        assert all(e["hops"] for e in entries)

    def test_uncaptured_requests_still_sampled_out(self, gpt_model):
        """The force-keep is CAPTURE-scoped: without capture, the
        head sampler drops trees as configured (whole-tree, counted)."""
        store = TraceStore(max_traces=64, sample=0.5)
        eng = ServingEngine(gpt_model, max_slots=2, page_size=16,
                            max_seq_len=64, steps_per_dispatch=4)
        rep = InprocReplica("u0", eng)
        router = FleetRouter([rep], trace_store=store)
        try:
            rng = np.random.default_rng(2)
            for _ in range(4):
                router.submit(
                    rng.integers(0, 256, (6,)).astype(np.int32), 4)
            router.run_to_completion()
            assert store.sampled_out == 2  # 0.5 keeps every other
        finally:
            router.close()
            eng.close()
