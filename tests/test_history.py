"""Telemetry history plane (observability/history.py), anomaly
sentinel (observability/sentinel.py), the metrics_diff --history
--at/--vs gate and the fleet_top renderer.

Pins the ISSUE-11 contracts:

- downsampling ladder: raw at scrape cadence, 10s/60s rungs holding
  the LAST cumulative value per bucket at its real last-update
  timestamp (a bucket-start stamp would smuggle future increments
  behind a past timestamp);
- range/rate/quantile-over-time reads, including windows that reach
  past the raw ring into the rungs;
- torn-snapshot reload: a snapshot TRUNCATED AT EVERY BYTE OFFSET
  reloads without crashing, never duplicates a sample, and drops at
  most the tail (the journal-fuzz discipline, applied to history);
- registry_snapshot_at + metrics_diff --history --at/--vs: one
  archive, any two instants, the gate runs on it;
- sentinel: quiet through warmup + steady state, fires on a genuine
  excursion (with a parseable fleet_anomaly flight dump + counters),
  re-arms only after the signal clears; offline replay over a saved
  archive; compile-delta signal fires on ANY recompile.
"""
import json
import os
import sys

import pytest

from paddle_tpu.observability.history import HistoryStore
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.observability.sentinel import AnomalySentinel
from paddle_tpu.observability import flightrec

T0 = 1_000_000.0


def _filled_store(n=120, spike_after=None, interval=1.0):
    """A registry driven n scrapes: counter +10/scrape, gauge ramp,
    latency histogram flat at 10ms (spiking to 300ms past
    ``spike_after``)."""
    reg = MetricsRegistry()
    c = reg.counter("w_total")
    g = reg.gauge("w_depth")
    h = reg.histogram("w_seconds")
    hs = HistoryStore(reg, interval_s=interval, raw_samples=64,
                      rungs=((10.0, 32), (60.0, 32)))
    for i in range(n):
        c.inc(10)
        g.set(i)
        h.observe(0.30 if spike_after is not None and i >= spike_after
                  else 0.01)
        hs.scrape(now=T0 + i * interval)
    return reg, hs


class TestHistoryStore:
    def test_ladder_shapes_and_query(self):
        _, hs = _filled_store(n=120)
        assert set(hs.keys()) == {"w_total", "w_depth", "w_seconds"}
        raw = hs.query("w_total", res="raw")
        assert len(raw) == 64          # ring bound, not 120
        assert raw[-1]["v"] == 1200    # cumulative
        ten = hs.query("w_total", res="10s")
        assert len(ten) <= 32
        # rung samples are stamped at their real last-update ts and
        # hold the bucket's LAST cumulative value
        for s in ten:
            exact = hs.query("w_total", t0=s["t"], t1=s["t"],
                             res="raw")
            if exact:   # inside raw reach
                assert exact[0]["v"] == s["v"]
        # gauges carry min/max per bucket
        g10 = hs.query("w_depth", res="10s")[-1]
        assert g10["min"] <= g10["v"] <= g10["max"]

    def test_maybe_scrape_cadence(self):
        reg = MetricsRegistry()
        reg.counter("w_total").inc()
        hs = HistoryStore(reg, interval_s=1.0)
        assert hs.maybe_scrape(now=T0) is not None
        assert hs.maybe_scrape(now=T0 + 0.5) is None
        assert hs.maybe_scrape(now=T0 + 1.5) is not None
        assert hs.scrapes == 2

    def test_rate_and_reset_tolerance(self):
        _, hs = _filled_store(n=120)
        r = hs.rate("w_total", 20.0)
        assert r == pytest.approx(10.0, rel=0.2)
        # a counter reset (process restart) must not go negative
        reg = MetricsRegistry()
        c = reg.counter("w_total")
        hs2 = HistoryStore(reg, interval_s=1.0)
        for i, v in enumerate((100, 200, 300)):
            c.value = v
            hs2.scrape(now=T0 + i)
        c.value = 50   # restart
        hs2.scrape(now=T0 + 3)
        c.value = 150
        hs2.scrape(now=T0 + 4)
        inc = hs2.increase("w_total", T0, T0 + 4)
        assert inc == 300   # 100+100 pre-reset + 100 post, never -150

    def test_rate_reaches_past_raw_ring_into_rungs(self):
        # 120 scrapes, raw ring 64: a 100s window must use the rungs
        _, hs = _filled_store(n=120)
        r = hs.rate("w_total", 100.0, now=T0 + 119)
        assert r == pytest.approx(10.0, rel=0.25)

    def test_quantile_over_time_sees_only_the_window(self):
        _, hs = _filled_store(n=120, spike_after=100)
        now = T0 + 119
        q_spike = hs.quantile_over_time("w_seconds", 0.5, 15.0,
                                        now=now)
        q_clean = hs.quantile_over_time("w_seconds", 0.99, 15.0,
                                        now=T0 + 90)
        assert q_spike > 0.1      # the spike window reads high
        assert q_clean < 0.05     # the clean window never sees it
        # unknown / non-histogram series answer None, never raise
        assert hs.quantile_over_time("nope", 0.99, 5.0) is None
        assert hs.quantile_over_time("w_total", 0.99, 5.0) is None

    def test_registry_snapshot_at(self):
        _, hs = _filled_store(n=120)
        snap = hs.registry_snapshot_at(T0 + 80)
        assert snap["metrics"]["w_total"]["value"] == 810
        hist = snap["metrics"]["w_seconds"]
        assert hist["count"] == 81 and len(hist["counts"]) \
            == len(hist["bounds"]) + 1
        # before the first sample: series omitted, not invented
        assert hs.registry_snapshot_at(T0 - 10)["metrics"] == {}


class TestSnapshotPersistence:
    def test_roundtrip(self, tmp_path):
        _, hs = _filled_store(n=50)
        p = str(tmp_path / "hist.json")
        hs.save(p)
        hs2 = HistoryStore.load(p)
        assert hs2.load_dropped == 0
        assert hs2.keys() == hs.keys()
        for key in hs.keys():
            for res in ("raw", "10s", "60s"):
                assert hs2.query(key, res=res) == hs.query(key,
                                                           res=res)
        assert hs2.rate("w_total", 20.0, now=T0 + 49) \
            == hs.rate("w_total", 20.0, now=T0 + 49)

    def test_torn_snapshot_every_byte_offset(self, tmp_path):
        """The journal-fuzz discipline: truncate at EVERY byte; reload
        never crashes, never duplicates a sample, drops at most the
        tail (sample sets are always a subset of the full archive's,
        and line-prefix truncation loses whole tail chunks only)."""
        _, hs = _filled_store(n=12)   # small → every offset is cheap
        p = str(tmp_path / "hist.json")
        hs.save(p)
        with open(p, "rb") as f:
            data = f.read()
        full = HistoryStore.load(p)
        full_samples = {
            (key, res): [tuple(s) for s in
                         full._series[key].rings[res]]
            for key in full.keys()
            for res in full._series[key].rings}
        tp = str(tmp_path / "torn.json")
        for cut in range(len(data) + 1):
            with open(tp, "wb") as f:
                f.write(data[:cut])
            store = HistoryStore.load(tp)     # must never raise
            for key in store.keys():
                ser = store._series[key]
                for res, ring in ser.rings.items():
                    got = [tuple(s) for s in ring]
                    ref = full_samples.get((key, res), [])
                    # exactly-once: a chunk is whole or absent —
                    # which also rules out any duplicated sample
                    assert got == [] or got == ref, \
                        f"cut={cut} {key}/{res}"
            # monotone tail-loss: what loads is a prefix-subset of
            # the full chunk set
            loaded = {(k, r) for k in store.keys()
                      for r, ring in store._series[k].rings.items()
                      if ring}
            assert loaded <= set(full_samples)

    def test_truncated_tail_drops_are_counted(self, tmp_path):
        _, hs = _filled_store(n=12)
        p = str(tmp_path / "h.json")
        hs.save(p)
        data = open(p, "rb").read()
        with open(p, "wb") as f:
            f.write(data[:len(data) - 5])
        store = HistoryStore.load(p)
        assert store.load_dropped == 1


class TestMetricsDiffHistoryMode:
    def _run(self, argv):
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        import importlib
        md = importlib.import_module("metrics_diff")
        return md.main(argv)

    def test_at_vs_two_instants_and_gate(self, tmp_path, capsys):
        _, hs = _filled_store(n=120, spike_after=100)
        p = str(tmp_path / "hist.json")
        hs.save(p)
        rc = self._run([
            "--history", p, "--at", str(T0 + 50), "--vs", "-0",
            "--quiet"])
        out = json.loads(capsys.readouterr().out.strip()
                         .splitlines()[-1])
        assert rc == 0 and out["ok"]
        # T0+50 is past the raw ring's reach: the 10s rung answers
        # with its latest sample AT-OR-BEFORE the instant (v=500 @
        # t=49) — conservative, never leaking future increments
        assert out["counters"]["w_total"]["a"] == 500
        assert out["counters"]["w_total"]["b"] == 1200
        # the gate trips on the spike between the two instants
        # (relative offsets anchor on the archive's earliest RETAINED
        # sample — the first 10s bucket's last update, T0+9 here)
        rc = self._run([
            "--history", p, "--at", "+85", "--vs", "-0", "--quiet",
            "--fail-on", "w_seconds:p99>100%"])
        assert rc == 1
        # and stays quiet across the clean span
        rc = self._run([
            "--history", p, "--at", "+10", "--vs", "+80", "--quiet",
            "--fail-on", "w_seconds:p99>100%"])
        capsys.readouterr()
        assert rc == 0

    def test_relative_offsets(self, tmp_path, capsys):
        _, hs = _filled_store(n=20)
        p = str(tmp_path / "hist.json")
        hs.save(p)
        rc = self._run(["--history", p, "--at", "+0", "--vs", "-0",
                        "--quiet"])
        out = json.loads(capsys.readouterr().out.strip()
                         .splitlines()[-1])
        assert rc == 0
        assert out["counters"]["w_total"]["a"] == 10
        assert out["counters"]["w_total"]["b"] == 200


def _sentinel_signals():
    return [{"name": "lat_p99", "kind": "quantile",
             "series": "w_seconds", "q": 0.99, "window_s": 5.0,
             "direction": "high"},
            {"name": "rate_low", "kind": "rate", "series": "w_total",
             "window_s": 5.0, "direction": "low"}]


class TestSentinel:
    def test_quiet_then_fires_once_and_rearms(self, tmp_path,
                                              monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        flightrec.get_recorder().clear()
        reg = MetricsRegistry()
        h = reg.histogram("w_seconds")
        c = reg.counter("w_total")
        hs = HistoryStore(reg, interval_s=1.0)
        sen = AnomalySentinel(hs, signals=_sentinel_signals(),
                              registry=reg, warmup=8,
                              min_consecutive=2, eval_interval_s=0.0)
        # steady state: quiet
        for i in range(40):
            h.observe(0.01)
            c.inc(10)
            hs.scrape(now=T0 + i)
            sen.evaluate(now=T0 + i)
        assert sen.fired_total == 0 and sen.alerting() == []
        # excursion: latency x30 — fires ONCE, stays alerting
        for i in range(40, 52):
            h.observe(0.3)
            c.inc(10)
            hs.scrape(now=T0 + i)
            sen.evaluate(now=T0 + i)
        assert sen.fired_total == 1
        assert "lat_p99" in sen.alerting()
        fired = reg.get("fleet_anomaly_fired_total",
                        {"signal": "lat_p99"})
        active = reg.get("fleet_anomaly_active",
                         {"signal": "lat_p99"})
        assert fired.value == 1 and active.value == 1
        # flight dump: parseable, tagged, carries the signal
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight_fleet_anomaly")]
        assert dumps
        doc = json.load(open(tmp_path / dumps[0]))
        assert doc["reason"] == "fleet_anomaly"
        assert doc["signal"] == "lat_p99"
        assert isinstance(doc["recent"], list)
        # recovery: signal clears, re-arms, a SECOND excursion fires
        # a second (fresh) excursion record
        for i in range(52, 90):
            h.observe(0.01)
            c.inc(10)
            hs.scrape(now=T0 + i)
            sen.evaluate(now=T0 + i)
        assert sen.alerting() == []
        assert active.value == 0
        for i in range(90, 102):
            h.observe(0.3)
            c.inc(10)
            hs.scrape(now=T0 + i)
            sen.evaluate(now=T0 + i)
        assert sen.fired_total == 2

    def test_throughput_collapse_fires_low_direction(self):
        reg = MetricsRegistry()
        c = reg.counter("w_total")
        hs = HistoryStore(reg, interval_s=1.0)
        # a SHORT rate window so the collapse hits the band as a
        # cliff: a band with per-eval adaptation absorbs slow ramps
        # by design — the sentinel is a cliff detector, the SLO
        # burn-rate layer owns slow budget spend
        sig = dict(_sentinel_signals()[1], window_s=2.0)
        sen = AnomalySentinel(hs, signals=[sig],
                              warmup=8, min_consecutive=2, z=3.0,
                              eval_interval_s=0.0, flight=False)
        for i in range(40):
            c.inc(100)
            hs.scrape(now=T0 + i)
            sen.evaluate(now=T0 + i)
        assert sen.fired_total == 0
        for i in range(40, 52):          # collapse: +0/s
            hs.scrape(now=T0 + i)
            sen.evaluate(now=T0 + i)
        assert sen.fired_total == 1
        assert sen.state()["rate_low"]["alert"]

    def test_demand_gate_suppresses_idle_collapse(self):
        """A client going quiet must NOT read as a throughput
        collapse: with demand_gate=fleet_pending, zero-demand windows
        evaluate as no-data (alert clears); the same collapse WITH
        pending work still fires."""
        reg = MetricsRegistry()
        c = reg.counter("w_total")
        g = reg.gauge("fleet_pending")
        hs = HistoryStore(reg, interval_s=1.0)
        sig = {"name": "tok_low", "kind": "rate", "series": "w_total",
               "window_s": 2.0, "direction": "low",
               "demand_gate": "fleet_pending"}
        sen = AnomalySentinel(hs, signals=[sig], warmup=8,
                              min_consecutive=2, z=3.0,
                              eval_interval_s=0.0, flight=False)
        for i in range(40):
            c.inc(100)
            g.set(3)
            hs.scrape(now=T0 + i)
            sen.evaluate(now=T0 + i)
        # demand stops WITH the throughput: suppressed, stays quiet
        g.set(0)
        for i in range(40, 60):
            hs.scrape(now=T0 + i)
            st = sen.evaluate(now=T0 + i)
        assert sen.fired_total == 0
        assert st["tok_low"]["value"] is None
        # demand and traffic return long enough for the band to
        # re-tighten, then throughput collapses WITH work pending:
        # a real regression, and it fires
        g.set(3)
        for i in range(60, 95):
            c.inc(100 if i < 85 else 0)
            hs.scrape(now=T0 + i)
            sen.evaluate(now=T0 + i)
        assert sen.fired_total == 1

    def test_compile_delta_fires_on_any_increase(self):
        reg = MetricsRegistry()
        hs = HistoryStore(reg, interval_s=1.0)
        counts = {"r0": {"decode": 1, "prefill_16": 1}}
        report = {"replicas": counts, "unexpected_retraces": 0}
        sen = AnomalySentinel(
            hs, signals=[{"name": "recompiles", "kind": "delta"}],
            compile_fn=lambda: report, eval_interval_s=0.0,
            flight=False)
        hs.scrape(now=T0)
        sen.evaluate(now=T0)           # baseline
        sen.evaluate(now=T0 + 1)
        assert sen.fired_total == 0
        counts["r0"]["prefill_32"] = 1  # a mid-wave recompile
        sen.evaluate(now=T0 + 2)
        assert sen.fired_total == 1
        assert sen.state()["recompiles"]["alert"]

    def test_replay_offline(self, tmp_path):
        reg = MetricsRegistry()
        h = reg.histogram("w_seconds")
        c = reg.counter("w_total")
        hs = HistoryStore(reg, interval_s=1.0)
        for i in range(60):
            h.observe(0.3 if i >= 45 else 0.01)
            c.inc(10)
            hs.scrape(now=T0 + i)
        p = str(tmp_path / "arch.json")
        hs.save(p)
        firings = AnomalySentinel.replay(
            HistoryStore.load(p), signals=[_sentinel_signals()[0]],
            warmup=8, min_consecutive=2)
        assert [f["signal"] for f in firings] == ["lat_p99"]
        # a clean archive replays quiet
        reg2 = MetricsRegistry()
        h2 = reg2.histogram("w_seconds")
        hs2 = HistoryStore(reg2, interval_s=1.0)
        for i in range(60):
            h2.observe(0.01)
            hs2.scrape(now=T0 + i)
        assert AnomalySentinel.replay(
            hs2, signals=[_sentinel_signals()[0]], warmup=8,
            min_consecutive=2) == []


class TestFleetTopRender:
    def test_render_offline_snapshot(self, tmp_path):
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        import importlib
        ft = importlib.import_module("fleet_top")
        reg = MetricsRegistry()
        reg.counter("fleet_tokens_out_total").inc(500)
        reg.histogram("fleet_ttft_seconds").observe(0.02, count=10)
        hs = HistoryStore(reg, interval_s=1.0)
        for i in range(10):
            hs.scrape(now=T0 + i)
        hs.save(str(tmp_path / "history_snapshot.json"))
        with open(tmp_path / "tenants.json", "w") as f:
            json.dump({"tracked": 1, "capacity": 8, "evictions": 0,
                       "error_bound": 0,
                       "totals": {"tokens_in": 9, "tokens_out": 500,
                                  "queue_wait_s": 0.1,
                                  "kv_page_s": 1.0, "requests": 3},
                       "tenants": [{"tenant": "acme", "weight": 509,
                                    "err": 0, "tokens_in": 9,
                                    "tokens_out": 500,
                                    "queue_wait_s": 0.1,
                                    "kv_page_s": 1.0,
                                    "requests": 3}]}, f)
        with open(tmp_path / "health.json", "w") as f:
            json.dump({"queue_depth": 0, "pending": 0, "lost": [],
                       "slo": {"alerting": []},
                       "anomaly": {"alerting": ["ttft_p99"]},
                       "replicas": {"r0": {
                           "state": "serving", "incarnation": 2,
                           "queued": 0, "running": 1,
                           "free_pages": 7, "scrape_age_s": 0.01,
                           "lost": False, "quarantined": False}}}, f)
        frame = ft.collect_snapshot(str(tmp_path))
        text = ft.render(frame)
        assert "acme" in text
        assert "anomaly:ttft_p99" in text
        assert "r0" in text and "serving" in text
        # main() offline mode end to end
        rc = ft.main(["--snapshot", str(tmp_path)])
        assert rc == 0
