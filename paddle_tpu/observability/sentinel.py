"""Online anomaly sentinel — the live counterpart of the offline
metrics_diff canary gate.

The Gemma-on-Cloud-TPU serving decomposition (PAPERS.md) names the
regressions that matter mid-wave: TTFT creep, decode throughput
collapse, queue-wait growth, and the silent killers (journal errors,
a recompile where the counts were frozen). Round 12's SLO burn rates
catch promise violations against FIXED thresholds; this module
catches *change* — it learns each signal's normal band from the
telemetry history plane (``observability.history``) and fires when
the live value leaves it:

- ``_Band``: EWMA mean + EWMA absolute deviation, read as a robust
  z-score (``(x - mean) / (1.4826 * ewma_dev)``, MAD-style scaling,
  with a relative floor so a perfectly flat clean wave does not turn
  microscopic jitter into an alarm). Breaching observations are NOT
  folded into the band — an anomaly must not widen its own band into
  acceptance.
- signal kinds: ``quantile`` (quantile-over-time of a histogram,
  e.g. TTFT p99), ``rate`` (per-second counter increase, e.g. decode
  tok/s — direction ``low`` — or journal errors — any positive rate
  after a zero baseline), and ``delta`` (ANY increase of a
  monotonic scalar read from a callback — the fleet compile report:
  the zero-recompile contract needs no band, one new trace is the
  anomaly).
- firing: ``min_consecutive`` breaching evaluations arm-and-dump ONE
  ``fleet_anomaly`` flight record (flightrec; re-armed only after the
  signal returns in band — a sustained regression is one postmortem,
  not a dump per poll), increment
  ``fleet_anomaly_fired_total{signal=...}`` and hold
  ``fleet_anomaly_active{signal=...}`` at 1. The router folds
  ``alerting`` into ``health()["anomaly"]`` exactly like SLO burn
  alerts, so placement/operators/the supervisor see it live.
- ``replay()``: run the same detector offline over a SAVED history
  snapshot — how tools/history_smoke.py proves the sentinel stays
  quiet across the committed clean golden wave and how ``tools/fleet_top.py
  --snapshot`` triages a post-mortem archive.

Stdlib-only by contract (standalone-loadable via tools/_obs.py);
flightrec/metrics are sibling stdlib modules, imported lazily.
"""
from __future__ import annotations

import threading
import time

__all__ = ["AnomalySentinel", "default_signals"]


def default_signals(window_s=5.0):
    """The fleet registry's watch list (series the FleetRouter
    publishes; a signal whose series has no data yet simply reads
    None and neither learns nor fires)."""
    w = float(window_s)
    return (
        {"name": "ttft_p99", "kind": "quantile",
         "series": "fleet_ttft_seconds", "q": 0.99, "window_s": w,
         "direction": "high"},
        {"name": "decode_tok_s", "kind": "rate",
         "series": "fleet_tokens_out_total", "window_s": w,
         "direction": "low", "demand_gate": "fleet_pending"},
        {"name": "queue_wait_p99", "kind": "quantile",
         "series": "fleet_placement_wait_seconds", "q": 0.99,
         "window_s": w, "direction": "high"},
        {"name": "journal_errors", "kind": "rate",
         "series": "fleet_journal_errors_total", "window_s": w,
         "direction": "high"},
        # device-memory pressure: the memory ledger's used-ratio
        # gauge. Sustained growth out of the learned band (a leak, a
        # runaway working set) trips the debounced flight dump with
        # the segment tree attached; a flat series — even near full —
        # is a steady state, not an anomaly.
        {"name": "mem_used_ratio", "kind": "gauge",
         "series": "engine_mem_hbm_used_ratio", "window_s": w,
         "direction": "high"},
        {"name": "recompiles", "kind": "delta", "series": None},
    )


class _Band:
    """EWMA mean + EWMA |deviation| with robust-z readout."""

    __slots__ = ("alpha", "z", "warmup", "rel_floor", "abs_floor",
                 "mean", "dev", "n")

    # rel_floor < 1/z by a margin: the floor caps |z| at 1/rel_floor
    # for a TOTAL collapse (x=0 → |z| = mean/(rel_floor*mean)), so a
    # floor of 0.25 against the default z=4 would make a full
    # throughput collapse read exactly 4.0 — never strictly above
    def __init__(self, alpha=0.2, z=4.0, warmup=8, rel_floor=0.2,
                 abs_floor=1e-9):
        self.alpha = float(alpha)
        self.z = float(z)
        self.warmup = int(warmup)
        self.rel_floor = float(rel_floor)
        self.abs_floor = float(abs_floor)
        self.mean = None
        self.dev = 0.0
        self.n = 0

    def observe(self, x, direction="both"):
        """Fold x; returns (z_score, breach). During warmup the band
        only learns; a breaching x is NEVER folded in (the band must
        not chase the anomaly)."""
        x = float(x)
        if self.mean is None:
            self.mean, self.n = x, 1
            return 0.0, False
        scale = max(1.4826 * self.dev,
                    self.rel_floor * abs(self.mean), self.abs_floor)
        zs = (x - self.mean) / scale
        breach = self.n >= self.warmup and abs(zs) > self.z and (
            direction == "both"
            or (direction == "high" and zs > 0)
            or (direction == "low" and zs < 0))
        if not breach:
            a = self.alpha
            self.dev = (1 - a) * self.dev + a * abs(x - self.mean)
            self.mean = (1 - a) * self.mean + a * x
            self.n += 1
        return zs, breach


class AnomalySentinel:
    """Online detector over a HistoryStore.

    history: observability.history.HistoryStore the signals read.
    signals: iterable of signal dicts (default: default_signals) —
        {"name", "kind": quantile|rate|delta, "series", "q",
         "window_s", "direction": high|low|both}.
    registry: MetricsRegistry for fleet_anomaly_* (None = unmetered).
    compile_fn: zero-arg callable returning a fleet compile report
        ({"replicas": {...}, "unexpected_retraces": n}) for the
        ``delta`` signal (FleetRouter.compile_report). None disables
        that signal.
    z / alpha / warmup / rel_floor: band knobs (per-signal overrides
        via the signal dict win).
    min_consecutive: breaching evaluations before a FIRE (debounce).
    eval_interval_s: maybe_evaluate cadence (default: the history
        store's scrape interval).
    flight: dump a ``fleet_anomaly`` flight record on fire (one per
        excursion; re-arms when the signal clears).
    """

    def __init__(self, history, *, signals=None, registry=None,
                 compile_fn=None, z=4.0, alpha=0.2, warmup=8,
                 rel_floor=0.2, min_consecutive=2,
                 eval_interval_s=None, flight=True):
        self.history = history
        self.signals = [dict(s) for s in
                        (signals if signals is not None
                         else default_signals())]
        names = [s["name"] for s in self.signals]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate signal names: {names}")
        self.compile_fn = compile_fn
        self.min_consecutive = int(min_consecutive)
        self.eval_interval_s = (float(eval_interval_s)
                                if eval_interval_s is not None
                                else getattr(history, "interval_s",
                                             1.0) or 1.0)
        self.flight = bool(flight)
        self._bands = {}
        for s in self.signals:
            self._bands[s["name"]] = _Band(
                alpha=float(s.get("alpha", alpha)),
                z=float(s.get("z", z)),
                warmup=int(s.get("warmup", warmup)),
                rel_floor=float(s.get("rel_floor", rel_floor)))
        self._streak = {n: 0 for n in names}
        self._armed = {n: True for n in names}
        self._active = {n: False for n in names}
        self._last_compile_total = None
        self._last_eval = 0.0
        self._state = {}
        self._lock = threading.Lock()
        self._m_fired = {}
        self._g_active = {}
        self._registry = registry
        self.fired_total = 0
        # export every signal's series at 0 NOW: the history plane
        # must carry them from the first scrape, or a canary gate
        # comparing two instants could never see the clean->fired
        # transition (a series missing on one side is skipped)
        for n in names:
            self._fired_counter(n)
            self._active_gauge(n)

    # -- metric export -----------------------------------------------------

    def _fired_counter(self, signal):
        if self._registry is None:
            return None
        c = self._m_fired.get(signal)
        if c is None:
            c = self._registry.counter(
                "fleet_anomaly_fired_total",
                help="anomaly-sentinel excursions fired (one per "
                     "excursion, debounced)", labels={"signal": signal})
            self._m_fired[signal] = c
        return c

    def _active_gauge(self, signal):
        if self._registry is None:
            return None
        g = self._g_active.get(signal)
        if g is None:
            g = self._registry.gauge(
                "fleet_anomaly_active",
                help="1 while the signal is outside its learned band",
                labels={"signal": signal})
            self._g_active[signal] = g
        return g

    # -- signal readout ----------------------------------------------------

    def _read(self, sig, now):
        kind = sig.get("kind", "quantile")
        if kind == "quantile":
            return self.history.quantile_over_time(
                sig["series"], float(sig.get("q", 0.99)),
                float(sig.get("window_s", 5.0)), now=now)
        if kind == "rate":
            return self.history.rate(
                sig["series"], float(sig.get("window_s", 5.0)),
                now=now)
        if kind == "gauge":
            # latest raw sample of a plain gauge series inside the
            # window (quantile_over_time is histogram-only); no data
            # reads None — "no news", neither learns nor fires
            w = float(sig.get("window_s", 5.0))
            rows = self.history.query(sig["series"], t0=now - w,
                                      t1=now, res="raw")
            if not rows:
                return None
            last = rows[-1]
            v = last.get("max", last.get("v"))
            return None if v is None else float(v)
        if kind == "delta":
            if self.compile_fn is None:
                return None
            try:
                rep = self.compile_fn()
            except Exception:  # noqa: BLE001 — a scrape hiccup is
                return None    # "no news", not an anomaly
            total = int(rep.get("unexpected_retraces", 0))
            for counts in (rep.get("replicas") or {}).values():
                total += sum(int(v) for v in (counts or {}).values())
            return total
        raise ValueError(f"unknown signal kind {kind!r}")

    def _demand_ok(self, sig, now):
        """True when the signal's ``demand_gate`` series (a gauge,
        e.g. fleet_pending) reads >= ``demand_min`` (default 1)
        anywhere inside the signal's window — i.e. the fleet actually
        had work to do. Signals without a gate always pass."""
        gate = sig.get("demand_gate")
        if gate is None:
            return True
        window = float(sig.get("window_s", 5.0))
        rows = self.history.query(gate, t0=now - window, t1=now,
                                  res="raw")
        if not rows:
            return False   # gate series absent: suppress, don't guess
        need = float(sig.get("demand_min", 1))
        return any((r.get("max", r.get("v", 0)) or 0) >= need
                   for r in rows)

    # -- evaluation --------------------------------------------------------

    def maybe_evaluate(self, now=None):
        """evaluate() iff the cadence elapsed; None otherwise. The
        attach point a control loop (FleetRouter.step) drives."""
        ts = time.time() if now is None else float(now)
        if ts - self._last_eval < self.eval_interval_s:
            return None
        return self.evaluate(now=ts)

    def evaluate(self, now=None):
        """One pass over every signal; returns (and caches) the state
        dict {signal: {"value", "z", "mean", "breach", "alert",
        "kind"}}. ``alert`` holds while the excursion lasts; the FIRST
        evaluation that reaches ``min_consecutive`` breaches dumps the
        flight record and bumps the fired counter."""
        ts = time.time() if now is None else float(now)
        state = {}
        with self._lock:
            self._last_eval = ts
            for sig in self.signals:
                name = sig["name"]
                row = {"kind": sig.get("kind", "quantile"),
                       "series": sig.get("series"), "value": None,
                       "z": None, "mean": None, "breach": False,
                       "alert": False}
                if sig.get("kind") == "delta":
                    total = self._read(sig, ts)
                    row["value"] = total
                    if total is not None:
                        base = self._last_compile_total
                        if base is None:
                            self._last_compile_total = total
                        elif total > base:
                            row["breach"] = True
                            row["z"] = float(total - base)
                            # the new level becomes the baseline once
                            # fired — ONE excursion per compile event
                            self._last_compile_total = total
                        else:
                            self._last_compile_total = total
                else:
                    v = self._read(sig, ts)
                    if v is not None and not self._demand_ok(sig, ts):
                        # zero-demand guard: a throughput collapse is
                        # only an anomaly while there IS work pending
                        # — a client simply going quiet must read as
                        # "no data" (clears/never fires), not as a
                        # replica regression
                        v = None
                    row["value"] = v
                    if v is not None:
                        band = self._bands[name]
                        zs, breach = band.observe(
                            v, sig.get("direction", "both"))
                        row.update(z=round(zs, 4), breach=breach,
                                   mean=None if band.mean is None
                                   else round(band.mean, 6))
                self._step_alerts(name, sig, row, ts)
                state[name] = row
            self._state = state
        return state

    def _step_alerts(self, name, sig, row, ts):
        if row["breach"]:
            self._streak[name] += 1
        else:
            self._streak[name] = 0
            self._active[name] = False
            self._armed[name] = True
        fire_at = 1 if sig.get("kind") == "delta" \
            else self.min_consecutive
        if self._streak[name] >= fire_at:
            self._active[name] = True
            if self._armed[name]:
                self._armed[name] = False
                self.fired_total += 1
                c = self._fired_counter(name)
                if c is not None:
                    c.inc()
                if self.flight:
                    self._flight_dump(name, sig, row, ts)
        g = self._active_gauge(name)
        if g is not None:
            g.set(1 if self._active[name] else 0)
        row["alert"] = self._active[name]

    def _flight_dump(self, name, sig, row, ts):
        """One parseable ``fleet_anomaly`` postmortem per excursion —
        never raises (same contract as every flight trigger)."""
        try:
            from . import flightrec
            flightrec.note("fleet_anomaly", signal=name,
                           value=row["value"], z=row["z"])
            extra = {"signal": name, "signal_spec": dict(sig),
                     "value": row["value"], "z": row["z"],
                     "mean": row["mean"], "eval_ts": ts,
                     "streak": self._streak[name]}
            series = sig.get("series")
            if series is not None:
                extra["recent"] = self.history.query(
                    series, t0=ts - 4 * float(sig.get("window_s", 5.0)),
                    t1=ts, res="raw", limit=64)
            # what the host was actually DOING when the signal tripped:
            # the continuous profiler's last ~minute of folded stacks
            # (None when no profiler is armed in this process); its
            # absence must never cost the dump itself
            try:
                from . import contprof
                extra["profile"] = contprof.current_profile()
            except ImportError:  # standalone file-load (tools/_obs.py)
                pass
            # ...and where device memory stood: the active memory
            # ledger's segment tree + headroom forecast (None when no
            # ledger is armed) — the mem_used_ratio signal's postmortem
            try:
                from . import memledger
                extra["memory"] = memledger.current_memory()
            except ImportError:  # standalone file-load (tools/_obs.py)
                pass
            flightrec.dump("fleet_anomaly", extra=extra)
        except Exception:  # noqa: BLE001
            pass

    # -- rollups -----------------------------------------------------------

    def state(self):
        with self._lock:
            return {n: dict(r) for n, r in self._state.items()}

    def alerting(self):
        """Signal names currently out of band — the health() rollup
        (cached from the last evaluate; cheap enough for HTTP
        threads)."""
        with self._lock:
            return sorted(n for n, r in self._state.items()
                          if r.get("alert"))

    def health(self):
        """The ``health()["anomaly"]`` shape, mirroring the SLO
        rollup: {"alerting": [...], "signals": {...}}."""
        with self._lock:
            return {"alerting": sorted(
                        n for n, r in self._state.items()
                        if r.get("alert")),
                    "signals": {n: {"alert": r.get("alert", False),
                                    "value": r.get("value"),
                                    "z": r.get("z")}
                                for n, r in self._state.items()}}

    # -- offline replay ----------------------------------------------------

    @classmethod
    def replay(cls, history, *, signals=None, step_s=None, **kw):
        """Run the detector over a saved history (no registry, no
        flight dumps): walk the archive's time span at ``step_s``
        (default: its scrape interval) and return every firing as
        {"t", "signal", "value", "z"}. Empty list == the archive is
        clean — the committed-golden quiet check."""
        first, last = history.span()
        if first is None:
            return []
        step = float(step_s) if step_s is not None \
            else max(float(getattr(history, "interval_s", 1.0)), 1e-3)
        sen = cls(history, signals=signals, registry=None,
                  compile_fn=None, flight=False,
                  eval_interval_s=0.0, **kw)
        firings = []
        t = first
        while t <= last + step / 2:
            armed_before = dict(sen._armed)
            state = sen.evaluate(now=t)
            # an armed -> disarmed transition IS a fire (re-arming
            # only happens when the signal clears)
            for n, r in state.items():
                if armed_before.get(n, True) and not sen._armed[n]:
                    firings.append({"t": t, "signal": n,
                                    "value": r["value"], "z": r["z"]})
            t += step
        return firings
