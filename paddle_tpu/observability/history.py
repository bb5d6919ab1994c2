"""Telemetry history plane — a bounded in-process time-series store.

Everything the observability stack exposed before this module was
instantaneous: the MetricsRegistry is a point-in-time snapshot, the
SLOTracker forgets past its horizon, and regression detection existed
only as the offline ``tools/metrics_diff.py`` gate over two dumps.
This module keeps *history*: a ``HistoryStore`` scrapes any
``MetricsRegistry`` on a cadence into per-series rings with a
raw → 10s → 60s downsampling ladder, and answers the questions a
scale/tune decision (ROADMAP items 3 and 5) or an online anomaly
detector (``observability.sentinel``) needs:

- ``query(key, t0, t1, res)`` — range read at a resolution;
- ``rate(key, window_s)`` — per-second increase of a counter (or a
  histogram's count), monotonic-reset tolerant;
- ``quantile_over_time(key, q, window_s)`` — bucket-delta quantile of
  a histogram over a window (what "TTFT p99 over the last 5s" means,
  computed from cumulative bucket counts at the window edges);
- ``registry_snapshot_at(t)`` — a full registry-snapshot
  reconstruction at any past instant, which is what lets ONE history
  archive drive the ``tools/metrics_diff.py --at/--vs`` canary gate
  at any two points in time.

Retention is bounded per series per resolution (deque rings): the raw
ring holds the recent past at scrape cadence, the 10s and 60s rungs
hold progressively longer horizons at progressively coarser grain —
the classic TSDB ladder, sized so a day of 1 Hz scrape stays a few MB.

Persistence follows the write-ahead journal's torn-tail discipline,
not trust: ``save()`` writes length-prefixed, CRC-checksummed JSONL
lines through ``io/atomic.py``'s write-then-rename, and ``load()``
drops (and counts) any line that is short, fails its checksum, or
does not parse — a snapshot truncated at ANY byte offset reloads
cleanly, never duplicates a sample, and loses at most the tail
(fuzz-pinned by tests/test_history.py).

Stdlib-only by contract: loadable standalone via ``tools/_obs.py``
(tools/metrics_diff.py reads archives with no jax, no package
import). The io/atomic helper is resolved lazily with a file-load
fallback, exactly like flightrec does.
"""
from __future__ import annotations

import json
import math
import os
import threading
import time
import zlib
from collections import deque

__all__ = ["HistoryStore", "DEFAULT_RUNGS"]

_FORMAT = 1

#: (bucket_seconds, retained_samples) downsampling ladder on top of
#: the raw ring — raw at scrape cadence, then 10s, then 60s.
DEFAULT_RUNGS = ((10.0, 360), (60.0, 1440))

_atomic_mod = None


def _atomic():
    """io/atomic.py, lazily — package import when available, straight
    file-load otherwise (standalone mode has no package context; the
    helper is stdlib-only by contract). Same pattern as flightrec."""
    global _atomic_mod
    if _atomic_mod is None:
        try:
            from ..io import atomic as mod
        except ImportError:
            import importlib.util as ilu
            path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                os.pardir, "io", "atomic.py")
            spec = ilu.spec_from_file_location(
                "_bench_obs_io_atomic", path)
            mod = ilu.module_from_spec(spec)
            spec.loader.exec_module(mod)
        _atomic_mod = mod
    return _atomic_mod


def _finite(obj):
    """Non-finite floats -> None (RFC-valid JSON). Duplicated across
    the stdlib-only observability modules on purpose — each stays
    standalone-loadable."""
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj


def _frame(rec):
    """One length-prefixed, CRC-checksummed line (the journal's wire
    format, duplicated here so this module stays standalone-loadable
    — serving_fleet.journal imports jax-adjacent packages)."""
    try:
        payload = json.dumps(rec, separators=(",", ":"),
                             allow_nan=False)
    except ValueError:
        payload = json.dumps(_finite(rec), separators=(",", ":"),
                             allow_nan=False)
    raw = payload.encode("utf-8")
    crc = zlib.crc32(raw) & 0xFFFFFFFF
    return b"%08x %08x " % (len(raw), crc) + raw + b"\n"


def _parse_line(line):
    """Record dict for one frame line, or None when torn/corrupt."""
    if len(line) < 19 or line[8:9] != b" " or line[17:18] != b" ":
        return None
    try:
        n = int(line[:8], 16)
        crc = int(line[9:17], 16)
    except ValueError:
        return None
    raw = line[18:]
    if len(raw) != n or (zlib.crc32(raw) & 0xFFFFFFFF) != crc:
        return None
    try:
        rec = json.loads(raw.decode("utf-8"))
    except (ValueError, UnicodeDecodeError):
        return None
    return rec if isinstance(rec, dict) else None


class _Series:
    """One metric series' history across every resolution.

    Sample shapes (compact lists, JSON-ready):
      counter:   [ts, value]                      (value cumulative)
      gauge:     [ts, last, min, max]
      histogram: [ts, count, sum, min, max, [cumulative bucket counts]]
    Downsampled rungs keep the LAST cumulative sample per bucket for
    counters/histograms (cumulative series need no averaging) and
    last/min/max for gauges.
    """

    __slots__ = ("key", "name", "labels", "mtype", "bounds", "rings")

    def __init__(self, key, name, labels, mtype, bounds, raw_samples,
                 rungs):
        self.key = key
        self.name = name
        self.labels = dict(labels or {})
        self.mtype = mtype
        self.bounds = None if bounds is None else tuple(bounds)
        self.rings = {"raw": deque(maxlen=int(raw_samples))}
        for sec, keep in rungs:
            self.rings[f"{sec:g}s"] = deque(maxlen=int(keep))

    def sample_of(self, ts, entry):
        if self.mtype == "counter":
            return [ts, entry["value"]]
        if self.mtype == "gauge":
            v = entry["value"]
            return [ts, v, v, v]
        return [ts, entry["count"], entry["sum"], entry.get("min"),
                entry.get("max"), list(entry["counts"])]

    def append(self, ts, entry, rungs):
        s = self.sample_of(ts, entry)
        self.rings["raw"].append(s)
        for sec, _keep in rungs:
            ring = self.rings[f"{sec:g}s"]
            # bucket identity by floor(ts/sec); the SAMPLE keeps the
            # real last-update timestamp, so a cumulative value is
            # always "as of its own ts" — a bucket-start stamp would
            # let a coarse sample smuggle future increments behind a
            # past timestamp and poison window deltas / --at reads
            tb = math.floor(ts / sec)
            if ring and math.floor(ring[-1][0] / sec) == tb:
                if self.mtype == "gauge":
                    last = ring[-1]
                    ring[-1] = [ts, s[1],
                                min(last[2], s[2]), max(last[3], s[3])]
                else:
                    ring[-1] = list(s)
            else:
                ring.append(list(s))


class HistoryStore:
    """Bounded TSDB over one MetricsRegistry.

    registry: the registry to scrape (None = attach later / load-only
        stores; scrape() then requires one passed explicitly).
    interval_s: ``maybe_scrape`` cadence (the raw ring's grain).
    raw_samples: raw ring bound per series.
    rungs: ((bucket_seconds, retained_samples), ...) downsampling
        ladder (DEFAULT_RUNGS: 10s and 60s).
    max_series: series-cardinality bound — beyond it NEW series are
        dropped (counted in ``dropped_series``), never existing rings.
    """

    def __init__(self, registry=None, *, interval_s=1.0,
                 raw_samples=600, rungs=DEFAULT_RUNGS, max_series=512):
        self.registry = registry
        self.interval_s = float(interval_s)
        self.raw_samples = int(raw_samples)
        self.rungs = tuple((float(s), int(k)) for s, k in rungs)
        self.max_series = int(max_series)
        self._series = {}
        self._lock = threading.Lock()
        self._last_scrape = 0.0
        self._thread = None
        self._stop = threading.Event()
        self.scrapes = 0
        self.dropped_series = 0
        self.load_dropped = 0

    # -- scraping ----------------------------------------------------------

    def scrape(self, now=None, registry=None):
        """Fold one registry snapshot into the rings. ``now`` is epoch
        seconds (tests pass explicit values for determinism)."""
        reg = registry if registry is not None else self.registry
        if reg is None:
            raise ValueError("HistoryStore has no registry to scrape")
        ts = time.time() if now is None else float(now)
        snap = reg.snapshot()
        with self._lock:
            for key, entry in snap["metrics"].items():
                ser = self._series.get(key)
                if ser is None:
                    if len(self._series) >= self.max_series:
                        self.dropped_series += 1
                        continue
                    ser = _Series(key, entry["name"], entry["labels"],
                                  entry["type"], entry.get("bounds"),
                                  self.raw_samples, self.rungs)
                    self._series[key] = ser
                ser.append(ts, entry, self.rungs)
            self.scrapes += 1
            self._last_scrape = ts
        return ts

    def maybe_scrape(self, now=None):
        """scrape() iff ``interval_s`` elapsed since the last one;
        returns the scrape ts or None. The pull-shaped attach point a
        control loop (FleetRouter.step) drives."""
        ts = time.time() if now is None else float(now)
        if ts - self._last_scrape < self.interval_s:
            return None
        return self.scrape(now=ts)

    def start(self):
        """Optional background scraper (daemon thread) for hosts with
        no control loop to ride. stop() (or close()) ends it."""
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop.clear()

        def loop():
            while not self._stop.wait(self.interval_s):
                try:
                    self.scrape()
                except Exception:  # noqa: BLE001 — a scrape must never
                    pass           # kill the scraper thread

        self._thread = threading.Thread(
            target=loop, daemon=True, name="paddle-tpu-history")
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=2.0)
        self._thread = None

    close = stop

    # -- reading -----------------------------------------------------------

    def keys(self):
        with self._lock:
            return sorted(self._series)

    def index(self):
        """Per-series catalogue rows (the /history endpoint's index)."""
        out = []
        with self._lock:
            for key, ser in sorted(self._series.items()):
                # first/last across EVERY ring: the rungs remember
                # further back than the raw ring — relative --at/--vs
                # offsets anchor on the archive's true reach
                firsts = [r[0][0] for r in ser.rings.values() if r]
                lasts = [r[-1][0] for r in ser.rings.values() if r]
                out.append({
                    "key": key, "name": ser.name,
                    "labels": dict(ser.labels), "type": ser.mtype,
                    "resolutions": {
                        res: len(ring)
                        for res, ring in ser.rings.items()},
                    "first_ts": min(firsts) if firsts else None,
                    "last_ts": max(lasts) if lasts else None})
        return out

    def query(self, key, t0=None, t1=None, res="raw", limit=None):
        """Samples of one series in [t0, t1] at a resolution, oldest
        first. Histograms omit their bucket vectors here (big); use
        quantile_over_time / registry_snapshot_at for bucket math."""
        with self._lock:
            ser = self._series.get(key)
            if ser is None:
                return []
            ring = ser.rings.get(res)
            if ring is None:
                return []
            rows = [s for s in ring
                    if (t0 is None or s[0] >= t0)
                    and (t1 is None or s[0] <= t1)]
        if limit is not None:
            rows = rows[-int(limit):]
        out = []
        for s in rows:
            if ser.mtype == "counter":
                out.append({"t": s[0], "v": s[1]})
            elif ser.mtype == "gauge":
                out.append({"t": s[0], "v": s[1], "min": s[2],
                            "max": s[3]})
            else:
                out.append({"t": s[0], "count": s[1], "sum": s[2],
                            "min": s[3], "max": s[4]})
        return out

    def _window_samples(self, key, t0, t1):
        """Samples covering [t0, t1]: raw where it reaches, coarser
        rungs ONLY for the part of the window before the finer ring's
        earliest sample (the ladder's whole point — and the finer
        data must win where both exist, or a coarse bucket's single
        end-of-bucket sample would flatten the deltas raw can see).
        Returned oldest-first, plus one anchor just before t0."""
        ser = self._series.get(key)
        if ser is None:
            return None, []
        picked = {}
        anchor = None   # latest sample strictly before the window —
        #                 ONE anchor only, or the delta walk would
        #                 count increase that happened before t0
        reach = None    # earliest instant finer resolutions cover
        for res in ["raw"] + [f"{sec:g}s" for sec, _ in
                              sorted(self.rungs)]:
            ring = ser.rings.get(res)
            if not ring:
                continue
            hi = t1 if reach is None else min(reach, t1)
            for s in ring:
                if t0 <= s[0] < hi or (reach is None
                                       and s[0] == hi):
                    picked.setdefault(s[0], s)
                elif s[0] < t0 and (anchor is None
                                    or s[0] > anchor[0]):
                    anchor = s
            reach = ring[0][0] if reach is None \
                else min(reach, ring[0][0])
        if anchor is not None:
            picked.setdefault(anchor[0], anchor)
        return ser, [picked[t] for t in sorted(picked)]

    def increase(self, key, t0, t1):
        """Monotonic increase of a counter (or histogram count) over
        [t0, t1] — sum of positive deltas, so a counter reset (process
        restart) never reads as a negative rate."""
        with self._lock:
            ser, rows = self._window_samples(key, t0, t1)
            if ser is None or len(rows) < 2:
                return None
            vals = [s[1] for s in rows]
        inc = 0
        for a, b in zip(vals, vals[1:]):
            if b > a:
                inc += b - a
        return inc

    def rate(self, key, window_s, now=None):
        """Per-second increase over the trailing window (None when
        the series is unknown or has < 2 samples in reach)."""
        t1 = (self._last_scrape if now is None else float(now))
        inc = self.increase(key, t1 - float(window_s), t1)
        if inc is None:
            return None
        return inc / float(window_s)

    def quantile_over_time(self, key, q, window_s, now=None):
        """Interpolated quantile of a histogram's observations that
        landed INSIDE the trailing window, from the cumulative bucket
        counts at the window edges. None when the series is not a
        histogram, out of reach, or saw no events in the window."""
        t1 = (self._last_scrape if now is None else float(now))
        t0 = t1 - float(window_s)
        with self._lock:
            ser, rows = self._window_samples(key, t0, t1)
            if ser is None or ser.mtype != "histogram" \
                    or ser.bounds is None or len(rows) < 2:
                return None
            first, last = rows[0], rows[-1]
            delta = [b - a for a, b in zip(first[5], last[5])]
            lo_all = last[3]
            hi_all = last[4]
        total = sum(d for d in delta if d > 0)
        if total <= 0:
            return None
        target = float(q) * total
        cum = 0
        bounds = ser.bounds
        for i, c in enumerate(delta):
            if c <= 0:
                continue
            lo = bounds[i - 1] if i > 0 else (
                lo_all if lo_all is not None else 0.0)
            hi = bounds[i] if i < len(bounds) else (
                hi_all if hi_all is not None else bounds[-1])
            lo = min(lo, hi)
            if cum + c >= target:
                frac = (target - cum) / c
                return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
            cum += c
        return hi_all

    def value_at(self, key, t):
        """The series' sample at-or-before epoch ``t`` (finest
        resolution that has one), or None."""
        with self._lock:
            ser = self._series.get(key)
            if ser is None:
                return None
            for res in ["raw"] + [f"{sec:g}s" for sec, _ in
                                  sorted(self.rungs)]:
                ring = ser.rings.get(res)
                if not ring:
                    continue
                at = [s for s in ring if s[0] <= t]
                if at:
                    return ser, at[-1]
        return None

    def registry_snapshot_at(self, t):
        """Reconstruct a ``MetricsRegistry.snapshot()``-shaped doc as
        of epoch ``t`` — the input ``tools/metrics_diff.py --at/--vs``
        feeds to its differ, so one history archive supports the
        canary gate at any two points in time. Series with no sample
        at-or-before ``t`` are omitted (they did not exist yet)."""
        metrics = {}
        for key in self.keys():
            hit = self.value_at(key, t)
            if hit is None:
                continue
            ser, s = hit
            base = {"name": ser.name, "labels": dict(ser.labels),
                    "type": ser.mtype}
            if ser.mtype == "counter":
                base["value"] = s[1]
            elif ser.mtype == "gauge":
                base["value"] = s[1]
            else:
                base.update(bounds=list(ser.bounds or ()),
                            counts=list(s[5]), count=s[1], sum=s[2],
                            min=s[3], max=s[4])
            metrics[key] = base
        return {"ts": float(t), "metrics": metrics}

    def span(self):
        """(first_ts, last_ts) across every series (None, None when
        empty) — what relative --at/--vs offsets anchor to."""
        first = last = None
        for row in self.index():
            if row["first_ts"] is not None:
                first = row["first_ts"] if first is None \
                    else min(first, row["first_ts"])
            if row["last_ts"] is not None:
                last = row["last_ts"] if last is None \
                    else max(last, row["last_ts"])
        return first, last

    # -- persistence (journal framing + io/atomic rename) ------------------

    def save(self, path):
        """Snapshot every ring to ``path``: checksummed JSONL lines
        (header first, then one line per series-resolution chunk)
        through the shared write-then-rename discipline. A reader of a
        PARTIAL copy (crash mid-replace is impossible, but operators
        truncate, disks lie) drops at most the tail."""
        lines = [_frame({"kind": "history_header", "format": _FORMAT,
                         "saved_ts": round(time.time(), 6),
                         "interval_s": self.interval_s,
                         "raw_samples": self.raw_samples,
                         "rungs": [list(r) for r in self.rungs]})]
        with self._lock:
            for key, ser in sorted(self._series.items()):
                for res, ring in ser.rings.items():
                    if not ring:
                        continue
                    lines.append(_frame({
                        "kind": "series", "key": key,
                        "name": ser.name, "labels": ser.labels,
                        "mtype": ser.mtype,
                        "bounds": None if ser.bounds is None
                        else list(ser.bounds),
                        "res": res, "samples": [list(s) for s in ring]}))
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        _atomic().atomic_replace(path, b"".join(lines))
        return path

    @classmethod
    def load(cls, path):
        """Rebuild a store from a snapshot. Torn/corrupt lines are
        dropped and counted (``load_dropped``) — never raised on, and
        a line that survives its checksum is applied exactly once, so
        truncation at any byte offset costs at most the tail."""
        store = cls(registry=None)
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            return store
        seen = set()
        for line in data.split(b"\n"):
            if not line:
                continue
            rec = _parse_line(line)
            if rec is None:
                store.load_dropped += 1
                continue
            kind = rec.get("kind")
            if kind == "history_header":
                store.interval_s = float(rec.get("interval_s", 1.0))
                store.raw_samples = int(rec.get("raw_samples", 600))
                store.rungs = tuple(
                    (float(s), int(k))
                    for s, k in rec.get("rungs") or DEFAULT_RUNGS)
            elif kind == "series":
                key, res = rec.get("key"), rec.get("res")
                if key is None or res is None or (key, res) in seen:
                    continue   # a duplicated chunk never duplicates
                seen.add((key, res))
                ser = store._series.get(key)
                if ser is None:
                    ser = _Series(key, rec.get("name", key),
                                  rec.get("labels"), rec.get("mtype"),
                                  rec.get("bounds"),
                                  store.raw_samples, store.rungs)
                    store._series[key] = ser
                ring = ser.rings.get(res)
                if ring is None:
                    continue   # rung retired between save and load
                for s in rec.get("samples") or []:
                    ring.append(list(s))
                if ring:
                    store._last_scrape = max(store._last_scrape,
                                             ring[-1][0])
        return store
