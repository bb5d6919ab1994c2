"""Span records -> one Perfetto/Chrome-trace timeline.

The profiler already times *regions* (aggregate stats) and jax dumps
*device* traces (xplane); what was missing is the HOST SCHEDULING
story with real timestamps: when did request 7 sit in the queue, when
did its prefill run, which decode dispatches carried it, where did a
guard skip stall the train loop. A ``SpanRecorder`` holds a bounded
ring of timestamped spans and exports them as Chrome trace events
(``{"traceEvents": [...]}``) that Perfetto/chrome://tracing open
directly — and several recorders (serving, train, profiler regions)
merge into ONE timeline via ``export_chrome``.

Conventions:
- time base: ``time.perf_counter()`` for durations, mapped to epoch
  microseconds through a base pair captured at module import — all
  recorders in a process share it, so merged timelines align;
- lanes: each span names a ``tid`` lane (e.g. ``req3``, ``decode``);
  lanes get stable integer tids plus ``thread_name`` metadata events;
- ``ph: "X"`` complete events for spans, ``ph: "i"`` instants for
  annotations (page release, eviction, guard skip).

Stdlib-only; safe to call at host step boundaries (one deque append
under a lock per span).
"""
from __future__ import annotations

import json
import math
import os
import threading
import time
from collections import deque

__all__ = ["SpanRecorder", "export_chrome"]

# one shared epoch<->perf_counter base so independently-created
# recorders (serving engine, telemetry callback, profiler) merge into
# an aligned timeline
_EPOCH_BASE = time.time()
_PERF_BASE = time.perf_counter()


def _finite(obj):
    """Non-finite floats -> None (RFC-valid JSON for jq/Perfetto).
    (Duplicated across the observability modules by contract — each
    stays standalone-loadable from tools/_obs.py.)"""
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj


def _to_epoch_us(perf_t):
    return (_EPOCH_BASE + (perf_t - _PERF_BASE)) * 1e6


def _suppressed():
    """True inside an introspection AOT replay: span emission is
    suppressed exactly like the tracer's counter bumps, so a replay
    that re-executes instrumented host code can never add phantom
    spans to a timeline (or perturb a span-count assertion)."""
    try:
        from .introspect import introspecting
    except ImportError:  # standalone file-load (tools/_obs.py)
        return False
    return introspecting()


class SpanRecorder:
    """Bounded ring of host spans, Chrome-trace exportable."""

    def __init__(self, name="run", maxlen=4096):
        self.name = name
        self._events = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self._lanes = {}           # lane name -> int tid
        # ring-overflow accounting: "dropped is visible, never
        # silent" — the count surfaces in export_chrome metadata and
        # the exporter /report, like trace-store and capture drops
        self.evicted = 0

    @staticmethod
    def now():
        """The recorder's clock (perf_counter seconds) — pass the
        returned value back to add()."""
        return time.perf_counter()

    def _lane(self, tid):
        lane = self._lanes.get(tid)
        if lane is None:
            lane = self._lanes[tid] = len(self._lanes)
        return lane

    # -- recording ---------------------------------------------------------
    def add(self, name, t0, t1=None, tid="main", cat="host", args=None):
        """One complete span: [t0, t1] in perf_counter seconds
        (t1 None = now). Returns the event dict."""
        if _suppressed():
            return None
        if t1 is None:
            t1 = time.perf_counter()
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": _to_epoch_us(t0),
              "dur": max((t1 - t0) * 1e6, 0.0),
              "tid": tid, "args": dict(args or {})}
        with self._lock:
            self._lane(tid)
            if len(self._events) == self._events.maxlen:
                self.evicted += 1
            self._events.append(ev)
        return ev

    def instant(self, name, tid="main", cat="host", args=None):
        """Zero-duration annotation (eviction, page release, skip)."""
        if _suppressed():
            return None
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": _to_epoch_us(time.perf_counter()),
              "tid": tid, "args": dict(args or {})}
        with self._lock:
            self._lane(tid)
            if len(self._events) == self._events.maxlen:
                self.evicted += 1
            self._events.append(ev)
        return ev

    def span(self, name, tid="main", cat="host", **args):
        """Context manager form: ``with rec.span("prefill_32",
        tid="req3"): ...``"""
        rec = self

        class _Span:
            def __enter__(self):
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                rec.add(name, self.t0, tid=tid, cat=cat, args=args)
        return _Span()

    # -- reading/export ----------------------------------------------------
    def events(self):
        with self._lock:
            return list(self._events)

    def to_chrome(self, pid=None):
        """Chrome trace events for this recorder: lane metadata
        (process/thread names) + the recorded spans with integer
        pid/tid (the strict reading of the trace-event format)."""
        pid = pid if pid is not None else self.name
        with self._lock:
            evs = list(self._events)
            lanes = dict(self._lanes)
        out = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": self.name}}]
        for lane_name, tid in sorted(lanes.items(), key=lambda kv: kv[1]):
            out.append({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": tid, "args": {"name": str(lane_name)}})
        for ev in evs:
            row = dict(ev)
            row["pid"] = pid
            row["tid"] = lanes.get(row["tid"], 0)
            out.append(row)
        return out

    def export(self, path, extra_recorders=()):
        """Write this recorder (+ any extras) as one Chrome trace
        JSON. Returns the path."""
        return export_chrome(path, [self, *extra_recorders])

    def clear(self):
        with self._lock:
            self._events.clear()


def export_chrome(path, recorders):
    """Merge several SpanRecorders into one Chrome trace file —
    Perfetto shows each recorder as a named process, each lane as a
    named thread, on one shared timeline (the spans all ride the same
    epoch base). Atomic write; returns the path."""
    events = []
    for i, rec in enumerate(recorders):
        events.extend(rec.to_chrome(pid=i + 1))
    events.sort(key=lambda e: (e.get("ts", 0), e.get("ph") != "M"))
    doc = {"traceEvents": events, "displayTimeUnit": "ms",
           "metadata": {"evicted_spans": {
               rec.name: int(getattr(rec, "evicted", 0))
               for rec in recorders}}}
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        try:
            json.dump(doc, f, allow_nan=False)
        except ValueError:
            # a NaN span arg (e.g. a loss annotation mid-storm) must
            # still land as valid JSON Perfetto will open
            f.seek(0)
            f.truncate()
            json.dump(_finite(doc), f, allow_nan=False)
    os.replace(tmp, path)
    return path
