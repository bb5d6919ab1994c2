"""Distributed request tracing — one causal span tree per fleet request.

Round 10's ``SpanRecorder`` answers "what did THIS engine's host loop
do"; a fleet request crosses router -> transport -> replica -> engine
(and, under failover or hedging, SEVERAL replicas), so the question
"where did this request's 800 ms go" needs spans that share a trace
identity across those hops. This module is that layer:

- a **trace context** — ``{"trace_id", "span_id", "proc", "hops"}`` —
  minted by ``FleetRouter.submit`` and propagated through the
  ``ReplicaClient`` transport verbs into ``InprocReplica`` /
  ``ServingEngine``. ``span_id`` is the parent for anything the
  receiving hop records; ``proc`` names the lane (router / replica
  name); ``hops`` is a propagation budget (``hop()``) so a
  pathological failover loop cannot grow a tree without bound;
- a **TraceStore**: bounded ring of whole span trees. Eviction is by
  TRACE, never by span — an exported tree can never contain an orphan
  child whose parent was evicted out from under it (the round-10 ring
  could); a tree that overflows ``max_spans_per_trace`` stops
  accepting spans and is marked ``truncated`` instead of losing
  interior nodes;
- **latency attribution**: ``attribution(trace_id)`` decomposes the
  root span into its direct-child hops (placement wait, transport,
  per-replica legs with their nested queue/prefill/decode), reports
  the interval-union coverage of the end-to-end wall time, and flags
  ``within_tolerance`` when the uncovered remainder is under
  ``tolerance`` (default 5%) — legs annotated ``hedge_loser`` stay in
  the tree but out of the serial sum, since they overlap the winner
  by construction;
- a **cross-process Perfetto merge**: ``to_chrome``/``export_chrome``
  emit one ``{"traceEvents": [...]}`` timeline with a process group
  per ``proc`` (router lane + one lane per replica) and a thread per
  request, on the same epoch<->perf_counter base as ``spans.py`` so
  fleet traces align with the round-10 engine/train/profiler
  timelines. ``clock_offsets={proc: seconds}`` reconciles per-process
  clock skew (the router estimates offsets from heartbeat
  timestamps; in-process replicas share the clock, so offsets are
  ~0 — the seam exists for the subprocess deployment).

All timestamps are ``time.perf_counter()`` seconds (``now()``).
Every mutating call is a no-op while ``introspect.introspecting()``
is set — tracing can never perturb the AOT replay or read as work in
a zero-recompile assertion — and tolerates ``ctx=None`` (an untraced
request records nothing). Stdlib-only; sibling imports are lazy.
"""
from __future__ import annotations

import itertools
import json
import math
import os
import threading
import time
from collections import OrderedDict

__all__ = ["TraceStore", "get_store", "hop", "now"]

_id_counter = itertools.count(1)


def now():
    """The trace clock (perf_counter seconds)."""
    return time.perf_counter()


def _suppressed():
    try:
        from .introspect import introspecting
    except ImportError:  # standalone file-load (tools/_obs.py)
        return False
    return introspecting()


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


def _to_epoch_us(perf_t):
    """Epoch microseconds on the SAME base spans.py uses, so a fleet
    timeline and an engine/train timeline land aligned in one
    Perfetto view."""
    try:
        from .spans import _to_epoch_us as base
        return base(perf_t)
    except ImportError:
        return (_EPOCH_BASE + (perf_t - _PERF_BASE)) * 1e6


_EPOCH_BASE = time.time()
_PERF_BASE = time.perf_counter()


def hop(ctx):
    """Cross one process/transport boundary: returns a propagatable
    copy with the hop budget decremented, or None when the budget is
    exhausted (the receiver then records nothing — the tree stays
    bounded even if requests bounce forever)."""
    if ctx is None or int(ctx.get("hops", 0)) <= 0:
        return None
    return dict(ctx, hops=int(ctx["hops"]) - 1)


class TraceStore:
    """Bounded store of causally-linked span trees.

    max_traces: whole-tree ring bound (oldest TRACE evicts first).
    max_spans_per_trace: per-tree span cap; overflowing trees are
        marked ``truncated`` and drop NEW spans — interior nodes are
        never removed, so parents outlive their children by
        construction.
    sample: keep-fraction in [0, 1] for whole trees (default 1.0 =
        trace everything; the process-global store reads
        ``PADDLE_TPU_TRACE_SAMPLE``). Sampling is head-based and
        DETERMINISTIC — a fractional accumulator keeps exactly
        ``sample`` of new_trace calls, evenly spaced, no RNG — and
        by WHOLE TREE: a sampled-out request records nothing anywhere
        (``new_trace`` returns None, every hop no-ops), so whole-tree
        tracing stays bounded at high QPS. Dropped traces are counted
        in ``sampled_out`` AND as ``fleet_traces_sampled_out_total``
        in the process-global registry — dropped is visible, never
        silent.
    """

    def __init__(self, max_traces=256, max_spans_per_trace=512,
                 sample=1.0):
        self.max_traces = int(max_traces)
        self.max_spans_per_trace = int(max_spans_per_trace)
        self.sample = min(max(float(sample), 0.0), 1.0)
        self._sample_acc = 0.0
        self.sampled_out = 0
        self._sampled_counter = None
        self._traces = OrderedDict()   # trace_id -> tree record
        self._lock = threading.Lock()

    # -- recording ---------------------------------------------------------

    def new_trace(self, name="request", proc="router", t0=None,
                  rid=None, hops=8, args=None, force=False):
        """Open a new trace with its root span; returns the root
        context (None under introspection). Evicts the oldest WHOLE
        trace beyond max_traces. ``force=True`` bypasses the
        head-sampling gate (never the introspection suppression) —
        the traffic-capture plane keeps every ARCHIVED request's span
        tree so an archive entry always carries its attribution,
        whatever PADDLE_TPU_TRACE_SAMPLE says about the rest."""
        if _suppressed():
            return None
        if not force and self.sample < 1.0 and not self._sample_keep():
            return None
        trace_id = f"t{os.getpid():x}-{next(_id_counter)}"
        span = {"id": next(_id_counter), "parent": None,
                "name": name, "proc": proc,
                "t0": now() if t0 is None else float(t0), "t1": None,
                "outcome": None, "args": dict(args or {})}
        with self._lock:
            self._traces[trace_id] = {
                "spans": OrderedDict([(span["id"], span)]),
                "rid": rid, "truncated": False}
            while len(self._traces) > self.max_traces:
                self._traces.popitem(last=False)  # whole tree, never
                #                                   an interior node
        return {"trace_id": trace_id, "span_id": span["id"],
                "proc": proc, "hops": int(hops), "t0": span["t0"]}

    def _sample_keep(self):
        """Deterministic fractional-accumulator sampling decision.
        Dropping increments the internal count and the
        ``fleet_traces_sampled_out_total`` counter (lazily resolved
        from the process-global registry; absent in standalone loads
        — the internal count still tells the story there)."""
        with self._lock:
            self._sample_acc += self.sample
            if self._sample_acc >= 1.0:
                self._sample_acc -= 1.0
                return True
            self.sampled_out += 1
        if self._sampled_counter is None:
            try:
                from .metrics import get_registry
                self._sampled_counter = get_registry().counter(
                    "fleet_traces_sampled_out_total",
                    help="whole request trace trees dropped by the "
                         "PADDLE_TPU_TRACE_SAMPLE head-sampling knob")
            except ImportError:
                self._sampled_counter = False   # standalone load
        if self._sampled_counter:
            self._sampled_counter.inc()
        return False

    def _append(self, trace_id, span):
        # every caller already holds self._lock (start_span/end_span/
        # annotate take it before delegating) — re-taking a plain Lock
        # here would self-deadlock
        # tpulint: disable-next-line=CON01
        rec = self._traces.get(trace_id)
        if rec is None:
            return False  # trace already evicted: drop, never orphan
        if len(rec["spans"]) >= self.max_spans_per_trace:
            rec["truncated"] = True
            return False
        rec["spans"][span["id"]] = span
        return True

    def start_span(self, ctx, name, proc=None, t0=None, args=None):
        """Open a child span under ``ctx``; returns the CHILD context
        (same trace, new span_id) or None (no ctx / suppressed /
        evicted / truncated). Pass the child ctx back to end_span."""
        if ctx is None or _suppressed():
            return None
        span = {"id": next(_id_counter), "parent": int(ctx["span_id"]),
                "name": name, "proc": proc or ctx.get("proc", "?"),
                "t0": now() if t0 is None else float(t0), "t1": None,
                "outcome": None, "args": dict(args or {})}
        with self._lock:
            if not self._append(ctx["trace_id"], span):
                return None
        return {"trace_id": ctx["trace_id"], "span_id": span["id"],
                "proc": span["proc"], "hops": int(ctx.get("hops", 0)),
                "t0": span["t0"]}

    def end_span(self, ctx, t1=None, outcome=None, args=None):
        """Close the span ``ctx`` points at (idempotent: the first
        close wins — a hedge loser's late result cannot rewrite the
        outcome the router recorded at cancel time)."""
        if ctx is None or _suppressed():
            return
        with self._lock:
            rec = self._traces.get(ctx["trace_id"])
            span = None if rec is None \
                else rec["spans"].get(int(ctx["span_id"]))
            if span is None or span["t1"] is not None:
                return
            span["t1"] = now() if t1 is None else float(t1)
            if outcome is not None:
                span["outcome"] = str(outcome)
            if args:
                span["args"].update(args)

    def add_span(self, ctx, name, t0, t1=None, proc=None, args=None,
                 outcome=None):
        """One complete child span of ``ctx`` ([t0, t1] perf_counter
        seconds, t1 None = now). Returns the span id or None."""
        if ctx is None or _suppressed():
            return None
        span = {"id": next(_id_counter), "parent": int(ctx["span_id"]),
                "name": name, "proc": proc or ctx.get("proc", "?"),
                "t0": float(t0),
                "t1": now() if t1 is None else float(t1),
                "outcome": None if outcome is None else str(outcome),
                "args": dict(args or {})}
        with self._lock:
            if not self._append(ctx["trace_id"], span):
                return None
        return span["id"]

    def annotate(self, ctx, **args):
        """Merge args into the span ``ctx`` points at (e.g. the
        prefix-dedup boundary on a continuation leg)."""
        if ctx is None or _suppressed():
            return
        with self._lock:
            rec = self._traces.get(ctx["trace_id"])
            span = None if rec is None \
                else rec["spans"].get(int(ctx["span_id"]))
            if span is not None:
                span["args"].update(args)

    # -- reading -----------------------------------------------------------

    def trace_ids(self):
        with self._lock:
            return list(self._traces)

    def find(self, rid):
        """Latest trace_id opened for fleet request ``rid`` (None when
        unknown or evicted)."""
        with self._lock:
            found = None
            for tid, rec in self._traces.items():
                if rec["rid"] == rid:
                    found = tid
            return found

    def summaries(self):
        """Per-trace index rows in ONE pass under the lock — no tree
        build, no span copies, no attribution. This is what a
        periodically-scraped /traces index must use: the full
        attribution machinery over every stored trace would contend
        with the serving control loop on this store's lock."""
        out = []
        with self._lock:
            for tid, rec in self._traces.items():
                spans = rec["spans"]
                root = next(iter(spans.values()), None)
                if root is None:
                    continue
                t1 = root["t1"]
                if t1 is None:  # still open: bound at latest child
                    t1 = max((s["t1"] for s in spans.values()
                              if s["t1"] is not None), default=None)
                out.append({
                    "trace_id": tid, "rid": rec["rid"],
                    "outcome": root["outcome"],
                    "e2e_s": None if t1 is None
                    else round(max(t1 - root["t0"], 0.0), 6),
                    "spans": len(spans),
                    "truncated": rec["truncated"]})
        return out

    def _snapshot(self, trace_id):
        with self._lock:
            rec = self._traces.get(trace_id)
            if rec is None:
                return None
            return {"rid": rec["rid"], "truncated": rec["truncated"],
                    "spans": [dict(s, args=dict(s["args"]))
                              for s in rec["spans"].values()]}

    def tree(self, trace_id):
        """Nested span tree: each node is the span dict plus
        ``children`` (insertion order). None for unknown traces."""
        rec = self._snapshot(trace_id)
        if rec is None:
            return None
        nodes = {s["id"]: dict(s, children=[]) for s in rec["spans"]}
        root = None
        for s in rec["spans"]:
            node = nodes[s["id"]]
            parent = nodes.get(s["parent"])
            if parent is not None:
                parent["children"].append(node)
            elif root is None:
                root = node
        if root is None:
            return None
        return {"trace_id": trace_id, "rid": rec["rid"],
                "truncated": rec["truncated"], "root": root}

    def spans(self, trace_id):
        rec = self._snapshot(trace_id)
        return [] if rec is None else rec["spans"]

    # -- attribution -------------------------------------------------------

    def attribution(self, trace_id, tolerance=0.05):
        """Hop-by-hop latency decomposition of one trace.

        The root span's direct children are the hops (placement wait,
        transport, replica legs). ``hops_sum_s`` adds the SERIAL hops
        — a hop annotated ``hedge_loser`` in its args is excluded
        because it overlaps the winning leg by construction (a
        client-CANCELLED only leg is real serial work and stays in);
        ``covered_s`` is the interval-union coverage of ALL hops
        against the root, so overlapping legs are counted once;
        ``within_tolerance`` holds when the uncovered remainder is
        under ``tolerance * e2e``. Each hop carries its own child
        breakdown (queue/prefill/decode inside a replica leg) plus
        ``self_s``, the hop time its children do not explain."""
        t = self.tree(trace_id)
        if t is None:
            return None
        root = t["root"]
        t_end = root["t1"]
        if t_end is None:  # still open: bound at the latest child
            t_end = max([root["t0"]]
                        + [s["t1"] for s in self.spans(trace_id)
                           if s["t1"] is not None])
        e2e = max(t_end - root["t0"], 0.0)

        def dur(n, default_end=t_end):
            end = n["t1"] if n["t1"] is not None else default_end
            return max(end - n["t0"], 0.0)

        hops, intervals, serial = [], [], 0.0
        for child in root["children"]:
            d = dur(child)
            kids = [{"name": k["name"], "proc": k["proc"],
                     "dur_s": round(dur(k), 6),
                     "outcome": k["outcome"], "args": k["args"]}
                    for k in child["children"]]
            row = {"span_id": child["id"], "name": child["name"],
                   "proc": child["proc"], "outcome": child["outcome"],
                   "t0_rel_s": round(child["t0"] - root["t0"], 6),
                   "dur_s": round(d, 6), "args": child["args"],
                   "children": kids,
                   "self_s": round(max(d - sum(k["dur_s"]
                                               for k in kids), 0.0), 6)}
            hops.append(row)
            lo = max(child["t0"], root["t0"])
            hi = min(child["t1"] if child["t1"] is not None else t_end,
                     t_end)
            if hi > lo:
                intervals.append((lo, hi))
            if not child["args"].get("hedge_loser"):
                serial += d
        # interval-union sweep: overlapping hops (hedge legs) count
        # their shared wall time once
        covered, cur_lo, cur_hi = 0.0, None, None
        for lo, hi in sorted(intervals):
            if cur_hi is None or lo > cur_hi:
                if cur_hi is not None:
                    covered += cur_hi - cur_lo
                cur_lo, cur_hi = lo, hi
            else:
                cur_hi = max(cur_hi, hi)
        if cur_hi is not None:
            covered += cur_hi - cur_lo
        unattributed = max(e2e - covered, 0.0)
        return {"trace_id": trace_id, "rid": t["rid"],
                "outcome": root["outcome"],
                "e2e_s": round(e2e, 6), "hops": hops,
                "hops_sum_s": round(serial, 6),
                "covered_s": round(covered, 6),
                "unattributed_s": round(unattributed, 6),
                "tolerance": float(tolerance),
                "within_tolerance": bool(
                    e2e == 0.0 or unattributed <= tolerance * e2e),
                "truncated": t["truncated"]}

    # -- Perfetto export ---------------------------------------------------

    def to_chrome(self, trace_ids=None, clock_offsets=None):
        """Chrome trace events for the given traces (default: all).
        One process group per ``proc`` — router first, replicas after —
        one thread per request inside it, so concurrent requests on a
        replica never render as a mis-nested stack. ``clock_offsets``
        maps proc -> seconds SUBTRACTED from that proc's timestamps
        (per-process skew reconciled from heartbeats)."""
        offsets = dict(clock_offsets or {})
        ids = self.trace_ids() if trace_ids is None else list(trace_ids)
        rows = []     # (proc, lane, span)
        procs, lanes = [], {}
        for tid in ids:
            rec = self._snapshot(tid)
            if rec is None:
                continue
            lane = f"req{rec['rid']}" if rec["rid"] is not None else tid
            t_end = max([s["t1"] for s in rec["spans"]
                         if s["t1"] is not None] or [None],
                        key=lambda v: -1 if v is None else v)
            for s in rec["spans"]:
                if s["t1"] is None and t_end is None:
                    continue  # nothing closed yet: skip open spans
                rows.append((s["proc"], lane, s, t_end))
                if s["proc"] not in procs:
                    procs.append(s["proc"])
                lanes.setdefault((s["proc"], lane),
                                 len([k for k in lanes
                                      if k[0] == s["proc"]]))
        procs.sort(key=lambda p: (p != "router", p))
        pid_of = {p: i + 1 for i, p in enumerate(procs)}
        events = []
        for p in procs:
            events.append({"name": "process_name", "ph": "M",
                           "pid": pid_of[p], "tid": 0,
                           "args": {"name": p}})
        for (p, lane), tid_i in sorted(lanes.items(),
                                       key=lambda kv: kv[1]):
            events.append({"name": "thread_name", "ph": "M",
                           "pid": pid_of[p], "tid": tid_i,
                           "args": {"name": lane}})
        for p, lane, s, t_end in rows:
            off = float(offsets.get(p, 0.0))
            t1 = s["t1"] if s["t1"] is not None else t_end
            if t1 is None:
                continue
            args = dict(s["args"])
            if s["outcome"] is not None:
                args["outcome"] = s["outcome"]
            events.append({
                "name": s["name"], "cat": "fleet", "ph": "X",
                "ts": _to_epoch_us(s["t0"] - off),
                "dur": max((t1 - s["t0"]) * 1e6, 0.0),
                "pid": pid_of[p], "tid": lanes[(p, lane)],
                "args": args})
        events.sort(key=lambda e: (e.get("ph") != "M", e.get("ts", 0)))
        return events

    def export_chrome(self, path, trace_ids=None, clock_offsets=None,
                      extra_recorders=()):
        """Write one merged Perfetto timeline (plus any round-10
        SpanRecorders — same epoch base) to ``path``. Atomic; always
        RFC-valid JSON."""
        events = self.to_chrome(trace_ids, clock_offsets)
        base_pid = max([e["pid"] for e in events], default=0)
        for i, rec in enumerate(extra_recorders):
            events.extend(rec.to_chrome(pid=base_pid + i + 1))
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            try:
                json.dump(doc, f, allow_nan=False)
            except ValueError:
                f.seek(0)
                f.truncate()
                json.dump(_finite(doc), f, allow_nan=False)
        os.replace(tmp, path)
        return path

    def clear(self):
        with self._lock:
            self._traces.clear()


_default = None
_default_lock = threading.Lock()


def get_store():
    """The process-global trace store (router mints into it, engines
    record into it; capacity via PADDLE_TPU_TRACE_CAP, default 256
    traces; head-sampling fraction via PADDLE_TPU_TRACE_SAMPLE,
    default 1.0 = keep everything — lower it so whole-tree tracing
    stays bounded at high QPS; drops count in
    ``fleet_traces_sampled_out_total``)."""
    global _default
    with _default_lock:
        if _default is None:
            try:
                cap = int(os.environ.get("PADDLE_TPU_TRACE_CAP", 256))
            except ValueError:
                cap = 256
            try:
                sample = float(os.environ.get(
                    "PADDLE_TPU_TRACE_SAMPLE", 1.0))
            except ValueError:
                sample = 1.0
            _default = TraceStore(max_traces=cap, sample=sample)
        return _default
