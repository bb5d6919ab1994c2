"""Traffic capture — recorded fleet workloads as replayable archives.

Every measurement the fleet produces today dies with the run: the
history plane remembers *aggregates*, the trace store remembers a
bounded ring of span trees, but nobody remembers the WORKLOAD — which
requests arrived when, with what prompts, tenants, priorities and
deadlines, and what the fleet answered. That record is the missing
input for every what-if question the ROADMAP's autotune/autoscale
items need: "would yesterday's traffic have met its SLO with a lower
hedge threshold" is only answerable by re-driving yesterday's traffic
(the Gemma-on-Cloud-TPU serving paper's trace-replayed TTFT/e2e
decomposition, PAPERS.md; TpuGraphs shows captured workload corpora
are what make knob search a learnable problem).

This module is the capture half (``tools/fleet_replay.py`` is the
replay half): a ``TrafficRecorder`` the FleetRouter writes through —

- one ``arrival`` record per ADMITTED request (rid, arrival offset on
  the shared epoch<->perf_counter base, tenant, priority, remaining
  deadline budget, prompt tokens, decode budget, eos) at submit;
- one ``resolve`` record per resolved request (status, output tokens,
  TTFT/e2e, failover/hedge flags, and the round-12 per-hop latency
  attribution compacted to ``[{name, proc, dur_s, outcome}, ...]``);
- ``meta`` records carrying fleet facts replay needs to reproduce
  tokens exactly (per-replica sampling params off the health plane).

Disk format = the write-ahead journal's, reused deliberately: bounded
rotating ``cap-NNNNNN.jsonl`` segments of ``<len:8hex> <crc:8hex>
<compact-json>`` lines, finalized with ``io/atomic`` ``.complete``
sidecars on rotation, torn-tail-tolerant replay (a bad line is
dropped and counted, never raised on). Rotation keeps at most
``max_segments`` segments — capture is a ring over the recent past,
not an unbounded log.

Capture discipline:

- **sampling** is head-based and deterministic (the TraceStore's
  fractional-accumulator, no RNG) via ``sample`` /
  ``PADDLE_TPU_CAPTURE_SAMPLE``; a sampled-out request is counted
  (``fleet_capture_sampled_out_total``), never silently absent;
- **trace coherence**: the router force-keeps the span tree of every
  captured request (``TraceStore.new_trace(force=True)``), so an
  archived request always carries its attribution; divergences (a
  captured request that still resolved without one) count in
  ``fleet_capture_trace_missing_total``;
- **suppressed under introspecting()** — capture can never perturb an
  AOT replay or read as work in a zero-recompile assertion;
- **best-effort**: a disk failure drops the record and counts
  ``fleet_capture_errors_total`` — losing a capture line must never
  take the serving path down (the journal owns durability-critical
  state; this plane owns measurement).

Cost is metered in the owner's registry (``fleet_capture_*``,
catalogue in docs/observability.md). Stdlib-only by contract
(standalone-loadable via tools/_obs.py; io/atomic resolved lazily
with the same file-load fallback flightrec/history use).
"""
from __future__ import annotations

import json
import math
import os
import re
import threading
import time
import zlib

__all__ = ["TrafficRecorder", "load_archive"]

_FORMAT = 1
_SEG_RE = re.compile(r"^cap-(\d{6})\.jsonl$")

_atomic_mod = None


def _atomic():
    """io/atomic.py, lazily — package import when available, straight
    file-load otherwise (standalone mode has no package context; the
    helper is stdlib-only by contract). Same pattern as history.py."""
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


def _frame(rec):
    """One length-prefixed, CRC-checksummed line (the journal's wire
    format, duplicated like history.py so this module stays
    standalone-loadable)."""
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


def _segments(directory):
    """[(num, path)] ascending for every cap segment in `directory`."""
    out = []
    try:
        names = os.listdir(directory)
    except OSError:
        return out
    for name in names:
        m = _SEG_RE.match(name)
        if m:
            out.append((int(m.group(1)),
                        os.path.join(directory, name)))
    out.sort()
    return out


class TrafficRecorder:
    """Bounded rotating request-capture archive writer.

    directory: created if missing; segments rotate inside it.
    registry: MetricsRegistry the ``fleet_capture_*`` series land in
        (None = unmetered — the internal counts still tell the story).
    sample: keep-fraction in [0, 1] for whole requests (arrival AND
        resolve travel together); default reads
        ``PADDLE_TPU_CAPTURE_SAMPLE`` (1.0 = capture everything).
        Deterministic fractional-accumulator head sampling, no RNG.
    segment_max_bytes: rotation threshold for the active segment.
    max_segments: ring bound — rotation deletes the oldest segments
        beyond this, so capture can never fill a disk.
    """

    def __init__(self, directory, *, registry=None, sample=None,
                 segment_max_bytes=1 << 20, max_segments=8):
        self.dir = os.path.abspath(str(directory))
        os.makedirs(self.dir, exist_ok=True)
        if sample is None:
            try:
                sample = float(os.environ.get(
                    "PADDLE_TPU_CAPTURE_SAMPLE", 1.0))
            except ValueError:
                sample = 1.0
        self.sample = min(max(float(sample), 0.0), 1.0)
        self.segment_max_bytes = int(segment_max_bytes)
        self.max_segments = max(int(max_segments), 1)
        self._sample_acc = 0.0
        self._lock = threading.Lock()
        self._meta = {}          # fleet facts (sampling params, ...)
        self._meta_dirty = False
        self._closed = False
        self._m = {}
        if registry is not None:
            for name, help_ in (
                    ("requests", "requests captured into the traffic "
                                 "archive (arrival records)"),
                    ("records", "archive records written (arrival + "
                                "resolve + meta)"),
                    ("bytes", "archive bytes written"),
                    ("errors", "capture writes dropped on an I/O "
                               "failure (capture is best-effort)"),
                    ("rotations", "archive segment rotations"),
                    ("sampled_out", "requests dropped by the capture "
                                    "sampling knob"),
                    ("trace_missing", "captured requests that resolved "
                                      "without a span tree / "
                                      "attribution (capture<->trace "
                                      "sampling divergence)")):
                self._m[name] = registry.counter(
                    f"fleet_capture_{name}_total", help=help_)
        self.sampled_out = 0
        self.errors = 0
        # epoch<->perf_counter base: arrival offsets are recorded on
        # BOTH clocks so replay schedules on a monotonic base while
        # the archive stays joinable with history/trace timelines
        self._epoch0 = time.time()
        self._perf0 = time.perf_counter()
        segs = _segments(self.dir)
        num = (segs[-1][0] + 1) if segs else 1
        self._active = self._seg_path(num)
        self._f = open(self._active, "ab")
        self._size = 0
        self._write_rec({"kind": "header", "format": _FORMAT,
                         "segment": num,
                         "epoch0": round(self._epoch0, 6)})
        self._prune(keep=self._active)

    # -- metrics ----------------------------------------------------------

    def _inc(self, name, n=1):
        c = self._m.get(name)
        if c is not None and n:
            c.inc(n)

    # -- sampling ---------------------------------------------------------

    def admit(self):
        """Deterministic capture decision for one request (call once
        per submit). Sampled-out requests count, never vanish."""
        if self._closed or _suppressed():
            return False
        if self.sample >= 1.0:
            return True
        with self._lock:
            self._sample_acc += self.sample
            if self._sample_acc >= 1.0:
                self._sample_acc -= 1.0
                return True
            self.sampled_out += 1
        self._inc("sampled_out")
        return False

    # -- recording --------------------------------------------------------

    def note_meta(self, **fields):
        """Merge fleet facts (e.g. per-replica sampling params) into
        the archive meta; written as a ``meta`` record on the next
        capture write and at the head of every later segment."""
        with self._lock:
            before = dict(self._meta)
            self._meta.update(fields)
            if self._meta != before:
                self._meta_dirty = True

    def record_arrival(self, rid, prompt, max_new, *, eos=None,
                       priority=0, tenant=None, deadline_ms=None,
                       t_epoch=None, t_pc=None):
        """Capture one admitted request. Returns ``{"segment",
        "offset"}`` (the /requests index's archive locator) or None
        (suppressed / closed / write failed)."""
        if self._closed or _suppressed():
            return None
        te = time.time() if t_epoch is None else float(t_epoch)
        tp = time.perf_counter() if t_pc is None else float(t_pc)
        rec = {"kind": "arrival", "rid": int(rid),
               "t_epoch": round(te, 6),
               "arrival_s": round(tp - self._perf0, 6),
               "tenant": tenant, "priority": int(priority),
               "deadline_ms": deadline_ms,
               "prompt": [int(t) for t in prompt],
               "max_new": int(max_new), "eos": eos}
        ref = self._append(rec)
        if ref is not None:
            self._inc("requests")
        return ref

    def note_trace_missing(self):
        """Count one capture<->trace sampling divergence (a captured
        request that resolved without a span tree / attribution) —
        part of the recorder's public surface so router wiring never
        reaches into private metric helpers."""
        self._inc("trace_missing")

    def record_resolve(self, rid, status, tokens, *, tenant=None,
                       replica=None, failovers=0, hedged=False,
                       e2e_s=None, ttft_s=None, hops=None,
                       trace_id=None):
        """Capture one resolved request's outcome + compact per-hop
        attribution rows. Returns the archive ref or None."""
        if self._closed or _suppressed():
            return None
        rec = {"kind": "resolve", "rid": int(rid),
               "status": str(status),
               "tokens": [int(t) for t in tokens],
               "tenant": tenant, "replica": replica,
               "failovers": int(failovers), "hedged": bool(hedged),
               "e2e_s": None if e2e_s is None else round(e2e_s, 6),
               "ttft_s": None if ttft_s is None else round(ttft_s, 6),
               "hops": hops, "trace_id": trace_id}
        return self._append(rec)

    def _write_rec(self, rec, fsync=False):
        """Frame + write one record to the active segment (caller
        holds no lock or the lock — pure file append). Raises OSError
        upward; _append owns the best-effort policy."""
        frame = _frame(dict(rec, ts=round(time.time(), 6)))
        self._f.write(frame)
        self._f.flush()
        if fsync:
            os.fsync(self._f.fileno())
        off = self._size
        self._size += len(frame)
        self._inc("records")
        self._inc("bytes", len(frame))
        return off

    def _append(self, rec):
        with self._lock:
            if self._closed:
                return None
            # best-effort contract: ANY write failure (OSError from
            # the disk, ValueError from a file handle a failed
            # rotation left closed) drops the record and counts — it
            # must never propagate into FleetRouter.submit
            try:
                if self._meta_dirty:
                    self._write_rec({"kind": "meta",
                                     "meta": dict(self._meta)})
                    # cleared only AFTER the write landed: a transient
                    # failure retries the meta on the next append
                    # instead of silently dropping the sampling params
                    self._meta_dirty = False
                seg = os.path.basename(self._active)
                off = self._write_rec(rec)
                if self._size >= self.segment_max_bytes:
                    self._rotate()
                return {"segment": seg, "offset": off}
            except (OSError, ValueError):
                self.errors += 1
                self._inc("errors")
                return None

    # -- rotation (ring of segments) --------------------------------------

    def _seg_path(self, num):
        return os.path.join(self.dir, f"cap-{num:06d}.jsonl")

    def _rotate(self):
        """Finalize the active segment (.complete sidecar — the
        io/atomic marker discipline) and open the next; drop the
        oldest segments beyond max_segments. Caller holds the lock."""
        atomic = _atomic()
        try:
            self._f.flush()
            os.fsync(self._f.fileno())
            self._f.close()
        except OSError:
            pass
        try:
            atomic.write_marker(atomic.marker_path(self._active),
                                {"bytes": self._size,
                                 "time": time.time()})
        except OSError:
            self.errors += 1
            self._inc("errors")
        segs = _segments(self.dir)
        num = (segs[-1][0] if segs else 0) + 1
        self._active = self._seg_path(num)
        try:
            self._f = open(self._active, "ab")
        except OSError:
            # the archive directory is gone/unwritable: capture is
            # dead. Close (errors counted) rather than leave a closed
            # handle every later append would crash on — the serving
            # path outlives its measurement plane, never vice versa
            self.errors += 1
            self._inc("errors")
            self._closed = True
            return
        self._size = 0
        self._write_rec({"kind": "header", "format": _FORMAT,
                         "segment": num,
                         "epoch0": round(self._epoch0, 6)})
        if self._meta:
            self._meta_dirty = True
        self._inc("rotations")
        self._prune(keep=self._active)

    def _prune(self, keep):
        atomic = _atomic()
        segs = _segments(self.dir)
        while len(segs) > self.max_segments:
            _num, victim = segs.pop(0)
            if victim == keep:
                break
            for path in (victim, atomic.marker_path(victim)):
                try:
                    os.remove(path)
                except OSError:
                    pass

    def close(self):
        """Flush + finalize the active segment (marker) — a closed
        archive replays with zero torn-tail drops. Idempotent."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            atomic = _atomic()
            try:
                self._f.flush()
                os.fsync(self._f.fileno())
                self._f.close()
            except OSError:
                pass
            try:
                atomic.write_marker(atomic.marker_path(self._active),
                                    {"bytes": self._size,
                                     "time": time.time()})
            except OSError:
                self.errors += 1
                self._inc("errors")


# -- reading ---------------------------------------------------------------


def load_archive(directory):
    """Parse a capture archive into replayable request entries.

    Returns ``(entries, meta, stats)``:

    - ``entries``: one dict per captured request, arrival order —
      ``{rid, t_epoch, arrival_s (offset from the FIRST captured
      arrival), tenant, priority, deadline_ms, prompt, max_new, eos,
      status, tokens, ttft_s, e2e_s, hops, failovers, hedged,
      replica}`` — resolve fields are None for requests whose resolve
      record was lost to the ring/tail (counted in
      ``stats["unresolved"]``);
    - ``meta``: the merged ``meta`` records (newest wins);
    - ``stats``: ``{"segments", "records", "torn_drops",
      "unresolved"}``.

    Torn/corrupt lines are dropped and counted, never raised on —
    an archive truncated at any byte offset loads its prefix."""
    stats = {"segments": 0, "records": 0, "torn_drops": 0,
             "unresolved": 0}
    arrivals, resolves, meta = {}, {}, {}
    order = []
    for _num, path in _segments(directory):
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            continue
        stats["segments"] += 1
        for line in data.split(b"\n"):
            if not line:
                continue
            rec = _parse_line(line)
            if rec is None:
                stats["torn_drops"] += 1
                continue
            stats["records"] += 1
            kind = rec.get("kind")
            if kind == "arrival" and rec.get("rid") is not None:
                rid = int(rec["rid"])
                if rid not in arrivals:
                    order.append(rid)
                arrivals[rid] = rec
            elif kind == "resolve" and rec.get("rid") is not None:
                resolves[int(rec["rid"])] = rec
            elif kind == "meta":
                meta.update(rec.get("meta") or {})
    entries = []
    base = None
    for rid in order:
        a = arrivals[rid]
        if base is None:
            base = float(a.get("arrival_s") or 0.0)
        r = resolves.get(rid) or {}
        if not r:
            stats["unresolved"] += 1
        entries.append({
            "rid": rid, "t_epoch": a.get("t_epoch"),
            "arrival_s": round(
                max(float(a.get("arrival_s") or 0.0) - base, 0.0), 6),
            "tenant": a.get("tenant"),
            "priority": int(a.get("priority") or 0),
            "deadline_ms": a.get("deadline_ms"),
            "prompt": [int(t) for t in a.get("prompt") or []],
            "max_new": int(a.get("max_new") or 0),
            "eos": a.get("eos"),
            "status": r.get("status"),
            "tokens": None if not r
            else [int(t) for t in r.get("tokens") or []],
            "ttft_s": r.get("ttft_s"), "e2e_s": r.get("e2e_s"),
            "hops": r.get("hops"),
            "failovers": int(r.get("failovers") or 0),
            "hedged": bool(r.get("hedged")),
            "replica": r.get("replica")})
    return entries, meta, stats
