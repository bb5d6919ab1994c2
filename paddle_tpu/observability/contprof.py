"""Continuous host-side sampling profiler with serving-phase tags.

Every round since r7 grew the *host* leg of the serving hot path —
prefix fingerprinting, draft/rewind bookkeeping, placement scoring,
journal appends — yet spans can only time regions somebody remembered
to instrument. This module closes the blind spot: a daemon thread
walks ``sys._current_frames()`` at a configurable rate (default 19 Hz,
``PADDLE_TPU_PROFILE`` / ``PADDLE_TPU_PROFILE_HZ``), folds every
thread's stack into a bounded weighted trie, and tags each sample with
the thread's current **serving phase** — a marker set exactly where
``ServingEngine``/``FleetRouter`` already open spans (``prefill_<b>``
/ ``decode`` / ``spec_verify`` / ``prefix_admit`` / ``placement`` /
``journal``; unmarked threads read as ``idle``) — so a profile answers
"host wall time, by phase, by frame".

Design contracts, matching the rest of the observability plane:

- **Host-side only, zero-recompile untouched.** The sampler never
  imports jax, never touches devices, and skips threads that are
  inside an ``introspecting()`` AOT replay (the introspect module
  publishes their thread ids) — profiling ON must leave compile
  counts frozen, chaos-asserted.
- **Self-measuring, never silent.** ``profile_overhead_ratio`` gauges
  the sampler's own duty cycle (EWMA of sample-cost / period) and the
  rate automatically halves while the ratio sits above a 1% cap
  (``profile_backoffs_total`` counts each step down, floor at
  ``min_hz``); when the stack trie hits its node bound the sample's
  weight lands on the deepest existing node and
  ``profile_samples_dropped_total`` counts the truncation.
- **Stdlib-only, standalone-loadable** (``tools/_obs.py``): no
  intra-package imports at module scope; ``io/atomic`` is file-loaded
  lazily for the write-then-rename persistence discipline.

Exports: ``fold()``/``folded_text()`` (collapsed one-line-per-stack
text, ``phase:decode;mod.fn;mod.fn2 N``), ``save()``/``load_folded()``
(torn-tolerant: a truncated copy loses at most the tail line),
``flamegraph_html()`` (self-contained — the folded profile rides an
embedded JSON ``<script>`` a machine can parse back out), ``digest()``
(bounded per-phase top-K leaf frames — the shape that rides replica
heartbeats into the router's fleet hotspot rollup) and ``report()``
(the ``/profile?window=S`` endpoint body). ``tools/profile_diff.py``
consumes two folded profiles and gates on wall-share deltas.
"""
from __future__ import annotations

import collections
import json
import math
import os
import sys
import threading
import time

__all__ = ["ContinuousProfiler", "phase", "set_phase", "current_phase",
           "active_profiler", "current_profile", "load_folded",
           "fold_shares", "IDLE_PHASE"]

IDLE_PHASE = "idle"


def _finite(obj):
    """Map non-finite floats to None for the JSON exports (the
    metrics.py discipline, duplicated — this module stays
    standalone-loadable, no intra-package imports at module scope)."""
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj

# -- serving-phase markers --------------------------------------------------
#
# A plain module-level dict keyed by thread id: single-key reads and
# writes are GIL-atomic, so the sampler thread can read markers set by
# dispatch threads with no lock on the hot path. A thread with no
# marker samples as "idle" — honest for the control loop's wait slots.

_phases = {}


def set_phase(name):
    """Set (or with ``None`` clear) the calling thread's phase."""
    tid = threading.get_ident()
    if name is None:
        _phases.pop(tid, None)
    else:
        _phases[tid] = str(name)


def current_phase(tid=None):
    """The phase marker of ``tid`` (default: calling thread), or
    None."""
    return _phases.get(threading.get_ident() if tid is None else tid)


class phase:
    """Context manager marking the calling thread's serving phase for
    the duration of a block; re-entrant (restores the outer phase on
    exit, so a journal append inside placement reads ``journal`` then
    goes back to ``placement``)."""

    __slots__ = ("name", "_prev", "_tid")

    def __init__(self, name):
        self.name = str(name)

    def __enter__(self):
        self._tid = threading.get_ident()
        self._prev = _phases.get(self._tid)
        _phases[self._tid] = self.name
        return self

    def __exit__(self, *exc):
        if self._prev is None:
            _phases.pop(self._tid, None)
        else:
            _phases[self._tid] = self._prev
        return False


def _introspecting_tids():
    """Thread ids currently inside an AOT introspection replay —
    published by introspect.py under either its package name or the
    standalone-load key of tools/_obs.py. No import: if the module was never
    loaded, no replay can be running."""
    for key in ("paddle_tpu.observability.introspect",
                "_bench_obs_introspect"):
        mod = sys.modules.get(key)
        if mod is not None:
            tids = getattr(mod, "_introspecting_threads", None)
            if tids:
                return tids
    return ()


# -- env knobs --------------------------------------------------------------

def profile_enabled_from_env(default=False):
    """The ``PADDLE_TPU_PROFILE`` arm switch (default OFF: never-armed
    engines stay byte-identical to the legacy goldens, the same
    dormancy contract spec-decode follows)."""
    raw = os.environ.get("PADDLE_TPU_PROFILE")
    if raw is None:
        return bool(default)
    return raw.lower() in ("1", "true", "on")


def profile_hz_from_env(default=19.0):
    """``PADDLE_TPU_PROFILE_HZ`` (default 19 — deliberately prime, so
    the sampler can't phase-lock with 10/100 Hz periodic work and
    systematically miss it)."""
    try:
        hz = float(os.environ.get("PADDLE_TPU_PROFILE_HZ", default))
    except ValueError:
        return float(default)
    return hz if hz > 0 else float(default)


def _atomic():
    """io/atomic.py, lazily — package import when available, straight
    file-load otherwise (standalone mode has no package context)."""
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


_atomic_mod = None


# -- the profiler -----------------------------------------------------------

class ContinuousProfiler:
    """Always-on sampling profiler for one process.

    ``start()`` spawns the daemon sampler; ``stop()`` joins it. All
    public readers (fold/digest/report) take the internal lock, so
    exporter HTTP threads can scrape a live profiler safely.
    """

    def __init__(self, *, hz=None, registry=None, name="host",
                 max_nodes=4096, max_depth=48, overhead_cap=0.01,
                 min_hz=1.0, topk=32, recent_samples=8192):
        self.name = str(name)
        self.hz = float(hz) if hz is not None else profile_hz_from_env()
        self.base_hz = self.hz
        self.max_nodes = int(max_nodes)
        self.max_depth = int(max_depth)
        self.overhead_cap = float(overhead_cap)
        self.min_hz = float(min_hz)
        self.topk = int(topk)
        self._lock = threading.Lock()
        self._root = [0, {}]          # [self_weight, {label: node}]
        self._nodes = 1
        self._recent = collections.deque(maxlen=int(recent_samples))
        self._intern = {}             # stack-key tuple -> itself
        self._phase_counts = {}       # phase -> samples
        self._phase_leaf = {}         # phase -> {leaf frame: samples}
        self.samples = 0
        self.dropped = 0
        self.backoffs = 0
        self.overhead_ratio = 0.0
        self._ewma_seeded = False
        self.started_at = None
        self._stop = threading.Event()
        self._thread = None
        self._g_overhead = self._g_hz = None
        self._c_samples = self._c_dropped = self._c_backoffs = None
        if registry is not None:
            self._g_overhead = registry.gauge(
                "profile_overhead_ratio",
                help="continuous profiler duty cycle (EWMA of "
                     "sample cost / sampling period); Hz backs off "
                     "above the cap")
            self._g_hz = registry.gauge(
                "profile_hz",
                help="continuous profiler's current sampling rate "
                     "(backed off below the configured rate when the "
                     "overhead cap is hit)")
            self._c_samples = registry.counter(
                "profile_samples_total",
                help="stack samples folded into the profile trie")
            self._c_dropped = registry.counter(
                "profile_samples_dropped_total",
                help="samples truncated at the trie node bound "
                     "(weight kept at the deepest existing node — "
                     "the cap is never silent)")
            self._c_backoffs = registry.counter(
                "profile_backoffs_total",
                help="automatic Hz halvings taken to stay under the "
                     "overhead cap")
            self._g_overhead.set(0.0)
            self._g_hz.set(self.hz)

    # -- lifecycle --------------------------------------------------------

    def start(self):
        if self._thread is not None:
            return self
        self.started_at = time.time()
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name=f"contprof-{self.name}",
            daemon=True)
        self._thread.start()
        with _active_lock:
            if self not in _active:
                _active.append(self)
        return self

    def stop(self, timeout=2.0):
        t = self._thread
        if t is None:
            return
        self._stop.set()
        t.join(timeout)
        self._thread = None
        with _active_lock:
            if self in _active:
                _active.remove(self)

    @property
    def running(self):
        return self._thread is not None and self._thread.is_alive()

    def _run(self):
        while not self._stop.wait(1.0 / self.hz):
            t0 = time.perf_counter()
            try:
                self._sample(time.time())
            except Exception:   # noqa: BLE001 — the profiler must
                pass            # never take the serving process down
            self._note_duty(time.perf_counter() - t0)

    # -- sampling ---------------------------------------------------------

    def _stack_of(self, frame):
        labels = []
        depth = 0
        while frame is not None and depth < self.max_depth:
            mod = frame.f_globals.get("__name__", "?")
            labels.append(f"{mod}.{frame.f_code.co_name}")
            frame = frame.f_back
            depth += 1
        labels.reverse()
        return tuple(labels)

    def _sample(self, now):
        me = threading.get_ident()
        intro = _introspecting_tids()
        frames = sys._current_frames()
        with self._lock:
            for tid, frame in frames.items():
                if tid == me or tid in intro:
                    continue
                ph = _phases.get(tid, IDLE_PHASE)
                stack = self._stack_of(frame)
                self._insert(ph, stack)
                self.samples += 1
                if self._c_samples is not None:
                    self._c_samples.inc()
                self._phase_counts[ph] = \
                    self._phase_counts.get(ph, 0) + 1
                leaf = stack[-1] if stack else "?"
                self._leaf_bump(ph, leaf)
                key = ("phase:" + ph,) + stack
                key = self._intern.setdefault(key, key)
                if len(self._intern) > 4 * self._recent.maxlen:
                    self._intern.clear()
                self._recent.append((now, key))

    def _insert(self, ph, stack):
        node = self._root
        truncated = False
        for label in ("phase:" + ph,) + stack:
            child = node[1].get(label)
            if child is None:
                if self._nodes >= self.max_nodes:
                    truncated = True
                    break
                child = [0, {}]
                node[1][label] = child
                self._nodes += 1
            node = child
        node[0] += 1
        if truncated:
            self.dropped += 1
            if self._c_dropped is not None:
                self._c_dropped.inc()

    def _leaf_bump(self, ph, leaf):
        d = self._phase_leaf.setdefault(ph, {})
        d[leaf] = d.get(leaf, 0) + 1
        if len(d) > 4 * self.topk:
            # bounded approximate top-K: evict the lightest half.
            # Frames that re-enter restart their count — fine for a
            # hotspot digest, documented, and the full trie still
            # holds the exact weights.
            keep = sorted(d.items(), key=lambda kv: -kv[1])
            self._phase_leaf[ph] = dict(keep[:2 * self.topk])

    def _note_duty(self, cost_s):
        """Fold one sampling pass's cost into the duty-cycle EWMA and
        back the rate off while it sits above the cap. Exposed for the
        deterministic backoff tests (no real sampling needed)."""
        period = 1.0 / max(self.hz, 1e-9)
        ratio = min(1.0, max(0.0, cost_s) / period)
        if not self._ewma_seeded:
            self.overhead_ratio = ratio
            self._ewma_seeded = True
        else:
            self.overhead_ratio = (0.8 * self.overhead_ratio
                                   + 0.2 * ratio)
        if self.overhead_ratio > self.overhead_cap \
                and self.hz > self.min_hz:
            self.hz = max(self.min_hz, self.hz / 2.0)
            self.backoffs += 1
            # halving Hz halves the duty cycle going forward; reflect
            # it now so one spike can't cascade straight to min_hz
            self.overhead_ratio /= 2.0
            if self._c_backoffs is not None:
                self._c_backoffs.inc()
            if self._g_hz is not None:
                self._g_hz.set(self.hz)
        if self._g_overhead is not None:
            self._g_overhead.set(self.overhead_ratio)

    # -- folding / export --------------------------------------------------

    def fold(self, window_s=None, now=None):
        """Collapsed profile as {'phase:p;mod.fn;...': weight}. With
        ``window_s``, folded from the bounded recent-sample ring
        (newest ``recent_samples`` samples) instead of the full
        trie."""
        out = {}
        with self._lock:
            if window_s is None:
                stack = [((), self._root)]
                while stack:
                    path, node = stack.pop()
                    if node[0] > 0 and path:
                        out[";".join(path)] = \
                            out.get(";".join(path), 0) + node[0]
                    for label, child in node[1].items():
                        stack.append((path + (label,), child))
            else:
                cutoff = (time.time() if now is None else now) \
                    - float(window_s)
                for t, key in self._recent:
                    if t >= cutoff:
                        k = ";".join(key)
                        out[k] = out.get(k, 0) + 1
        return out

    def folded_text(self, window_s=None, now=None):
        """The collapsed-stack text format (one ``stack weight`` line,
        sorted): flamegraph.pl-compatible and profile_diff's input."""
        folded = self.fold(window_s=window_s, now=now)
        return "\n".join(f"{k} {v}" for k, v in sorted(folded.items()))

    def save(self, path, window_s=None):
        """Persist the folded profile via write-then-rename. The text
        format is torn-tolerant by construction: ``load_folded`` of a
        truncated copy drops at most the tail line."""
        header = (f"# contprof folded v1 name={self.name} "
                  f"hz={self.hz:g} samples={self.samples} "
                  f"dropped={self.dropped}\n")
        body = self.folded_text(window_s=window_s)
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        _atomic().atomic_replace(
            path, (header + body + "\n").encode("utf-8"))
        return path

    def digest(self, topk=8):
        """Bounded per-phase hotspot digest — the shape that rides
        replica heartbeats (host-side JSON, a few hundred bytes)."""
        with self._lock:
            phases = dict(self._phase_counts)
            top = {ph: sorted(d.items(), key=lambda kv: -kv[1])[:topk]
                   for ph, d in self._phase_leaf.items()}
        return {"samples": self.samples, "dropped": self.dropped,
                "backoffs": self.backoffs,
                "overhead_ratio": round(self.overhead_ratio, 6),
                "hz": self.hz, "phases": phases,
                "top": {ph: [[f, int(n)] for f, n in rows]
                        for ph, rows in top.items()}}

    def stats(self):
        """Flat monotonic counters for the router's restart-tolerant
        delta fold (the _fold_spec/_fold_prefix idiom)."""
        return {"samples": int(self.samples),
                "dropped": int(self.dropped),
                "backoffs": int(self.backoffs)}

    def report(self, window_s=None):
        """The ``/profile?window=S`` endpoint body."""
        return {"name": self.name, "running": self.running,
                "hz": self.hz, "base_hz": self.base_hz,
                "overhead_ratio": round(self.overhead_ratio, 6),
                "overhead_cap": self.overhead_cap,
                "samples": self.samples, "dropped": self.dropped,
                "backoffs": self.backoffs, "nodes": self._nodes,
                "window_s": window_s,
                "folded": self.folded_text(window_s=window_s),
                "digest": self.digest()}

    def flamegraph_html(self, path=None, window_s=None, title=None):
        """Self-contained flamegraph: the folded profile is embedded
        as a JSON ``<script>`` block (machine-parseable back out,
        tests/test_contprof.py) and a small inline
        renderer draws the flame as nested divs. No external assets,
        openable from a triage dir years later."""
        folded = self.fold(window_s=window_s)
        doc = {"name": self.name, "title": title or
               f"contprof {self.name}", "samples": self.samples,
               "dropped": self.dropped, "hz": self.hz,
               "window_s": window_s, "folded": folded}
        try:
            payload = json.dumps(doc, sort_keys=True, allow_nan=False)
        except ValueError:
            payload = json.dumps(_finite(doc), sort_keys=True,
                                 allow_nan=False)
        # "</" would close the script tag early inside inline JSON
        payload = payload.replace("</", "<\\/")
        html_text = _FLAME_TEMPLATE.replace("__PROFILE_JSON__", payload)
        if path is None:
            return html_text
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        _atomic().atomic_replace(path, html_text.encode("utf-8"))
        return path


_FLAME_TEMPLATE = """<!doctype html>
<html><head><meta charset="utf-8"><title>contprof flamegraph</title>
<style>
body { font: 12px monospace; margin: 12px; background: #fff; }
#flame div.fr { position: absolute; height: 16px; overflow: hidden;
  white-space: nowrap; border: 1px solid #fff; box-sizing: border-box;
  cursor: default; }
#flame { position: relative; }
#info { margin: 8px 0; color: #444; }
</style></head><body>
<h3 id="t"></h3><div id="info"></div><div id="flame"></div>
<script id="profile-data" type="application/json">__PROFILE_JSON__</script>
<script>
var doc = JSON.parse(document.getElementById("profile-data").text);
document.getElementById("t").textContent = doc.title;
var root = {c: {}, w: 0};
var total = 0;
Object.keys(doc.folded).forEach(function (k) {
  var w = doc.folded[k]; total += w;
  var node = root;
  k.split(";").forEach(function (label) {
    node = node.c[label] || (node.c[label] = {c: {}, w: 0});
    node.sub = (node.sub || 0) + w;
  });
  node.w += w;
});
document.getElementById("info").textContent =
  total + " samples @ " + doc.hz + " Hz" +
  (doc.dropped ? " (" + doc.dropped + " truncated)" : "");
var flame = document.getElementById("flame");
var W = Math.max(600, window.innerWidth - 40);
var maxDepth = 0;
function draw(node, label, x, width, depth) {
  if (depth >= 0 && width >= 1) {
    var d = document.createElement("div");
    d.className = "fr";
    d.style.left = x + "px"; d.style.top = depth * 17 + "px";
    d.style.width = width + "px";
    var hue = label.indexOf("phase:") === 0 ? 210 : 30;
    d.style.background = "hsl(" + hue + ", 70%, " +
      (85 - (depth % 5) * 4) + "%)";
    d.textContent = label;
    d.title = label + " — " + (node.sub || node.w) + " samples (" +
      (100 * (node.sub || node.w) / Math.max(total, 1)).toFixed(1) +
      "%)";
    flame.appendChild(d);
    if (depth > maxDepth) maxDepth = depth;
  }
  var cx = x;
  Object.keys(node.c).sort().forEach(function (k) {
    var child = node.c[k];
    var cw = W * (child.sub || child.w) / Math.max(total, 1);
    draw(child, k, cx, cw, depth + 1);
    cx += cw;
  });
}
draw(root, "", 0, W, -1);
flame.style.height = (maxDepth + 2) * 17 + "px";
</script></body></html>
"""


# -- module-level active-profiler registry ---------------------------------
#
# The anomaly sentinel and the flight recorder attach "what was the
# process actually doing" evidence without holding a profiler
# reference — they ask for the most recently started one.

_active = []
_active_lock = threading.Lock()


def active_profiler():
    """The most recently started, still-running profiler (or None)."""
    with _active_lock:
        for p in reversed(_active):
            if p.running:
                return p
    return None


def current_profile(window_s=60.0):
    """``report(window_s)`` of the active profiler, or None — the
    guarded attach point for flight dumps."""
    p = active_profiler()
    if p is None:
        return None
    try:
        return p.report(window_s=window_s)
    except Exception:   # noqa: BLE001 — evidence attach never raises
        return None


# -- loaders / share math ---------------------------------------------------

def load_folded(path):
    """Folded-profile file -> {stack: weight}. Torn-tolerant: comment,
    blank and unparseable lines are skipped (a truncated tail line
    either still parses — smaller weight — or drops); an unreadable
    file is an empty profile, never an exception."""
    out = {}
    try:
        with open(path, "r", encoding="utf-8", errors="replace") as f:
            data = f.read()
    except OSError:
        return out
    for line in data.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        stack, _, weight = line.rpartition(" ")
        if not stack:
            continue
        try:
            n = int(weight)
        except ValueError:
            continue
        if n > 0:
            out[stack] = out.get(stack, 0) + n
    return out


def fold_shares(folded):
    """{stack: weight} -> ({phase: share}, {leaf_frame: share}) with
    shares in [0, 1] of total weight — the units profile_diff gates
    on. Self-weight by leaf frame; the phase is the stack's
    ``phase:*`` head (``idle`` when a profile predates phase tags)."""
    total = float(sum(folded.values())) or 1.0
    phases, frames = {}, {}
    for stack, w in folded.items():
        parts = stack.split(";")
        ph = parts[0][6:] if parts[0].startswith("phase:") \
            else IDLE_PHASE
        phases[ph] = phases.get(ph, 0.0) + w / total
        leaf = parts[-1] if parts else "?"
        frames[leaf] = frames.get(leaf, 0.0) + w / total
    return phases, frames
