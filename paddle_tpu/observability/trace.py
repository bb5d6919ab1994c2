"""RecompileTracer — every XLA trace becomes a queryable run fact.

"Zero-recompile" was a private assertion (ServingEngine counted
traces privately; the Engine counted nothing). This tracer is the one
mechanism both ride: ``tracer.jit(site, fn, **jit_kwargs)`` returns a
jitted callable whose body bumps a per-site counter exactly when jax
(re)traces — the same ground truth the serving zero-recompile contract
already used — and whose host wrapper, ONLY on a call that traced,
records an event carrying:

- the site name ("decode", "prefill_32", "train_step", ...);
- the argument shape/dtype signature (computed lazily, never on the
  steady-state hot path);
- a wall timestamp and the call's wall time (trace + compile +
  dispatch — the cost a recompile cliff actually charges);
- whether the trace was UNEXPECTED: a signature this site has already
  traced once. First-time signatures (a new prefill bucket, an
  intentional shape change) are expected; re-tracing a seen signature
  means a compiled program was dropped and rebuilt — the cliff the
  MLPerf/TPU-pod postmortems say to hunt first.

Per-call steady-state overhead is two dict reads and a perf_counter —
no device sync, no shape walking. Tracers register in a process-wide
WeakSet; ``report_all()`` merges every live tracer's report into the
run report exported next to metrics.json.

A call that traced also gets a STAGED record of the program's build
(the set-up a warm start still pays): ``t0``/``t1`` on perf_counter
(``t1`` after the outputs are ready), the ``stages`` jax reported
through ``jax.monitoring`` on this thread between them — trace,
lowering (Pallas -> Mosaic lives there), backend compile or
persistent-cache load, cache retrieval and hit, first run — the same
for the introspection replay under ``introspect``, the Pallas call
sites the trace met (``kernel_places``) and the enclosing build
(``parent``: a ``phase`` such as ``ServingEngine.warmup``, or the outer
site). The listeners only fire while jax builds something, and the
wrapper reads what they kept only on a call that traced.
"""
from __future__ import annotations

import collections
import contextlib
import hashlib
import re
import threading
import time

__all__ = ["RecompileTracer", "get_tracer", "all_tracers", "report_all",
           "program_name", "kernel_place"]

# REENTRANT: close() runs from GC finalizers (Engine's
# weakref.finalize, ServingEngine.__del__), and a cyclic collection
# can fire on an allocation made while this same thread already holds
# the lock (report_all builds dicts under it) — a plain Lock would
# self-deadlock there
_all_lock = threading.RLock()
# strong refs, deliberately: a short-lived Engine (and its tracer)
# is often garbage before the end-of-run report is written — a weak
# registry would silently drop exactly the sites the report is for.
# Cost is bounded per tracer (counts + a maxlen event deque), and a
# long-lived host that retires engines bounds the COUNT by calling
# tracer.close() (Engine/ServingEngine finalizers do), which folds the
# tracer's aggregates into _closed_agg — a CUMULATIVE per-tracer-name
# rollup, never evicted, so an unexpected retrace recorded by engine
# #3 of a 500-engine sweep still shows in the final report (a bounded
# list of individual reports would silently drop it).
_all_tracers = []
_closed_agg = {}

# what jax reports while it builds a program (jax.monitoring), by the
# stage each duration is summed into
_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    # on a persistent-cache hit: key, read and deserialise
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_listening = False


class _Local(threading.local):
    """Per thread: `reported`, a bounded deque of (stage or event,
    seconds, perf_counter at its end) — the newest matter, a build's
    outermost stages end last; `building`, the stack of _Frames open;
    `traced`, the frame of the trace that ended last."""

    def __init__(self):
        self.reported = collections.deque(maxlen=4096)
        self.building = []
        self.traced = None


_local = _Local()


class _Frame:
    """A build open on this thread: a program being traced (`program`)
    or a phase; `places` counts the Pallas call sites traced inside it."""
    __slots__ = ("name", "program", "places")

    def __init__(self, name, program):
        self.name, self.program, self.places = name, program, {}


def _on_duration(event, seconds, **_meta):
    stage = _STAGE_EVENTS.get(event)
    if stage is not None or event == _RETRIEVAL:
        _local.reported.append((stage or event, seconds,
                                time.perf_counter()))


def _on_event(event, **_meta):
    if event == _CACHE_ASKED or event == _CACHE_HIT:
        _local.reported.append((event, 0.0, time.perf_counter()))


def _listen():
    """Register the two stage listeners with jax.monitoring, once per
    process (the registry is jax's own, process-wide). They fire only
    while jax builds something."""
    global _listening
    with _all_lock:
        if _listening:
            return
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True


def _stages(t0, t1):
    """The build stages jax reported on this thread that ended inside
    [t0, t1]: seconds of `trace_s`, `lower_s`, `backend_s` and
    `cache_retrieval_s` (inside `backend_s`), and `cache_hit`: whether
    every persistent-cache lookup hit, None where none was made. A stage
    that ran inside another (a nested jit traced inside the program's
    trace, a nested site's replay) is part of that one and not added
    again, so the stages never sum past the wall time. Also returns
    where the last stage ended (t0 when none did)."""
    out = dict.fromkeys(("trace_s", "lower_s", "backend_s",
                         "cache_retrieval_s"), 0.0)
    asked = hits = 0
    end = t0
    outer = []      # (start, end) of the stages kept, newest first
    # an enclosing stage ends after what it encloses: newest first, so
    # it is met before them (the slack absorbs the two clocks' skew)
    for event, seconds, at in reversed(_local.reported):
        if at < t0:
            break
        if at > t1:
            continue
        if event == _RETRIEVAL:
            out["cache_retrieval_s"] += seconds
        elif event == _CACHE_ASKED:
            asked += 1
        elif event == _CACHE_HIT:
            hits += 1
        elif not any(s - 1e-4 <= at - seconds and at <= e
                     for s, e in outer):
            outer.append((at - seconds, at))
            out[event] += seconds
            end = max(end, at)
    out["cache_hit"] = hits >= asked if asked else None
    return out, end


def kernel_place(name):
    """Count one call site of the Pallas kernel `name` in every build
    open on this thread. Called by `ops.pallas._common.pallas_call` as
    the kernel enters a trace, so a kernel in a `lax.scan` body counts
    once, and one inside a nested `jax.jit` once for each trace of that
    jit (not for the calls that reuse it); the introspection replay's
    re-trace counts nothing."""
    frames = _local.building
    if not frames or _introspecting_fn()():
        return
    for f in frames:
        f.places[name] = f.places.get(name, 0) + 1


def _introspecting_fn():
    """introspect.introspecting, or a False one where this file was
    loaded on its own (tools/_obs.py)."""
    try:
        from .introspect import introspecting
    except ImportError:
        return lambda: False
    return introspecting


def program_name(site):
    """`site` made a Python identifier: what the compiled program is
    called. jax names a program after the function it jits, so the
    module of site "decode" is `jit_decode` in the compiled text and in
    a device trace, and a reader finds it by that name."""
    name = re.sub(r"\W", "_", str(site))
    return name if name[:1].isalpha() or name[:1] == "_" else "_" + name


def _leaf_sig(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return f"{x.dtype}{list(x.shape)}"
    return type(x).__name__


def _signature(args, kwargs):
    import jax
    leaves = jax.tree_util.tree_leaves((args, kwargs))
    parts = [_leaf_sig(l) for l in leaves]
    s = ";".join(parts)
    if len(s) > 512:
        digest = hashlib.sha1(s.encode()).hexdigest()[:12]
        s = f"{parts[0]};...;{parts[-1]} ({len(parts)} leaves, " \
            f"sha1:{digest})"
    return s


class RecompileTracer:
    """Per-subsystem trace accounting (Engine and ServingEngine each
    own one; ad-hoc code can share ``get_tracer()``)."""

    def __init__(self, name="default", registry=None, max_events=256):
        self.name = name
        self._counts = {}          # site -> total traces
        self._sigs = {}            # site -> set of seen signatures
        self._unexpected = {}      # site -> retraces of a seen sig
        self._events = collections.deque(maxlen=max_events)
        self._registry = registry
        self._closed = False
        with _all_lock:
            _all_tracers.append(self)

    # -- wrapping ----------------------------------------------------------
    def jit(self, site, fn, introspect=True, **jit_kwargs):
        """jax.jit(fn) with trace accounting at `site`. The inner bump
        runs exactly when jax traces (compiles); the outer wrapper
        stays host-side and records the event + signature only on a
        call that traced. On such a call the site's compiled
        executable is also introspected (cost/memory analysis — see
        introspect.py) via an AOT replay whose re-trace is SUPPRESSED
        from all accounting here: both the counter bump and the
        host-side note check ``introspecting()``, so the replay can
        never masquerade as a recompile (nested sites included —
        train_step re-traced inside train_step_multi's replay stays
        silent too). ``introspect=False`` keeps the accounting but
        skips the AOT replay — for user-facing one-shot compiles
        (to_static) where doubling the compile buys nothing."""
        import jax
        introspecting = _introspecting_fn()
        _listen()
        counts = self._counts

        def traced(*args, **kw):
            if introspecting():
                return fn(*args, **kw)
            counts[site] = counts.get(site, 0) + 1
            frames = _local.building
            frame = _Frame(site, True)
            frames.append(frame)
            try:
                return fn(*args, **kw)
            finally:
                frames.pop()
                # for the wrapper's record, read right after this trace
                _local.traced = frame

        # the one place a compiled program gets its name (see
        # program_name): without it every site is `jit_traced`
        traced.__name__ = traced.__qualname__ = program_name(site)
        jfn = jax.jit(traced, **jit_kwargs)
        tracer = self

        def call(*args, **kw):
            if introspecting():
                return jfn(*args, **kw)
            before = counts.get(site, 0)
            t0 = time.perf_counter()
            out = jfn(*args, **kw)
            if counts.get(site, 0) != before:
                tracer._built(site, jfn if introspect else None, args, kw,
                              out, t0)
            return out

        call.site = site
        call.jitted = jfn
        # drop-in for a bare jax.jit: callers introspect the compiled
        # function (Engine AOT-lowers grad/apply steps to audit
        # donation; tests clear one function's executable cache)
        for attr in ("lower", "clear_cache", "eval_shape", "trace"):
            if hasattr(jfn, attr):
                setattr(call, attr, getattr(jfn, attr))
        return call

    def _built(self, site, jfn, args, kwargs, out, t0):
        """Record a call that traced, with its staged build; then (jfn
        given) run the introspection replay and add its stages."""
        wall = time.perf_counter() - t0
        frame, _local.traced = _local.traced, None
        frames = _local.building
        if not any(f.program for f in frames):
            # (inside an outer site's trace the outputs are tracers:
            # nothing runs, and the outer record holds this build)
            import jax
            try:
                jax.block_until_ready(out)
            except Exception:  # noqa: BLE001 — the caller meets a failed run
                pass
        t1 = time.perf_counter()
        stages, end = _stages(t0, t1)
        stages["first_run_s"] = t1 - end
        ev = self._note(site, args, kwargs, wall, {
            "kind": "program", "t0": t0, "t1": t1, "stages": stages,
            "introspect": None,
            "kernel_places": dict(frame.places)
            if frame is not None and frame.name == site else {},
            "parent": frames[-1].name if frames else None})
        if jfn is not None:
            ev["introspect"] = self._introspect(site, jfn, args, kwargs,
                                                wall)

    @contextlib.contextmanager
    def phase(self, name):
        """A stretch of set-up that builds programs (ServingEngine.warmup):
        the sites traced inside it name it as their `parent`, and it is
        one record of `kind` "phase" in the event log, with its wall time
        and the Pallas call sites of everything built inside it."""
        frames = _local.building
        frame = _Frame(name, False)
        t0 = time.perf_counter()
        frames.append(frame)
        try:
            yield
        finally:
            frames.remove(frame)
            t1 = time.perf_counter()
            self._events.append({
                "site": name, "kind": "phase", "ts": round(time.time(), 6),
                "t0": t0, "t1": t1, "wall_s": t1 - t0,
                "kernel_places": frame.places,
                "parent": frames[-1].name if frames else None})

    def _note(self, site, args, kwargs, wall_s, staged):
        try:
            sig = _signature(args, kwargs)
        except Exception:  # noqa: BLE001 — accounting must never kill a step
            sig = "<unavailable>"
        seen = self._sigs.setdefault(site, set())
        unexpected = sig in seen
        seen.add(sig)
        if unexpected:
            self._unexpected[site] = self._unexpected.get(site, 0) + 1
        ev = {
            "site": site, "signature": sig,
            "ts": round(time.time(), 6),
            "compile_s": round(wall_s, 6),
            "unexpected": unexpected,
            **staged,
        }
        self._events.append(ev)
        reg = self._registry
        if reg is not None:
            reg.counter("recompile_traces_total",
                        help="XLA traces (== compiles) per jit site",
                        labels={"tracer": self.name,
                                "site": site}).inc()
            if unexpected:
                reg.counter(
                    "recompile_unexpected_retraces_total",
                    help="re-traces of an already-seen signature",
                    labels={"tracer": self.name, "site": site}).inc()
            reg.histogram("recompile_wall_seconds",
                          help="wall time of calls that traced",
                          labels={"tracer": self.name}).observe(wall_s)
        return ev

    def _introspect(self, site, jfn, args, kwargs, wall_s):
        """Capture the freshly-compiled executable's cost/memory
        analysis (introspect.capture_site). Failure-proof: a broken
        AOT path records a skip reason, never kills the step. Returns
        the replay's stages (it runs nothing) and its `wall_s`."""
        t0 = time.perf_counter()
        try:
            from .introspect import capture_site
            capture_site(self.name, site, jfn, args, kwargs,
                         wall_s=wall_s, registry=self._registry)
        except Exception:  # noqa: BLE001 — accounting must never kill a step
            pass
        t1 = time.perf_counter()
        stages, _ = _stages(t0, t1)
        stages["wall_s"] = t1 - t0
        return stages

    # -- manual accounting (sites not built via .jit) ----------------------
    def count_trace(self, site):
        """Bump `site` from inside a hand-rolled traced body (legacy
        callers); no signature/event is recorded."""
        self._counts[site] = self._counts.get(site, 0) + 1

    def forget(self, site):
        """Drop a site's accounting. For dynamically-minted sites
        (to_static wrappers releasing theirs on GC) so a
        wrapper-churning process doesn't grow the tracer — and its
        report — without bound. A site that recorded an UNEXPECTED
        retrace is kept: that signal must survive the wrapper that
        produced it, or churn could launder a real recompile out of
        the report. Returns True when the site was dropped."""
        if self._unexpected.get(site):
            return False
        self._counts.pop(site, None)
        self._sigs.pop(site, None)
        self._unexpected.pop(site, None)
        return True

    # -- queries -----------------------------------------------------------
    def counts(self):
        return dict(self._counts)

    def unexpected_retraces(self):
        return sum(self._unexpected.values())

    def events(self, site=None):
        return [e for e in self._events
                if site is None or e["site"] == site]

    def report(self):
        """The queryable recompile report: per-site trace totals,
        distinct signatures, unexpected retraces, plus the bounded
        event log."""
        sites = {}
        for site, n in sorted(self._counts.items()):
            sites[site] = {
                "traces": n,
                "signatures": len(self._sigs.get(site, ())),
                "unexpected_retraces": self._unexpected.get(site, 0),
            }
        return {"tracer": self.name, "sites": sites,
                "unexpected_retraces": self.unexpected_retraces(),
                "events": list(self._events)}

    def close(self):
        """Retire this tracer: drop it from the live set (so repeated
        engine construction can't grow memory for the process
        lifetime) while keeping its site aggregates — minus the event
        log and signature sets — visible to report_all(), folded into
        the cumulative per-name rollup. Safe to call twice; the
        wrapped jitted callables keep working, they just stop
        contributing new facts to the merged report."""
        with _all_lock:
            try:
                _all_tracers.remove(self)
            except ValueError:
                return  # already closed
            self._closed = True
            rep = self.report()
            if not rep["sites"]:
                return
            agg = _closed_agg.setdefault(
                self.name, {"tracer": self.name, "sites": {},
                            "unexpected_retraces": 0, "events": [],
                            "closed": True, "closed_tracers": 0})
            for site, row in rep["sites"].items():
                dst = agg["sites"].setdefault(
                    site, {"traces": 0, "signatures": 0,
                           "unexpected_retraces": 0})
                dst["traces"] += row["traces"]
                # distinct-per-tracer counts summed: an upper bound on
                # process-wide distinct signatures (the sets are gone)
                dst["signatures"] += row["signatures"]
                dst["unexpected_retraces"] += row["unexpected_retraces"]
            agg["unexpected_retraces"] += rep["unexpected_retraces"]
            agg["closed_tracers"] += 1


_default = RecompileTracer(name="default")


def get_tracer():
    return _default


def all_tracers():
    with _all_lock:
        return list(_all_tracers)


def report_all():
    """Merge every live tracer's report (plus the compact reports of
    closed ones) — the `recompile_report` section of the exported run
    report. `unexpected_retraces` == 0 is the queryable form of the
    zero-recompile claim."""
    with _all_lock:
        # one lock acquisition across live builds AND the closed-agg
        # read, plus a final _closed re-check: a tracer whose GC
        # finalizer closes it mid-report (the RLock re-entry the module
        # comment anticipates) folds into _closed_agg and is then
        # dropped from the live pass — counted once, never twice
        pairs = [(t, t.report()) for t in list(_all_tracers)]
        tracers = [{**r, "sites": {s: dict(v)
                                   for s, v in r["sites"].items()}}
                   for r in list(_closed_agg.values())]
        tracers += [r for t, r in pairs if not t._closed]
    tracers = [t for t in tracers if t["sites"]]
    tracers.sort(key=lambda t: t["tracer"])
    return {"tracers": tracers,
            "unexpected_retraces": sum(t["unexpected_retraces"]
                                       for t in tracers)}
