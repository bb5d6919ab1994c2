"""Device-memory ledger — typed allocation accounting with headroom
forecasting (the "where did HBM go" plane).

A single replica now owns KV page pools, COW prefix sidecars,
spec-draft pools, int8 scale sidecars, weights, optimizer state and
AOT artifacts — yet nothing could answer "where did device memory go"
or "will admitting this request OOM the chip". This module closes the
gap: a process-wide **MemoryLedger** attributes every framework
allocation into a typed, labeled segment tree via explicit
``ledger.track(tag, buf)`` calls at the allocation seams (the engine's
page pool, the prefix index's dense sidecars, the speculative draft
pool, optimizer state, artifact restore), cross-checked against
ground truth — ``device.memory_stats()`` when the backend exposes it,
a ``jax.live_arrays()`` nbytes sum otherwise (CPU: tier-1 exercises
the same code) — with an ``unattributed_bytes`` residual so drift is
visible, never silent.

Design contracts, matching the rest of the observability plane:

- **Host-side only, zero-recompile untouched.** ``track``/``release``
  are pure dict arithmetic; the only jax touch is the periodic
  ``sweep()`` (driven from ``health()``/close, never the dispatch hot
  path) and even that is a host-side live-array walk, no device sync.
- **Dormant unless armed.** A never-armed engine creates NO ledger
  object and registers NO ``mem_*`` series (the spec-decode/profiler
  dormancy contract), so legacy goldens stay byte-identical.
- **Never silent.** The residual series carries what the seams missed;
  ``residual_alarm`` trips on growth past the baseline
  (tests/test_memledger.py proves it fires), and audit callbacks (e.g. the prefix
  refcount audit) count failures into
  ``engine_mem_audit_failures_total``.
- **Stdlib-only, standalone-loadable** (``tools/_obs.py``): no
  intra-package imports at module scope; jax is imported lazily and
  its absence degrades to "no ground truth", never an exception.

Exports: ``MemoryLedger`` (track/release/set_level, ``would_fit``
admission hints, ``digest()`` for heartbeats, ``report()`` for the
``/memory`` endpoint, ``save()``/``load_snapshot()`` snapshot
persistence for ``tools/mem_diff.py``), ``MemoryAdmissionError`` (the
``PADDLE_TPU_MEM_ADMISSION=hard`` rejection type),
``active_ledger()``/``current_memory()`` (the flight-dump attach
point) and the env-knob readers.
"""
from __future__ import annotations

import json
import math
import os
import threading
import time

__all__ = ["MemoryLedger", "MemoryAdmissionError", "SEGMENTS",
           "nbytes_of", "default_ground_truth", "active_ledger",
           "current_memory", "load_snapshot",
           "mem_ledger_enabled_from_env", "mem_admission_from_env",
           "mem_capacity_from_env"]

#: the typed segment set — unknown tags fold into "other" (loudly:
#: the tag is kept as the label), never dropped
SEGMENTS = ("kv_pages", "prefix_sidecar", "spec_draft_pool", "weights",
            "optimizer_state", "grads", "activations_peak", "other")

ADMISSION_MODES = ("advisory", "hard")


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


def nbytes_of(obj):
    """Total ``.nbytes`` over an arbitrary nest of arrays (tuples,
    lists, dicts; jax or numpy — anything with an ``nbytes`` attr).
    Deduplicates by object identity inside ONE call, so a buffer
    referenced twice in the same nest counts once. None and
    unknown leaves count zero — the ledger under-attributes rather
    than guessing (the residual series carries the difference)."""
    seen = set()

    def walk(o):
        if o is None:
            return 0
        if isinstance(o, (list, tuple)):
            return sum(walk(x) for x in o)
        if isinstance(o, dict):
            return sum(walk(x) for x in o.values())
        nb = getattr(o, "nbytes", None)
        if nb is None:
            return 0
        oid = id(o)
        if oid in seen:
            return 0
        seen.add(oid)
        try:
            return int(nb)
        except (TypeError, ValueError):
            return 0

    return walk(obj)


def default_ground_truth():
    """(used_bytes, capacity_bytes) from the backend, or (None, None).

    Prefers the device's own ``memory_stats()`` (bytes_in_use /
    bytes_limit — real HBM accounting on TPU); falls back to a
    ``jax.live_arrays()`` nbytes sum (capacity unknown) so the CPU
    backend — and therefore tier-1 — exercises the exact same
    cross-check code path. Host-side only: enumerating live arrays is
    bookkeeping, not a device sync. No jax at all reads as "no ground
    truth", never an exception."""
    try:
        import jax
    except Exception:  # noqa: BLE001 — standalone/minimal environments
        return None, None
    try:
        stats = jax.devices()[0].memory_stats() or {}
    except Exception:  # noqa: BLE001 — backend without the API
        stats = {}
    used = stats.get("bytes_in_use")
    cap = stats.get("bytes_limit")
    if used:
        return int(used), (int(cap) if cap else None)
    try:
        return (int(sum(int(getattr(a, "nbytes", 0) or 0)
                        for a in jax.live_arrays())),
                (int(cap) if cap else None))
    except Exception:  # noqa: BLE001 — live_arrays absent/failed
        return None, (int(cap) if cap else None)


# -- env knobs --------------------------------------------------------------

def mem_ledger_enabled_from_env(default=False):
    """The ``PADDLE_TPU_MEM_LEDGER`` arm switch (default OFF:
    never-armed engines stay byte-identical to the legacy goldens,
    the spec-decode/profiler dormancy contract)."""
    raw = os.environ.get("PADDLE_TPU_MEM_LEDGER")
    if raw is None:
        return bool(default)
    return raw.lower() in ("1", "true", "on")


def mem_admission_from_env(default="advisory"):
    """``PADDLE_TPU_MEM_ADMISSION``: ``advisory`` (count-only hints)
    or ``hard`` (submit() rejects would-not-fit requests with a typed
    MemoryAdmissionError instead of OOMing mid-decode). Unknown values
    read as the default — a typo must not silently arm rejections."""
    raw = (os.environ.get("PADDLE_TPU_MEM_ADMISSION") or "").lower()
    return raw if raw in ADMISSION_MODES else default


def mem_capacity_from_env(default=None):
    """``PADDLE_TPU_MEM_CAPACITY_BYTES``: explicit device-memory
    budget for backends whose memory_stats() carries no bytes_limit
    (CPU tests, capped deployments). None = learn it from the device
    or run capacity-blind (would_fit answers None)."""
    raw = os.environ.get("PADDLE_TPU_MEM_CAPACITY_BYTES")
    if not raw:
        return default
    try:
        v = int(float(raw))
    except ValueError:
        return default
    return v if v > 0 else default


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


class MemoryAdmissionError(RuntimeError):
    """Typed hard-mode admission rejection: the request's KV page
    allocation would not fit the forecast headroom. Carries the
    numbers an operator/caller needs to size down or shed."""

    def __init__(self, need_bytes, headroom_bytes, capacity_bytes):
        self.need_bytes = int(need_bytes)
        self.headroom_bytes = (None if headroom_bytes is None
                               else int(headroom_bytes))
        self.capacity_bytes = (None if capacity_bytes is None
                               else int(capacity_bytes))
        super().__init__(
            f"admission would not fit: need {self.need_bytes} B, "
            f"headroom {self.headroom_bytes} B of "
            f"{self.capacity_bytes} B capacity "
            f"(PADDLE_TPU_MEM_ADMISSION=hard)")


# -- the ledger -------------------------------------------------------------

class MemoryLedger:
    """Typed, labeled device-allocation accounting for one process.

    Two attribution channels share the segment tree:

    - **tracked tokens** (``track``/``track_bytes`` -> token,
      ``release(token)``): allocations with an owner who sees both
      ends of the lifetime (the engine's page pool, the draft pool);
    - **levels** (``set_level``): segments whose byte count is
      recomputed from an authoritative source at the seam (the prefix
      index's sidecar inventory, optimizer state) — idempotent
      absolute sets, no release bookkeeping to get wrong.

    ``sweep()`` refreshes the ground-truth cross-check, the
    unattributed residual, the high watermark and the EWMA growth
    forecast; every public reader takes the internal lock, so
    exporter HTTP threads can read a live ledger safely.
    """

    def __init__(self, *, registry=None, name="engine",
                 capacity_bytes=None, ewma_alpha=0.3,
                 min_sweep_interval_s=0.5, residual_alarm_ratio=0.5,
                 residual_alarm_floor=1 << 20, ground_truth_fn=None):
        self.name = str(name)
        self.capacity_bytes = (None if capacity_bytes is None
                               else int(capacity_bytes))
        self.ewma_alpha = float(ewma_alpha)
        self.min_sweep_interval_s = float(min_sweep_interval_s)
        self.residual_alarm_ratio = float(residual_alarm_ratio)
        self.residual_alarm_floor = int(residual_alarm_floor)
        self._ground_truth_fn = (ground_truth_fn
                                 if ground_truth_fn is not None
                                 else default_ground_truth)
        self._lock = threading.RLock()
        self._tracked = {}      # token -> (segment, label, bytes)
        self._levels = {}       # (segment, label) -> bytes
        self._next_token = 0
        self._audits = []       # callables -> list of problem strings
        self.audit_problems = []    # last sweep's findings (bounded)
        # cross-check state (refreshed by sweep())
        self.ground_truth_bytes = None
        self.unattributed_bytes = None
        self._baseline_unattributed = None
        self.high_watermark_bytes = 0
        self.growth_bytes_per_s = 0.0
        self._growth_seeded = False
        self._last_sweep_t = None
        self._last_sweep_used = None
        self._closed = False
        # monotonic counters (health()/heartbeat views; the fleet
        # router delta-folds them into fleet_mem_* restart-tolerantly)
        self.tracked_allocs = 0
        self.released_allocs = 0
        self.admission_checks = 0
        self.admission_rejections = 0
        self.sweeps = 0
        self.audit_failures = 0
        self._registry = registry
        self._g_seg = {}
        self._g = {}
        self._c = {}
        if registry is not None:
            g = self._g
            g["attributed"] = registry.gauge(
                "engine_mem_attributed_bytes",
                help="device bytes attributed to typed ledger "
                     "segments (tracked allocations + level sets)")
            g["unattributed"] = registry.gauge(
                "engine_mem_unattributed_bytes",
                help="ground-truth device bytes the allocation seams "
                     "did not attribute — the residual that makes "
                     "accounting drift visible, never silent")
            g["used_ratio"] = registry.gauge(
                "engine_mem_hbm_used_ratio",
                help="device bytes in use / capacity (0 when "
                     "capacity is unknown); the sentinel's sustained-"
                     "growth band watches this series")
            g["headroom"] = registry.gauge(
                "engine_mem_headroom_bytes",
                help="forecast free device bytes (capacity - used; "
                     "0 when capacity is unknown)")
            g["watermark"] = registry.gauge(
                "engine_mem_high_watermark_bytes",
                help="peak device bytes in use observed by the "
                     "ledger's sweeps")
            g["growth"] = registry.gauge(
                "engine_mem_growth_bytes_per_s",
                help="EWMA growth of device bytes in use between "
                     "sweeps — the headroom-exhaustion forecast's "
                     "slope")
            c = self._c
            c["tracked_allocs"] = registry.counter(
                "engine_mem_tracked_allocs_total",
                help="allocations attributed through ledger.track at "
                     "the framework's allocation seams")
            c["released_allocs"] = registry.counter(
                "engine_mem_released_allocs_total",
                help="tracked allocations released back (the other "
                     "end of the lifetime the seams own)")
            c["admission_checks"] = registry.counter(
                "engine_mem_admission_checks_total",
                help="would_fit admission hints consulted before KV "
                     "page allocation")
            c["admission_rejections"] = registry.counter(
                "engine_mem_admission_rejections_total",
                help="admissions the hint judged would NOT fit "
                     "(advisory mode counts, hard mode also rejects)")
            c["audit_failures"] = registry.counter(
                "engine_mem_audit_failures_total",
                help="ledger sweep audit problems (e.g. prefix-index "
                     "refcounts disagreeing with live page-table "
                     "references — the release-on-failover leak "
                     "class)")
            c["sweeps"] = registry.counter(
                "engine_mem_sweeps_total",
                help="ground-truth cross-check sweeps taken")
            for m in g.values():
                m.set(0)
        with _active_lock:
            _active.append(self)

    # -- attribution -------------------------------------------------------

    @staticmethod
    def _seg_label(tag, label):
        tag = str(tag)
        if tag in SEGMENTS:
            return tag, ("" if label is None else str(label))
        # unknown tags fold into "other" with the tag kept as label —
        # a misspelled seam shows up in the tree, never vanishes
        return "other", (tag if label is None
                         else f"{tag},{label}")

    def track(self, tag, buf, label=None):
        """Attribute a live allocation: ``tag`` a SEGMENTS name (an
        unknown tag folds into "other" labeled with it), ``buf`` any
        nest of arrays. Returns a token for ``release()``."""
        return self.track_bytes(tag, nbytes_of(buf), label=label)

    def track_bytes(self, tag, nbytes, label=None):
        """``track`` for sizes known without a buffer in hand (e.g.
        restored artifact blobs)."""
        seg, lab = self._seg_label(tag, label)
        n = max(int(nbytes), 0)
        with self._lock:
            token = self._next_token
            self._next_token += 1
            self._tracked[token] = (seg, lab, n)
            self.tracked_allocs += 1
            if self._c:
                self._c["tracked_allocs"].inc()
            self._refresh_gauges_locked()
        return token

    def release(self, token):
        """Drop a tracked allocation; returns its bytes (0 for an
        unknown/already-released token — release is idempotent)."""
        with self._lock:
            rec = self._tracked.pop(token, None)
            if rec is None:
                return 0
            self.released_allocs += 1
            if self._c:
                self._c["released_allocs"].inc()
            self._refresh_gauges_locked()
            return rec[2]

    def set_level(self, tag, nbytes, label=None):
        """Absolute byte level for a segment recomputed at its seam
        (idempotent; 0 clears). The level channel for inventories the
        owner already keeps (prefix sidecars, optimizer state)."""
        seg, lab = self._seg_label(tag, label)
        n = max(int(nbytes), 0)
        with self._lock:
            if n:
                self._levels[(seg, lab)] = n
            else:
                self._levels.pop((seg, lab), None)
            self._refresh_gauges_locked()

    def add_audit(self, fn):
        """Register a zero-arg callable returning a list of problem
        strings, run by every sweep (the prefix refcount audit's
        attach point). Failures count, never raise."""
        self._audits.append(fn)

    # -- readers -----------------------------------------------------------

    def attributed_bytes(self):
        with self._lock:
            return self._attributed_locked()

    def _attributed_locked(self):
        return (sum(n for _, _, n in self._tracked.values())
                + sum(self._levels.values()))

    def segment_tree(self):
        """{segment: {"bytes": total, "labels": {label: bytes}}} over
        both channels — the /memory endpoint's and flight dumps'
        payload."""
        with self._lock:
            tree = {}
            for seg, lab, n in self._tracked.values():
                node = tree.setdefault(seg, {"bytes": 0, "labels": {}})
                node["bytes"] += n
                node["labels"][lab] = node["labels"].get(lab, 0) + n
            for (seg, lab), n in self._levels.items():
                node = tree.setdefault(seg, {"bytes": 0, "labels": {}})
                node["bytes"] += n
                node["labels"][lab] = node["labels"].get(lab, 0) + n
            return tree

    def segments(self):
        """Flat {segment: bytes} (mem_diff's unit)."""
        return {seg: node["bytes"]
                for seg, node in self.segment_tree().items()}

    def used_bytes(self):
        """Best current estimate of device bytes in use: the last
        ground truth when we have one, floored at the live attributed
        sum (attribution mutates between sweeps; ground truth only at
        sweeps)."""
        with self._lock:
            att = self._attributed_locked()
            gt = self.ground_truth_bytes
            return att if gt is None else max(int(gt), att)

    def headroom_bytes(self):
        cap = self.capacity_bytes
        if cap is None:
            return None
        return max(cap - self.used_bytes(), 0)

    def used_ratio(self):
        cap = self.capacity_bytes
        if not cap:
            return None
        return min(max(self.used_bytes() / float(cap), 0.0), 1.0)

    @property
    def residual_alarm(self):
        """True when the unattributed residual grew past its baseline
        by more than max(floor, ratio * baseline) — the leak drill's
        alarm. Baseline = first sweep after arming (or the last
        ``mark_baseline()``)."""
        with self._lock:
            un, base = self.unattributed_bytes, \
                self._baseline_unattributed
            if un is None or base is None:
                return False
            slack = max(self.residual_alarm_floor,
                        int(self.residual_alarm_ratio * base))
            return (un - base) > slack

    def mark_baseline(self):
        """Pin the CURRENT residual as the alarm baseline (engines
        call this after warmup, once the steady working set exists)."""
        self.sweep(force=True)
        with self._lock:
            self._baseline_unattributed = self.unattributed_bytes

    def conservation(self, tolerance=0.01):
        """The cross-check invariant, checkable: typed segments +
        unattributed must equal ground truth within ``tolerance``
        (relative). Over-attribution — a seam counting bytes the
        device no longer holds — is the only way it breaks, which is
        exactly the bug class it exists to catch."""
        self.sweep(force=True)
        with self._lock:
            att = self._attributed_locked()
            gt = self.ground_truth_bytes
            un = self.unattributed_bytes
            if gt is None or un is None:
                return {"ok": None, "attributed_bytes": att,
                        "unattributed_bytes": un,
                        "ground_truth_bytes": gt, "rel_err": None}
            err = abs((att + un) - gt) / float(max(gt, 1))
            return {"ok": err <= float(tolerance),
                    "attributed_bytes": att, "unattributed_bytes": un,
                    "ground_truth_bytes": gt, "rel_err": round(err, 6)}

    # -- admission hints ---------------------------------------------------

    def would_fit(self, nbytes):
        """Would an allocation of ``nbytes`` fit the forecast
        headroom? True/False, or None when capacity is unknown (the
        hint cannot answer; callers treat None as "proceed")."""
        hr = self.headroom_bytes()
        if hr is None:
            return None
        return int(nbytes) <= hr

    def admission_check(self, nbytes):
        """The engine's pre-page-allocation consult: counts the check
        (and the would-not-fit verdicts) and returns would_fit's
        answer. Counter-only — policy (advisory vs hard) is the
        caller's."""
        fits = self.would_fit(nbytes)
        with self._lock:
            self.admission_checks += 1
            if self._c:
                self._c["admission_checks"].inc()
            if fits is False:
                self.admission_rejections += 1
                if self._c:
                    self._c["admission_rejections"].inc()
        return fits

    # -- sweep (ground truth + forecast) -----------------------------------

    def sweep(self, force=False, now=None):
        """Refresh ground truth, the unattributed residual, the high
        watermark, the EWMA growth forecast and the audit findings.
        Rate-limited (``min_sweep_interval_s``) unless forced; driven
        from health()/close — never the dispatch hot path."""
        t = time.monotonic() if now is None else float(now)
        with self._lock:
            if not force and self._last_sweep_t is not None \
                    and t - self._last_sweep_t \
                    < self.min_sweep_interval_s:
                return False
        problems = []
        for fn in list(self._audits):
            try:
                problems.extend(fn() or [])
            except Exception as e:  # noqa: BLE001 — an audit bug must
                # not take the sweep (or the serving process) down
                problems.append(f"audit raised {type(e).__name__}: "
                                f"{e}")
        try:
            gt, cap = self._ground_truth_fn()
        except Exception:  # noqa: BLE001 — ground truth is optional
            gt, cap = None, None
        with self._lock:
            self.sweeps += 1
            if self._c:
                self._c["sweeps"].inc()
            if problems:
                self.audit_failures += len(problems)
                if self._c:
                    self._c["audit_failures"].inc(len(problems))
            self.audit_problems = problems[:16]
            if cap is not None and self.capacity_bytes is None:
                self.capacity_bytes = int(cap)
            att = self._attributed_locked()
            if gt is not None:
                self.ground_truth_bytes = int(gt)
                self.unattributed_bytes = max(int(gt) - att, 0)
                if self._baseline_unattributed is None:
                    self._baseline_unattributed = \
                        self.unattributed_bytes
            used = att if gt is None else max(int(gt), att)
            self.high_watermark_bytes = max(self.high_watermark_bytes,
                                            used)
            if self._last_sweep_t is not None \
                    and t > self._last_sweep_t \
                    and self._last_sweep_used is not None:
                rate = ((used - self._last_sweep_used)
                        / (t - self._last_sweep_t))
                if not self._growth_seeded:
                    self.growth_bytes_per_s = rate
                    self._growth_seeded = True
                else:
                    a = self.ewma_alpha
                    self.growth_bytes_per_s = \
                        (1 - a) * self.growth_bytes_per_s + a * rate
            self._last_sweep_t = t
            self._last_sweep_used = used
            self._refresh_gauges_locked()
        if problems:
            self._flight_note(problems)
        return True

    def _flight_note(self, problems):
        """Audit findings are postmortem evidence — note them to the
        flight recorder when it is importable; never raise."""
        try:
            from . import flightrec
            flightrec.note("mem_audit_failure", name=self.name,
                           problems=problems[:4])
        except Exception:  # noqa: BLE001 — evidence attach never raises
            pass

    def _refresh_gauges_locked(self):
        if not self._g:
            return
        att = self._attributed_locked()
        self._g["attributed"].set(att)
        if self.unattributed_bytes is not None:
            self._g["unattributed"].set(self.unattributed_bytes)
        cap = self.capacity_bytes
        gt = self.ground_truth_bytes
        used = att if gt is None else max(int(gt), att)
        if cap:
            self._g["used_ratio"].set(
                min(max(used / float(cap), 0.0), 1.0))
            self._g["headroom"].set(max(cap - used, 0))
        self._g["watermark"].set(max(self.high_watermark_bytes, used))
        self._g["growth"].set(round(self.growth_bytes_per_s, 3))

    # -- exports -----------------------------------------------------------

    def seconds_to_exhaustion(self):
        """Headroom / EWMA growth — None when capacity is unknown or
        usage is flat/shrinking (no exhaustion forecast)."""
        hr = self.headroom_bytes()
        if hr is None or self.growth_bytes_per_s <= 0.0:
            return None
        return hr / self.growth_bytes_per_s

    def stats(self):
        """Flat monotonic counters for the router's restart-tolerant
        delta fold (the _fold_spec/_fold_profile idiom)."""
        with self._lock:
            return {"tracked_allocs": int(self.tracked_allocs),
                    "released_allocs": int(self.released_allocs),
                    "admission_checks": int(self.admission_checks),
                    "admission_rejections":
                        int(self.admission_rejections),
                    "audit_failures": int(self.audit_failures)}

    def digest(self, sweep=True):
        """Bounded heartbeat digest (host-side JSON, a few hundred
        bytes) — the shape the fleet router folds into fleet_mem_*
        counters and the MEM%/HEADROOM rollup."""
        if sweep:
            self.sweep()
        with self._lock:
            att = self._attributed_locked()
            gt = self.ground_truth_bytes
            used = att if gt is None else max(int(gt), att)
            cap = self.capacity_bytes
            return {"attributed_bytes": att,
                    "unattributed_bytes": self.unattributed_bytes,
                    "used_bytes": used,
                    "capacity_bytes": cap,
                    "used_ratio": (None if not cap else round(
                        min(max(used / float(cap), 0.0), 1.0), 6)),
                    "headroom_bytes": (None if cap is None
                                       else max(cap - used, 0)),
                    "high_watermark_bytes":
                        max(self.high_watermark_bytes, used),
                    "growth_bytes_per_s":
                        round(self.growth_bytes_per_s, 3),
                    "residual_alarm": self.residual_alarm,
                    "audit_problems": list(self.audit_problems),
                    "segments": {seg: node["bytes"] for seg, node
                                 in self.segment_tree().items()},
                    "stats": self.stats()}

    def report(self, window_s=None, sweep=True):
        """The ``/memory`` endpoint body: the digest plus the full
        labeled segment tree and forecast. ``window_s`` is accepted
        for route symmetry with /profile and ignored (a ledger is a
        level, not a ring)."""
        d = self.digest(sweep=sweep)
        d.update(name=self.name, armed=True, window_s=window_s,
                 tree=self.segment_tree(),
                 live_tokens=len(self._tracked),
                 seconds_to_exhaustion=self.seconds_to_exhaustion(),
                 conservation=self.conservation())
        return d

    def save(self, path, extra=None):
        """Persist a snapshot (mem_diff's input) via write-then-rename
        — valid JSON or absent, never torn (load_snapshot of a torn
        copy reads as empty)."""
        doc = {"memledger": 1, "name": self.name,
               "digest": self.digest(),
               "tree": self.segment_tree()}
        if extra:
            doc.update(extra)
        try:
            body = json.dumps(doc, sort_keys=True, allow_nan=False)
        except ValueError:
            body = json.dumps(_finite(doc), sort_keys=True,
                              allow_nan=False)
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        _atomic().atomic_replace(path, body.encode("utf-8"))
        return path

    def close(self):
        """Final sweep + detach from the active registry. Idempotent;
        tracked tokens are left as-is (the process owns the buffers,
        not the ledger)."""
        if self._closed:
            return
        self._closed = True
        try:
            self.sweep(force=True)
        except Exception:  # noqa: BLE001 — close never raises
            pass
        with _active_lock:
            if self in _active:
                _active.remove(self)


# -- module-level active-ledger registry ------------------------------------
#
# The flight recorder, the anomaly sentinel and the optimizer seam
# attach "where is device memory" evidence without holding a ledger
# reference — they ask for the most recently armed one.

_active = []
_active_lock = threading.Lock()


def active_ledger():
    """The most recently armed, still-open ledger (or None)."""
    with _active_lock:
        for led in reversed(_active):
            if not led._closed:
                return led
    return None


def current_memory():
    """``report()`` of the active ledger, or None — the guarded
    attach point for flight dumps."""
    led = active_ledger()
    if led is None:
        return None
    try:
        return led.report()
    except Exception:  # noqa: BLE001 — evidence attach never raises
        return None


def load_snapshot(path):
    """Snapshot file -> {"segments": {...}, "attributed", ...} for
    mem_diff. Torn/absent/unparseable files read as an empty snapshot,
    never an exception (the load_folded discipline)."""
    try:
        with open(path, "r", encoding="utf-8", errors="replace") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(doc, dict) or doc.get("memledger") != 1:
        return {}
    return doc
