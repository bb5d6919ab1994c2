"""Typed metrics registry — the single place run facts accumulate.

Pure stdlib (no jax, no numpy): the registry must be importable from
jax-free tools and DataLoader worker processes alike. Three metric
types, Prometheus-shaped:

- Counter: monotonically increasing total (requests served, steps
  skipped). ``inc(n)`` only; resets happen at the registry level.
- Gauge: last-written value (free KV pages, current loss).
- Histogram: fixed log-spaced buckets (a 1-2-5 ladder across decades),
  cumulative-bucket Prometheus export, count-weighted ``observe`` so a
  K-token decode dispatch records K per-token latencies in O(1), and
  bucket-interpolated ``quantile`` for p50/p99 rollups.

Snapshots are plain dicts and MERGEABLE: ``registry.merge(snapshot)``
folds another process/rung's snapshot in (counters and histogram
buckets add, gauges last-write-wins), which is how a router folds
its replicas' registries into one.

Label support is deliberately minimal: a metric series is identified
by (name, sorted labels); ``registry.counter(name, labels={...})``
returns the series. Exports: ``to_prometheus()`` text and
``to_json()`` / ``dump(path)`` for the run report.

Hot-path cost: one ``observe`` is a bisect + four scalar updates under
the GIL — safe to call at host step boundaries; never call it from
inside a jitted function.
"""
from __future__ import annotations

import bisect
import json
import math
import os
import threading
import time

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "get_registry", "default_time_buckets"]


def default_time_buckets(lo_exp=-5, hi_exp=2):
    """Fixed log-spaced bucket bounds: a 1-2-5 ladder covering
    10**lo_exp .. 10**hi_exp seconds (default 10us .. 100s)."""
    return tuple(float(f"{m}e{e:+03d}")
                 for e in range(lo_exp, hi_exp + 1) for m in (1, 2, 5))


def _fmt(v):
    """Compact exact float formatting shared by exports (golden-string
    stable: repr of a float parsed from its own literal round-trips)."""
    if v == float("inf"):
        return "+Inf"
    return repr(float(v))


def _finite(obj):
    """Map non-finite floats to None for the JSON exports: bare
    NaN/Infinity tokens are not RFC JSON and break jq/JS consumers.
    (Duplicated in telemetry.py — these modules stay standalone-
    loadable, no intra-package imports at module scope.)"""
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj


def _esc_label(v):
    """Prometheus exposition-format label escaping (backslash, quote,
    newline). Applied at series-key build time, so the key doubles as
    the exposition form AND crafted values cannot collide two
    distinct series into one key."""
    return (str(v).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _series_key(name, labels):
    if not labels:
        return name
    inner = ",".join(f'{k}="{_esc_label(labels[k])}"'
                     for k in sorted(labels))
    return f"{name}{{{inner}}}"


class _Metric:
    kind = "abstract"

    def __init__(self, name, help="", labels=None):
        self.name = name
        self.help = help
        self.labels = dict(labels or {})
        self.key = _series_key(name, self.labels)


class Counter(_Metric):
    kind = "counter"

    def __init__(self, name, help="", labels=None):
        super().__init__(name, help, labels)
        self.value = 0

    def inc(self, n=1):
        self.value += n

    def snapshot(self):
        return {"name": self.name, "labels": self.labels,
                "type": self.kind, "value": self.value}

    def merge(self, snap):
        self.value += snap["value"]

    def reset(self):
        self.value = 0


class Gauge(_Metric):
    kind = "gauge"

    def __init__(self, name, help="", labels=None):
        super().__init__(name, help, labels)
        self.value = 0.0

    def set(self, v):
        self.value = v

    def inc(self, n=1):
        self.value += n

    def dec(self, n=1):
        self.value -= n

    def snapshot(self):
        return {"name": self.name, "labels": self.labels,
                "type": self.kind, "value": self.value}

    def merge(self, snap):
        self.value = snap["value"]  # last write wins

    def reset(self):
        self.value = 0.0


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name, help="", labels=None, buckets=None):
        super().__init__(name, help, labels)
        self.bounds = tuple(sorted(buckets)) if buckets \
            else default_time_buckets()
        # counts[i] = observations in (bounds[i-1], bounds[i]];
        # counts[-1] = overflow (> bounds[-1])
        self.counts = [0] * (len(self.bounds) + 1)
        self.sum = 0.0
        self.count = 0
        self.min = None
        self.max = None

    def observe(self, v, count=1):
        """Record `count` observations of value v (count-weighted: a
        batched dispatch of K tokens records K identical per-token
        latencies in one call)."""
        v = float(v)
        i = bisect.bisect_left(self.bounds, v)
        self.counts[i] += count
        self.sum += v * count
        self.count += count
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)

    def quantile(self, q):
        """Bucket-interpolated quantile estimate in [min, max]; None
        when empty."""
        if not self.count:
            return None
        target = q * self.count
        cum = 0
        for i, c in enumerate(self.counts):
            if not c:
                continue
            lo = self.bounds[i - 1] if i > 0 else self.min
            hi = self.bounds[i] if i < len(self.bounds) else self.max
            lo = max(lo, self.min)
            hi = min(hi, self.max)
            if cum + c >= target:
                frac = (target - cum) / c
                return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
            cum += c
        return self.max

    def mean(self):
        return self.sum / self.count if self.count else None

    def snapshot(self):
        return {"name": self.name, "labels": self.labels,
                "type": self.kind, "bounds": list(self.bounds),
                "counts": list(self.counts), "sum": self.sum,
                "count": self.count, "min": self.min, "max": self.max}

    def merge(self, snap):
        if tuple(snap["bounds"]) != self.bounds:
            raise ValueError(
                f"histogram {self.key}: cannot merge mismatched bucket "
                f"bounds ({len(snap['bounds'])} vs {len(self.bounds)})")
        for i, c in enumerate(snap["counts"]):
            self.counts[i] += c
        self.sum += snap["sum"]
        self.count += snap["count"]
        for attr, pick in (("min", min), ("max", max)):
            other = snap.get(attr)
            if other is not None:
                mine = getattr(self, attr)
                setattr(self, attr,
                        other if mine is None else pick(mine, other))

    def reset(self):
        self.counts = [0] * (len(self.bounds) + 1)
        self.sum = 0.0
        self.count = 0
        self.min = None
        self.max = None


class MetricsRegistry:
    """A set of named metric series. One process-global default
    (``get_registry()``); private instances are cheap and their
    snapshots merge into any other registry."""

    def __init__(self):
        self._metrics = {}
        # reentrant: merge() holds it across _get(); readers
        # (snapshot/scrape) hold it so a lazily-registered series
        # can't resize the dict mid-iteration under a scrape thread
        self._lock = threading.RLock()

    # -- creation/lookup ---------------------------------------------------
    def _get(self, cls, name, help, labels, **kw):
        key = _series_key(name, dict(labels or {}))
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = cls(name, help=help, labels=labels, **kw)
                self._metrics[key] = m
            elif not isinstance(m, cls):
                raise TypeError(f"metric {key!r} already registered as "
                                f"{m.kind}, requested {cls.kind}")
            return m

    def counter(self, name, help="", labels=None):
        return self._get(Counter, name, help, labels)

    def gauge(self, name, help="", labels=None):
        return self._get(Gauge, name, help, labels)

    def histogram(self, name, help="", labels=None, buckets=None):
        return self._get(Histogram, name, help, labels, buckets=buckets)

    def get(self, name, labels=None):
        """Existing series or None (read-side: tests, rollups). Under
        the lock like every other reader: a lazily-registered series
        resizing the dict mid-lookup on a scrape thread is the same
        race snapshot() guards against."""
        with self._lock:
            return self._metrics.get(
                _series_key(name, dict(labels or {})))

    def series(self):
        with self._lock:
            return list(self._metrics.values())

    def names(self):
        with self._lock:
            return sorted({m.name for m in self._metrics.values()})

    # -- snapshot/merge ----------------------------------------------------
    def snapshot(self):
        with self._lock:
            return {"ts": round(time.time(), 6),
                    "metrics": {m.key: m.snapshot()
                                for m in self._metrics.values()}}

    def merge(self, snap):
        """Fold a snapshot() (possibly from another registry/process)
        into this registry: counters/histograms add, gauges last-win.
        Atomic — a scrape sees all of the snapshot or none of it."""
        cls_by_kind = {"counter": Counter, "gauge": Gauge,
                       "histogram": Histogram}
        with self._lock:
            for entry in snap["metrics"].values():
                cls = cls_by_kind[entry["type"]]
                kw = {}
                if cls is Histogram:
                    kw["buckets"] = entry["bounds"]
                m = self._get(cls, entry["name"], "", entry["labels"],
                              **kw)
                m.merge(entry)

    def reset(self):
        """Zero every series IN PLACE (handles held by instrumented
        code stay valid) — splits a warmup from a timed window."""
        with self._lock:
            for m in self._metrics.values():
                m.reset()

    def clear(self):
        """Drop every series (test isolation)."""
        with self._lock:
            self._metrics.clear()

    # -- exports -----------------------------------------------------------
    def to_prometheus(self):
        """Prometheus text exposition format."""
        lines = []
        seen_names = set()
        for m in sorted(self.series(), key=lambda m: m.key):
            if m.name not in seen_names:
                seen_names.add(m.name)
                if m.help:
                    lines.append(f"# HELP {m.name} {m.help}")
                lines.append(f"# TYPE {m.name} {m.kind}")
            lab = m.key[len(m.name):]  # "" or {k="v",...}
            if isinstance(m, Histogram):
                cum = 0
                for bound, c in zip(m.bounds, m.counts):
                    cum += c
                    le = _series_key(
                        m.name + "_bucket",
                        {**m.labels, "le": _fmt(bound)})
                    lines.append(f"{le} {cum}")
                le = _series_key(m.name + "_bucket",
                                 {**m.labels, "le": "+Inf"})
                lines.append(f"{le} {m.count}")
                lines.append(f"{m.name}_sum{lab} {_fmt(m.sum)}")
                lines.append(f"{m.name}_count{lab} {m.count}")
            else:
                v = m.value
                lines.append(f"{m.key} {v if isinstance(v, int) else _fmt(v)}")
        return "\n".join(lines) + ("\n" if lines else "")

    def to_json(self, indent=None):
        doc = self.snapshot()
        try:
            return json.dumps(doc, indent=indent, allow_nan=False)
        except ValueError:
            return json.dumps(_finite(doc), indent=indent,
                              allow_nan=False)

    def dump(self, path, extra=None):
        """Write the snapshot (plus optional extra sections, e.g. the
        RecompileTracer report) as JSON to `path` — the metrics.json
        artifact tools/metrics_diff.py compares. Always RFC-valid JSON: a
        NaN gauge (e.g. train_loss on a storm's last step) is nulled,
        never emitted as a bare NaN token jq/JS consumers reject."""
        doc = self.snapshot()
        if extra:
            doc.update(extra)
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            try:
                json.dump(doc, f, indent=1, allow_nan=False)
            except ValueError:
                f.seek(0)
                f.truncate()
                json.dump(_finite(doc), f, indent=1, allow_nan=False)
        os.replace(tmp, path)
        return path


_default = MetricsRegistry()


def get_registry():
    """The process-global default registry (train/serving/dataloader
    instrumentation publishes here unless handed a private one)."""
    return _default
