"""Per-tenant usage accounting with bounded cardinality.

"Millions of users" (ROADMAP north star) means per-tenant attribution
cannot be a dict that grows one entry per user: the fleet needs the
HEAVY HITTERS — who is consuming the tokens, the KV pages, the queue —
inside a fixed memory budget, with the error bound stated instead of
hidden. This module is that layer:

- ``SpaceSavingSketch`` — the Metwally et al. space-saving top-K
  algorithm. At most ``capacity`` tracked tenants; an increment for an
  untracked tenant past capacity EVICTS the minimum-weight entry and
  INHERITS its weight (recorded per entry as ``err``, the classic
  overestimate bound: ``true_weight >= weight - err`` and every tenant
  whose true weight exceeds ``min_weight`` is guaranteed tracked).
  Crucially the evict-and-inherit move conserves every accumulator, so
  **the sketch's per-field sums equal the exact fleet totals at all
  times** — the invariant the chaos wave asserts (per-tenant token
  totals sum exactly to fleet totals) holds by construction, not
  sampling luck.
- ``TenantAccountant`` — the fleet-facing wrapper: thread-safe
  ``account()`` of tokens in/out, queue-wait seconds, KV-page-seconds
  and request counts per tenant; a ``report()`` the ``/tenants``
  endpoint serves (top-K rows, per-entry error bounds, exact totals,
  eviction count); and ``usage()``, the weight read the router's
  priority shedding folds in (heaviest tenants shed first within a
  priority band).

The ``tenant=`` label itself rides ``FleetRouter.submit`` →
``ReplicaClient`` → the transport verbs (Inproc + Proc frames) →
``ServingEngine.submit``; the engine accounts what only it can see
(KV-page-seconds, admission queue wait) and stamps them on each
result, the router accounts fleet-level totals at resolve time.

Stdlib-only by contract (standalone-loadable via tools/_obs.py).
"""
from __future__ import annotations

import threading

__all__ = ["SpaceSavingSketch", "TenantAccountant", "USAGE_FIELDS"]

#: the accumulators every entry (and the exact-totals row) carries
USAGE_FIELDS = ("tokens_in", "tokens_out", "queue_wait_s",
                "kv_page_s", "requests", "prefix_hit_pages",
                "prefix_pages", "spec_proposed", "spec_accepted")


class SpaceSavingSketch:
    """Space-saving top-K heavy hitters over a weight + side fields.

    capacity: max tracked keys. ``weight`` drives tracking/eviction
    (callers use tokens in+out); the side fields ride along and are
    conserved through evictions (the inheritor absorbs them), so
    per-field sums over the sketch stay EXACT fleet totals.
    """

    def __init__(self, capacity=128):
        if int(capacity) < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = int(capacity)
        self._entries = {}   # key -> {"weight", "err", fields...}
        self.evictions = 0
        self.totals = {f: 0 for f in USAGE_FIELDS}
        self.total_weight = 0

    def add(self, key, weight, **fields):
        """Fold one observation for ``key``. Unknown field names
        raise — silent typos would quietly unbalance the totals."""
        bad = set(fields) - set(USAGE_FIELDS)
        if bad:
            raise ValueError(f"unknown usage fields {sorted(bad)}")
        weight = max(int(weight), 0)
        self.total_weight += weight
        for f, v in fields.items():
            self.totals[f] += v
        ent = self._entries.get(key)
        if ent is None:
            if len(self._entries) < self.capacity:
                ent = {"weight": 0, "err": 0}
                ent.update({f: 0 for f in USAGE_FIELDS})
                self._entries[key] = ent
            else:
                # evict the minimum-weight entry; the newcomer
                # inherits its weight (as err — the overestimate
                # bound) AND its side accumulators, conserving sums
                victim_key = min(self._entries,
                                 key=lambda k: (
                                     self._entries[k]["weight"], k))
                ent = self._entries.pop(victim_key)
                ent["err"] = ent["weight"]
                self._entries[key] = ent
                self.evictions += 1
        ent["weight"] += weight
        for f, v in fields.items():
            ent[f] += v
        return ent

    def usage(self, key):
        """The tracked weight for ``key`` (an overestimate by at most
        that entry's ``err``), 0 when untracked — i.e. provably light."""
        ent = self._entries.get(key)
        return 0 if ent is None else ent["weight"]

    def top(self, k=None):
        """Entries by descending weight (name-tiebroken), each with
        its error bound."""
        rows = sorted(self._entries.items(),
                      key=lambda kv: (-kv[1]["weight"], kv[0]))
        if k is not None:
            rows = rows[:int(k)]
        return [dict(ent, tenant=key) for key, ent in rows]

    @property
    def error_bound(self):
        """Max overestimate across tracked entries (0 until the first
        eviction — below capacity the sketch is exact)."""
        return max((e["err"] for e in self._entries.values()),
                   default=0)

    def __len__(self):
        return len(self._entries)


class TenantAccountant:
    """Thread-safe per-tenant usage accounting over a space-saving
    sketch, with the registry export and report shape the fleet's
    ``/tenants`` endpoint serves.

    capacity: sketch bound (tenants tracked at once).
    registry: MetricsRegistry for ``tenants_tracked`` /
        ``tenant_sketch_evictions_total`` (None = unmetered).
    """

    def __init__(self, capacity=128, registry=None):
        self.sketch = SpaceSavingSketch(capacity=capacity)
        self._lock = threading.Lock()
        self._g_tracked = None
        self._m_evict = None
        if registry is not None:
            self._g_tracked = registry.gauge(
                "tenants_tracked",
                help="tenants currently tracked by the space-saving "
                     "sketch (bounded by its capacity)")
            self._m_evict = registry.counter(
                "tenant_sketch_evictions_total",
                help="sketch evictions (min-weight tenant displaced "
                     "by a newcomer; its usage is inherited, totals "
                     "stay exact)")

    def account(self, tenant, *, tokens_in=0, tokens_out=0,
                queue_wait_s=0.0, kv_page_s=0.0, requests=0,
                prefix_hit_pages=0, prefix_pages=0,
                spec_proposed=0, spec_accepted=0):
        """Fold one request's usage for ``tenant`` (None is skipped —
        untagged traffic costs nothing here; the ROUTER maps untagged
        to 'anon' so fleet sums stay exact regardless)."""
        if tenant is None:
            return
        with self._lock:
            ev0 = self.sketch.evictions
            self.sketch.add(str(tenant), int(tokens_in) + int(tokens_out),
                            tokens_in=int(tokens_in),
                            tokens_out=int(tokens_out),
                            queue_wait_s=float(queue_wait_s),
                            kv_page_s=float(kv_page_s),
                            requests=int(requests),
                            prefix_hit_pages=int(prefix_hit_pages),
                            prefix_pages=int(prefix_pages),
                            spec_proposed=int(spec_proposed),
                            spec_accepted=int(spec_accepted))
            if self._m_evict is not None \
                    and self.sketch.evictions > ev0:
                self._m_evict.inc(self.sketch.evictions - ev0)
            if self._g_tracked is not None:
                self._g_tracked.set(len(self.sketch))

    def usage(self, tenant):
        with self._lock:
            return 0 if tenant is None \
                else self.sketch.usage(str(tenant))

    def heaviest(self, k):
        """The k heaviest tenant names by sketch weight (descending,
        name-tiebroken) — the brownout ladder's clamp set: level L
        clamps exactly ``heaviest(L)``."""
        if int(k) < 1:
            return []
        with self._lock:
            return [r["tenant"] for r in self.sketch.top(int(k))]

    @property
    def tracked(self):
        with self._lock:
            return len(self.sketch)

    def report(self, k=None):
        """The ``/tenants`` payload: top-K rows (weight + err bound +
        the per-field accumulators), EXACT totals, sketch meta. The
        sum of any field over ``tenants`` equals ``totals[field]`` —
        by construction, asserted by the chaos wave."""
        with self._lock:
            rows = self.sketch.top(k)
            return {
                "capacity": self.sketch.capacity,
                "tracked": len(self.sketch),
                "evictions": self.sketch.evictions,
                "error_bound": self.sketch.error_bound,
                "exact_below_capacity": self.sketch.evictions == 0,
                "total_weight": self.sketch.total_weight,
                "totals": {f: self.sketch.totals[f]
                           for f in USAGE_FIELDS},
                "tenants": [
                    {"tenant": r["tenant"], "weight": r["weight"],
                     "err": r["err"],
                     **{f: round(r[f], 6) if isinstance(r[f], float)
                        else r[f] for f in USAGE_FIELDS}}
                    for r in rows]}
