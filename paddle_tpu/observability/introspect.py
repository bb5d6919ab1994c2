"""Compiled-executable introspection — what XLA actually built.

Every FLOP/MFU number the bench reported before this module was
*analytic*: a hand-derived 6N+12Lhs convention multiplied by a
hardcoded peak. The compiler knows better — each compiled executable
carries its own ``cost_analysis()`` (real FLOPs, bytes accessed) and
``memory_analysis()`` (argument/output/temp bytes). This module
captures both per RecompileTracer jit site, so "measured MFU"
(compiled FLOPs / step wall / chip peak) becomes a queryable run fact
that can DRIFT from the analytic one — and that drift is the story
(a fused kernel XLA didn't build, a recompute policy doubling the
backward, an attention variant the convention ignores).

Capture rides the tracer: a site is introspected at most once per
trace (i.e. per compile), via an AOT ``jitted.lower(*args).compile()``
replay with ALL trace accounting suppressed (the replay must never
read as a recompile — ``trace.py`` checks ``introspecting()`` at its
counter bump). The replay costs one extra trace + compile of the same
program; sites whose observed compile exceeded
``PADDLE_TPU_INTROSPECT_MAX_S`` (default 120s) are skipped with a
recorded reason, and
``PADDLE_TPU_INTROSPECT=0`` switches the whole layer off.

API-shape guards: ``cost_analysis()`` returns a dict, but CPU-only
builds may return None or omit the ``flops`` key — both normalize to
a plain dict (or None) here. ``memory_analysis()`` is a
``CompiledMemoryStats`` when available, None otherwise.

Stdlib-only at import (bench's lean workers file-load this module);
jax is imported inside functions. When loaded standalone the relative
registry import is unavailable — pass ``registry=`` explicitly there.
"""
from __future__ import annotations

import os
import threading
import time

__all__ = ["resolve_peak_flops", "normalize_cost", "normalize_memory",
           "capture_site", "site_cost", "cost_report", "measured_mfu",
           "enabled", "clear", "PEAK_FLOPS_BY_DEVICE_KIND"]

# bf16 matmul peak per chip, matched by lowercase substring of
# jax's device_kind string (e.g. "TPU v5 lite", "TPU v4"). MFU is
# reported against the bf16 peak regardless of the dtype actually
# used, so an fp32 run shows honestly low MFU rather than flattering
# itself (the long-standing bench.py convention).
PEAK_FLOPS_BY_DEVICE_KIND = (
    ("v5 lite", 197e12), ("v5litepod", 197e12), ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6 lite", 918e12), ("v6e", 918e12), ("trillium", 918e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

_lock = threading.Lock()
_sites = {}            # (tracer_name, site) -> capture dict
_skipped = {}          # (tracer_name, site) -> reason str
_introspecting = threading.local()
# thread ids currently inside a replay, readable from OTHER threads:
# the continuous profiler (contprof.py) skips them so an AOT replay
# never pollutes a serving profile. set.add/discard are GIL-atomic.
_introspecting_threads = set()


def enabled():
    return os.environ.get("PADDLE_TPU_INTROSPECT", "1").lower() \
        not in ("0", "false", "off")


def introspecting():
    """True while this thread is inside an AOT introspection replay —
    trace.py suppresses ALL trace accounting under it, so the replay
    can never read as a (unexpected) recompile."""
    return getattr(_introspecting, "on", False)


def _max_compile_budget():
    try:
        return float(os.environ.get("PADDLE_TPU_INTROSPECT_MAX_S", 120))
    except ValueError:
        return 120.0


# -- peak-FLOPs resolution -------------------------------------------------

def resolve_peak_flops(device_kind=None):
    """(peak_flops, source) for MFU denominators.

    Resolution order: env ``PADDLE_TPU_PEAK_FLOPS`` (any backend —
    how CPU smoke runs exercise the MFU plumbing), then the
    per-device-kind table (TPU only). (None, reason) when neither
    applies — callers report MFU as null, never against a made-up
    peak."""
    env = os.environ.get("PADDLE_TPU_PEAK_FLOPS")
    if env:
        try:
            return float(env), "env:PADDLE_TPU_PEAK_FLOPS"
        except ValueError:
            pass  # fall through to the table
    if device_kind is None:
        try:
            import jax
            dev = jax.devices()[0]
            if dev.platform != "tpu":
                return None, f"no-table:{dev.platform}"
            device_kind = dev.device_kind
        except Exception:  # noqa: BLE001 — resolution must never raise
            return None, "no-device"
    kind_l = str(device_kind).lower()
    for frag, peak in PEAK_FLOPS_BY_DEVICE_KIND:
        if frag in kind_l:
            return peak, f"table:{frag}"
    return None, f"unknown-device-kind:{device_kind}"


def measured_mfu(flops, step_seconds, peak=None):
    """compiled FLOPs / step wall / peak, or None when any leg is
    missing (the honest null the bench stanzas record)."""
    if not flops or not step_seconds:
        return None
    if peak is None:
        peak, _ = resolve_peak_flops()
    if not peak:
        return None
    return flops / step_seconds / peak


# -- analysis normalization ------------------------------------------------

def normalize_cost(ca):
    """cost_analysis() dict -> {"flops", "bytes_accessed",
    "transcendentals"} (values may be None where the backend reports
    no such key)."""
    if not isinstance(ca, dict):
        return None

    def num(key):
        v = ca.get(key)
        try:
            return float(v) if v is not None else None
        except (TypeError, ValueError):
            return None
    return {"flops": num("flops"),
            "bytes_accessed": num("bytes accessed"),
            "transcendentals": num("transcendentals")}


def normalize_memory(ms):
    """CompiledMemoryStats -> plain dict. peak_bytes is the
    argument+output+temp upper bound (XLA reports no single live-peak
    number through this API; temp is the scratch high-water mark)."""
    if ms is None:
        return None
    out = {}
    for field, name in (("argument_size_in_bytes", "argument_bytes"),
                        ("output_size_in_bytes", "output_bytes"),
                        ("temp_size_in_bytes", "temp_bytes"),
                        ("alias_size_in_bytes", "alias_bytes"),
                        ("generated_code_size_in_bytes", "code_bytes")):
        v = getattr(ms, field, None)
        if v is not None:
            out[name] = int(v)
    if not out:
        return None
    out["peak_bytes"] = (out.get("argument_bytes", 0)
                         + out.get("output_bytes", 0)
                         + out.get("temp_bytes", 0))
    return out


# -- capture ---------------------------------------------------------------

def capture_site(tracer_name, site, jitted, args, kwargs, wall_s=0.0,
                 registry=None):
    """AOT-replay `jitted` on the call's args and record its compiled
    cost/memory analysis under (tracer_name, site). Called by the
    RecompileTracer exactly when a site traced; never raises — a
    failed capture records its reason and returns None.

    The replay happens under the `introspecting()` flag so the
    re-trace (and any nested tracer sites it re-executes) bumps no
    counters and flags no unexpected retraces."""
    key = (tracer_name, site)
    if not enabled():
        return None
    if wall_s > _max_compile_budget():
        with _lock:
            _skipped[key] = (f"compile took {wall_s:.1f}s > "
                             f"PADDLE_TPU_INTROSPECT_MAX_S budget")
        return None
    _introspecting.on = True
    _introspecting_threads.add(threading.get_ident())
    try:
        compiled = jitted.lower(*args, **kwargs).compile()
        cost = normalize_cost(compiled.cost_analysis())
        mem = normalize_memory(compiled.memory_analysis())
    except Exception as e:  # noqa: BLE001 — introspection never kills a step
        with _lock:
            _skipped[key] = f"{type(e).__name__}: {e}"
        return None
    finally:
        _introspecting.on = False
        _introspecting_threads.discard(threading.get_ident())
    entry = {"tracer": tracer_name, "site": site,
             "ts": round(time.time(), 6),
             "flops": (cost or {}).get("flops"),
             "bytes_accessed": (cost or {}).get("bytes_accessed"),
             "transcendentals": (cost or {}).get("transcendentals"),
             "memory": mem, "captures": 1}
    with _lock:
        prev = _sites.get(key)
        if prev is not None:
            entry["captures"] = prev["captures"] + 1
        _sites[key] = entry
        _skipped.pop(key, None)
    _publish(entry, registry)
    return entry


def _publish(entry, registry):
    if registry is None:
        try:
            from .metrics import get_registry
            registry = get_registry()
        except ImportError:
            return  # standalone-loaded module with no registry handed in
    labels = {"tracer": entry["tracer"], "site": entry["site"]}
    if entry.get("flops") is not None:
        registry.gauge("xla_cost_flops",
                       help="compiled-executable FLOPs (XLA "
                            "cost_analysis) per jit site",
                       labels=labels).set(entry["flops"])
    if entry.get("bytes_accessed") is not None:
        registry.gauge("xla_cost_bytes_accessed",
                       help="compiled-executable HBM bytes accessed "
                            "per jit site",
                       labels=labels).set(entry["bytes_accessed"])
    mem = entry.get("memory") or {}
    for field in ("argument_bytes", "output_bytes", "temp_bytes",
                  "peak_bytes"):
        if field in mem:
            registry.gauge(f"xla_memory_{field}",
                           help="compiled-executable memory "
                                f"({field.replace('_', ' ')}) per site",
                           labels=labels).set(mem[field])


# -- queries ---------------------------------------------------------------

def site_cost(site, tracer=None):
    """Latest capture for `site` (optionally pinned to a tracer name);
    None when never captured. Latest-wins across same-named tracers
    (two Engines both report as 'engine')."""
    with _lock:
        if tracer is not None:
            e = _sites.get((tracer, site))
            return dict(e) if e else None
        best = None
        for (_t, s), e in _sites.items():
            if s == site and (best is None or e["ts"] >= best["ts"]):
                best = e
        return dict(best) if best else None


def cost_report():
    """The `cost_report` section of the exported run report: every
    captured site plus the sites introspection skipped (and why) and
    the resolved peak-FLOPs."""
    peak, src = resolve_peak_flops()
    with _lock:
        sites = {f"{t}/{s}": dict(e) for (t, s), e in
                 sorted(_sites.items())}
        skipped = {f"{t}/{s}": r for (t, s), r in
                   sorted(_skipped.items())}
    return {"sites": sites, "skipped": skipped,
            "peak_flops": peak, "peak_flops_source": src,
            "enabled": enabled()}


def clear():
    """Drop every captured site (test hygiene)."""
    with _lock:
        _sites.clear()
        _skipped.clear()
