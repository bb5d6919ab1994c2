"""Compiled-executable introspection — what XLA actually built.

Every FLOP/MFU number reported before this module was
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

Beside the numbers a capture keeps the compiled object itself, so
that ``site_scopes(site)`` can say, on request, which layer each
instruction of the compiled program belongs to: the map *instruction
name -> scope path* parsed from the compiled text's
``metadata={op_name=...}``, which carries the ``jax.named_scope`` names
the program was built under (nn.Layer.__call__ gives every layer its
class name; the step builders add `loss`, `optimizer`, `kv_write`, ...).
A device trace names its operations by instruction, so a reader joins
the two. The text is produced and parsed on the first query, never on
the capture path.

API-shape guards: ``cost_analysis()`` returns a dict, but CPU-only
builds may return None or omit the ``flops`` key — both normalize to
a plain dict (or None) here. ``memory_analysis()`` is a
``CompiledMemoryStats`` when available, None otherwise.

Stdlib-only at import (tools/_obs.py file-loads this module);
jax is imported inside functions. When loaded standalone the relative
registry import is unavailable — pass ``registry=`` explicitly there.
"""
from __future__ import annotations

import os
import re
import threading
import time

__all__ = ["resolve_peak_flops", "normalize_cost", "normalize_memory",
           "capture_site", "site_cost", "site_scopes", "scope_of",
           "parse_scopes", "cost_report", "measured_mfu", "enabled", "clear",
           "PEAK_FLOPS_BY_DEVICE_KIND"]

# bf16 matmul peak per chip, matched by lowercase substring of
# jax's device_kind string (e.g. "TPU v5 lite", "TPU v4"). MFU is
# reported against the bf16 peak regardless of the dtype actually
# used, so an fp32 run shows honestly low MFU rather than flattering
# itself (the benchmark's `mfu.train` follows the same convention).
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
    missing (an honest null, never a made-up number)."""
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
             "memory": mem, "captures": 1,
             # for site_scopes, which reads its text when first asked
             "_compiled": compiled}
    with _lock:
        prev = _sites.get(key)
        if prev is not None:
            entry["captures"] = prev["captures"] + 1
        _sites[key] = entry
        _skipped.pop(key, None)
    _publish(entry, registry)
    return _public(entry)


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

def _public(entry):
    """A capture as callers and reports see it: the numbers, without
    the compiled object and the scope map kept beside them."""
    return {k: v for k, v in entry.items() if not k.startswith("_")}


def _latest(site, tracer):
    """The registry's own entry for `site`; call under _lock."""
    if tracer is not None:
        return _sites.get((tracer, site))
    best = None
    for (_t, s), e in _sites.items():
        if s == site and (best is None or e["ts"] >= best["ts"]):
            best = e
    return best


def site_cost(site, tracer=None):
    """Latest capture for `site` (optionally pinned to a tracer name);
    None when never captured. Latest-wins across same-named tracers
    (two Engines both report as 'engine')."""
    with _lock:
        e = _latest(site, tracer)
        return _public(e) if e else None


# -- which layer an instruction belongs to ---------------------------------

# `%fusion.12 = ... metadata={op_name="jit(train_step)/.../mul" ...}`
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*'
    r'metadata=\{[^}]*?op_name="([^"]*)"', re.M)
# what a transformation wraps a scope in: `transpose(jvp(GPTMLP))`
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
# functions jax names on the way (the program itself, jnp's own helpers)
# and the structure of control flow: part of the path, no layer
_CALLS = {"jit", "pjit"}
_STRUCTURE = re.compile(
    r"^(while|body|cond|closed_call|core_call|checkpoint|remat\d*|"
    r"rematted_computation|custom_jvp_call|custom_vjp_call|"
    r"custom_vjp_call_jaxpr|custom_lin|shard_map|branch_\d+_fun)$")
_SCOPE = re.compile(r"^[A-Za-z_]\w*$")


def scope_of(op_name):
    """The scope path of one `op_name`, normalised so that forward and
    backward of one layer, inside a loop or not, read the same:
    `jit(step)/transpose(jvp(GPTModel))/GPTMLP/mul` and
    `jit(step)/while/body/closed_call/GPTModel/GPTMLP/add` both give
    `GPTModel/GPTMLP`. Transformation wrappers are taken off, jitted
    functions and control-flow structure are left out, what is no
    identifier (an einsum's spec) too, and the trailing primitive is
    dropped. None where nothing is left (an operation built outside
    every scope, a parameter, a compiler-made reduction)."""
    parts, depth, cur = [], 0, []
    for ch in op_name:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += ch == "("
        depth -= ch == ")"
        cur.append(ch)
    # what is left in `cur` is the last component: the primitive, never
    # a scope
    path = []
    for part in parts:
        call = False
        while True:
            m = _WRAPPED.match(part)
            if m is None:
                break
            call = call or m.group(1) in _CALLS
            part = m.group(2)
        if call or _STRUCTURE.match(part) or not _SCOPE.match(part):
            continue
        path.append(part)
    return "/".join(path) or None


def parse_scopes(text):
    """{instruction name: scope path} of a compiled program's text;
    instructions whose metadata gives no scope are left out."""
    out = {}
    for name, op_name in _INSTRUCTION.findall(text):
        scope = scope_of(op_name)
        if scope is not None:
            out[name] = scope
    return out


def site_scopes(site, tracer=None):
    """{instruction name: scope path} of the latest capture of `site`,
    or None when the site was never captured or its executable gives
    no text. Built on the first call (the capture only kept the
    compiled object), then kept, and the compiled object let go. The
    lock is held meanwhile: a query comes when a run is read out, not
    while it serves."""
    with _lock:
        e = _latest(site, tracer)
        if e is None:
            return None
        if "_scopes" not in e:
            text = None
            try:
                text = e.pop("_compiled").as_text()
            except Exception as err:  # noqa: BLE001 — a backend without text
                _skipped[(e["tracer"], site)] = (
                    f"no compiled text: {type(err).__name__}: {err}")
            e["_scopes"] = parse_scopes(text) if text else None
        return e["_scopes"]


def cost_report():
    """The `cost_report` section of the exported run report: every
    captured site plus the sites introspection skipped (and why) and
    the resolved peak-FLOPs."""
    peak, src = resolve_peak_flops()
    with _lock:
        sites = {f"{t}/{s}": _public(e) for (t, s), e in
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
