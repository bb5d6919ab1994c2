"""Live metrics endpoint — scrape a RUNNING engine, not its files.

A stdlib ``http.server`` on a daemon thread serving three endpoints:

- ``/metrics``  — Prometheus text exposition of a MetricsRegistry
  (what a prometheus/grafana scraper or ``curl`` reads mid-run);
- ``/healthz``  — JSON health snapshot (ServingEngine.health() when
  attached there; a minimal liveness doc otherwise) — the thing a
  load balancer probes;
- ``/report``   — JSON recompile report + compiled-cost report
  (trace.report_all + introspect.cost_report): the "what did XLA
  build and did anything retrace" question, answered live.

Every read happens under the registry's own lock (to_prometheus /
snapshot take it), so a scrape landing mid-serve-dispatch sees a
consistent registry — never a torn histogram whose ``_count``
disagrees with its ``+Inf`` bucket.

Attachment is one call: ``ServingEngine.serve_metrics(port=...)`` or
``Model.serve_metrics(port=...)`` (port 0 picks a free one —
``exporter.port`` tells you which). ``close()`` is idempotent and
releases the port immediately (``allow_reuse_address`` covers the
TIME_WAIT rebind); the serving thread is a daemon, so SIGTERM'd
processes exit without joining it.

Stdlib-only by contract (standalone-loadable via tools/_obs.py);
the /report handler imports sibling modules lazily and degrades to
an empty section when they are unavailable.
"""
from __future__ import annotations

import json
import math
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

__all__ = ["MetricsExporter", "serve_metrics"]


def _finite(obj):
    """Non-finite floats -> None (RFC-valid JSON). Duplicated across
    the stdlib-only observability modules on purpose: each stays
    standalone-loadable (tools/_obs.py) with no intra-package imports
    at module scope."""
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj


class MetricsExporter:
    """HTTP exporter for one registry (+ optional health/report fns).

    registry: MetricsRegistry to expose (None -> the process-global
        one, resolved lazily so a standalone load can still pass one).
    health_fn: zero-arg callable returning a JSON-able dict
        (ServingEngine.health); None serves a minimal liveness doc.
    report_fn: zero-arg callable returning extra /report sections
        merged over the defaults.
    traces_fn: one-arg callable serving ``/traces`` (arg None = the
        index of known traces) and ``/traces/<key>`` (arg = the key —
        a trace id or fleet rid; return None for unknown keys -> 404).
        None disables the endpoint (FleetRouter.serve_metrics wires
        its trace_report here).
    requests_fn: one-arg callable serving ``/requests`` (arg None =
        the recent-resolved index: rid, tenant, status, ttft/e2e,
        archive locator — the /traces index's request-plane sibling)
        and ``/requests/<rid>`` (one row; None -> 404). None disables
        the endpoint.
    history_fn: one-arg callable serving ``/history`` — receives the
        parsed query params ({} for a bare GET = the series index;
        keys like series/res/window/q/op select a range/rate/quantile
        read; return None for unknown series -> 404). None disables
        the endpoint (FleetRouter.serve_metrics wires its
        HistoryStore here).
    tenants_fn: zero-arg callable serving ``/tenants`` (the
        TenantAccountant report: top-K heavy hitters + exact totals).
        None disables the endpoint.
    profile_fn: one-arg callable serving ``/profile?window=S`` — the
        continuous profiler's report (folded stacks + per-phase
        digest) over the last S seconds (None = since start); return
        None when no profiler is armed -> 404. None disables the
        endpoint (ServingEngine/FleetRouter wire their
        ContinuousProfiler here, the /traces attach-point pattern).
    memory_fn: one-arg callable serving ``/memory?window=S`` — the
        memory ledger's typed segment tree + headroom forecast (the
        window arg is accepted for route symmetry; a ledger is a
        level, not a ring). Return None -> 404; engines instead
        answer a stub JSON ({"armed": false, ...}) when no ledger is
        armed, so the route itself is always probeable. None disables
        the endpoint.
    host/port: bind address; port 0 = ephemeral (read .port after).

    Every route observes its own wall time into the per-route
    ``exporter_scrape_seconds`` histogram: a slow ``/metrics`` render
    stretches the history plane's scrape cadence and skews rate()
    windows, so scrape latency is itself a first-class series. The
    ``/metrics`` route measures a throwaway render FIRST, observes it,
    then serves a fresh render — so the served exposition already
    contains the observation and stays byte-identical to a subsequent
    in-process ``to_prometheus()`` (the parity contract,
    tests/test_observability.py).
    """

    def __init__(self, registry=None, port=0, host="127.0.0.1",
                 health_fn=None, report_fn=None, traces_fn=None,
                 history_fn=None, tenants_fn=None, requests_fn=None,
                 profile_fn=None, memory_fn=None):
        if registry is None:
            from .metrics import get_registry
            registry = get_registry()
        self.registry = registry
        self.health_fn = health_fn
        self.report_fn = report_fn
        self.traces_fn = traces_fn
        self.history_fn = history_fn
        self.tenants_fn = tenants_fn
        self.requests_fn = requests_fn
        self.profile_fn = profile_fn
        self.memory_fn = memory_fn
        self._scrape_hists = {}
        self._started = time.time()
        exporter = self

        class Handler(BaseHTTPRequestHandler):
            # scrapes every few seconds would spam stderr
            def log_message(self, *a):  # noqa: D102
                pass

            def _send(self, code, body, ctype):
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def _send_json(self, doc, code=200):
                try:
                    body = json.dumps(doc, allow_nan=False)
                except ValueError:
                    # a NaN loss in a health/report doc must still
                    # answer as valid JSON (the storm runs this layer
                    # exists to observe)
                    body = json.dumps(_finite(doc), allow_nan=False)
                self._send(code, body, "application/json")

            def do_GET(self):  # noqa: N802 — http.server API
                parts = self.path.split("?", 1)
                path = parts[0].rstrip("/") or "/"
                seg = "/" + path.split("/")[1] if path != "/" else "/"
                t0 = time.perf_counter()
                try:
                    if path == "/metrics":
                        # double render: measure + observe FIRST, then
                        # serve a fresh exposition that already holds
                        # the observation (byte-parity contract above)
                        exporter.registry.to_prometheus()
                        exporter._observe_scrape(
                            "/metrics", time.perf_counter() - t0)
                        self._send(200, exporter.registry.to_prometheus(),
                                   "text/plain; version=0.0.4; "
                                   "charset=utf-8")
                    elif path == "/healthz":
                        self._send_json(exporter._health())
                    elif path == "/report":
                        self._send_json(exporter._report())
                    elif exporter.traces_fn is not None and (
                            path == "/traces"
                            or path.startswith("/traces/")):
                        key = (path[len("/traces/"):]
                               if path.startswith("/traces/")
                               else "") or None
                        doc = exporter.traces_fn(key)
                        if doc is None:
                            self._send_json(
                                {"error": f"unknown trace {key!r}"},
                                code=404)
                        else:
                            self._send_json(doc)
                    elif exporter.requests_fn is not None and (
                            path == "/requests"
                            or path.startswith("/requests/")):
                        key = (path[len("/requests/"):]
                               if path.startswith("/requests/")
                               else "") or None
                        doc = exporter.requests_fn(key)
                        if doc is None:
                            self._send_json(
                                {"error": f"unknown request {key!r}"},
                                code=404)
                        else:
                            self._send_json(doc)
                    elif exporter.history_fn is not None \
                            and path == "/history":
                        from urllib.parse import parse_qs
                        params = {k: v[-1] for k, v in parse_qs(
                            parts[1] if len(parts) > 1 else ""
                            ).items()}
                        doc = exporter.history_fn(params)
                        if doc is None:
                            self._send_json(
                                {"error": "unknown history query "
                                          f"{params!r}"}, code=404)
                        else:
                            self._send_json(doc)
                    elif exporter.tenants_fn is not None \
                            and path == "/tenants":
                        self._send_json(exporter.tenants_fn())
                    elif exporter.profile_fn is not None \
                            and path == "/profile":
                        from urllib.parse import parse_qs
                        params = {k: v[-1] for k, v in parse_qs(
                            parts[1] if len(parts) > 1 else ""
                            ).items()}
                        window = None
                        if params.get("window"):
                            try:
                                window = float(params["window"])
                            except ValueError:
                                window = None
                        doc = exporter.profile_fn(window)
                        if doc is None:
                            self._send_json(
                                {"error": "no profiler armed "
                                          "(PADDLE_TPU_PROFILE=1)"},
                                code=404)
                        else:
                            self._send_json(doc)
                    elif exporter.memory_fn is not None \
                            and path == "/memory":
                        from urllib.parse import parse_qs
                        params = {k: v[-1] for k, v in parse_qs(
                            parts[1] if len(parts) > 1 else ""
                            ).items()}
                        window = None
                        if params.get("window"):
                            try:
                                window = float(params["window"])
                            except ValueError:
                                window = None
                        doc = exporter.memory_fn(window)
                        if doc is None:
                            self._send_json(
                                {"error": "no ledger armed "
                                          "(PADDLE_TPU_MEM_LEDGER=1)"},
                                code=404)
                        else:
                            self._send_json(doc)
                    else:
                        endpoints = ["/metrics", "/healthz", "/report"]
                        if exporter.traces_fn is not None:
                            endpoints.append("/traces")
                        if exporter.requests_fn is not None:
                            endpoints.append("/requests")
                        if exporter.history_fn is not None:
                            endpoints.append("/history")
                        if exporter.tenants_fn is not None:
                            endpoints.append("/tenants")
                        if exporter.profile_fn is not None:
                            endpoints.append("/profile")
                        if exporter.memory_fn is not None:
                            endpoints.append("/memory")
                        self._send_json(
                            {"error": f"unknown path {path!r}",
                             "endpoints": endpoints}, code=404)
                except Exception as e:  # noqa: BLE001 — a handler bug must
                    # answer 500, not silently drop the connection
                    try:
                        self._send_json({"error": f"{type(e).__name__}: "
                                                  f"{e}"}, code=500)
                    except OSError:
                        pass
                finally:
                    if seg != "/metrics":
                        exporter._observe_scrape(
                            seg, time.perf_counter() - t0)

        Handler.protocol_version = "HTTP/1.1"
        # a close()d exporter's port rebinds immediately (no TIME_WAIT
        # stall between tests): http.server's HTTPServer
        # already sets allow_reuse_address
        self._server = ThreadingHTTPServer((host, port), Handler)
        self._server.daemon_threads = True
        self.host, self.port = self._server.server_address[:2]
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            kwargs={"poll_interval": 0.1},
            daemon=True, name=f"paddle-tpu-metrics-{self.port}")
        self._thread.start()
        self._closed = False

    @property
    def url(self):
        return f"http://{self.host}:{self.port}"

    def _observe_scrape(self, route, dur_s):
        """Per-route scrape-latency self-metric. Never raises — a
        telemetry bug must not turn a scrape into a 500."""
        try:
            h = self._scrape_hists.get(route)
            if h is None:
                h = self._scrape_hists[route] = self.registry.histogram(
                    "exporter_scrape_seconds",
                    help="wall seconds serving one exporter route "
                         "(slow renders stretch scrape cadence and "
                         "skew rate() windows)",
                    labels={"route": route})
            h.observe(dur_s)
        except Exception:   # noqa: BLE001
            pass

    def _health(self):
        doc = {"status": "ok", "ts": round(time.time(), 6),
               "uptime_s": round(time.time() - self._started, 3)}
        if self.health_fn is not None:
            doc.update(self.health_fn())
        return doc

    def _report(self):
        doc = {"ts": round(time.time(), 6)}
        try:
            from .trace import report_all
            doc["recompile_report"] = report_all()
        except ImportError:
            doc["recompile_report"] = None
        try:
            from .introspect import cost_report
            doc["cost_report"] = cost_report()
        except ImportError:
            doc["cost_report"] = None
        if self.report_fn is not None:
            doc.update(self.report_fn())
        return doc

    def close(self):
        """Stop serving and release the port. Idempotent — engines
        call this from close() AND finalizers."""
        if self._closed:
            return
        self._closed = True
        self._server.shutdown()
        self._server.server_close()
        self._thread.join(timeout=2.0)

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter-shutdown safety
            pass


def serve_metrics(port=0, registry=None, host="127.0.0.1",
                  health_fn=None, report_fn=None, traces_fn=None,
                  history_fn=None, tenants_fn=None, requests_fn=None,
                  profile_fn=None):
    """Start a MetricsExporter (the one-call attach the docs show);
    returns it — read ``.port`` / ``.url``, call ``.close()``."""
    return MetricsExporter(registry=registry, port=port, host=host,
                           health_fn=health_fn, report_fn=report_fn,
                           traces_fn=traces_fn, history_fn=history_fn,
                           tenants_fn=tenants_fn,
                           requests_fn=requests_fn,
                           profile_fn=profile_fn)
