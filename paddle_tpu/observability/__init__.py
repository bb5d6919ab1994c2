"""Unified telemetry subsystem (metrics registry, recompile tracer,
structured run telemetry, compiled-cost introspection, live exporter,
spans, distributed tracing, SLO burn-rate accounting, crash flight
recorder) — docs/observability.md.

Layering: ``metrics``, ``telemetry``, ``exporter``, ``spans``,
``contprof``, ``dtrace``, ``slo``, ``flightrec``, ``history``,
``tenancy``, ``trafficrec`` and ``sentinel`` are pure stdlib
(importable from jax-free tools and worker processes); ``trace`` and ``introspect`` import jax lazily inside
the wrapping calls.
"""
from . import (contprof, dtrace, exporter, flightrec,  # noqa: F401
               history, introspect, metrics, sentinel, slo, spans,
               telemetry, tenancy, trace, trafficrec)
from .contprof import ContinuousProfiler  # noqa: F401
from .dtrace import TraceStore, get_store  # noqa: F401
from .exporter import MetricsExporter, serve_metrics  # noqa: F401
from .flightrec import FlightRecorder  # noqa: F401
from .history import HistoryStore  # noqa: F401
from .sentinel import AnomalySentinel  # noqa: F401
from .tenancy import SpaceSavingSketch, TenantAccountant  # noqa: F401
from .trafficrec import TrafficRecorder, load_archive  # noqa: F401
from .introspect import (cost_report, measured_mfu,  # noqa: F401
                         resolve_peak_flops)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,  # noqa: F401
                      default_time_buckets, get_registry)
from .slo import SLObjective, SLOTracker  # noqa: F401
from .spans import SpanRecorder, export_chrome  # noqa: F401
from .telemetry import TelemetryCallback, TelemetryLogger  # noqa: F401
from .trace import RecompileTracer, get_tracer, report_all  # noqa: F401

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "default_time_buckets", "get_registry",
           "TelemetryCallback", "TelemetryLogger", "RecompileTracer",
           "get_tracer", "report_all", "MetricsExporter",
           "serve_metrics", "SpanRecorder", "export_chrome",
           "TraceStore", "get_store", "SLObjective", "SLOTracker",
           "FlightRecorder", "cost_report", "measured_mfu",
           "resolve_peak_flops", "HistoryStore", "AnomalySentinel",
           "SpaceSavingSketch", "TenantAccountant",
           "TrafficRecorder", "load_archive",
           "ContinuousProfiler",
           "metrics", "telemetry", "trace",
           "introspect", "exporter", "spans", "contprof", "dtrace",
           "slo", "flightrec", "history", "sentinel", "tenancy",
           "trafficrec"]
