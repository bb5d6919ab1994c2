"""Structured run telemetry — JSONL records + the hapi callback.

``TelemetryLogger`` writes one JSON object per line (one record per
train step / serve request / workload event) into a run directory,
with size-based rotation so a week-long run can't fill a disk, and a
``summarize()`` rollup (counts + numeric-field min/mean/max/last per
record kind) that powers the exportable run report.

``TelemetryCallback`` is the hapi side: drop it into ``Model.fit
(callbacks=[...])`` and every train step emits a record carrying
step_time, loss, grad-norm, samples/s and the TrainGuard/GradScaler
skip/rollback/found-inf counters, while the same values land in the
metrics registry (histograms/counters/gauges) for the metrics.json
export. On train end it writes ``metrics.json`` (registry snapshot +
recompile report) next to ``telemetry.jsonl``.

The callback is duck-typed against hapi's Callback protocol (it
implements the hook surface directly) so this module never imports
hapi — hapi.callbacks re-exports it without an import cycle.
"""
from __future__ import annotations

import json
import math
import numbers
import os
import time

__all__ = ["TelemetryLogger", "TelemetryCallback"]


def _json_default(o):
    try:
        return float(o)
    except (TypeError, ValueError):
        return repr(o)


def _finite(obj):
    """Map non-finite floats to None: json.dumps' default NaN/Infinity
    tokens are not RFC JSON and break jq/JS consumers — exactly on the
    NaN-storm runs this subsystem exists to record."""
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj


class TelemetryLogger:
    """Append-only JSONL with rotation.

    run_dir/filename is the live file; on crossing rotate_bytes it is
    rotated to filename.1 (older files shift up; at most max_rotated
    rotated files are kept, oldest dropped)."""

    def __init__(self, run_dir, filename="telemetry.jsonl",
                 rotate_bytes=16 * 1024 * 1024, max_rotated=3):
        os.makedirs(run_dir, exist_ok=True)
        self.run_dir = run_dir
        self.path = os.path.join(run_dir, filename)
        self.rotate_bytes = int(rotate_bytes)
        self.max_rotated = int(max_rotated)
        self.rotations = 0
        self._f = open(self.path, "a")
        self._bytes = os.path.getsize(self.path)
        self.records = 0

    # -- writing -----------------------------------------------------------
    def emit(self, kind, **fields):
        """Write one record: {"ts", "kind", **fields}. Returns the
        record dict."""
        rec = {"ts": round(time.time(), 6), "kind": kind}
        rec.update(fields)
        try:
            line = json.dumps(rec, default=_json_default,
                              allow_nan=False) + "\n"
        except ValueError:
            # a NaN loss (the storm the guard records) must still land
            # as valid JSON: normalize via a tolerant round-trip, then
            # null out the non-finite leaves
            # the inner dumps MUST keep allow_nan: it is the tolerant
            # normalization round-trip whose output _finite() then
            # nulls — the emitted line below carries allow_nan=False
            # tpulint: disable-next-line=OBS01
            raw = json.dumps(rec, default=_json_default)
            rec = _finite(json.loads(raw))
            line = json.dumps(rec, allow_nan=False) + "\n"
        self._f.write(line)
        self._bytes += len(line)
        self.records += 1
        if self._bytes >= self.rotate_bytes:
            self._rotate()
        return rec

    def _rotate(self):
        self._f.close()
        oldest = f"{self.path}.{self.max_rotated}"
        if os.path.exists(oldest):
            os.remove(oldest)
        for i in range(self.max_rotated - 1, 0, -1):
            src = f"{self.path}.{i}"
            if os.path.exists(src):
                os.replace(src, f"{self.path}.{i + 1}")
        os.replace(self.path, f"{self.path}.1")
        self._f = open(self.path, "a")
        self._bytes = 0
        self.rotations += 1

    def flush(self):
        self._f.flush()

    def close(self):
        if not self._f.closed:
            self._f.close()

    # -- reading -----------------------------------------------------------
    def files(self):
        """All telemetry files, oldest first (rotated then live)."""
        out = []
        for i in range(self.max_rotated, 0, -1):
            p = f"{self.path}.{i}"
            if os.path.exists(p):
                out.append(p)
        if os.path.exists(self.path):
            out.append(self.path)
        return out

    def iter_records(self):
        for p in self.files():
            with open(p) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        yield json.loads(line)
                    except json.JSONDecodeError:
                        continue  # a torn last line must not kill rollup

    def summarize(self):
        """Rollup over every retained record: per-kind counts and
        numeric-field stats (min/mean/max/last)."""
        self.flush()
        by_kind = {}
        total = 0
        for rec in self.iter_records():
            total += 1
            kind = rec.get("kind", "?")
            slot = by_kind.setdefault(kind, {"count": 0, "fields": {}})
            slot["count"] += 1
            for k, v in rec.items():
                if k in ("kind", "ts") or not isinstance(
                        v, numbers.Number) or isinstance(v, bool):
                    continue
                st = slot["fields"].setdefault(
                    k, {"min": v, "max": v, "sum": 0.0, "n": 0,
                        "last": v})
                st["min"] = min(st["min"], v)
                st["max"] = max(st["max"], v)
                st["sum"] += v
                st["n"] += 1
                st["last"] = v
        for slot in by_kind.values():
            for st in slot["fields"].values():
                st["mean"] = st.pop("sum") / st.pop("n")
        return {"records": total, "rotations": self.rotations,
                "by_kind": by_kind}


class TelemetryCallback:
    """hapi train-loop instrumentation (pass via fit(callbacks=[...])).

    Per batch: step_time, loss, grad-norm (from the compiled step's
    fused reduction — Engine.last_grad_norm), samples/s, plus guard
    skip/rollback and scaler found-inf counters (diffed into monotonic
    registry counters). Per run: a train_begin/train_end pair, the
    summarize() rollup, and a metrics.json export.

    Beyond the counters, each step also publishes MFU two ways
    (docs/observability.md "analytic vs measured"): `train_mfu_measured`
    divides the compiled executable's XLA cost_analysis FLOPs
    (introspect.site_cost of the engine's train-step site) by step wall
    and the resolved chip peak; `train_mfu_analytic` does the same with
    the hand-derived `flops_per_step=` the caller supplies (omitted ->
    measured only). Either gauge is absent — never fabricated — when
    its FLOPs leg or the peak is unresolvable (CPU without
    PADDLE_TPU_PEAK_FLOPS). A per-step span lands on the callback's
    SpanRecorder (`.spans`, lane "train", guard outcomes as instants)
    and is exported to `spans.json` at train end — merge it with
    engine/serving/profiler recorders via spans.export_chrome for one
    Perfetto timeline. Every step is also note()d into the crash
    flight recorder.

    jsonl_every: emit a JSONL record every N batches (registry metrics
    update every batch regardless).
    """

    METRIC_NAMES = ("train_step_seconds", "train_steps_total",
                    "train_loss", "train_samples_per_s",
                    "train_grad_norm", "train_skipped_steps_total",
                    "train_rollbacks_total", "train_found_inf_total",
                    "train_mfu_measured", "train_mfu_analytic",
                    "train_peak_flops")

    def __init__(self, run_dir=None, logger=None, registry=None,
                 jsonl_every=1, write_metrics=True, flops_per_step=None,
                 write_spans=True):
        if run_dir is None and logger is None:
            raise ValueError("TelemetryCallback needs run_dir= or "
                             "logger=")
        self.run_dir = run_dir if run_dir is not None else logger.run_dir
        self.logger = logger
        self._owns_logger = logger is None
        self.jsonl_every = max(1, int(jsonl_every))
        self.write_metrics = write_metrics
        self.write_spans = write_spans
        self.flops_per_step = flops_per_step
        self._registry = registry
        self.model = None
        self.params = {}
        self._t0 = None
        self._seen = {}
        self.last_summary = None
        self.metrics_path = None
        self.spans_path = None
        # sibling modules are optional under standalone file-loading
        # (tools/_obs.py loads telemetry.py without the package)
        try:
            from . import introspect as _intro
            from .flightrec import note as _fnote
            from .spans import SpanRecorder
            self._intro = _intro
            self._fnote = _fnote
            self.spans = SpanRecorder(name="train")
        except ImportError:
            self._intro = None
            self._fnote = None
            self.spans = None
        self._peak = None
        self._peak_src = None

    # -- Callback protocol (duck-typed; hapi never imported here) ----------
    def set_params(self, params):
        self.params = params or {}

    def set_model(self, model):
        self.model = model

    def _reg(self):
        if self._registry is None:
            from .metrics import get_registry
            self._registry = get_registry()
        return self._registry

    def on_train_begin(self, logs=None):
        if self.logger is None or self.logger._f.closed:
            self.logger = TelemetryLogger(self.run_dir)
            self._owns_logger = True
        # grad-norm collection is opt-in on the Engine (the in-step
        # reduction is free to fuse but not free to run); enable it
        # here, before the step first compiles
        eng = getattr(self.model, "_engine", None)
        if eng is not None and hasattr(eng, "enable_grad_norm"):
            eng.enable_grad_norm()
        # guard/scaler totals are lifetime-absolute on the guard object:
        # baseline them here so a second fit() on the same model diffs
        # only ITS OWN skips into the (often process-global) registry
        # instead of re-counting fit 1's history
        self._seen = {}
        guard = getattr(eng, "guard", None) if eng is not None else None
        if guard is not None:
            self._seen["skipped"] = int(guard.skipped_steps)
            self._seen["rollbacks"] = int(guard.rollbacks)
            if guard.scaler is not None:
                self._seen["found_inf"] = int(
                    guard.scaler.found_inf_count)
        self._t0 = None
        # one peak-FLOPs resolution per run (env override > device-kind
        # table > None); publishing the denominator makes every MFU
        # gauge auditable from the export alone
        if self._intro is not None:
            self._peak, self._peak_src = self._intro.resolve_peak_flops()
            if self._peak:
                self._reg().gauge(
                    "train_peak_flops",
                    help="peak FLOPs MFU is computed against "
                         f"({self._peak_src})").set(self._peak)
        self.logger.emit("train_begin",
                         epochs=self.params.get("epochs"),
                         steps=self.params.get("steps"))

    def on_train_batch_begin(self, step, logs=None):
        self._t0 = time.perf_counter()

    def _measured_flops(self):
        """XLA cost_analysis FLOPs of the engine's compiled train-step
        site (whichever variant this run built); None before the first
        compile or where the backend reports no flops key."""
        if self._intro is None:
            return None
        for site in ("train_step_guarded", "train_step"):
            e = self._intro.site_cost(site, tracer="engine")
            if e and e.get("flops"):
                return e["flops"]
        return None

    @staticmethod
    def _scalar(v):
        if isinstance(v, (list, tuple)):
            v = v[0] if v else None
        return float(v) if isinstance(v, numbers.Number) else None

    def _diff_counter(self, reg, name, key, absolute):
        """Fold an absolute (monotonic) source total into a registry
        counter by increments. The series registers on first call even
        at zero — a clean run exports skip/rollback counters of 0, not
        an absent metric."""
        if absolute is None:
            return None
        absolute = int(absolute)
        c = reg.counter(name)
        prev = self._seen.get(key, 0)
        if absolute > prev:
            c.inc(absolute - prev)
        self._seen[key] = absolute
        return absolute

    def on_train_batch_end(self, step, logs=None):
        logs = logs or {}
        now = time.perf_counter()
        dt = (now - self._t0) if self._t0 is not None else None
        self._t0 = None
        reg = self._reg()
        eng = getattr(self.model, "_engine", None)

        loss = self._scalar(logs.get("loss"))
        bs = self._scalar(logs.get("batch_size"))
        samples_per_s = (bs / dt) if (bs and dt) else None
        grad_norm = None
        gn = getattr(eng, "last_grad_norm", None)
        if gn is not None:
            try:
                import numpy as np
                grad_norm = float(np.asarray(gn))
            except Exception:  # noqa: BLE001 — telemetry must not kill fit
                grad_norm = None

        if dt is not None:
            reg.histogram(
                "train_step_seconds",
                help="hapi train step wall time").observe(dt)
        reg.counter("train_steps_total",
                    help="train batches seen by fit()").inc()
        if loss is not None:
            reg.gauge("train_loss", help="last train loss").set(loss)
        if samples_per_s is not None:
            reg.gauge("train_samples_per_s",
                      help="last step's samples/s").set(samples_per_s)
        if grad_norm is not None:
            reg.gauge("train_grad_norm",
                      help="last step's global grad L2 norm").set(
                          grad_norm)

        # guard/scaler counters: fit() puts the absolute totals into
        # the batch logs when a guard is attached; fall back to the
        # guard object for direct Engine use
        guard = getattr(eng, "guard", None)
        skipped = self._scalar(logs.get("skipped"))
        rollbacks = self._scalar(logs.get("rollbacks"))
        found_inf = self._scalar(logs.get("found_inf"))
        if guard is not None:
            if skipped is None:
                skipped = guard.skipped_steps
            if rollbacks is None:
                rollbacks = guard.rollbacks
            if found_inf is None and guard.scaler is not None:
                found_inf = guard.scaler.found_inf_count
        skipped = self._diff_counter(
            reg, "train_skipped_steps_total", "skipped", skipped)
        rollbacks = self._diff_counter(
            reg, "train_rollbacks_total", "rollbacks", rollbacks)
        found_inf = self._diff_counter(
            reg, "train_found_inf_total", "found_inf", found_inf)

        # MFU both ways (docs/observability.md): measured rides the
        # compiled executable's cost_analysis, analytic the caller's
        # convention — published side by side so drift is queryable
        mfu_measured = mfu_analytic = None
        if self._peak and dt:
            cf = self._measured_flops()
            if cf:
                mfu_measured = cf / dt / self._peak
                reg.gauge("train_mfu_measured",
                          help="compiled-FLOPs MFU (XLA cost_analysis "
                               "/ step wall / chip peak)").set(
                              mfu_measured)
            if self.flops_per_step:
                mfu_analytic = self.flops_per_step / dt / self._peak
                reg.gauge("train_mfu_analytic",
                          help="analytic-FLOPs MFU (caller convention "
                               "/ step wall / chip peak)").set(
                              mfu_analytic)

        outcome = guard.last_outcome if guard is not None else None
        step_n = getattr(eng, "_step", None)
        if self.spans is not None and dt is not None:
            self.spans.add("train_step", now - dt, now, tid="train",
                           cat="train",
                           args={"step": step_n, "loss": loss})
            if outcome in ("skipped", "rolled_back"):
                self.spans.instant(f"guard_{outcome}", tid="train",
                                   cat="train", args={"step": step_n})
        if self._fnote is not None:
            self._fnote("train_step", step=step_n, loss=loss,
                        step_time_s=None if dt is None else round(dt, 6),
                        outcome=outcome)

        n = int(reg.counter("train_steps_total").value)
        if n % self.jsonl_every == 0:
            rec = {"step": getattr(eng, "_step", n), "loss": loss,
                   "step_time_s": None if dt is None else round(dt, 6),
                   "samples_per_s": None if samples_per_s is None
                   else round(samples_per_s, 3),
                   "grad_norm": grad_norm, "batch_size": bs,
                   "mfu_measured": None if mfu_measured is None
                   else round(mfu_measured, 5),
                   "mfu_analytic": None if mfu_analytic is None
                   else round(mfu_analytic, 5)}
            if guard is not None:
                rec.update(skipped=skipped, rollbacks=rollbacks,
                           outcome=guard.last_outcome)
            if found_inf is not None:
                rec["found_inf"] = found_inf
            self.logger.emit("train_step",
                             **{k: v for k, v in rec.items()
                                if v is not None or k == "loss"})

    def on_epoch_begin(self, epoch, logs=None):
        pass

    def on_epoch_end(self, epoch, logs=None):
        self.logger.emit("epoch_end", epoch=epoch)

    def on_train_end(self, logs=None):
        guard = getattr(getattr(self.model, "_engine", None), "guard",
                        None)
        end = {}
        if guard is not None:
            end.update(guard.stats())
        self.last_summary = self.logger.summarize()
        self.logger.emit("train_end",
                         records=self.last_summary["records"], **end)
        self.logger.flush()
        if self.write_metrics:
            from .trace import report_all
            self.metrics_path = self._reg().dump(
                os.path.join(self.run_dir, "metrics.json"),
                extra={"recompile_report": report_all()})
        if self.write_spans and self.spans is not None \
                and self.spans.events():
            # the run's host-scheduling timeline, Perfetto-openable on
            # its own; merge more lanes (engine serving spans, profiler
            # regions) via spans.export_chrome([...]) instead
            self.spans_path = self.spans.export(
                os.path.join(self.run_dir, "spans.json"))
        if self._owns_logger:
            self.logger.close()

    # remaining hook surface (CallbackList calls these unconditionally)
    def on_eval_begin(self, logs=None):
        pass

    def on_eval_end(self, logs=None):
        pass

    def on_eval_batch_begin(self, step, logs=None):
        pass

    def on_eval_batch_end(self, step, logs=None):
        pass

    def on_predict_begin(self, logs=None):
        pass

    def on_predict_end(self, logs=None):
        pass
