"""What every driver and the command share: finding files by the names in
BENCHMARK.json, the device check, the compile counter, the profiler window
and small statistics. Nothing here knows a cell, a model or a length."""
from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import os
import shutil
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(*parts):
    """Import benchmarks/<parts> by path, so that a name with a dot in it
    (`mfu.train.py`) is no trouble."""
    path = os.path.join(BENCH_DIR, *parts)
    name = "bench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(manifest, name):
    """(cell, configuration entry) of one `workloads` name."""
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            cfg = next(c for c in manifest["configs"]
                       if c["name"] == cell["config"])
            return cell, cfg
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json: "
                     f"{[c['name'] for c in manifest['workloads']]}")


def load_cell(manifest, name, traffic_dir=None):
    """(cell, its configuration file, its traffic file) by the names in the
    manifest; `traffic_dir` lets a test keep its tiny mixes beside itself."""
    cell, cfg_entry = find_cell(manifest, name)
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic_dir = traffic_dir or os.path.join(BENCH_DIR, "traffic")
    with open(os.path.join(traffic_dir, cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return cell, config, traffic


def metrics_of(manifest, section, cell_name):
    """The metrics of `end_to_end` or `per_layer` that this cell reports."""
    out = []
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest[section]:
        cells = m.get("workloads")
        if cells is None and section == "per_layer":
            cells = e2e[m["moves"]].get("workloads")
        if cells is None or cell_name in cells:
            out.append(m)
    return out


class Context:
    """One run: the cell, its configuration and traffic files, the seed."""

    def __init__(self, cell, config, traffic, seed, seconds, trace, peak,
                 t_start):
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.peak = peak
        self.t_start = t_start

    def log(self, what):
        """A line of the set-up's progress, with the seconds since start."""
        print(f"[{time.perf_counter() - self.t_start:7.2f} s] {what}",
              flush=True)

    @property
    def window_seconds(self):
        """A traced run measures a shorter window where the traffic file
        says so: traces are large and the reading has to fit the run."""
        cap = self.traffic.get("trace_seconds")
        if self.trace and cap:
            return min(self.seconds, float(cap))
        return self.seconds


def device_info():
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def require_chip(chips, peaks):
    """The device this run is measured on, or SystemExit: a measurement
    path never falls back to the CPU, and a chip the peaks table does not
    hold has no roofline."""
    info = device_info()
    print(f"device: {json.dumps(info)}", flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"benchmark needs a TPU, found {info['platform']}")
    if info["count"] < chips:
        raise SystemExit(f"cell needs {chips} chips, found {info['count']}")
    if info["kind"] not in peaks:
        raise SystemExit(f"device kind {info['kind']!r} is not in peaks.json")
    return info, peaks[info["kind"]]


def memory_peak_bytes():
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileClock:
    """Counts jax's own backend compiles (copy of chip_smoke.CompileClock):
    a persistent-cache hit shows as a short compile plus a cache_hits
    event, so `compiles - cache_hits` is what really compiled."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class HostSpans:
    """The driver's own spans on `time.perf_counter`, and marks that tie
    that clock to the profiler's: each mark is a TraceAnnotation whose
    name carries its index, entered right after the clock was read."""

    def __init__(self):
        self.spans = []     # (name, t0, t1)
        self.marks = []     # perf_counter at the start of "bench:mark<i>"

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.perf_counter()))

    def add(self, name, t0, t1):
        self.spans.append((name, t0, t1))

    def mark(self):
        import jax
        i = len(self.marks)
        self.marks.append(time.perf_counter())
        with jax.profiler.TraceAnnotation(f"bench:mark{i}"):
            pass


@contextlib.contextmanager
def profiler_window(enabled):
    """Run the body under jax.profiler when `enabled`; yields a dict that
    holds the path of the .xplane.pb afterwards. The directory lies under
    TMPDIR, outside the checkout; `cleanup` removes it."""
    out = {"xplane": None, "dir": None}
    if not enabled:
        yield out
        return
    import jax
    from jax.profiler import ProfileOptions
    out["dir"] = tempfile.mkdtemp(prefix="bench_trace_")
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out["dir"], profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(out["dir"], "**", "*.xplane.pb"),
                          recursive=True)
        out["xplane"] = found[0] if found else None


def cleanup(prof):
    if prof.get("dir"):
        shutil.rmtree(prof["dir"], ignore_errors=True)


def percentile(values, q):
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    xs = sorted(values)
    k = max(0, min(len(xs) - 1, int(-(-q * len(xs) // 100)) - 1))
    return xs[k]


def np_rng(seed, *stream):
    """A numpy generator for one named stream of one seed: seeds are any
    whole number up to a little over 2**31."""
    import numpy as np
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])
