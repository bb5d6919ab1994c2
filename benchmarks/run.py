"""python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, once. Finds the cell's configuration, traffic mix,
driver and per-layer readers by the names in BENCHMARK.json; holds no
cell-specific code. The last line of standard output is the result."""
import time
T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import harness  # noqa: E402


def per_layer(manifest, cell_name, run, trace):
    """Each per-layer metric of this cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in harness.metrics_of(manifest, "per_layer", cell_name):
        reader = harness.load_module("layer_metrics", m["name"] + ".py")
        value = reader.read(run, trace)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(numbers, limits):
    """[{name, value, limit}] and whether every number is within its
    limit; a number with no limit, or not finite, fails."""
    rows, ok = [], True
    for name, value in numbers:
        limit = limits.get(name)
        rows.append({"name": name, "value": value, "limit": limit})
        if limit is None or not math.isfinite(value) or value > limit:
            ok = False
    return rows, ok


def execute(manifest, args, info, peak, traffic_dir=None):
    cell, config, traffic = harness.load_cell(manifest, args.workload,
                                              traffic_dir)
    ctx = harness.Context(cell, config, traffic, args.seed, args.seconds,
                          args.trace, peak, T_START)
    drv = importlib.import_module(f"benchmarks.drivers.{traffic['driver']}")
    host = harness.HostSpans()
    clock = harness.CompileClock()

    state = drv.setup(ctx, host)
    setup_s = time.perf_counter() - T_START
    real_compiles = clock.compiles - clock.cache_hits
    print(f"setup_s={setup_s:.2f} compiles={clock.compiles} "
          f"cache_hits={clock.cache_hits}", flush=True)

    compiled_before = clock.compiles
    with harness.profiler_window(ctx.trace) as prof:
        raw = drv.window(state, ctx, host)
    compiled_in_window = clock.compiles - compiled_before
    try:
        result = drv.settle(state, ctx, host, raw)
        if compiled_in_window:
            raise SystemExit(f"{compiled_in_window} compilations inside the "
                             "measured window: it measured the compiler")
        device = dict(info, memory_peak_bytes=harness.memory_peak_bytes())
        run = drv.run_data(state, ctx, result)
        run.update(config=config, traffic=traffic, peak=peak,
                   window_s=result["t1"] - result["t0"])
        extra = {}
        if ctx.trace:
            from benchmarks import trace_reduce
            t_read = time.perf_counter()
            tr = trace_reduce.summarize(prof["xplane"], host, result["t0"],
                                        result["t1"])
            print(f"trace of {os.path.getsize(prof['xplane'])} bytes reduced "
                  f"in {time.perf_counter() - t_read:.1f} s", flush=True)
            device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
            extra["breakdown"] = {"device_ops": tr["device_ops"],
                                 "idle_gaps": tr["idle_gaps"]}
            metrics = per_layer(manifest, cell["name"], run, tr)
        else:
            metrics = {
                m["name"]: {"value": result["end_to_end"][m["name"]],
                            "unit": m["unit"]}
                for m in harness.metrics_of(manifest, "end_to_end",
                                            cell["name"])
                if m["name"] != "setup_s"}
            metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    finally:
        harness.cleanup(prof)

    numbers, detail = drv.check(state, ctx, result)
    rows, ok = judge(numbers, traffic["limits"])
    return {"correct": ok, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": device, **extra,
            "first_run_compiles": real_compiles, "check_detail": detail,
            "compared": rows}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = harness.load_manifest()
    cell, _ = harness.find_cell(manifest, args.workload)
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    import jax
    # every program goes to the cache, the quick ones too: a second run
    # of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    info, peak = harness.require_chip(cell["chips"],
                                      harness.load_json("peaks.json"))
    line = execute(manifest, args, info, peak)
    for row in line["compared"]:
        print(f"compared {row['name']}: {row['value']!r} limit "
              f"{row['limit']!r}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
