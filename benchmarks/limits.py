"""python3 benchmarks/limits.py --workload <cell> --seeds 1,2,3 [--seconds 8]

Reads, on the chip and at the cell's own size, the numbers that `correct`
compares: the program's (the lower reading), the control's (the reference put
in the program's place and computed in the precision below the one the
configuration states) and, for a training cell, the fault of a mean over half
the batch. Several seeds in one process; one JSON line per seed. The limits in
the traffic files were set from these readings (PERF.md section 2); the
benchmark's own runs never run this."""
import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import harness  # noqa: E402


def read_train(drv, ctx, host):
    st = drv.setup(ctx, host)
    drv.release(st)
    ref = drv.reference_steps(ctx, st)
    control = ctx.config["precision"]["control"]
    out = {}
    for name, other in (
            ("program", st.first),
            ("control_" + control, drv.reference_steps(ctx, st, control)),
            ("fault_half_batch",
             drv.reference_steps(ctx, st, fault="half_batch")),
            ("fault_state_unchanged",
             drv.reference_steps(ctx, st, fault="state_unchanged"))):
        numbers, detail = drv.compare(other, ref)
        out[name] = dict(numbers, **detail)
    return out


def read_serve(drv, ctx, host):
    st = drv.setup(ctx, host)
    result = drv.settle(st, ctx, host, drv.window(st, ctx, host))
    sample = drv.sample_for_check(st.loop, ctx.seed,
                                  ctx.traffic["check_requests"])
    drv.release(st)
    control = ctx.config["precision"]["control"]
    return {"program": {"served_logit_gap_max":
                        drv.served_gap(st, ctx, sample)},
            "control_" + control: {"served_logit_gap_max":
                                   drv.served_gap(st, ctx, sample, control)},
            "checked_tokens": sum(len(r.tokens) for r in sample),
            "finished": result["finished_in_window"],
            "end_to_end": result["end_to_end"]}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--traffic-dir", default=None)
    ap.add_argument("--manifest", default=None)
    args = ap.parse_args(argv)
    if args.manifest:
        with open(args.manifest) as f:
            manifest = json.load(f)
    else:
        manifest = harness.load_manifest()
    cell, config, traffic = harness.load_cell(manifest, args.workload,
                                              args.traffic_dir)
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    info = harness.device_info()
    drv = importlib.import_module(f"benchmarks.drivers.{traffic['driver']}")
    read = {"train": read_train, "serve": read_serve}[traffic["driver"]]
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        ctx = harness.Context(cell, config, traffic, seed, args.seconds,
                              False, None, t)
        row = read(drv, ctx, harness.HostSpans())
        row.update(seed=seed, device=info, workload=cell["name"],
                   seconds=time.perf_counter() - t)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
