"""Share of its roofline that one decode step of the live batch reaches:
the least time to read every weight once and the live keys and values
(HBM bandwidth bounds it: 16 rows per weight read) over the device time of
the decode scan's program per step. The scan is the program that ran about
as often as the engine counted decode dispatches; of several, the one with
the most device time."""
from benchmarks.kernels import decode_step as k

BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def decode_module(run, trace):
    want = run["counters"]["decode_dispatches"]
    near = [v for v in trace["modules"].values()
            if abs(v[1] - want) <= max(2, 0.02 * want)]
    return max(near, key=lambda v: v[0]) if near else None


def read(run, trace):
    if trace is None or not trace["modules"]:
        return None
    mod = decode_module(run, trace)
    if mod is None:
        return None
    per_step = mod[0] / (mod[1] * run["steps_per_dispatch"])
    sh = k.shapes(run["config"], BYTES[run["config"]["serve"]["weight_dtype"]],
                  BYTES[run["engine"]["cache_dtype"]],
                  run["mean_live_tokens"], run["mean_live_slots"])
    least = max(k.bytes(sh) / run["peak"]["hbm_bytes_per_s"],
                k.ops(sh) / run["peak"]["bf16_flops_per_s"])
    return 100.0 * least / per_step
