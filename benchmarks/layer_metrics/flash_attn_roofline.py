"""Share of its roofline that causal flash attention reaches in the train
step: the least time the chip could take for the forward and backward
attention of the steps traced (the larger of operations over the bf16 peak
and bytes over the HBM bandwidth; at these shapes operations bound it) over
the summed device time of the kernel's events."""
from benchmarks import trace_reduce
from benchmarks.kernels import flash_attention as k


def read(run, trace):
    if trace is None or not trace["modules"]:
        return None
    seconds, _ = trace_reduce.matching_seconds(trace["ops"], k.PATTERN)
    if not seconds:
        return None
    steps = max(trace["modules"].values(), key=lambda v: v[0])[1]
    sh = k.shapes(run["config"], run["batch"], run["seq"])
    least = max(k.ops(sh) / run["peak"]["bf16_flops_per_s"],
                k.bytes(sh) / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * steps * least / seconds
