"""Device time of the held experts' part of one decode step, all expert
layers: the `moe_experts` scope's operations in `jit_decode` (sort, gather,
activation, combine) plus the grouped products' kernels, which the compiler
names itself."""
from benchmarks import axk1_read as r
from benchmarks.kernels import moe_experts as k


def read(run, trace):
    return r.scope_ms_per_step(run, trace, k.SCOPE, k.PATTERN)
