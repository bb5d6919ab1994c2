"""What the A.X-K1 readers under layer_metrics/ share: the decode steps of
the window, the routing counters per step, and a scope's device seconds
per step in `jit_decode`. Each returns None where the run or the trace
holds nothing to read (a run of another driver, a program without the
scopes)."""
from benchmarks import named

BYTES = {"float32": 4, "bfloat16": 2}


def decode_steps(run):
    """Decode steps the engine counted in the window."""
    steps = run["counters"]["decode_dispatches"] * run["steps_per_dispatch"]
    return steps or None


def routing_per_step(run):
    """{counter: per decode step, summed over the expert layers}."""
    counted = (run.get("routing") or {}).get("decode")
    steps = decode_steps(run)
    if not counted or not steps:
        return None
    return {k: v / steps for k, v in counted.items()}


def traced_steps(run, trace):
    """Decode steps inside the traced window: executions of `jit_decode`
    times the steps of a dispatch."""
    mod = named.module(trace, "decode")
    if mod is None or not mod[1]:
        return None
    return mod[0], mod[1] * run["steps_per_dispatch"]


def scope_ms_per_step(run, trace, scope, *kernels):
    """Device milliseconds per decode step of the operations `jit_decode`'s
    own map puts under `scope`, plus those of the kernels named `kernels`
    (which the compiler builds outside every scope)."""
    found = named.scope_seconds(trace, "decode")
    steps = traced_steps(run, trace)
    if found is None or steps is None:
        return None
    seconds = named.seconds_under(found[0], lambda c: c == scope)
    if not seconds:
        return None
    if kernels:
        seconds += named.kernel_seconds(trace, *kernels) or 0.0
    return seconds / steps[1] * 1e3


def least_ms(nbytes, nops, peak):
    return max(nbytes / peak["hbm_bytes_per_s"],
               nops / peak["bf16_flops_per_s"]) * 1e3
