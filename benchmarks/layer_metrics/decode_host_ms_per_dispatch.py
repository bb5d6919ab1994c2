"""What one decode dispatch costs beyond its program: the engine's own
`decode_seconds / decode_dispatches` over the window (upload, dispatch, the
read-back of the tokens) minus the device time of `jit_decode` per
execution."""
from benchmarks import named


def read(run, trace):
    c = run["counters"]
    mod = named.module(trace, "decode")
    if mod is None or not mod[1] or not c["decode_dispatches"]:
        return None
    return (c["decode_seconds"] / c["decode_dispatches"]
            - mod[0] / mod[1]) * 1e3
