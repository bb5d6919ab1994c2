"""Wall time of one decode step as the engine counts it: `decode_seconds`
over `decode_dispatches` x `steps_per_dispatch`, over the window."""


def read(run, trace):
    c = run["counters"]
    steps = c["decode_dispatches"] * run["steps_per_dispatch"]
    return c["decode_seconds"] / steps * 1e3 if steps else None
