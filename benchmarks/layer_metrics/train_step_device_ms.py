"""Device time of the train step's program per execution, from the trace:
the window runs one program, so it is the module with the most device time."""


def read(run, trace):
    if trace is None or not trace["modules"]:
        return None
    seconds, count = max(trace["modules"].values(), key=lambda v: v[0])
    return seconds / count * 1e3 if count else None
