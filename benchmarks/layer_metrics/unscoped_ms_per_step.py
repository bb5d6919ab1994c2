"""Device time per train step of the operations to which the program's
scope map gives no scope: what the naming itself does not reach (in ms, not
a share: the manifest keeps `%` for shares of a roofline or a peak)."""
from benchmarks import named


def read(run, trace):
    found = named.scope_seconds(trace, "train_step")
    if found is None:
        return None
    return named.per_step_ms(found[1], trace, "train_step")
