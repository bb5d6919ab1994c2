"""Device time per train step of the optimizer's update and the gradient
clipping before it: the operations that the program's scope map puts under
`optimizer` or `grad_clip`."""
from benchmarks import named


def read(run, trace):
    found = named.scope_seconds(trace, "train_step")
    if found is None:
        return None
    seconds = named.seconds_under(
        found[0], lambda c: c in ("optimizer", "grad_clip"))
    return named.per_step_ms(seconds, trace, "train_step")
