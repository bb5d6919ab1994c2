"""Device time per train step of flash attention's forward kernel: the
operations whose name holds `flash_fwd` (the `name=` of its `pallas_call`)
over the executions of `jit_train_step` in the window."""
from benchmarks import named


def read(run, trace):
    return named.per_step_ms(named.kernel_seconds(trace, "flash_fwd"),
                             trace, "train_step")
