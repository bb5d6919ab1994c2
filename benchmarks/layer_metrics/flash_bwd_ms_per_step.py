"""Device time per train step of flash attention's two backward kernels:
the operations whose name holds `flash_bwd_dq` or `flash_bwd_dkv` over the
executions of `jit_train_step` in the window."""
from benchmarks import named


def read(run, trace):
    return named.per_step_ms(
        named.kernel_seconds(trace, "flash_bwd_dq", "flash_bwd_dkv"),
        trace, "train_step")
