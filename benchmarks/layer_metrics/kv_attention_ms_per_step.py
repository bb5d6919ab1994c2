"""Device time of grouped-query attention over the paged K/V cache in one
decode step, all attention layers: the `paged_attention` scope's
operations in `jit_decode` (the `flash_decode` kernel and what feeds it)."""
from benchmarks import axk1_read as r
from benchmarks import lfm2_read
from benchmarks.kernels import kv_attention as k


def read(run, trace):
    if lfm2_read.config_of(run) is None:
        return None
    return r.scope_ms_per_step(run, trace, k.SCOPE)
