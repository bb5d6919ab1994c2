"""Device time of the Gated DeltaNet layers' own part of one decode step,
all of them: the `gated_delta` scope's operations in `jit_decode` (the
convolution's step, the l2 norms, the decay, the delta update, the readout
and the gated norm); the layers' matrices are not under it."""
from benchmarks import axk1_read as r
from benchmarks import olmo_hybrid_read
from benchmarks.kernels import gated_delta as k


def read(run, trace):
    if olmo_hybrid_read.config_of(run) is None:
        return None
    return r.scope_ms_per_step(run, trace, k.SCOPE)
