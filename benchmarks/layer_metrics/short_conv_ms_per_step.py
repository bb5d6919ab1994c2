"""Device time of the short convolutions' own part of one decode step, all
convolution layers: the `short_conv` scope's operations in `jit_decode`
(the two gates, the three taps, the shift of the slots' state); the
layers' matrices are not under it."""
from benchmarks import axk1_read as r
from benchmarks import lfm2_read


def read(run, trace):
    if lfm2_read.config_of(run) is None:
        return None
    return r.scope_ms_per_step(run, trace, "short_conv")
