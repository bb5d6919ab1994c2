"""Pallas kernel call sites met while the set-up's programs were traced
(`kernel_places` of each staged record): one for each call that entered a
trace, so a kernel in a scan body counts once, and so does one in a nested
jit that jax traced once for several calls."""
from benchmarks import setup_read


def read(run, trace):
    return setup_read.total(lambda e: sum(e["kernel_places"].values()))
