"""Seconds of the set-up's programs in tracing and in lowering to MLIR (where
Pallas kernels become Mosaic), in the call and in its introspection replay."""
from benchmarks import setup_read


def read(run, trace):
    return setup_read.total(
        lambda e: sum(e["stages"][k] + setup_read.replay(e, k)
                      for k in ("trace_s", "lower_s")))
