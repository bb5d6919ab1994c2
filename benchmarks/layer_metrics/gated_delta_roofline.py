"""Share of its roofline that the Gated DeltaNet layers' own part of a
decode step reaches: the least time for each live slot's state read once
and written once at float32, its convolution rows, the per-token vectors
and the taps (benchmarks/kernels/gated_delta.py), over the `gated_delta`
scope's device time per step."""
from benchmarks import axk1_read as r
from benchmarks import olmo_hybrid_read
from benchmarks.kernels import gated_delta as k


def read(run, trace):
    cfg = olmo_hybrid_read.config_of(run)
    took = r.scope_ms_per_step(run, trace, k.SCOPE) if cfg else None
    if took is None:
        return None
    sh = k.shapes(cfg, r.BYTES[run["engine"]["cache_dtype"]],
                  run["mean_live_slots"])
    return 100.0 * r.least_ms(k.bytes(sh), k.ops(sh), run["peak"]) / took
