"""Share of its roofline that attention over the paged K/V cache reaches
in a decode step: the live keys and values read once at the cache's width
against every query head's operations over them
(benchmarks/kernels/kv_attention.py), over the `paged_attention` scope's
device time per step."""
from benchmarks import axk1_read as r
from benchmarks import lfm2_read
from benchmarks.kernels import kv_attention as k


def read(run, trace):
    cfg = lfm2_read.config_of(run)
    took = r.scope_ms_per_step(run, trace, k.SCOPE) if cfg else None
    if took is None:
        return None
    sh = k.shapes(cfg, r.BYTES[run["engine"]["cache_dtype"]],
                  run["mean_live_tokens"])
    return 100.0 * r.least_ms(k.bytes(sh), k.ops(sh), run["peak"]) / took
