"""Share of its roofline that latent attention over the paged cache
reaches in a decode step: the live rows read once at the cache's width
against every head's operations over them
(benchmarks/kernels/latent_attention.py), over the `latent_attention`
scope's device time per step."""
from benchmarks import axk1_read as r
from benchmarks.kernels import latent_attention as k


def read(run, trace):
    took = r.scope_ms_per_step(run, trace, k.SCOPE)
    if took is None or "routing" not in run:
        return None
    sh = k.shapes(run["config"], r.BYTES[run["engine"]["cache_dtype"]],
                  run["mean_live_tokens"])
    return 100.0 * r.least_ms(k.bytes(sh), k.ops(sh), run["peak"]) / took
