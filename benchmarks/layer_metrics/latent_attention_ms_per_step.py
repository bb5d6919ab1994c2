"""Device time of latent attention over the paged cache in one decode
step, all layers: the `latent_attention` scope's operations in
`jit_decode` (the page gather, scores, softmax, the sum over c_kv)."""
from benchmarks import axk1_read as r
from benchmarks.kernels import latent_attention as k


def read(run, trace):
    return r.scope_ms_per_step(run, trace, k.SCOPE)
