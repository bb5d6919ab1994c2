"""The one general traffic generator. A traffic mix is a data file under
`benchmarks/traffic/`; this module turns its parameters and the seed into
batches or requests. Every seed gets the same set of sizes in another
order, so a seed changes the inputs and not the amount of work."""
from __future__ import annotations

import statistics

import numpy as np

from benchmarks.harness import np_rng


def train_batches(traffic, vocab, seed):
    """Endless (ids, labels) int32 [batch, seq]: each row is seq+1 random
    tokens, the labels the row shifted by one. All rows differ."""
    rng = np_rng(seed, 1)
    b, s = traffic["batch"], traffic["seq"]
    while True:
        t = rng.integers(0, vocab, (b, s + 1), dtype=np.int32)
        yield t[:, :-1], t[:, 1:]


def _lognormal_quantiles(spec, n):
    """n lengths at the mid-quantiles of a clipped log-normal."""
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = spec["median"] * np.exp(spec["sigma"] * z)
        out.append(int(min(max(round(float(x)), spec["min"]), spec["max"])))
    return out


def length_pool(traffic):
    """The fixed set of (prompt length, output length) pairs of a serving
    mix: stratified draws of both log-normals, paired by a permutation
    that the traffic file's own `pool_seed` fixes."""
    n = traffic["pool"]
    prompts = _lognormal_quantiles(traffic["prompt_tokens"], n)
    outs = _lognormal_quantiles(traffic["output_tokens"], n)
    order = np.random.default_rng(traffic["pool_seed"]).permutation(n)
    cap = traffic["max_total_tokens"]
    return [(p, min(outs[j], cap - p)) for p, j in zip(prompts, order)]


def serve_requests(traffic, vocab, seed):
    """Endless (prompt ids, output length): the pool in an order drawn
    from the seed, again and again, with fresh random token ids."""
    rng = np_rng(seed, 2)
    pool = length_pool(traffic)
    while True:
        for i in rng.permutation(len(pool)):
            p, o = pool[i]
            yield rng.integers(0, vocab, (p,), dtype=np.int32), o
