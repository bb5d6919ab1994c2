"""The serve driver for Olmo-Hybrid-7B (`configs/olmo-hybrid-7b-l16.json`):
the closed loop, the window, its settling, the sample for the check and the
run's data are `drivers/serve.py`'s own (a dense model: the run carries no
routing counters); this file defines what names the model: how it is built
in bfloat16 and given the seed's weights a leaf at a time, and the
comparison with `reference/olmo_hybrid.py`."""
from __future__ import annotations

import dataclasses
import functools
import types
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import traffic_gen, weights_leaf
from benchmarks.drivers.serve import (fill, release,  # noqa: F401
                                      run_data, sample_for_check, settle,
                                      window)
from benchmarks.reference import olmo_hybrid as reference
from benchmarks.weights import seed_key
from paddle_tpu.nlp.olmo_hybrid import OlmoHybridConfig, OlmoHybridForCausalLM


def model_config(cfg):
    """The program's configuration from the file: the keys it shares with
    `OlmoHybridConfig`, in the served weights' dtype."""
    names = {f.name for f in dataclasses.fields(OlmoHybridConfig)}
    kw = {k: v for k, v in cfg.items() if k in names}
    return OlmoHybridConfig(**kw, dtype=cfg["serve"]["weight_dtype"])


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype"))
def _decay_leaf(key, shape, kind, dtype):
    u = jax.random.uniform(key, shape, jnp.float32)
    if kind == "A_log":                 # A uniform in [1, 16]
        x = jnp.log(1.0 + 15.0 * u)
    else:                               # dt log-uniform in [1e-3, 0.1]
        lo, hi = np.log(1e-3), np.log(0.1)
        dt = jnp.exp(lo + (hi - lo) * u)
        x = dt + jnp.log(-jnp.expm1(-dt))
    return x.astype(jnp.bfloat16).astype(dtype)


def make_leaf(name, shape, seed, std, dtype):
    """`weights_leaf.make_leaf`, but A_log and dt_bias as
    flash-linear-attention initialises them (the configuration's
    `assumed`), from the same per-leaf stream and rounded to bfloat16 once
    alike."""
    kind = name.rsplit(".", 1)[-1]
    if kind not in ("A_log", "dt_bias"):
        return weights_leaf.make_leaf(name, shape, seed, std, dtype)
    key = jax.random.fold_in(seed_key(seed),
                             zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return _decay_leaf(key, tuple(int(d) for d in shape), kind,
                       jnp.dtype(dtype))


def adopt_seed_weights(model, ctx):
    """Give the model the seed's weights, a leaf at a time (the old leaf is
    let go as the new one lands); returns the leaves' shapes, which have to
    be the reference's."""
    shapes = {n: tuple(p.shape) for n, p in model.named_parameters()}
    want = reference.leaf_shapes(ctx.config)
    if shapes != want:
        odd = sorted(n for n in set(shapes) | set(want)
                     if shapes.get(n) != want.get(n))
        raise SystemExit("the program's leaves differ from the reference's: "
                         f"{odd[:6]}")
    std, dtype = ctx.config["initializer_range"], \
        ctx.config["serve"]["weight_dtype"]
    # every leaf of the model's own initialisation goes first, so that the
    # new ones land side by side and not in the holes between old ones
    model.load_raw_state({n: jnp.zeros((), dtype) for n in shapes})
    for name, shape in shapes.items():
        model.load_raw_state({name: make_leaf(name, shape, ctx.seed, std,
                                              dtype)})
    return shapes


def setup(ctx, host):
    from paddle_tpu.nlp.serving import ServingEngine
    cfg, tr = ctx.config, ctx.traffic
    st = types.SimpleNamespace()
    model = OlmoHybridForCausalLM(model_config(cfg))
    model.eval()
    ctx.log("model built")
    st.shapes = adopt_seed_weights(model, ctx)
    ctx.log("weights made and loaded")
    st.eng = ServingEngine(model, **cfg["serve"]["engine"])
    del model
    pool = traffic_gen.length_pool(tr)
    st.eng.warmup(buckets=sorted({p for p, _ in pool}))
    st.compile_counts = dict(st.eng.compile_counts())
    ctx.log(f"engine warmed: {sorted(st.compile_counts)}")
    stats = jax.local_devices()[0].memory_stats() or {}
    if stats:
        ctx.log("device memory: " + ", ".join(
            f"{k} {v / 1e9:.2f} GB" for k, v in sorted(stats.items())
            if k.endswith("bytes") or k.startswith("bytes")))
    fill(st, ctx, host)
    ctx.log("slots full, first requests answered")
    return st


def served_numbers(st, ctx, sample, control=None):
    """Over the sample's served tokens: the widest gap by which one's
    logit lies below the float32 reference's best, and the share of them
    that are not the reference's best (at 100,352 rows of random weights
    the best logits lie close, and bfloat16 operands reorder some)."""
    std = ctx.config["initializer_range"]

    def leaves(names):
        return {n: make_leaf(n, st.shapes[n], ctx.seed, std, "float32")
                for n in names}

    gaps = reference.served_gaps(
        leaves, ctx.config, [(r.prompt.tolist(), r.tokens) for r in sample],
        control)
    if not gaps:
        return 0.0, 0.0
    gaps = np.concatenate([np.asarray(g) for g in gaps])
    return float(gaps.max()), float(np.mean(gaps > 0))


def served_gap(st, ctx, sample, control=None):
    return served_numbers(st, ctx, sample, control)[0]


def check(st, ctx, result):
    loop = st.loop
    sample = sample_for_check(loop, ctx.seed, ctx.traffic["check_requests"])
    release(st)
    wrong = sum(1 for r in loop.requests.values() if not r.ok)
    gap, off_best = served_numbers(st, ctx, sample)
    numbers = [("served_logit_gap_max", gap),
               ("served_off_best_share", off_best),
               ("requests_not_answered_in_full", float(wrong))]
    detail = {"checked_requests": len(sample),
              "checked_tokens": sum(len(r.tokens) for r in sample),
              "longest_checked": max((len(r.prompt) + len(r.tokens)
                                      for r in sample), default=0)}
    return numbers, detail
