"""The serve driver for LFM2-8B-A1B (`configs/lfm2-8b-a1b-l16.json`): the
closed loop, the window, its reduction and the sample for the check are
`drivers/serve.py`'s own, the window's routing counters, the settling and
the run's data `drivers/serve_axk1.py`'s (nothing in them names a model);
this file defines what names the model: how it is built in bfloat16 and
given the seed's weights a leaf at a time, and the comparison with
`reference/lfm2.py`."""
from __future__ import annotations

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import traffic_gen, weights_leaf
from benchmarks.drivers.serve import fill, release, sample_for_check
from benchmarks.drivers.serve_axk1 import (run_data, settle,  # noqa: F401
                                           window)
from benchmarks.reference import lfm2 as reference
from paddle_tpu.nlp.lfm2 import LFM2Config, LFM2ForCausalLM


def model_config(cfg):
    """The program's configuration from the file: the keys it shares with
    `LFM2Config`, in the served weights' dtype."""
    names = {f.name for f in dataclasses.fields(LFM2Config)}
    kw = {k: v for k, v in cfg.items() if k in names}
    return LFM2Config(**kw, dtype=cfg["serve"]["weight_dtype"])


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
        model.load_raw_state({name: weights_leaf.make_leaf(
            name, shape, ctx.seed, std, dtype)})
    return shapes


def setup(ctx, host):
    from paddle_tpu.nlp.serving import ServingEngine
    cfg, tr = ctx.config, ctx.traffic
    st = types.SimpleNamespace()
    model = LFM2ForCausalLM(model_config(cfg))
    model.eval()
    ctx.log("model built")
    st.shapes = adopt_seed_weights(model, ctx)
    ctx.log("weights made and loaded")
    st.eng = ServingEngine(model, **cfg["serve"]["engine"])
    st.expert_layers = sum(model.config.is_expert_layer(i) for i in
                           range(model.config.num_hidden_layers))
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
    that are not the reference's best (an expert layer is discontinuous:
    `serve_axk1.served_numbers` says why the cell judges both)."""
    std = ctx.config["initializer_range"]

    def leaves(names):
        return {n: weights_leaf.make_leaf(n, st.shapes[n], ctx.seed, std,
                                          "float32") for n in names}

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
