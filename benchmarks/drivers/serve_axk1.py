"""The serve driver for A.X-K1 (`configs/axk1-ep16.json`): the closed loop,
the window, its reduction and the sample for the check are
`drivers/serve.py`'s own; this file defines what names the model: how it is
built in bfloat16 and given the seed's weights a leaf at a time, the
comparison with `reference/axk1.py`, and the routing counters of the window."""
from __future__ import annotations

import dataclasses
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import traffic_gen, weights_leaf
from benchmarks.drivers import serve
from benchmarks.drivers.serve import (ClosedLoop, fill, release,  # noqa: F401
                                      sample_for_check)
from benchmarks.reference import axk1 as reference
from paddle_tpu.nlp.axk1 import AXK1Config, AXK1ForCausalLM
from paddle_tpu.nlp.paged_cache import AUX_COUNTERS


def model_config(cfg):
    """The program's configuration from the file: the published router
    width and vocabulary with the deployment's share of them."""
    pub, dep = cfg["published"], cfg["deployment"]
    names = {f.name for f in dataclasses.fields(AXK1Config)}
    kw = {k: v for k, v in cfg.items() if k in names}
    kw.update(n_routed_experts=pub["n_routed_experts"],
              vocab_size=pub["vocab_size"], layer_chips=dep["layer_chips"],
              chip_rank=dep["chip_rank"], vocab_shards=dep["vocab_shards"],
              dtype=cfg["serve"]["weight_dtype"])
    return AXK1Config(**kw)


def adopt_seed_weights(model, ctx):
    """Give the model the seed's weights, a leaf at a time (the old leaf is
    let go as the new one lands); returns the leaves' shapes, which have to
    be the reference's."""
    shapes = {n: tuple(p.shape) for n, p in model.named_parameters()}
    if shapes != reference.leaf_shapes(ctx.config):
        want = reference.leaf_shapes(ctx.config)
        odd = sorted(n for n in set(shapes) | set(want)
                     if shapes.get(n) != want.get(n))
        raise SystemExit("the program's leaves differ from the reference's: "
                         f"{odd[:6]}")
    std, dtype = ctx.config["initializer_range"], \
        ctx.config["serve"]["weight_dtype"]
    # every leaf of the model's own initialisation goes first: a new leaf
    # made beside the old one it replaces lands wherever there is room,
    # and 11 GB of weights end up scattered over the device with the free
    # memory in holes between them
    model.load_raw_state({n: jnp.zeros((), dtype) for n in shapes})
    for name, shape in shapes.items():
        model.load_raw_state({name: weights_leaf.make_leaf(
            name, shape, ctx.seed, std, dtype)})
    return shapes


def setup(ctx, host):
    from paddle_tpu.nlp.serving import ServingEngine
    cfg, tr = ctx.config, ctx.traffic
    st = types.SimpleNamespace()
    model = AXK1ForCausalLM(model_config(cfg))
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


def _routing(eng):
    return {k: np.array(v) for k, v in eng.aux_counts.items()}


def window(st, ctx, host):
    before = _routing(st.eng)
    raw = serve.window(st, ctx, host)
    after = _routing(st.eng)
    raw["routing"] = {
        prog: dict(zip(AUX_COUNTERS,
                       (int(x) for x in after[prog] - before.get(prog, 0))))
        for prog in after}
    return raw


def settle(st, ctx, host, raw):
    """`serve.settle`, after the requests in flight have had the traffic
    file's `settle_seconds` to finish: an output of 2048 tokens sent as the
    window closes takes most of the minute `serve.settle` itself allows."""
    loop = st.loop
    loop.submitting = False
    deadline = time.perf_counter() + ctx.traffic.get("settle_seconds", 0)
    while loop.in_flight() and time.perf_counter() < deadline:
        loop.round()
    result = serve.settle(st, ctx, host, raw)
    result["routing"] = raw["routing"]
    ctx.log(window_note(loop, host, raw["t0"], raw["t1"]))
    return result


def window_note(loop, host, t0, t1):
    """One line on the window's steadiness: a stalled host shows as a round
    far above the median, or as time in `step` outside every program."""
    steps = [(s, e) for n, s, e in host.spans if n == "step" and t0 <= s < t1]
    if not steps:
        return "window: no rounds"
    walls = sorted(e - s for s, e in steps)
    longest = max(steps, key=lambda se: se[1] - se[0])[0] - t0
    inside = sum(e - s for _, s, e in loop.program_spans if t0 <= s < t1)
    worst = {}
    for n, s, e in loop.program_spans:
        if t0 <= s < t1:
            worst[n] = max(worst.get(n, 0.0), e - s)
    return (f"window: {len(steps)} rounds, step median "
            f"{walls[len(walls) // 2] * 1e3:.1f} ms, max {walls[-1] * 1e3:.1f}"
            f" ms at {longest:.1f} s; outside programs "
            f"{sum(walls) - inside:.3f} s; longest "
            + ", ".join(f"{n} {v * 1e3:.1f}" for n, v in sorted(worst.items())))


def served_numbers(st, ctx, sample, control=None):
    """Over the sample's served tokens: the widest gap by which one's
    logit lies below the float32 reference's best, and the share of them
    that are not the reference's best. An expert layer is discontinuous:
    where a token's last router pick changes, one held expert's output
    comes or goes, so the widest gap is set by a few such tokens in
    thousands, in the program and the control alike; the share counts
    how many there are."""
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


def run_data(st, ctx, result):
    """`serve.run_data` and, per kind of program, what the expert layers
    counted in the window (`ServingEngine.aux_counts`)."""
    run = serve.run_data(st, ctx, result)
    run.update(routing=result["routing"], expert_layers=st.expert_layers,
               finished_in_window=result["finished_in_window"])
    return run
