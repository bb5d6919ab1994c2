"""Drives `hapi.Engine.train_batch` for a window: one optimizer step per
fresh batch, the loss read back every `sync_every` steps as `Model.fit` logs
it. Every number (batch, sequence, steps checked) comes from the traffic and
configuration files.

Set-up builds ONE engine, loads the seed's weights, drives it through its
first `check_steps` steps by the window's own call and feed, keeps what the
comparison needs (each loss, the per-leaf norms of the first gradient as
AdamW got it, the per-leaf norms of the parameters' change), and hands the
same engine to the window."""
from __future__ import annotations

import gc
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import traffic_gen, weights
from benchmarks.reference import gpt as reference


@jax.jit
def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def _delta_norms(p, p0):
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        p[k].astype(jnp.float32) - p0[k]))) for k in p0}


def _floats(tree):
    return {k: float(v) for k, v in tree.items()}


def check_sizes(program_cfg, cfg):
    """The configuration file holds the sizes as they are run."""
    for k in reference.SIZES + ("hidden_dropout_prob",
                                "attention_probs_dropout_prob"):
        got = getattr(program_cfg, k)
        if got != cfg[k]:
            raise SystemExit(f"configuration file says {k}={cfg[k]}, the "
                             f"program runs {got}")


def seed_weights(shapes, ctx):
    return weights.make_weights(shapes, ctx.seed,
                                ctx.config["initializer_range"])


def adopt_seed_weights(network, ctx):
    """Give the program's model the seed's weights; returns the leaves'
    shapes, which have to be the reference's."""
    params, _ = network.raw_state()
    shapes = {n: tuple(v.shape) for n, v in params.items()}
    if shapes != reference.leaf_shapes(ctx.config):
        raise SystemExit("the program's leaves differ from the reference's")
    del params
    network.load_raw_state(seed_weights(shapes, ctx))
    return shapes


def setup(ctx, host):
    import bench
    cfg, tr = ctx.config, ctx.traffic
    build = cfg["train"]
    st = types.SimpleNamespace()
    st.eng = bench.build_engine(cfg["preset"], tr["batch"], tr["seq"],
                                amp=build["amp"],
                                use_flash=build["use_flash"],
                                recompute=build["recompute"])
    check_sizes(st.eng.network.config, cfg)
    ctx.log("engine built")
    st.shapes = adopt_seed_weights(st.eng.network, ctx)
    st.eng.sync_from_layer()
    ctx.log("weights made and loaded")
    st.feed = traffic_gen.train_batches(tr, cfg["vocab_size"], ctx.seed)
    st.steps = 0

    st.first = {"batches": [], "losses": []}
    beta1 = build["optimizer"]["beta1"]
    for i in range(tr["check_steps"]):
        ids, labels = next(st.feed)
        st.first["batches"].append((ids.copy(), labels.copy()))
        st.first["losses"].append(float(_step(st, ids, labels)))
        if i == 0:
            m = st.eng.opt_state_dict()["state"]["m"]
            st.first["grad_norms"] = {
                k: v / (1.0 - beta1) for k, v in _floats(_leaf_norms(m)).items()}
            del m
    ctx.log("first steps done")
    params, _ = st.eng.network.raw_state()
    st.first["delta_norms"] = _floats(_delta_norms(
        params, seed_weights(st.shapes, ctx)))
    del params
    # the window's own rhythm once, so that the read-back path is warm too
    for _ in range(tr["sync_every"]):
        loss = _step(st, *next(st.feed))
    float(loss)
    return st


def _step(st, ids, labels):
    loss, _ = st.eng.train_batch([ids], [labels])
    st.steps += 1
    return loss


def window(st, ctx, host):
    tr = ctx.traffic
    n, loss = 0, None
    host.mark()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ctx.window_seconds:
        with host.span("dispatch"):
            loss = _step(st, *next(st.feed))
        n += 1
        if n % tr["sync_every"] == 0:
            with host.span("log_sync"):
                float(loss)
    with host.span("final_sync"):
        jax.block_until_ready(loss)
    t1 = time.perf_counter()
    host.mark()
    return {"t0": t0, "t1": t1, "steps": n}


def settle(st, ctx, host, raw):
    n, tr = raw["steps"], ctx.traffic
    tokens = n * tr["batch"] * tr["seq"]
    return {"t0": raw["t0"], "t1": raw["t1"], "attempted": n, "failed": 0,
            "steps": n, "tokens": tokens,
            "end_to_end": {"train_tokens_per_s":
                           tokens / (raw["t1"] - raw["t0"])}}


def release(st):
    """Free the program's state on the device before the reference runs."""
    st.eng = None
    gc.collect()


def compare(first, ref):
    """The numbers of `correct`: [(name, value)] from the program's and the
    reference's first steps. Gaps of norms are taken by the worst leaf,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. Leaves whose reference gradient is under a
    thousandth of the median leaf's move under Adam by round-off alone and
    are left out of the change."""
    out, loss_gaps = [], [abs(a - b) / abs(b) for a, b in
                          zip(first["losses"], ref["losses"])]
    # the first step's loss has no upper reading (PERF.md section 2): it is
    # printed, not judged
    for i, gap in enumerate(loss_gaps[1:], 2):
        out.append((f"loss_step{i}_rel", gap))
    g_ref, d_ref = ref["grad_norms"], ref["delta_norms"]
    g_med = float(np.median(list(g_ref.values())))
    d_med = float(np.median(list(d_ref.values())))
    worst_g = max(g_ref, key=lambda k: abs(first["grad_norms"][k] - g_ref[k])
                  / max(g_ref[k], g_med))
    moved = [k for k in d_ref if g_ref[k] >= 1e-3 * g_med]
    worst_d = max(moved, key=lambda k: abs(first["delta_norms"][k] - d_ref[k])
                  / max(d_ref[k], d_med))
    out.append(("grad1_worst_leaf_rel",
                abs(first["grad_norms"][worst_g] - g_ref[worst_g])
                / max(g_ref[worst_g], g_med)))
    out.append(("delta3_worst_leaf_rel",
                abs(first["delta_norms"][worst_d] - d_ref[worst_d])
                / max(d_ref[worst_d], d_med)))
    detail = {"loss_step1_rel": loss_gaps[0],
              "grad1_worst_leaf": worst_g, "delta3_worst_leaf": worst_d,
              "leaves_left_out_of_delta": len(d_ref) - len(moved)}
    return out, detail


def reference_steps(ctx, st, prec="float32", fault=None):
    cfg = ctx.config
    return reference.train_steps(
        lambda: seed_weights(st.shapes, ctx),
        st.first["batches"], cfg, cfg["train"]["optimizer"], prec, fault)


def check(st, ctx, result):
    """After the window: the float32 reference follows the same first
    steps from the same seed's weights."""
    release(st)
    numbers, detail = compare(st.first, reference_steps(ctx, st))
    return numbers, detail


def run_data(st, ctx, result):
    """What the per-layer readers get beside the trace."""
    return {"kind": "train", "steps": result["steps"],
            "tokens": result["tokens"], "batch": ctx.traffic["batch"],
            "seq": ctx.traffic["seq"]}
