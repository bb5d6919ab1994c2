"""The plain reference for both GPT configurations: a float32 `jax.numpy`
pre-LayerNorm decoder (GPT-2/GPT-3 as PaddleNLP's GPTModel has it: learned
positions, erf GELU, biases everywhere, tied output head), its loss, and
AdamW. No kernel, no cache, no batching, nothing imported from the program.
Every matrix product is taken at `Precision.HIGHEST`, since a TPU otherwise
multiplies float32 in bfloat16.

`prec` lowers the precision of every product's operands and is what the
controls use: "bfloat16", or "float8" / "int8" (e4m3 or symmetric integers,
one scale per row along the contracted axis, straight-through gradient), the
step below bfloat16 that would tempt a later PR.

Leaves are named as the program names them (`gpt.h.<i>.attn.q_proj.weight`,
weights stored [in, out]); the weights themselves come from
`benchmarks/weights.py`, never from the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
LAYER_LEAVES = ("ln_1.weight", "ln_1.bias", "attn.q_proj.weight",
                "attn.q_proj.bias", "attn.k_proj.weight", "attn.k_proj.bias",
                "attn.v_proj.weight", "attn.v_proj.bias",
                "attn.out_proj.weight", "attn.out_proj.bias", "ln_2.weight",
                "ln_2.bias", "mlp.fc1.weight", "mlp.fc1.bias",
                "mlp.fc2.weight", "mlp.fc2.bias")
SIZES = ("vocab_size", "hidden_size", "num_hidden_layers",
         "num_attention_heads", "intermediate_size",
         "max_position_embeddings", "layer_norm_epsilon")
WORD = "gpt.embeddings.word_embeddings.weight"
POS = "gpt.embeddings.position_embeddings.weight"


def sizes(cfg):
    """The keys of a configuration file that the reference reads."""
    return {k: cfg[k] for k in SIZES}


def leaf_shapes(cfg):
    """{leaf name: shape} of the whole model, from the sizes alone."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    per = {"ln_1.weight": (h,), "ln_1.bias": (h,), "ln_2.weight": (h,),
           "ln_2.bias": (h,), "mlp.fc1.weight": (h, f), "mlp.fc1.bias": (f,),
           "mlp.fc2.weight": (f, h), "mlp.fc2.bias": (h,)}
    for p in ("q_proj", "k_proj", "v_proj", "out_proj"):
        per[f"attn.{p}.weight"] = (h, h)
        per[f"attn.{p}.bias"] = (h,)
    out = {WORD: (cfg["vocab_size"], h),
           POS: (cfg["max_position_embeddings"], h),
           "gpt.ln_f.weight": (h,), "gpt.ln_f.bias": (h,)}
    for i in range(cfg["num_hidden_layers"]):
        for n, s in per.items():
            out[f"gpt.h.{i}.{n}"] = s
    return out


def layer_leaves(w, i):
    return {n: w[f"gpt.h.{i}.{n}"] for n in LAYER_LEAVES}


def _fq(x, prec, axis):
    """Operand rounded to `prec` along the contracted `axis`; the gradient
    passes straight through."""
    if prec == "float32":
        return x
    if prec == "bfloat16":
        q = x.astype(jnp.bfloat16).astype(F32)
    elif prec in ("int8", "float8"):
        top = 127.0 if prec == "int8" else 448.0
        s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
        s = jnp.where(s == 0, 1.0, s)
        q = jnp.round(x / s) if prec == "int8" else \
            (x / s).astype(jnp.float8_e4m3fn).astype(F32)
        q = q * s
    else:
        raise ValueError(f"unknown precision {prec!r}")
    return x + lax.stop_gradient(q - x)


def linear(x, wt, b, prec):
    y = jnp.einsum("...k,kn->...n", _fq(x, prec, -1), _fq(wt, prec, 0),
                   precision=HI)
    return y + b


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def attention(q, k, v, prec):
    """Causal softmax attention; q, k, v [b, s, H, d]."""
    s, d = q.shape[1], q.shape[-1]
    sc = jnp.einsum("bqhd,bkhd->bhqk", _fq(q, prec, -1), _fq(k, prec, -1),
                    precision=HI) / math.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", _fq(p, prec, -1), _fq(v, prec, 1),
                      precision=HI)


def block(x, lw, heads, eps, prec):
    b, s, h = x.shape
    y = layer_norm(x, lw["ln_1.weight"], lw["ln_1.bias"], eps)
    q, k, v = (linear(y, lw[f"attn.{p}.weight"], lw[f"attn.{p}.bias"],
                      prec).reshape(b, s, heads, h // heads)
               for p in ("q_proj", "k_proj", "v_proj"))
    a = attention(q, k, v, prec).reshape(b, s, h)
    x = x + linear(a, lw["attn.out_proj.weight"], lw["attn.out_proj.bias"],
                   prec)
    y = layer_norm(x, lw["ln_2.weight"], lw["ln_2.bias"], eps)
    y = jax.nn.gelu(linear(y, lw["mlp.fc1.weight"], lw["mlp.fc1.bias"],
                           prec), approximate=False)
    return x + linear(y, lw["mlp.fc2.weight"], lw["mlp.fc2.bias"], prec)


def embed(w, ids):
    s = ids.shape[1]
    return w[WORD][ids] + w[POS][jnp.arange(s, dtype=jnp.int32)][None]


def head(w, x, eps, prec):
    """Final LayerNorm and the tied output head: logits of rows x."""
    x = layer_norm(x, w["gpt.ln_f.weight"], w["gpt.ln_f.bias"], eps)
    return jnp.einsum("...k,vk->...v", _fq(x, prec, -1),
                      _fq(w[WORD], prec, -1), precision=HI)


def forward(w, ids, cfg, prec="float32", remat=False):
    """Logits [b, s, vocab] of token ids [b, s]. Layers run under one
    lax.scan over stacked leaves (one block to compile); `remat` keeps
    only each block's input for the backward pass."""
    heads, eps = cfg["num_attention_heads"], cfg["layer_norm_epsilon"]
    stacked = {n: jnp.stack([w[f"gpt.h.{i}.{n}"]
                             for i in range(cfg["num_hidden_layers"])])
               for n in LAYER_LEAVES}

    def body(x, lw):
        return block(x, lw, heads, eps, prec), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = lax.scan(body, embed(w, ids), stacked)
    return head(w, x, eps, prec)


def loss_fn(w, ids, labels, cfg, prec="float32", rows=None):
    """Mean token cross-entropy; `rows` (a slice) plants the fault of a
    mean over part of the batch."""
    if rows is not None:
        ids, labels = ids[rows], labels[rows]
    lg = forward(w, ids, cfg, prec, remat=True)
    picked = jnp.take_along_axis(lg, labels[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - picked)


@functools.partial(jax.jit, static_argnames=("cfg", "opt", "prec", "half"),
                   donate_argnums=(0, 1, 2))
def _adamw_step(w, m, v, ids, labels, t, cfg, opt, prec, half):
    cfg, opt = dict(cfg), dict(opt)
    rows = slice(0, ids.shape[0] // 2) if half else None
    loss, g = jax.value_and_grad(loss_fn)(w, ids, labels, cfg, prec, rows)
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
    lr, wd = opt["learning_rate"], opt["weight_decay"]
    t = t.astype(F32)
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    m = {k: b1 * m[k] + (1 - b1) * g[k] for k in w}
    v = {k: b2 * v[k] + (1 - b2) * jnp.square(g[k]) for k in w}
    w = {k: w[k] - lr * ((m[k] / bc1) / (jnp.sqrt(v[k] / bc2) + eps)
                         + wd * w[k]) for k in w}
    gnorm = {k: jnp.sqrt(jnp.sum(jnp.square(g[k]))) for k in g}
    return w, m, v, loss, gnorm


@jax.jit
def delta_norms(w, w0):
    return {k: jnp.sqrt(jnp.sum(jnp.square(w[k] - w0[k]))) for k in w}


def train_steps(make_w, batches, cfg, opt, prec="float32", fault=None):
    """AdamW steps over `batches` [(ids, labels)] from the weights that
    `make_w()` returns (called twice: the second copy is the starting
    point the change is measured from). Returns the losses, the per-leaf
    norms of the first gradient and of the parameters' change.

    fault: None, "half_batch" (loss over the first half of the rows) or
    "state_unchanged" (the step's result is thrown away)."""
    w = make_w()
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    cfg_t = tuple(sorted(sizes(cfg).items()))
    opt_t = tuple(sorted(opt.items()))
    losses, g1 = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        out = _adamw_step(w, m, v, jnp.asarray(ids), jnp.asarray(labels),
                          jnp.int32(t), cfg_t, opt_t, prec,
                          fault == "half_batch")
        losses.append(float(out[3]))
        if t == 1:
            g1 = {k: float(x) for k, x in out[4].items()}
        if fault == "state_unchanged":
            w, m, v = make_w(), *(jax.tree_util.tree_map(jnp.zeros_like, a)
                                  for a in out[1:3])
        else:
            w, m, v = out[:3]
    dp = {k: float(x) for k, x in delta_norms(w, make_w()).items()}
    return {"losses": losses, "grad_norms": g1, "delta_norms": dp}


# -- serving: the reference follows served tokens, it does not decode --------

@functools.partial(jax.jit, static_argnames=("heads", "eps", "prec"))
def _block_jit(x, lw, heads, eps, prec):
    return block(x, lw, heads, eps, prec)


@functools.partial(jax.jit, static_argnames=("eps", "prec"))
def _head_jit(w_head, rows, eps, prec):
    return head(w_head, rows, eps, prec)


def _bucket(n, lo=128):
    b = lo
    while b < n:
        b *= 2
    return b


def next_token_logits(w, cfg, ids, first, count, prec="float32"):
    """Logits [count, vocab] that predict positions first+1 .. first+count
    of the one sequence `ids`, layer by layer so that full-width float32
    fits beside nothing else. The sequence is padded to a power of two:
    under the causal mask the padding cannot reach the rows read."""
    import numpy as np
    heads, eps = cfg["num_attention_heads"], cfg["layer_norm_epsilon"]
    n = len(ids)
    padded = np.zeros((1, _bucket(n)), np.int32)
    padded[0, :n] = ids
    x = embed(w, jnp.asarray(padded))
    for i in range(cfg["num_hidden_layers"]):
        x = _block_jit(x, layer_leaves(w, i), heads, eps, prec)
    rows = x[0, first:first + count]
    w_head = {k: w[k] for k in ("gpt.ln_f.weight", "gpt.ln_f.bias", WORD)}
    return _head_jit(w_head, rows, eps, prec)


def served_gaps(w, cfg, prompt, tokens, control=None):
    """For each served token, how far its float32 logit lies below the
    reference's best at that position. With `control` (a precision) the
    tokens judged are those that the reference in that precision puts
    first, at the same positions of the same prompt and served tokens."""
    ids = list(prompt) + list(tokens[:-1])
    first, count = len(prompt) - 1, len(tokens)
    lg = next_token_logits(w, cfg, ids, first, count)
    judged = jnp.asarray(tokens, jnp.int32)
    if control is not None:
        judged = jnp.argmax(
            next_token_logits(w, cfg, ids, first, count, control), axis=-1)
    picked = jnp.take_along_axis(lg, judged[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1) - picked
