"""The plain reference for LFM2-8B-A1B as `configs/lfm2-8b-a1b-l16.json`
cuts it: a float32 `jax.numpy` forward pass of the whole sequence at once,
every product at `Precision.HIGHEST`. No cache, no state, no batching, no
sorting, no kernel, nothing imported from the program: the short
convolution is an explicit sum over three shifted copies of the sequence,
attention repeats each K/V head for its query heads and masks a full
[s, s] score matrix, and an expert layer loops over the experts with a
dense [tokens, experts] matrix of the router's weights.

Layer equations (the configuration's keys; the Hugging Face `lfm2_moe`
modelling code where the config is silent, as the file's `assumed` lists):

    h = x + Op_i(RMSNorm(x));  y = h + FFN_i(RMSNorm(h));  eps = norm_eps
    Op_i, layer_types[i] == "conv":  [B | C | z] = u W_in (thirds, in that
          order);  g = B * z;  c_t = sum_j k[j] * g_{t-(L-1)+j}, g zero before
          the sequence starts;  Op(u) = (C * c) W_out
    Op_i, "full_attention":  q, k, v = u W_q, u W_k, u W_v by heads of
          hidden / heads numbers;  q, k <- RMSNorm over each head (gains of
          one head's size);  RoPE, half-split: (x1, x2) -> (x1 cos - x2 sin,
          x2 cos + x1 sin), frequencies theta^(-2i/d), no scaling;
          p = causal softmax(q.k * d^-0.5), K/V head j serving query heads
          j*G .. j*G+G-1;  Op(u) = concat_h(p v) W_o
    FFN_i, i < num_dense_layers:  W2 (silu(W1 u) * W3 u)
    FFN_i, after:  s = sigmoid(u W_g) (float32, in the control too); the
          k highest of s + b;  w = s at those / (their sum + 1e-6) * scaling;
          sum_e w_e W2_e (silu(W1_e u) * W3_e u)
    logits = RMSNorm(y_last) E^T, E the embedding (tied)

Departures from the Hugging Face code, all in storage and none in the
mathematics: matrices are stored [in, out] (its Linear stores [out, in]); the
convolution's taps are [L, channels] (its Conv1d weight is [channels, 1, L]);
an expert layer's W1 and W3 are one stack [experts, hidden, 2 * width], W1
first, and W2 a stack [experts, width, hidden] (it keeps a module an expert).

`prec` rounds every matrix product's operands and both attention products'
(bfloat16, or float8 e4m3 with one scale per row of the contracted axis): the
control of `correct`. The convolution's three multiplications by a tap are
no matrix product and stay float32.

Leaves are named as the program names them; the arrays come from the
benchmark (`drivers/serve_lfm2.py`), never from the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
SIZES = ("vocab_size", "hidden_size", "intermediate_size",
         "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
         "num_key_value_heads", "num_experts", "num_experts_per_tok",
         "num_dense_layers", "conv_L_cache", "norm_eps", "norm_topk_prob",
         "routed_scaling_factor", "use_expert_bias", "rope_theta")
EMBED, FINAL_NORM = "model.embed_tokens", "model.embedding_norm.weight"
ROUTER_EPS = 1e-6
HEAD_CHUNK = 8      # query heads whose [s, s] scores are alive at once


def sizes(cfg):
    """The keys of a configuration file that the reference reads, flat
    and hashable."""
    out = {k: cfg[k] for k in SIZES}
    out["layer_types"] = tuple(cfg["layer_types"])
    return out


def is_expert_layer(sz, i):
    return i >= sz["num_dense_layers"]


def layer_shapes(sz, i):
    """{leaf name: shape} of layer i."""
    h, heads, kvh = (sz["hidden_size"], sz["num_attention_heads"],
                     sz["num_key_value_heads"])
    d = h // heads
    p = f"model.layers.{i}."
    out = {p + "operator_norm.weight": (h,), p + "ffn_norm.weight": (h,)}
    if sz["layer_types"][i] == "full_attention":
        out.update({p + "self_attn.q_proj": (h, heads * d),
                    p + "self_attn.k_proj": (h, kvh * d),
                    p + "self_attn.v_proj": (h, kvh * d),
                    p + "self_attn.out_proj": (h, h),
                    p + "self_attn.q_layernorm.weight": (d,),
                    p + "self_attn.k_layernorm.weight": (d,)})
    else:
        out.update({p + "conv.in_proj": (h, 3 * h),
                    p + "conv.conv": (sz["conv_L_cache"], h),
                    p + "conv.out_proj": (h, h)})
    if is_expert_layer(sz, i):
        e, m = sz["num_experts"], sz["moe_intermediate_size"]
        out[p + "feed_forward.gate"] = (h, e)
        if sz["use_expert_bias"]:
            out[p + "feed_forward.expert_bias"] = (e,)
        out[p + "feed_forward.experts.gate_up_proj"] = (e, h, 2 * m)
        out[p + "feed_forward.experts.down_proj"] = (e, m, h)
    else:
        f = sz["intermediate_size"]
        out.update({p + "feed_forward.w1": (h, f),
                    p + "feed_forward.w3": (h, f),
                    p + "feed_forward.w2": (f, h)})
    return out


def outer_shapes(sz):
    """The leaves outside the layers: the embedding (which is the head's
    matrix too) and the norm before the head."""
    h = sz["hidden_size"]
    return {EMBED: (sz["vocab_size"], h), FINAL_NORM: (h,)}


def leaf_shapes(cfg):
    """{leaf name: shape} of the whole model as the file cuts it."""
    sz = sizes(cfg)
    out = outer_shapes(sz)
    for i in range(sz["num_hidden_layers"]):
        out.update(layer_shapes(sz, i))
    return out


# -- pieces ------------------------------------------------------------------

def _fq(x, prec, axis):
    """Operand rounded to `prec` along the contracted `axis`."""
    if prec == "float32":
        return x
    if prec == "bfloat16":
        return x.astype(jnp.bfloat16).astype(F32)
    if prec == "float8":
        s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
        s = jnp.where(s == 0, 1.0, s)
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown precision {prec!r}")


def linear(x, w, prec):
    return jnp.einsum("...k,kn->...n", _fq(x, prec, -1), _fq(w, prec, 0),
                      precision=HI)


def rms_norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x [s, heads, d]: row t's pair (i, i + d/2) turned by t * theta^(-2i/d)."""
    s, d = x.shape[0], x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(x, lw, sz, prec):
    """x [s, h] one sequence -> [s, h]."""
    s, h = x.shape
    heads, kvh = sz["num_attention_heads"], sz["num_key_value_heads"]
    d, eps = h // heads, sz["norm_eps"]
    q = linear(x, lw["self_attn.q_proj"], prec).reshape(s, heads, d)
    k = linear(x, lw["self_attn.k_proj"], prec).reshape(s, kvh, d)
    v = linear(x, lw["self_attn.v_proj"], prec).reshape(s, kvh, d)
    q = rope(rms_norm(q, lw["self_attn.q_layernorm.weight"], eps),
             sz["rope_theta"])
    k = rope(rms_norm(k, lw["self_attn.k_layernorm.weight"], eps),
             sz["rope_theta"])
    k, v = (jnp.repeat(t, heads // kvh, axis=1) for t in (k, v))
    mask = jnp.tril(jnp.ones((s, s), bool))

    def some_heads(args):
        qh, kh, vh = args                           # [c, s, d]
        sc = jnp.einsum("hqd,hkd->hqk", _fq(qh, prec, -1), _fq(kh, prec, -1),
                        precision=HI) * d ** -0.5
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", _fq(p, prec, -1), _fq(vh, prec, 1),
                          precision=HI)

    chunk = np.gcd(heads, HEAD_CHUNK)

    def chunks(t):                                  # [s, H, d] -> [n, c, s, d]
        t = jnp.swapaxes(t, 0, 1)
        return t.reshape((heads // chunk, chunk) + t.shape[1:])

    o = lax.map(some_heads, (chunks(q), chunks(k), chunks(v)))
    o = jnp.swapaxes(o.reshape(heads, s, d), 0, 1).reshape(s, h)
    return linear(o, lw["self_attn.out_proj"], prec)


def short_conv(x, lw, sz, prec):
    """x [s, h] one sequence -> [s, h]: the gated short convolution as a
    sum over L shifted copies of the whole sequence."""
    s, h = x.shape
    taps = lw["conv.conv"]                          # [L, h]
    n = taps.shape[0]
    bcz = linear(x, lw["conv.in_proj"], prec)
    b, c, z = bcz[:, :h], bcz[:, h:2 * h], bcz[:, 2 * h:]
    g = b * z
    conv = jnp.zeros_like(g)
    for j in range(n):
        back = n - 1 - j                            # tokens ago
        shifted = jnp.concatenate(
            [jnp.zeros((back, h), F32), g[:s - back]], axis=0)
        conv = conv + taps[j][None, :] * shifted
    return linear(c * conv, lw["conv.out_proj"], prec)


def swiglu(u, w1, w3, w2, prec):
    return linear(jax.nn.silu(linear(u, w1, prec)) * linear(u, w3, prec),
                  w2, prec)


def route(u, w_router, bias, sz):
    """[s, experts] float32: each token's weight on every expert, zero off
    its k highest by score + bias; the weights come from the scores."""
    scores = jax.nn.sigmoid(jnp.einsum("sk,ke->se", u, w_router,
                                       precision=HI))
    chosen_by = scores if bias is None else scores + bias[None, :]
    k = sz["num_experts_per_tok"]
    kth = jnp.sort(chosen_by, axis=-1)[:, -k][:, None]
    picked = jnp.where(chosen_by >= kth, scores, 0.0)
    if sz["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + ROUTER_EPS)
    return picked * sz["routed_scaling_factor"]


def expert_ffn(u, lw, sz, prec):
    m = sz["moe_intermediate_size"]
    g = route(u, lw["feed_forward.gate"],
              lw.get("feed_forward.expert_bias"), sz)

    def one(acc, args):
        w_gu, w_dn, col = args
        y = swiglu(u, w_gu[:, :m], w_gu[:, m:], w_dn, prec)
        return acc + col[:, None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(u),
                      (lw["feed_forward.experts.gate_up_proj"],
                       lw["feed_forward.experts.down_proj"], g.T))
    return out


def layer_kind(sz, i):
    """(the operator's name, whether the FFN is the expert layer)."""
    return sz["layer_types"][i], is_expert_layer(sz, i)


def block(x, lw, sz, kind, prec):
    """A layer of `kind` over one sequence x [s, h]; lw's names are the
    layer's leaves without the `model.layers.<i>.` prefix."""
    eps = sz["norm_eps"]
    op = attention if kind[0] == "full_attention" else short_conv
    h = x + op(rms_norm(x, lw["operator_norm.weight"], eps), lw, sz, prec)
    u = rms_norm(h, lw["ffn_norm.weight"], eps)
    if kind[1]:
        return h + expert_ffn(u, lw, sz, prec)
    return h + swiglu(u, lw["feed_forward.w1"], lw["feed_forward.w3"],
                      lw["feed_forward.w2"], prec)


def head(x, g, emb, sz, prec):
    """Logits of rows x [n, h] over the tied embedding emb [vocab, h]."""
    return jnp.einsum("nk,vk->nv",
                      _fq(rms_norm(x, g, sz["norm_eps"]), prec, -1),
                      _fq(emb, prec, 1), precision=HI)


def layer_leaves(w, i):
    p = f"model.layers.{i}."
    return {n[len(p):]: v for n, v in w.items() if n.startswith(p)}


def forward(w, ids, cfg, prec="float32"):
    """Logits [b, s, vocab] of token ids [b, s] from all leaves `w`
    (float32), a sequence at a time: for the tests' sizes."""
    sz = sizes(cfg)
    out = []
    for row in np.asarray(ids):
        x = w[EMBED][jnp.asarray(row)]
        for i in range(sz["num_hidden_layers"]):
            x = block(x, layer_leaves(w, i), sz, layer_kind(sz, i), prec)
        out.append(head(x, w[FINAL_NORM], w[EMBED], sz, prec))
    return jnp.stack(out)


# -- serving: the reference follows served tokens, it does not decode --------

@functools.partial(jax.jit, static_argnames=("sz", "kind", "prec"))
def _block_jit(x, lw, sz, kind, prec):
    return block(x, lw, dict(sz), kind, prec)


@functools.partial(jax.jit, static_argnames=("sz", "prec"))
def _head_jit(rows, g, emb, sz, prec):
    return head(rows, g, emb, dict(sz), prec)


def _bucket(n, lo=128):
    b = lo
    while b < n:
        b *= 2
    return b


def served_gaps(leaves, cfg, requests, control=None):
    """[per request: for each served token, how far its float32 logit lies
    below the reference's best at that position]. `requests` is
    [(prompt ids, served tokens)]; `leaves(names)` returns those leaves as
    float32 arrays and is asked for one layer at a time, so that the
    published widths fit: each layer's leaves are made once, used for every
    request (and for the control's pass beside the float32 one) and let
    go. With `control` (a precision) the tokens judged are those that the
    reference in that precision puts first, at the same positions of the
    same prompt and served tokens. Sequences are padded to a power of two:
    attention's mask and the convolution are both causal, so the padding
    cannot reach the rows read."""
    sz = sizes(cfg)
    key = tuple(sorted(sz.items()))
    precs = ("float32",) + ((control,) if control else ())
    seqs = []
    for prompt, tokens in requests:
        ids = list(prompt) + list(tokens[:-1])
        padded = np.zeros((_bucket(len(ids)),), np.int32)
        padded[:len(ids)] = ids
        seqs.append(padded)
    emb = leaves([EMBED])[EMBED]
    xs = [[emb[jnp.asarray(s)] for s in seqs] for _ in precs]
    del emb
    for i in range(sz["num_hidden_layers"]):
        lw = layer_leaves(leaves(list(layer_shapes(sz, i))), i)
        for p, prec in enumerate(precs):
            xs[p] = [_block_jit(x, lw, key, layer_kind(sz, i), prec)
                     for x in xs[p]]
        del lw
    outer = leaves([FINAL_NORM, EMBED])
    gaps = []
    for r, (prompt, tokens) in enumerate(requests):
        first, count = len(prompt) - 1, len(tokens)
        lg = [_head_jit(xs[p][r][first:first + count], outer[FINAL_NORM],
                        outer[EMBED], key, prec)
              for p, prec in enumerate(precs)]
        judged = jnp.asarray(tokens, jnp.int32) if control is None \
            else jnp.argmax(lg[1], axis=-1)
        picked = jnp.take_along_axis(lg[0], judged[:, None], axis=-1)[:, 0]
        gaps.append(jnp.max(lg[0], axis=-1) - picked)
    return gaps
