"""The plain reference for Olmo-Hybrid-7B as `configs/olmo-hybrid-7b-l16.json`
cuts it: a float32 `jax.numpy` forward pass, every product at
`Precision.HIGHEST`. No cache, no chunks, no batching, no kernel, nothing
imported from the program: the Gated DeltaNet layer runs its recurrence a
token at a time (a `lax.scan` over positions with the [dk, dv] state of
every head as the carry), its convolution is an explicit sum over shifted
copies of the sequence, and attention masks a full [s, s] score matrix.

Layer equations (the configuration's keys; flash-linear-attention's
`GatedDeltaNet` and Olmo 2/3 where the config is silent, as the file's
`assumed` lists):

    h = x + RMSNorm(Mixer_i(x));  y = h + RMSNorm(W_down (silu(W_gate h)
          * W_up h));  eps = rms_norm_eps
    Mixer_i, layer_types[i] == "full_attention":  q = RMSNorm(x W_q),
          k = RMSNorm(x W_k) (over the whole projection), v = x W_v, by heads
          of hidden / heads numbers;  p = causal softmax(q.k * d^-0.5);
          Mixer(x) = concat_h(p v) W_o.  No rotary embedding (NoPE)
    Mixer_i, "linear_attention":  c = silu(sum_j w[j] * m_{t-(L-1)+j}) with
          m = [x W_q | x W_k | x W_v], zero before the sequence starts;
          per head q = l2norm(c_q) / sqrt(dk), k = l2norm(c_k), v = c_v,
          l2norm(a) = a / sqrt(sum a^2 + 1e-6);  beta = 2 sigmoid(x W_b);
          g = -exp(A_log) softplus(x W_a + dt_bias), alpha = exp(g);
          S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T,
          S_0 = 0;  o_t = S_t^T q_t;  Mixer(x) = concat_h(RMSNorm(o_t) *
          silu(x W_z)) W_o, one gain of dv shared by the heads
    logits = RMSNorm(y_last) W_head (untied)

Departures from the published description: none in the mathematics but
NoPE, which is the config's null `rope_theta` read literally (the
configuration file's `assumed` gives the reason). In storage: matrices are
[in, out]; the three convolutions of q, k and v are one stack of taps
[L, channels], q's channels then k's then v's.

`prec` rounds every matrix product's operands and both attention products'
(bfloat16, or float8 e4m3 with one scale per row of the contracted axis):
the control of `correct`. The recurrence and the convolution's taps are no
products with weights and stay float32.

Leaves are named as the program names them; the arrays come from the
benchmark (`drivers/serve_olmo_hybrid.py`), never from the program.
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
         "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
         "linear_num_key_heads", "linear_key_head_dim",
         "linear_value_head_dim", "linear_conv_kernel_dim",
         "linear_allow_neg_eigval", "rms_norm_eps")
EMBED, FINAL_NORM, HEAD = ("model.embed_tokens", "model.norm.weight",
                           "lm_head")
L2_EPS = 1e-6
HEAD_CHUNK = 8      # query heads whose [s, s] scores are alive at once


def sizes(cfg):
    """The keys of a configuration file that the reference reads, flat
    and hashable."""
    out = {k: cfg[k] for k in SIZES}
    out["layer_types"] = tuple(cfg["layer_types"])
    return out


def layer_shapes(sz, i):
    """{leaf name: shape} of layer i."""
    h, f = sz["hidden_size"], sz["intermediate_size"]
    p = f"model.layers.{i}."
    out = {p + "post_attention_layernorm.weight": (h,),
           p + "post_feedforward_layernorm.weight": (h,),
           p + "mlp.gate_proj": (h, f), p + "mlp.up_proj": (h, f),
           p + "mlp.down_proj": (f, h)}
    if sz["layer_types"][i] == "full_attention":
        heads, kvh = sz["num_attention_heads"], sz["num_key_value_heads"]
        d = h // heads
        out.update({p + "self_attn.q_proj": (h, heads * d),
                    p + "self_attn.k_proj": (h, kvh * d),
                    p + "self_attn.v_proj": (h, kvh * d),
                    p + "self_attn.o_proj": (heads * d, h),
                    p + "self_attn.q_norm.weight": (heads * d,),
                    p + "self_attn.k_norm.weight": (kvh * d,)})
    else:
        nh = sz["linear_num_key_heads"]
        dk, dv = sz["linear_key_head_dim"], sz["linear_value_head_dim"]
        a = p + "linear_attn."
        out.update({a + "q_proj": (h, nh * dk), a + "k_proj": (h, nh * dk),
                    a + "v_proj": (h, nh * dv), a + "z_proj": (h, nh * dv),
                    a + "a_proj": (h, nh), a + "b_proj": (h, nh),
                    a + "conv": (sz["linear_conv_kernel_dim"],
                                 nh * (2 * dk + dv)),
                    a + "A_log": (nh,), a + "dt_bias": (nh,),
                    a + "o_norm.weight": (dv,), a + "o_proj": (nh * dv, h)})
    return out


def outer_shapes(sz):
    """The leaves outside the layers: the embedding, the norm before the
    head and the head."""
    h, v = sz["hidden_size"], sz["vocab_size"]
    return {EMBED: (v, h), FINAL_NORM: (h,), HEAD: (h, v)}


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


def attention(x, lw, sz, prec):
    """x [s, h] one sequence -> [s, h]."""
    s, h = x.shape
    heads, kvh = sz["num_attention_heads"], sz["num_key_value_heads"]
    d, eps = h // heads, sz["rms_norm_eps"]
    q = rms_norm(linear(x, lw["self_attn.q_proj"], prec),
                 lw["self_attn.q_norm.weight"], eps).reshape(s, heads, d)
    k = rms_norm(linear(x, lw["self_attn.k_proj"], prec),
                 lw["self_attn.k_norm.weight"], eps).reshape(s, kvh, d)
    v = linear(x, lw["self_attn.v_proj"], prec).reshape(s, kvh, d)
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
    return linear(o, lw["self_attn.o_proj"], prec)


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def gated_delta(x, lw, sz, prec):
    """x [s, h] one sequence -> [s, h]: the convolution as a sum over L
    shifted copies of the whole sequence, then the recurrence a token at
    a time."""
    s, h = x.shape
    nh = sz["linear_num_key_heads"]
    dk, dv = sz["linear_key_head_dim"], sz["linear_value_head_dim"]
    a = "linear_attn."
    m = jnp.concatenate([linear(x, lw[a + n], prec)
                         for n in ("q_proj", "k_proj", "v_proj")], axis=-1)
    taps = lw[a + "conv"]                           # [L, C]
    n = taps.shape[0]
    c = jnp.zeros_like(m)
    for j in range(n):
        back = n - 1 - j                            # tokens ago
        shifted = jnp.concatenate(
            [jnp.zeros((back, m.shape[1]), F32), m[:s - back]], axis=0)
        c = c + taps[j][None, :] * shifted
    c = jax.nn.silu(c)
    q = l2norm(c[:, :nh * dk].reshape(s, nh, dk)) * float(dk) ** -0.5
    k = l2norm(c[:, nh * dk:2 * nh * dk].reshape(s, nh, dk))
    v = c[:, 2 * nh * dk:].reshape(s, nh, dv)
    beta = jax.nn.sigmoid(linear(x, lw[a + "b_proj"], prec))
    if sz["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(lw[a + "A_log"]) * jax.nn.softplus(
        linear(x, lw[a + "a_proj"], prec) + lw[a + "dt_bias"])

    def token(state, xs):                           # state [H, dk, dv]
        qt, kt, vt, gt, bt = xs
        state = jnp.exp(gt)[:, None, None] * state
        mem = jnp.einsum("hkv,hk->hv", state, kt, precision=HI)
        state = state + jnp.einsum("hk,hv->hkv", kt,
                                   bt[:, None] * (vt - mem), precision=HI)
        return state, jnp.einsum("hkv,hk->hv", state, qt, precision=HI)

    _, o = lax.scan(token, jnp.zeros((nh, dk, dv), F32), (q, k, v, g, beta))
    o = rms_norm(o, lw[a + "o_norm.weight"], sz["rms_norm_eps"])
    z = linear(x, lw[a + "z_proj"], prec).reshape(s, nh, dv)
    return linear((o * jax.nn.silu(z)).reshape(s, nh * dv),
                  lw[a + "o_proj"], prec)


def block(x, lw, sz, kind, prec):
    """A layer of `kind` over one sequence x [s, h]; lw's names are the
    layer's leaves without the `model.layers.<i>.` prefix."""
    eps = sz["rms_norm_eps"]
    mixer = attention if kind == "full_attention" else gated_delta
    h = x + rms_norm(mixer(x, lw, sz, prec),
                     lw["post_attention_layernorm.weight"], eps)
    f = linear(jax.nn.silu(linear(h, lw["mlp.gate_proj"], prec))
               * linear(h, lw["mlp.up_proj"], prec), lw["mlp.down_proj"],
               prec)
    return h + rms_norm(f, lw["post_feedforward_layernorm.weight"], eps)


def head(x, g, w_head, sz, prec):
    """Logits of rows x [n, h] through the head w_head [h, vocab]."""
    return linear(rms_norm(x, g, sz["rms_norm_eps"]), w_head, prec)


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
            x = block(x, layer_leaves(w, i), sz, sz["layer_types"][i], prec)
        out.append(head(x, w[FINAL_NORM], w[HEAD], sz, prec))
    return jnp.stack(out)


# -- serving: the reference follows served tokens, it does not decode --------

@functools.partial(jax.jit, static_argnames=("sz", "kind", "prec"))
def _block_jit(x, lw, sz, kind, prec):
    with jax.default_matmul_precision("highest"):
        return block(x, lw, dict(sz), kind, prec)


@functools.partial(jax.jit, static_argnames=("sz", "prec"))
def _head_jit(rows, g, w_head, sz, prec):
    with jax.default_matmul_precision("highest"):
        return head(rows, g, w_head, dict(sz), prec)


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
    attention's mask, the convolution and the recurrence are all causal,
    so the padding cannot reach the rows read."""
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
            xs[p] = [_block_jit(x, lw, key, sz["layer_types"][i], prec)
                     for x in xs[p]]
        del lw
    outer = leaves([FINAL_NORM, HEAD])
    gaps = []
    for r, (prompt, tokens) in enumerate(requests):
        first, count = len(prompt) - 1, len(tokens)
        lg = [_head_jit(xs[p][r][first:first + count], outer[FINAL_NORM],
                        outer[HEAD], key, prec)
              for p, prec in enumerate(precs)]
        judged = jnp.asarray(tokens, jnp.int32) if control is None \
            else jnp.argmax(lg[1], axis=-1)
        picked = jnp.take_along_axis(lg[0], judged[:, None], axis=-1)[:, 0]
        gaps.append(jnp.max(lg[0], axis=-1) - picked)
    return gaps
