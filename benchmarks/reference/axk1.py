"""The plain reference for A.X-K1 as `configs/axk1-ep16.json` cuts it: a
float32 `jax.numpy` forward pass with every product at
`Precision.HIGHEST`. No cache, no absorbed form, no sorting, no kernel,
nothing imported from the program: attention rebuilds every head's keys and
values from the latent rows of the whole sequence for every position, and an
expert layer loops over the experts held here with a dense [tokens, experts]
matrix of the router's weights.

Layer equations (the configuration's keys; DeepSeek-V3's modelling code where
the config is silent, as the file's `assumed` lists):

    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    Attn: c_q = RMSNorm(x W_qa); q = c_q W_qb -> per head [q_nope | q_rope]
          x W_kva -> [c_kv | k_rope]; c_kv <- RMSNorm(c_kv)
          RoPE (YaRN frequencies; pairs (2i, 2i+1)) on q_rope and the one k_rope
          c_kv W_kvb -> per head [k_nope | v]
          p = causal softmax((q_nope.k_nope + q_rope.k_rope) * d_qk^-0.5 * m^2)
          out = concat_h(p v) W_o,   m = 0.1 mscale_all_dim ln(factor) + 1
    FFN, first `first_k_dense_replace` layers: SwiGLU of `intermediate_size`
    FFN, after: s = sigmoid(u W_r) over the PUBLISHED router width (float32,
          in the control too); the k highest; w = s / sum(s) * scaling;
          Shared(u) + sum over the experts HELD HERE of w_e Expert_e(u).

The share is the configuration's: `n_routed_experts` experts from
`deployment.chip_rank * n_routed_experts` on, `vocab_size` rows. What absent
experts would add is left out, here as in the program.

`prec` rounds every weight product's operands and both attention products'
(bfloat16, or float8 e4m3 with one scale per row of the contracted axis): the
control of `correct`.

Leaves are named as the program names them and stored [in, out]; the arrays
come from the benchmark (`drivers/serve_axk1.py`), never from the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
SIZES = ("vocab_size", "hidden_size", "intermediate_size",
         "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
         "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
         "n_shared_experts", "num_experts_per_tok", "first_k_dense_replace",
         "moe_layer_freq", "norm_topk_prob", "routed_scaling_factor",
         "rms_norm_eps", "rope_theta")
ROPE = ("factor", "beta_fast", "beta_slow", "mscale", "mscale_all_dim",
        "original_max_position_embeddings")
EMBED, FINAL_NORM, HEAD = "model.embed_tokens", "model.norm.weight", "lm_head"
HEAD_CHUNK = 8      # heads whose [s, s] scores are alive at once


def sizes(cfg):
    """The keys of a configuration file that the reference reads, flat
    and hashable: the sizes as run, the YaRN group, the published router
    width and where this chip's experts start."""
    out = {k: cfg[k] for k in SIZES}
    out.update({"rope_" + k: cfg["rope_scaling"][k] for k in ROPE})
    out["router_width"] = cfg["published"]["n_routed_experts"]
    out["expert_offset"] = (cfg["deployment"]["chip_rank"]
                            * cfg["n_routed_experts"])
    return out


def is_expert_layer(sz, i):
    return (i >= sz["first_k_dense_replace"]
            and i % sz["moe_layer_freq"] == 0)


def layer_shapes(sz, i):
    """{leaf name: shape} of layer i."""
    h, heads = sz["hidden_size"], sz["num_attention_heads"]
    dn, dr, dv = (sz["qk_nope_head_dim"], sz["qk_rope_head_dim"],
                  sz["v_head_dim"])
    qr, r = sz["q_lora_rank"], sz["kv_lora_rank"]
    p = f"model.layers.{i}."
    out = {p + "input_layernorm.weight": (h,),
           p + "post_attention_layernorm.weight": (h,),
           p + "self_attn.q_a_proj": (h, qr),
           p + "self_attn.q_a_layernorm.weight": (qr,),
           p + "self_attn.q_b_proj": (qr, heads * (dn + dr)),
           p + "self_attn.kv_a_proj_with_mqa": (h, r + dr),
           p + "self_attn.kv_a_layernorm.weight": (r,),
           p + "self_attn.kv_b_proj": (r, heads * (dn + dv)),
           p + "self_attn.o_proj": (heads * dv, h)}

    def mlp(prefix, width):
        return {prefix + "gate_proj": (h, width),
                prefix + "up_proj": (h, width),
                prefix + "down_proj": (width, h)}

    if is_expert_layer(sz, i):
        held, m = sz["n_routed_experts"], sz["moe_intermediate_size"]
        out[p + "mlp.gate"] = (h, sz["router_width"])
        out[p + "mlp.experts.gate_up_proj"] = (held, h, 2 * m)
        out[p + "mlp.experts.down_proj"] = (held, m, h)
        out.update(mlp(p + "mlp.shared_experts.",
                       m * sz["n_shared_experts"]))
    else:
        out.update(mlp(p + "mlp.", sz["intermediate_size"]))
    return out


def outer_shapes(sz):
    """The leaves outside the layers: embedding, final norm, head."""
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


def yarn_mscale(scale, mscale):
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(sz):
    """[d_rope / 2] inverse frequencies: the original `theta^(-2i/d)`
    below the dimension that turns `beta_fast` times over the original
    context, those over `factor` above the one that turns `beta_slow`
    times, and a linear ramp between the two."""
    d, theta = sz["qk_rope_head_dim"], sz["rope_theta"]
    orig = sz["rope_original_max_position_embeddings"]

    def dim_of(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(sz["rope_beta_fast"])), 0)
    high = min(math.ceil(dim_of(sz["rope_beta_slow"])), d - 1)
    i = np.arange(d // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / d)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain / sz["rope_factor"]) * ramp + plain * (1.0 - ramp)


def softmax_scale(sz):
    m = yarn_mscale(sz["rope_factor"], sz["rope_mscale_all_dim"])
    return (sz["qk_nope_head_dim"] + sz["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, sz):
    """x [s, ..., d]: each pair (2i, 2i+1) of row t turned by t * f_i,
    times mscale / mscale_all_dim."""
    s, d = x.shape[0], x.shape[-1]
    ang = (jnp.arange(s, dtype=F32)[:, None]
           * jnp.asarray(yarn_inv_freq(sz), F32)[None, :])
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    scale = yarn_mscale(sz["rope_factor"], sz["rope_mscale"]) \
        / yarn_mscale(sz["rope_factor"], sz["rope_mscale_all_dim"])
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(x, lw, sz, prec):
    """x [s, h] one sequence -> [s, h]."""
    s = x.shape[0]
    heads, dn, dr, dv, r = (sz["num_attention_heads"],
                            sz["qk_nope_head_dim"], sz["qk_rope_head_dim"],
                            sz["v_head_dim"], sz["kv_lora_rank"])
    eps = sz["rms_norm_eps"]
    cq = rms_norm(linear(x, lw["self_attn.q_a_proj"], prec),
                  lw["self_attn.q_a_layernorm.weight"], eps)
    q = linear(cq, lw["self_attn.q_b_proj"], prec).reshape(s, heads, dn + dr)
    ckv = linear(x, lw["self_attn.kv_a_proj_with_mqa"], prec)
    c = rms_norm(ckv[:, :r], lw["self_attn.kv_a_layernorm.weight"], eps)
    k_rope = rope(ckv[:, r:], sz)                                # [s, dr]
    q_rope = rope(q[..., dn:], sz)
    kv = linear(c, lw["self_attn.kv_b_proj"], prec).reshape(s, heads, dn + dv)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scale = softmax_scale(sz)

    def some_heads(args):
        qn, qr, kn, v = args                        # [c, s, d]
        sc = (jnp.einsum("hqd,hkd->hqk", _fq(qn, prec, -1), _fq(kn, prec, -1),
                         precision=HI)
              + jnp.einsum("hqd,kd->hqk", _fq(qr, prec, -1),
                           _fq(k_rope, prec, -1), precision=HI)) * scale
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", _fq(p, prec, -1), _fq(v, prec, 1),
                          precision=HI)

    chunk = math.gcd(heads, HEAD_CHUNK)

    def chunks(t):                                  # [s, H, d] -> [n, c, s, d]
        t = jnp.swapaxes(t, 0, 1)
        return t.reshape((heads // chunk, chunk) + t.shape[1:])

    o = lax.map(some_heads, (chunks(q[..., :dn]), chunks(q_rope),
                             chunks(kv[..., :dn]), chunks(kv[..., dn:])))
    o = jnp.swapaxes(o.reshape(heads, s, dv), 0, 1).reshape(s, heads * dv)
    return linear(o, lw["self_attn.o_proj"], prec)


def swiglu(u, wg, wu, wd, prec):
    return linear(jax.nn.silu(linear(u, wg, prec)) * linear(u, wu, prec),
                  wd, prec)


def route(u, w_router, sz):
    """[s, router width] float32: each token's weight on every expert of
    the published layer, zero off its k highest sigmoid scores."""
    scores = jax.nn.sigmoid(jnp.einsum("sk,ke->se", u, w_router,
                                       precision=HI))
    k = sz["num_experts_per_tok"]
    kth = jnp.sort(scores, axis=-1)[:, -k][:, None]
    picked = jnp.where(scores >= kth, scores, 0.0)
    if sz["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return picked * sz["routed_scaling_factor"]


def expert_ffn(u, lw, sz, prec):
    m = sz["moe_intermediate_size"]
    g = route(u, lw["mlp.gate"], sz)
    off, held = sz["expert_offset"], sz["n_routed_experts"]
    g = g[:, off:off + held]

    def one(acc, args):
        w_gu, w_dn, col = args
        y = swiglu(u, w_gu[:, :m], w_gu[:, m:], w_dn, prec)
        return acc + col[:, None] * y, None

    routed, _ = lax.scan(one, jnp.zeros_like(u),
                         (lw["mlp.experts.gate_up_proj"],
                          lw["mlp.experts.down_proj"], g.T))
    return routed + swiglu(u, lw["mlp.shared_experts.gate_proj"],
                           lw["mlp.shared_experts.up_proj"],
                           lw["mlp.shared_experts.down_proj"], prec)


def block(x, lw, sz, expert_layer, prec):
    """One layer over one sequence x [s, h]; lw's names are the layer's
    leaves without the `model.layers.<i>.` prefix."""
    eps = sz["rms_norm_eps"]
    h = x + attention(rms_norm(x, lw["input_layernorm.weight"], eps), lw,
                      sz, prec)
    u = rms_norm(h, lw["post_attention_layernorm.weight"], eps)
    if expert_layer:
        return h + expert_ffn(u, lw, sz, prec)
    return h + swiglu(u, lw["mlp.gate_proj"], lw["mlp.up_proj"],
                      lw["mlp.down_proj"], prec)


def head(x, g, w_head, sz, prec):
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
            x = block(x, layer_leaves(w, i), sz, is_expert_layer(sz, i), prec)
        out.append(head(x, w[FINAL_NORM], w[HEAD], sz, prec))
    return jnp.stack(out)


# -- serving: the reference follows served tokens, it does not decode --------

@functools.partial(jax.jit, static_argnames=("sz", "expert_layer", "prec"))
def _block_jit(x, lw, sz, expert_layer, prec):
    return block(x, lw, dict(sz), expert_layer, prec)


@functools.partial(jax.jit, static_argnames=("sz", "prec"))
def _head_jit(rows, g, w_head, sz, prec):
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
    under the causal mask the padding cannot reach the rows read."""
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
        names = layer_shapes(sz, i)
        lw = layer_leaves(leaves(list(names)), i)
        for p, prec in enumerate(precs):
            xs[p] = [_block_jit(x, lw, key, is_expert_layer(sz, i), prec)
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
