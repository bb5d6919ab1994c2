"""A.X-K1 (SK Telecom; `model_type: axk1`), TPU-native, as one chip's
share of an expert-parallel deployment.

Source: https://huggingface.co/skt/A.X-K1/blob/main/config.json. The
config's keys are DeepSeek-V3's one for one, and where the config is
silent its modelling code is followed (the interleaved-to-half-split
rotary layout, `+1e-20` in the router's normaliser). Every layer is
`h = x + Attn(RMSNorm(x))`, `y = h + FFN(RMSNorm(h))`, no biases:

- latent attention: queries through a low-rank pair (`q_a_proj`, RMSNorm,
  `q_b_proj`), keys and values through one latent row per token,
  `[c_kv | k_rope]` (`kv_lora_rank + qk_rope_head_dim` numbers), which is
  ALL the cache holds. Two forms of one mathematics: *expanded* (prefill
  and the uncached forward: `c_kv W_kvb` gives each head its keys and
  values, through ops/attention.flash_attention) and *absorbed* (paged
  decode: `q_nope W_UK^T` is dotted with `c_kv` itself and `sum p c_kv`
  goes through `W_UV`, so no per-head key or value is rebuilt from the
  cache). RoPE is YaRN's blend of interpolated and original frequencies,
  applied at every length; the softmax scale carries `mscale^2`.
- FFN: a dense SwiGLU in the first `first_k_dense_replace` layers, then
  expert layers: float32 sigmoid scores over all `n_routed_experts`, the
  `num_experts_per_tok` highest, normalised and scaled, plus a shared
  expert. `topk_method: "none"` is read as selection by the scores alone
  (no group limit, no correction bias): `nlp/moe.select_experts` is the
  one place to change.

**The share.** `layer_chips` chips share each layer (expert-parallel FFN,
data-parallel attention): this chip (`chip_rank`) holds
`n_routed_experts / layer_chips` routed experts, all of attention and the
shared expert, and `vocab_size / vocab_shards` rows of the vocabulary.
The expert layer routes over the published width and computes the held
experts' part of the sum plus the shared expert; what absent experts
would add is left out, and that partial sum goes on to the next layer.
Nothing stands in for absent chips. With `layer_chips == 1` it is the
whole layer. The held experts' products are grouped products over the
rows sorted by expert (`jax.lax.ragged_dot`): dropless, static shapes.

Weights are stored in `dtype`; products take operands in that dtype and
accumulate in float32; the residual stream, norms, router, softmax and
logits are float32.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from ..nn.layer import Layer
from ..nn.initializer import Normal, ParamAttr
from ..nn.layers_common import LayerList
from ..nn.layers_norm import RMSNorm
from ..tensor import Tensor
from .llama import apply_rope
from .moe import held_experts, select_experts
from .paged_cache import (LatentCacheSpec, LatentRows,
                          PagedLatentCache, _rope_rows,
                          latent_paged_attention, write_token_latent)

__all__ = ["AXK1Config", "AXK1Model", "AXK1ForCausalLM", "AXK1_CONFIGS",
           "yarn_inv_freq", "yarn_mscale", "select_experts"]


def _yarn_defaults():
    return {"type": "yarn", "factor": 32, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096}


@dataclass
class AXK1Config:
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 192
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "none"
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict = field(default_factory=_yarn_defaults)
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    # the deployment share (module docstring)
    layer_chips: int = 1
    chip_rank: int = 0
    vocab_shards: int = 1
    dtype: str = "float32"

    def __post_init__(self):
        for what, got, want in (
                ("scoring_func", self.scoring_func, "sigmoid"),
                ("topk_method", self.topk_method, "none"),
                ("hidden_act", self.hidden_act, "silu"),
                ("rope_scaling.type", self.rope_scaling.get("type"), "yarn"),
                ("tie_word_embeddings", self.tie_word_embeddings, False),
                ("attention_bias", self.attention_bias, False)):
            if got != want:
                raise ValueError(f"AXK1Config: {what} = {got!r}; this "
                                 f"model implements {want!r} only")
        if self.n_routed_experts % self.layer_chips or \
                not 0 <= self.chip_rank < self.layer_chips:
            raise ValueError(
                f"{self.n_routed_experts} experts over layer_chips="
                f"{self.layer_chips}, chip_rank={self.chip_rank}")
        if self.vocab_size % self.vocab_shards:
            raise ValueError(f"vocab_size {self.vocab_size} over "
                             f"vocab_shards={self.vocab_shards}")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("num_experts_per_tok > n_routed_experts")

    @property
    def experts_held(self):
        return self.n_routed_experts // self.layer_chips

    @property
    def expert_offset(self):
        return self.chip_rank * self.experts_held

    @property
    def vocab_rows(self):
        """Rows of the vocabulary held here: ids, logits and sampling are
        over this slice."""
        return self.vocab_size // self.vocab_shards

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self):
        """Numbers cached per token and layer: `[c_kv | k_rope]`."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_expert_layer(self, i):
        return (i >= self.first_k_dense_replace
                and i % self.moe_layer_freq == 0)


AXK1_CONFIGS = {
    "axk1": {},
    # the CPU tests' size: every mechanism, nothing published
    "axk1-tiny": dict(vocab_size=512, hidden_size=64, intermediate_size=160,
                      moe_intermediate_size=32, num_hidden_layers=3,
                      num_attention_heads=4,
                      q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
                      num_experts_per_tok=4, max_position_embeddings=4096,
                      rope_scaling=dict(
                          _yarn_defaults(),
                          original_max_position_embeddings=64)),
}


def _resolve_config(name, **overrides):
    cfg = dict(AXK1_CONFIGS[name])
    cfg.update(overrides)
    return AXK1Config(**cfg)


# -- YaRN ------------------------------------------------------------------

def yarn_mscale(scale, mscale=1.0):
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(dim, theta, scaling):
    """[dim/2] inverse frequencies: below `low` the original ones, above
    `high` those divided by `factor`, a linear ramp between, with the two
    bounds the dimensions that turn `beta_fast` and `beta_slow` times
    over the original context (DeepseekV3YarnRotaryEmbedding)."""
    factor = scaling["factor"]
    orig = scaling["original_max_position_embeddings"]

    def turns_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(scaling["beta_slow"])), dim - 1)
    extra = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    out = []
    for i, f in enumerate(extra):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return out


def _softmax_scale(cfg):
    m = yarn_mscale(cfg.rope_scaling["factor"],
                    cfg.rope_scaling["mscale_all_dim"])
    return cfg.qk_head_dim ** -0.5 * m * m


def _rope_mscale(cfg):
    sc = cfg.rope_scaling
    return yarn_mscale(sc["factor"], sc["mscale"]) \
        / yarn_mscale(sc["factor"], sc["mscale_all_dim"])


def _half_split(x):
    """The source stores each rotary pair interleaved (2i, 2i+1); RoPE
    here is half-split, so the pairs' first members go first."""
    d = x.shape[-1]
    return jnp.swapaxes(x.reshape(x.shape[:-1] + (d // 2, 2)), -1,
                        -2).reshape(x.shape)


# -- small pieces ------------------------------------------------------------

def _mm(x, w):
    """x in w's dtype times w, accumulated and returned in float32."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _swiglu(x, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(x, wg)) * _mm(x, wu), wd)


class _StackedNormal(Normal):
    """N(mean, std) for a stack of matrices, a member at a time: the
    float32 draws of a whole stack of experts (and the sampler's
    temporaries, four times that) need not lie beside the model."""

    def __call__(self, shape, dtype):
        if len(shape) < 3:
            return super().__call__(shape, dtype)
        return jnp.stack([super(_StackedNormal, self).__call__(
            shape[1:], dtype) for _ in range(shape[0])])


def _param(layer, cfg, *shape):
    return layer.create_parameter(
        shape, attr=ParamAttr(initializer=_StackedNormal(
            mean=0.0, std=cfg.initializer_range)), dtype=cfg.dtype)


def _norm(n, cfg):
    norm = RMSNorm(n, epsilon=cfg.rms_norm_eps)
    norm.weight._value = norm.weight._value.astype(cfg.dtype)
    return norm


# -- layers ------------------------------------------------------------------

class AXK1Attention(Layer):
    def __init__(self, cfg: AXK1Config):
        super().__init__()
        self.cfg = cfg
        h, heads = cfg.hidden_size, cfg.num_attention_heads
        self.q_a_proj = _param(self, cfg, h, cfg.q_lora_rank)
        self.q_a_layernorm = _norm(cfg.q_lora_rank, cfg)
        self.q_b_proj = _param(self, cfg, cfg.q_lora_rank,
                               heads * cfg.qk_head_dim)
        self.kv_a_proj_with_mqa = _param(self, cfg, h, cfg.latent_width)
        self.kv_a_layernorm = _norm(cfg.kv_lora_rank, cfg)
        self.kv_b_proj = _param(
            self, cfg, cfg.kv_lora_rank,
            heads * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        self.o_proj = _param(self, cfg, heads * cfg.v_head_dim, h)
        self._inv_freq = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                                       cfg.rope_scaling)
        if _rope_mscale(cfg) != 1.0:
            raise ValueError("rope_scaling with mscale != mscale_all_dim "
                             "scales cos/sin; not implemented")

    def _q_and_rows(self, x, positions, rows_rope):
        """Queries per head and the token's cache row, RoPE applied.
        x [..., h]; returns (q_nope [..., H, dn], q_rope [..., H, dr],
        rows [..., W] float32 `[RMSNorm(c_kv) | k_rope]`)."""
        cfg = self.cfg
        heads, dn, dr = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim)
        cq = self.q_a_layernorm(Tensor(_mm(x, self.q_a_proj._value)))._value
        q = _mm(cq, self.q_b_proj._value).reshape(
            x.shape[:-1] + (heads, dn + dr))
        ckv = _mm(x, self.kv_a_proj_with_mqa._value)
        c = self.kv_a_layernorm(Tensor(ckv[..., :cfg.kv_lora_rank]))._value
        k_rope = _half_split(ckv[..., cfg.kv_lora_rank:])[..., None, :]
        q_rope = rows_rope(_half_split(q[..., dn:]), positions)
        k_rope = rows_rope(k_rope, positions)[..., 0, :]
        return q[..., :dn], q_rope, jnp.concatenate([c, k_rope], axis=-1)

    def _expanded(self, x, kv_lens):
        """[B, S, h] -> (out [B, S, h], rows [B, S, W]): every head's keys
        and values rebuilt from the prompt's own latent rows."""
        from ..ops.attention import flash_attention
        cfg = self.cfg
        b, s = x.shape[0], x.shape[1]
        heads, dn, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.v_head_dim)
        pos = jnp.arange(s, dtype=jnp.int32)
        q_nope, q_rope, rows = self._q_and_rows(
            x, pos, lambda t, p: apply_rope(t, p, cfg.rope_theta,
                                            self._inv_freq))
        w = self.kv_b_proj._value
        kv = _mm(rows[..., :cfg.kv_lora_rank], w).reshape(
            b, s, heads, dn + dv)
        k_rope = jnp.broadcast_to(rows[:, :, None, cfg.kv_lora_rank:],
                                  (b, s, heads, cfg.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :dn], k_rope], axis=-1)
        v = kv[..., dn:]
        # the flash kernel takes ONE head size for q, k and v, of 64, 128
        # or 256: pad 192 (and the values' 128) with zeros to 256, which
        # changes no score and no sum (PERF.md says what it costs)
        d = q.shape[-1]
        dp = next((p for p in (64, 128, 256) if p >= d), d)

        def padded(t):
            t = t.astype(w.dtype)
            return jnp.pad(t, ((0, 0),) * 3 + ((0, dp - t.shape[-1]),))

        with jax.named_scope("latent_attention"):
            o = flash_attention(padded(q), padded(k), padded(v), causal=True,
                                sm_scale=_softmax_scale(cfg),
                                kv_lens=kv_lens)[..., :dv]
        return _mm(o.reshape(b, s, heads * dv), self.o_proj._value), rows

    def _absorbed(self, x, cache: PagedLatentCache):
        """[B, 1, h] through the paged latent pool: the new row written,
        then every head's query dotted with the rows themselves."""
        cfg = self.cfg
        heads, dn, dv, r = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                            cfg.v_head_dim, cfg.kv_lora_rank)
        pos = cache.positions
        q_nope, q_rope, rows = self._q_and_rows(
            x[:, 0], pos, lambda t, p: _rope_rows(t, p, cfg.rope_theta,
                                                  self._inv_freq))
        pages = write_token_latent(cache, rows)
        w = self.kv_b_proj._value.reshape(r, heads, dn + dv)
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope.astype(w.dtype),
                           w[:, :, :dn], preferred_element_type=jnp.float32)
        o_lat = latent_paged_attention(
            jnp.concatenate([q_lat, q_rope], axis=-1), pages,
            cache.page_table, pos + 1, r, _softmax_scale(cfg),
            use_flash=cache.use_flash)
        o = jnp.einsum("bhr,rhd->bhd", o_lat.astype(w.dtype), w[:, :, dn:],
                       preferred_element_type=jnp.float32)
        out = _mm(o.reshape(o.shape[0], 1, heads * dv), self.o_proj._value)
        return out, pages

    def forward(self, x, cache=None, kv_lens=None):
        """x [B, S, h] Tensor. cache None: (out, rows) by the expanded
        form; a PagedLatentCache: (out, new pages) by the absorbed one."""
        if cache is None:
            out, rows = self._expanded(x._value, kv_lens)
        else:
            out, rows = self._absorbed(x._value, cache)
        return Tensor(out), rows


class AXK1MLP(Layer):
    """SwiGLU: down(silu(gate(x)) * up(x))."""

    def __init__(self, cfg: AXK1Config, width):
        super().__init__()
        self.gate_proj = _param(self, cfg, cfg.hidden_size, width)
        self.up_proj = _param(self, cfg, cfg.hidden_size, width)
        self.down_proj = _param(self, cfg, width, cfg.hidden_size)

    def forward(self, x):
        return Tensor(_swiglu(x._value, self.gate_proj._value,
                              self.up_proj._value, self.down_proj._value))


class AXK1Experts(Layer):
    """The routed experts held here, stacked: gate and up side by side."""

    def __init__(self, cfg: AXK1Config):
        super().__init__()
        held, h, m = (cfg.experts_held, cfg.hidden_size,
                      cfg.moe_intermediate_size)
        self.gate_up_proj = _param(self, cfg, held, h, 2 * m)
        self.down_proj = _param(self, cfg, held, m, h)


class AXK1MoE(Layer):
    def __init__(self, cfg: AXK1Config):
        super().__init__()
        self.cfg = cfg
        self.gate = _param(self, cfg, cfg.hidden_size, cfg.n_routed_experts)
        self.experts = AXK1Experts(cfg)
        self.shared_experts = AXK1MLP(
            cfg, cfg.moe_intermediate_size * cfg.n_shared_experts)

    def forward(self, u, rows_live=None):
        """u [B, S, h] Tensor -> (Tensor [B, S, h], counters int32 [3])."""
        cfg = self.cfg
        x = u._value
        flat = x.reshape(-1, x.shape[-1])
        with jax.named_scope("moe_router"):
            scores = jax.nn.sigmoid(jnp.dot(
                flat.astype(jnp.float32),
                self.gate._value.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            idx, w = select_experts(scores, cfg.num_experts_per_tok,
                                    cfg.norm_topk_prob,
                                    cfg.routed_scaling_factor)
        routed, aux = held_experts(
            flat, idx, w, self.experts.gate_up_proj._value,
            self.experts.down_proj._value, cfg.expert_offset,
            None if rows_live is None else rows_live.reshape(-1))
        with jax.named_scope("moe_shared"):
            shared = self.shared_experts(u)._value
        return Tensor(shared + routed.reshape(x.shape)), aux


class AXK1DecoderLayer(Layer):
    def __init__(self, cfg: AXK1Config, index):
        super().__init__()
        self.input_layernorm = _norm(cfg.hidden_size, cfg)
        self.self_attn = AXK1Attention(cfg)
        self.post_attention_layernorm = _norm(cfg.hidden_size, cfg)
        self.is_expert_layer = cfg.is_expert_layer(index)
        self.mlp = AXK1MoE(cfg) if self.is_expert_layer \
            else AXK1MLP(cfg, cfg.intermediate_size)

    def forward(self, x, cache=None, kv_lens=None, rows_live=None):
        """(y, rows or new pages, the expert layer's counters or None)."""
        a, kept = self.self_attn(self.input_layernorm(x), cache, kv_lens)
        h = Tensor(x._value + a._value)
        u = self.post_attention_layernorm(h)
        if self.is_expert_layer:
            f, aux = self.mlp(u, rows_live)
        else:
            f, aux = self.mlp(u), None
        return Tensor(h._value + f._value), kept, aux


class AXK1Model(Layer):
    def __init__(self, config: AXK1Config = None, **kwargs):
        super().__init__()
        if config is None:
            config = AXK1Config(**kwargs)
        elif isinstance(config, dict):
            config = AXK1Config(**config)
        self.config = config
        self.embed_tokens = _param(self, config, config.vocab_rows,
                                   config.hidden_size)
        self.layers = LayerList([AXK1DecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = _norm(config.hidden_size, config)

    def forward(self, input_ids, attention_mask=None, use_cache=False,
                cache=None, cache_index=None):
        """Three paths of one contract (nlp/serving.py calls the last
        two): no cache -> hidden; `use_cache=True` -> (hidden, a
        LatentRows per layer: the prompt's dense cache rows); `cache` a
        list of PagedLatentCache (one token per slot, positions in the
        caches) -> (hidden, the new caches). `attention_mask` [B, S] of
        ones then zeros marks right padding."""
        del cache_index     # the paged caches carry their positions
        if cache is not None and not all(
                isinstance(c, PagedLatentCache) for c in cache):
            raise ValueError(
                "AXK1 decodes through PagedLatentCache (nlp/serving.py) "
                "only; it has no dense static-cache path")
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        rows_live = kv_lens = None
        if attention_mask is not None and cache is None:
            m = attention_mask._value if isinstance(attention_mask, Tensor) \
                else jnp.asarray(attention_mask)
            rows_live = m.astype(bool)
            kv_lens = jnp.sum(m, axis=-1).astype(jnp.int32)
        x = Tensor(self.embed_tokens._value[ids].astype(jnp.float32))
        kept = []
        for i, layer in enumerate(self.layers):
            x, rows, aux = layer(x, None if cache is None else cache[i],
                                 kv_lens, rows_live)
            kept.append(cache[i].replaced(rows, aux) if cache is not None
                        else LatentRows(rows, aux))
        x = self.norm(x)
        return (x, kept) if (use_cache or cache is not None) else x


class AXK1ForCausalLM(Layer):
    """AXK1Model and the untied output head over the held vocabulary
    rows; float32 logits."""

    def __init__(self, config: AXK1Config = None, **kwargs):
        super().__init__()
        self.model = AXK1Model(config, **kwargs)
        self.config = self.model.config
        self.lm_head = _param(self, self.config, self.config.hidden_size,
                              self.config.vocab_rows)

    @classmethod
    def from_config_name(cls, name, **overrides):
        return cls(_resolve_config(name, **overrides))

    def cache_spec(self):
        """What nlp/serving.py pages for each layer: one latent row."""
        return LatentCacheSpec(self.config.latent_width)

    def forward(self, input_ids, attention_mask=None, use_cache=False,
                cache=None, cache_index=None):
        out = self.model(input_ids, attention_mask, use_cache=use_cache,
                         cache=cache, cache_index=cache_index)
        hidden, kept = out if isinstance(out, tuple) else (out, None)
        with jax.named_scope("lm_head"):
            logits = Tensor(_mm(hidden._value, self.lm_head._value))
        return logits if kept is None else (logits, kept)

