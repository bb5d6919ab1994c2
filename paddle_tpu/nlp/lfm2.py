"""LFM2-8B-A1B (Liquid AI; `model_type: lfm2_moe`), TPU-native, for serving.

Source: https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json;
where the config is silent the Hugging Face `lfm2_moe` modelling code is
followed (the split order of the convolution's input projection, the two
per-head norms before RoPE, `+1e-6` in the router's normaliser, the tied
output head). Every layer i is `h = x + Op_i(RMSNorm(x))`,
`y = h + FFN_i(RMSNorm(h))`, no biases:

- `Op_i` where `layer_types[i] == "conv"`, the gated short convolution:
  `[B, C, z] = split3(u W_in)`, `g = B * z`, a depthwise causal
  convolution of `g` over `conv_L_cache` positions, `Op(u) = (C * conv) W_out`.
  What a sequence carries from token to token is the last `conv_L_cache`
  rows of `g`, one fixed-size state per serving slot and no pages
  (`paged_cache.ConvStateSpec`): a prefill hands back the state after its
  last TRUE token, a decode step shifts it under the live mask. The state
  is stored in the cache's dtype; the sum over the taps is float32.
- `Op_i` where it is `"full_attention"`: grouped-query attention with an
  RMSNorm over each head of q and k before RoPE (half-split, no scaling),
  through the paged K/V cache exactly as nlp/llama.py
  (`paged_update_and_attend`); the prefill goes through
  ops/attention.flash_attention.
- `FFN_i`: a dense SwiGLU for `i < num_dense_layers`, then the expert
  layer: float32 sigmoid scores, the `num_experts_per_tok` highest of
  `scores + expert_bias` (the bias takes part in the choice only), their
  scores over their sum + 1e-6, times `routed_scaling_factor`; every
  expert is held here (`moe.held_experts` with offset 0), no shared one.

Weights are stored in `dtype`; products take operands in that dtype and
accumulate in float32; the residual stream, norms, router, softmax, the
convolution's sum and the logits are float32.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from ..nn.layer import Layer
from ..nn.layers_common import LayerList
from ..tensor import Tensor
from ..nn.layers_norm import RMSNorm
from .axk1 import _mm, _param, _swiglu
from .llama import _repeat_kv, apply_rope
from .moe import held_experts, select_experts
from .paged_cache import (ConvStateCache, ConvStateRows, ConvStateSpec,
                          KVCacheSpec, PagedLayerCache, PromptKV,
                          conv_state_at, conv_state_step,
                          paged_update_and_attend)

__all__ = ["LFM2Config", "LFM2Model", "LFM2ForCausalLM", "LFM2_CONFIGS"]

# the published pattern: three short convolutions to one attention layer,
# the last period cut short
_PUBLISHED_LAYERS = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


@dataclass
class LFM2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_experts: int = 32
    num_experts_per_tok: int = 4
    num_dense_layers: int = 2
    layer_types: tuple = field(default_factory=lambda: _PUBLISHED_LAYERS)
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True
    dtype: str = "float32"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        for what, got, want in (
                ("conv_bias", self.conv_bias, False),
                ("tie_word_embeddings", self.tie_word_embeddings, True)):
            if got != want:
                raise ValueError(f"LFM2Config: {what} = {got!r}; this "
                                 f"model implements {want!r} only")
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {"conv", "full_attention"}:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of "
                f"{self.num_hidden_layers}, each 'conv' or "
                f"'full_attention': {self.layer_types}")
        if self.num_attention_heads % self.num_key_value_heads or \
                self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"{self.num_attention_heads} heads over "
                f"{self.num_key_value_heads} K/V heads and hidden "
                f"{self.hidden_size}")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("num_experts_per_tok > num_experts")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    def is_expert_layer(self, i):
        return i >= self.num_dense_layers


LFM2_CONFIGS = {
    "lfm2-8b-a1b": {},
    # the CPU tests' size: every mechanism, nothing published
    "lfm2-tiny": dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                      moe_intermediate_size=32, num_hidden_layers=6,
                      num_attention_heads=4, num_key_value_heads=2,
                      num_experts=8, num_experts_per_tok=2,
                      layer_types=("conv", "conv", "full_attention") * 2,
                      max_position_embeddings=4096),
}


def _resolve_config(name, **overrides):
    cfg = dict(LFM2_CONFIGS[name])
    cfg.update(overrides)
    return LFM2Config(**cfg)


def _norm(n, cfg):
    norm = RMSNorm(n, epsilon=cfg.norm_eps)
    norm.weight._value = norm.weight._value.astype(cfg.dtype)
    return norm


@jax.named_scope("short_conv")
def _conv_prefill(b, c, z, taps, lens):
    """The gates and the taps over a whole (right-padded) prompt. b, c, z
    [B, S, C] float32; taps [L, C]; returns (C * conv(B * z) [B, S, C],
    the state after each row's last true token [B, L, C])."""
    g = b * z
    n, s = taps.shape[0], g.shape[1]
    padded = jnp.pad(g, ((0, 0), (n - 1, 0), (0, 0)))
    conv = sum(taps[j].astype(jnp.float32) * padded[:, j:j + s]
               for j in range(n))
    return c * conv, conv_state_at(g, lens, n)


@jax.named_scope("short_conv")
def _conv_step(b, c, z, taps, cache: ConvStateCache):
    """One token per slot: b, c, z [B, C] float32. Returns (C * conv [B, C],
    the slots' new state)."""
    window, state = conv_state_step(cache, b * z)
    conv = jnp.sum(taps.astype(jnp.float32)[None] * window, axis=1)
    return c * conv, state


class LFM2ShortConv(Layer):
    def __init__(self, cfg: LFM2Config):
        super().__init__()
        h = cfg.hidden_size
        self.in_proj = _param(self, cfg, h, 3 * h)
        # [taps, channels]: tap j multiplies the input of `taps - 1 - j`
        # tokens ago (the source's Conv1d weight [C, 1, taps], transposed)
        self.conv = _param(self, cfg, cfg.conv_L_cache, h)
        self.out_proj = _param(self, cfg, h, h)

    def forward(self, x, cache=None, kv_lens=None):
        """x [B, S, h] Tensor. cache None: (out, the prompt's state
        [B, taps, C]); a ConvStateCache (S = 1): (out, the new state)."""
        u = x._value
        b, c, z = jnp.split(_mm(u, self.in_proj._value), 3, axis=-1)
        taps = self.conv._value
        if cache is None:
            y, state = _conv_prefill(b, c, z, taps, kv_lens)
        else:
            y, state = _conv_step(b[:, 0], c[:, 0], z[:, 0], taps, cache)
            y = y[:, None]
        return Tensor(_mm(y, self.out_proj._value)), state


class LFM2Attention(Layer):
    def __init__(self, cfg: LFM2Config):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        self.q_proj = _param(self, cfg, h, cfg.num_attention_heads * d)
        self.k_proj = _param(self, cfg, h, cfg.num_key_value_heads * d)
        self.v_proj = _param(self, cfg, h, cfg.num_key_value_heads * d)
        self.out_proj = _param(self, cfg, h, h)
        self.q_layernorm = _norm(d, cfg)
        self.k_layernorm = _norm(d, cfg)

    def forward(self, x, cache=None, kv_lens=None):
        """x [B, S, h] Tensor. cache None: (out, (k, v) of [B, S, Hkv, D],
        k after its norm and RoPE: what the pages hold); a PagedLayerCache
        (S = 1): (out, the new page arrays)."""
        cfg = self.cfg
        u = x._value
        b, s = u.shape[0], u.shape[1]
        d, groups = cfg.head_dim, \
            cfg.num_attention_heads // cfg.num_key_value_heads
        q = self.q_layernorm(Tensor(
            _mm(u, self.q_proj._value).reshape(b, s, -1, d)))._value
        k = self.k_layernorm(Tensor(
            _mm(u, self.k_proj._value).reshape(b, s, -1, d)))._value
        v = _mm(u, self.v_proj._value).reshape(b, s, -1, d)
        if cache is not None:
            o, kept = paged_update_and_attend(q, k, v, cache, groups=groups,
                                              rope_theta=cfg.rope_theta)
        else:
            from ..ops.attention import flash_attention
            pos = jnp.arange(s, dtype=jnp.int32)
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)
            dt = self.q_proj._value.dtype
            o = flash_attention(
                q.astype(dt), _repeat_kv(k, groups).astype(dt),
                _repeat_kv(v, groups).astype(dt), causal=True,
                kv_lens=kv_lens)
            kept = (k, v)
        out = _mm(o.reshape(b, s, -1), self.out_proj._value)
        return Tensor(out), kept


class LFM2MLP(Layer):
    """SwiGLU: w2(silu(w1(x)) * w3(x))."""

    def __init__(self, cfg: LFM2Config):
        super().__init__()
        h, f = cfg.hidden_size, cfg.intermediate_size
        self.w1 = _param(self, cfg, h, f)
        self.w3 = _param(self, cfg, h, f)
        self.w2 = _param(self, cfg, f, h)

    def forward(self, x):
        return Tensor(_swiglu(x._value, self.w1._value, self.w3._value,
                              self.w2._value))


class LFM2Experts(Layer):
    """All the experts, stacked: w1 and w3 side by side, then w2."""

    def __init__(self, cfg: LFM2Config):
        super().__init__()
        e, h, m = cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size
        self.gate_up_proj = _param(self, cfg, e, h, 2 * m)
        self.down_proj = _param(self, cfg, e, m, h)


class LFM2MoE(Layer):
    def __init__(self, cfg: LFM2Config):
        super().__init__()
        self.cfg = cfg
        self.gate = _param(self, cfg, cfg.hidden_size, cfg.num_experts)
        self.expert_bias = _param(self, cfg, cfg.num_experts) \
            if cfg.use_expert_bias else None
        self.experts = LFM2Experts(cfg)

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
            bias = None if self.expert_bias is None \
                else self.expert_bias._value.astype(jnp.float32)
            idx, w = select_experts(scores, cfg.num_experts_per_tok,
                                    cfg.norm_topk_prob,
                                    cfg.routed_scaling_factor, bias=bias,
                                    eps=1e-6)
        routed, aux = held_experts(
            flat, idx, w, self.experts.gate_up_proj._value,
            self.experts.down_proj._value, 0,
            None if rows_live is None else rows_live.reshape(-1))
        return Tensor(routed.reshape(x.shape)), aux


class LFM2DecoderLayer(Layer):
    def __init__(self, cfg: LFM2Config, index):
        super().__init__()
        self.operator_norm = _norm(cfg.hidden_size, cfg)
        self.is_attention = cfg.layer_types[index] == "full_attention"
        if self.is_attention:
            self.self_attn = LFM2Attention(cfg)
        else:
            self.conv = LFM2ShortConv(cfg)
        self.ffn_norm = _norm(cfg.hidden_size, cfg)
        self.is_expert_layer = cfg.is_expert_layer(index)
        self.feed_forward = LFM2MoE(cfg) if self.is_expert_layer \
            else LFM2MLP(cfg)

    def forward(self, x, cache=None, kv_lens=None, rows_live=None):
        """(y, what the operator keeps: the prompt's rows or state, or the
        layer's new cache arrays; the expert layer's counters or None)."""
        op = self.self_attn if self.is_attention else self.conv
        a, kept = op(self.operator_norm(x), cache, kv_lens)
        h = Tensor(x._value + a._value)
        u = self.ffn_norm(h)
        if self.is_expert_layer:
            f, aux = self.feed_forward(u, rows_live)
        else:
            f, aux = self.feed_forward(u), None
        return Tensor(h._value + f._value), kept, aux


class LFM2Model(Layer):
    def __init__(self, config: LFM2Config = None, **kwargs):
        super().__init__()
        if config is None:
            config = LFM2Config(**kwargs)
        elif isinstance(config, dict):
            config = LFM2Config(**config)
        self.config = config
        self.embed_tokens = _param(self, config, config.vocab_size,
                                   config.hidden_size)
        self.layers = LayerList([LFM2DecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        # the source's name for the norm after the last layer
        self.embedding_norm = _norm(config.hidden_size, config)

    def forward(self, input_ids, attention_mask=None, use_cache=False,
                cache=None, cache_index=None):
        """Three paths of one contract (nlp/serving.py calls the last
        two): no cache -> hidden; `use_cache=True` -> (hidden, per layer a
        PromptKV or a ConvStateRows: what the prompt leaves in that
        layer's cache); `cache` a list of PagedLayerCache / ConvStateCache
        by the layer's kind (one token per slot) -> (hidden, the new
        caches). `attention_mask` [B, S] of ones then zeros marks right
        padding."""
        del cache_index     # the paged caches carry their positions
        if cache is not None:
            for layer, c in zip(self.layers, cache):
                want = PagedLayerCache if layer.is_attention \
                    else ConvStateCache
                if not isinstance(c, want):
                    raise ValueError(
                        "LFM2 decodes through the caches its cache_spec() "
                        "names (nlp/serving.py) only; it has no dense "
                        "static-cache path")
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
            x, keep, aux = layer(x, None if cache is None else cache[i],
                                 kv_lens, rows_live)
            if cache is None:
                kept.append(PromptKV(*keep, aux=aux) if layer.is_attention
                            else ConvStateRows(keep, aux))
            elif layer.is_attention:
                kept.append(cache[i].replaced(*keep, aux=aux))
            else:
                kept.append(cache[i].replaced(keep, aux))
        x = self.embedding_norm(x)
        return (x, kept) if (use_cache or cache is not None) else x


class LFM2ForCausalLM(Layer):
    """LFM2Model and the output head, whose matrix is the embedding's;
    float32 logits."""

    def __init__(self, config: LFM2Config = None, **kwargs):
        super().__init__()
        self.model = LFM2Model(config, **kwargs)
        self.config = self.model.config

    @classmethod
    def from_config_name(cls, name, **overrides):
        return cls(_resolve_config(name, **overrides))

    def cache_spec(self):
        """What nlp/serving.py holds for each layer: K/V pages for an
        attention layer, a per-slot state for a short convolution."""
        cfg = self.config
        kv = KVCacheSpec(cfg.num_key_value_heads, cfg.head_dim)
        state = ConvStateSpec(cfg.hidden_size, cfg.conv_L_cache)
        return [kv if t == "full_attention" else state
                for t in cfg.layer_types]

    def forward(self, input_ids, attention_mask=None, use_cache=False,
                cache=None, cache_index=None):
        out = self.model(input_ids, attention_mask, use_cache=use_cache,
                         cache=cache, cache_index=cache_index)
        hidden, kept = out if isinstance(out, tuple) else (out, None)
        with jax.named_scope("lm_head"):
            w = self.model.embed_tokens._value
            logits = Tensor(jax.lax.dot_general(
                hidden._value.astype(w.dtype), w,
                (((hidden._value.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))
        return logits if kept is None else (logits, kept)
