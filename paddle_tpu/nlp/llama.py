"""Llama-family models, TPU-native.

ref parity: paddlenlp/transformers/llama/modeling.py (LlamaModel /
LlamaForCausalLM: RMSNorm pre-norm blocks, rotary position embeddings,
grouped-query attention, SwiGLU MLP, untied-or-tied LM head). The
reference runs CUDA fused rope/rms kernels and fleet mp; here the
whole step compiles through XLA with the same TPU levers as GPT:
GSPMD tensor parallelism (Column/RowParallelLinear specs), flash
attention (Pallas), scan-over-layers, remat, sequence parallelism,
and the fused chunked head+CE (the [N, vocab] logits never
materialize). RoPE cos/sin are computed in-trace from positions —
no table buffers, so the cached-decode path (positions = cache_index
+ arange) stays a single compiled program (nlp/generation.py's static
cache/cache_index contract, shared with GPT).

Numerics are pinned against torch/transformers' LlamaForCausalLM in
tests/test_llama.py (same half-split rotate convention).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..nn.layer import Layer
from ..nn.initializer import Normal, ParamAttr
from ..nn.layers_common import LayerList
from ..nn.layers_norm import RMSNorm
from ..tensor import Tensor
from ..distributed.fleet.mpu import (ColumnParallelLinear,
                                     RowParallelLinear,
                                     VocabParallelEmbedding,
                                     parallel_matmul)
from .modeling_utils import FromPretrainedMixin, normalize_attention_mask
from .gpt import GPTPretrainingCriterion
import paddle_tpu.nn.functional as F

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "LlamaPretrainingCriterion", "LLAMA_CONFIGS"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    # grouped-query attention: kv heads < heads (0 -> = heads)
    num_key_value_heads: int = 0
    intermediate_size: int = 0  # 0 -> the Llama 8/3*h rounded to 256
    max_position_embeddings: int = 2048
    initializer_range: float = 0.02
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    recompute: bool = False
    scan_layers: bool = False
    sequence_parallel: str = ""
    chunked_ce: int = 0

    def __post_init__(self):
        if not self.num_key_value_heads:
            self.num_key_value_heads = self.num_attention_heads
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"heads ({self.num_attention_heads}) must be a multiple "
                f"of num_key_value_heads ({self.num_key_value_heads})")
        if not self.intermediate_size:
            m = int(8 * self.hidden_size / 3)
            self.intermediate_size = (m + 255) // 256 * 256
        if self.sequence_parallel not in ("", "ring", "ulysses"):
            raise ValueError(
                f"sequence_parallel={self.sequence_parallel!r}")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


# ref: llama/configuration.py pretrained configs (paddlenlp model zoo)
LLAMA_CONFIGS = {
    "llama-7b": dict(hidden_size=4096, num_hidden_layers=32,
                     num_attention_heads=32, intermediate_size=11008),
    "llama2-7b": dict(hidden_size=4096, num_hidden_layers=32,
                      num_attention_heads=32, intermediate_size=11008,
                      max_position_embeddings=4096),
    "llama3-8b": dict(vocab_size=128256, hidden_size=4096,
                      num_hidden_layers=32, num_attention_heads=32,
                      num_key_value_heads=8, intermediate_size=14336,
                      max_position_embeddings=8192,
                      rope_theta=500000.0),
    "llama-tiny": dict(vocab_size=256, hidden_size=64,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, intermediate_size=128,
                       max_position_embeddings=128),
    # single-chip size of the GQA family: a TinyLlama-class 1.1B shape
    # (GQA 4:16); like gpt3-1.3B, training it on one 16GB chip needs
    # bf16 Adam moments + remat
    "llama-1b": dict(vocab_size=32000, hidden_size=2048,
                     num_hidden_layers=22, num_attention_heads=16,
                     num_key_value_heads=4, intermediate_size=5632,
                     max_position_embeddings=2048),
}


def _resolve_config(name, **overrides):
    cfg = dict(LLAMA_CONFIGS[name])
    cfg.update(overrides)
    return LlamaConfig(**cfg)


def _init_attr(cfg):
    return ParamAttr(initializer=Normal(mean=0.0,
                                        std=cfg.initializer_range))


def apply_rope(x, positions, theta, inv_freq=None):
    """Rotary embedding, HF/paddlenlp half-split convention:
    x [B, S, H, D]; positions [S] (absolute, shared across the batch)
    or [B, S] (per-row — the paged serving decode, where every slot
    sits at its own offset). rotate_half(x) = concat(-x2, x1) over the
    last-dim halves; out = x*cos + rot*sin with cos/sin of
    freqs = pos * theta^(-2i/D) repeated over halves. Computed
    in-trace (no tables) so cached decode's dynamic offset (positions
    = cache_index + arange) compiles into the one decode program.
    `inv_freq` [D/2] replaces theta's frequencies (a scaled RoPE such
    as YaRN's blend: nlp/axk1.py)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    freqs = positions.astype(jnp.float32)[..., None] * inv  # [..., D/2]
    cos = jnp.concatenate([jnp.cos(freqs), jnp.cos(freqs)], axis=-1)
    sin = jnp.concatenate([jnp.sin(freqs), jnp.sin(freqs)], axis=-1)
    if positions.ndim == 1:      # [S] -> broadcast over batch + heads
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:                        # [B, S] -> broadcast over heads
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x.astype(jnp.float32) * cos
            + rot.astype(jnp.float32) * sin).astype(x.dtype)


def _repeat_kv(x, n):
    """[B, S, Hkv, D] -> [B, S, Hkv*n, D] (GQA share): each kv head
    serves n query heads, laid out so query head h reads kv head
    h // n — matching HF/paddlenlp repeat_kv."""
    if n == 1:
        return x
    b, s, hkv, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :],
                            (b, s, hkv, n, d)).reshape(b, s, hkv * n, d)


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.cfg = config
        h = config.hidden_size
        kvh = config.num_key_value_heads * config.head_dim
        wa = _init_attr(config)
        self.q_proj = ColumnParallelLinear(h, h, weight_attr=wa,
                                           has_bias=False,
                                           gather_output=False)
        self.k_proj = ColumnParallelLinear(h, kvh, weight_attr=wa,
                                           has_bias=False,
                                           gather_output=False)
        self.v_proj = ColumnParallelLinear(h, kvh, weight_attr=wa,
                                           has_bias=False,
                                           gather_output=False)
        self.o_proj = RowParallelLinear(h, h, weight_attr=wa,
                                        has_bias=False,
                                        input_is_parallel=True)

    def _shaped_qkv(self, x):
        b, s = x.shape[0], x.shape[1]
        d = self.cfg.head_dim
        q = self.q_proj(x).reshape([b, s, -1, d])
        k = self.k_proj(x).reshape([b, s, -1, d])
        v = self.v_proj(x).reshape([b, s, -1, d])
        return q, k, v

    def forward(self, x, attn_mask=None, cache=None, cache_index=None):
        from ..autograd import apply_op
        cfg = self.cfg
        groups = cfg.num_attention_heads // cfg.num_key_value_heads
        if cache_index is not None and cache is None:
            raise ValueError(
                "cache_index was given without cache: the static-cache "
                "decode path updates preallocated [B, S_max, Hkv, D] "
                "buffers in place — build them first (generation.py's "
                "init_cache / forward(use_cache=True)) or drop "
                "cache_index")
        q, k, v = self._shaped_qkv(x)
        from .paged_cache import PagedLayerCache, paged_layer_forward
        if isinstance(cache, PagedLayerCache):
            # serving path (nlp/serving.py): the shared paged contract
            # handles per-slot RoPE + page write + GQA attention
            return paged_layer_forward(q, k, v, cache, self.o_proj,
                                       groups=groups,
                                       rope_theta=cfg.rope_theta)
        if cache_index is not None:
            return self._forward_static_cache(q, k, v, cache,
                                              cache_index, groups)
        s = q.shape[1]
        # eager cache continuation: positions offset by the prefix
        # length (concrete at trace — this is the eager parity path;
        # jit decode goes through _forward_static_cache)
        offset = cache[0].shape[1] if cache is not None else 0
        rope = lambda t, p: apply_rope(t, p, cfg.rope_theta)
        pos = offset + jnp.arange(s, dtype=jnp.int32)
        q = apply_op(rope, q, Tensor(pos))
        k = apply_op(rope, k, Tensor(pos))
        if cache is not None:
            if cache[0].shape[1]:
                from ..tensor_ops.manip import concat
                k = concat([cache[0], k], axis=1)
                v = concat([cache[1], v], axis=1)
            cache = (k, v)
        kr = apply_op(_repeat_kv, k, n=groups)
        vr = apply_op(_repeat_kv, v, n=groups)
        sp_out = None if cache is not None else \
            self._maybe_sp(q, kr, vr, attn_mask)
        if sp_out is not None:
            out = sp_out
        else:
            out = F.scaled_dot_product_attention(
                q, kr, vr, attn_mask=attn_mask, is_causal=True,
                training=self.training,
                use_flash=cfg.use_flash_attention)
        b, so = out.shape[0], out.shape[1]
        out = self.o_proj(out.reshape([b, so, -1]))
        return (out, cache) if cache is not None else out

    def _maybe_sp(self, q, k, v, attn_mask):
        """Training/no-cache path only: cached decode grows S
        dynamically (rectangular q/k), which a static sequence shard
        cannot host — same contract as GPT's _maybe_sequence_parallel
        (the caller guards cache is None)."""
        mode = self.cfg.sequence_parallel
        if not mode:
            return None
        from ..distributed.mesh import get_mesh
        mesh = get_mesh()
        if mesh is None or "sp" not in mesh.axis_names or \
                mesh.shape["sp"] <= 1:
            return None
        if attn_mask is not None:
            raise ValueError("sequence_parallel attention takes no "
                             "padding mask (mask the loss instead)")
        from ..autograd import apply_op
        from ..distributed.fleet.sequence_parallel import (
            ring_attention_spmd, ulysses_attention_spmd)
        fn = (ring_attention_spmd if mode == "ring"
              else ulysses_attention_spmd)
        return apply_op(
            lambda qq, kk, vv: fn(qq, kk, vv, mesh, causal=True),
            q, k, v)

    def _forward_static_cache(self, q, k, v, cache, cache_index, groups):
        """jit decode fast path: fixed [B, S_max, Hkv, D] buffers
        updated in place at cache_index; RoPE positions offset by the
        index (one compiled program decodes every token). GQA attends
        with a GROUPED einsum against the kv-head buffers directly —
        the repeated [B, S_max, H_full, D] tensors the naive repeat_kv
        materializes per step never exist (that repeat would negate the
        GQA cache saving at decode time)."""
        from ..autograd import apply_op
        theta = self.cfg.rope_theta

        def run(qv, kv, vv, kbuf, vbuf, idx):
            idx = jnp.asarray(idx, jnp.int32)
            s = qv.shape[1]
            pos = idx + jnp.arange(s, dtype=jnp.int32)
            qv = apply_rope(qv, pos, theta)
            kv = apply_rope(kv, pos, theta)
            zero = jnp.int32(0)
            kbuf = jax.lax.dynamic_update_slice(
                kbuf, kv.astype(kbuf.dtype), (zero, idx, zero, zero))
            vbuf = jax.lax.dynamic_update_slice(
                vbuf, vv.astype(vbuf.dtype), (zero, idx, zero, zero))
            b, sq, h, d = qv.shape
            s_max = kbuf.shape[1]
            scale = 1.0 / math.sqrt(d)
            if groups == 1 and sq == 1:
                # single-token MHA decode: valid-length masked kernel
                # (env-gated Pallas on TPU, jnp fallback) — same route
                # as GPT's static-cache fast path
                from ..ops.attention import flash_decode
                lens = jnp.broadcast_to(idx + 1, (b,))
                o = flash_decode(qv.astype(kbuf.dtype), kbuf, vbuf,
                                 lens).astype(qv.dtype)
                return o, kbuf, vbuf
            qg = qv.reshape(b, sq, h // groups, groups, d)
            logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                                kbuf.astype(qv.dtype),
                                preferred_element_type=jnp.float32)
            logits = logits * scale
            # causal vs the WRITTEN prefix: key j visible iff j <= idx+i
            kpos = jnp.arange(s_max)[None, None, None, None, :]
            qpos = (idx + jnp.arange(sq))[None, None, None, :, None]
            logits = jnp.where(kpos <= qpos, logits, -1e30)
            p = jax.nn.softmax(logits, axis=-1).astype(qv.dtype)
            o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vbuf.astype(qv.dtype))
            return o.reshape(b, sq, h, d), kbuf, vbuf

        out, kbuf, vbuf = apply_op(
            run, q, k, v, cache[0], cache[1],
            cache_index if isinstance(cache_index, Tensor)
            else Tensor(jnp.asarray(cache_index)))
        b, s = out.shape[0], out.shape[1]
        out = self.o_proj(out.reshape([b, s, -1]))
        return out, (kbuf, vbuf)


class LlamaMLP(Layer):
    """SwiGLU (ref LlamaMLP): down(silu(gate(x)) * up(x))."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        wa = _init_attr(config)
        h, i = config.hidden_size, config.intermediate_size
        self.gate_proj = ColumnParallelLinear(h, i, weight_attr=wa,
                                              has_bias=False,
                                              gather_output=False)
        self.up_proj = ColumnParallelLinear(h, i, weight_attr=wa,
                                            has_bias=False,
                                            gather_output=False)
        self.down_proj = RowParallelLinear(i, h, weight_attr=wa,
                                           has_bias=False,
                                           input_is_parallel=True)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.cfg = config
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, attn_mask=None, cache=None, cache_index=None):
        h = self.input_layernorm(x)
        if cache is not None or cache_index is not None:
            h, cache = self.self_attn(h, attn_mask, cache,
                                      cache_index=cache_index)
        else:
            h = self.self_attn(h, attn_mask)
        x = x + h
        x = x + self.mlp(self.post_attention_layernorm(x))
        return (x, cache) if (cache is not None) else x


def _build_layers(config):
    blocks = [LlamaDecoderLayer(config)
              for _ in range(config.num_hidden_layers)]
    if not config.scan_layers:
        return LayerList(blocks)
    from ..nn.scan_stack import ScannedLayerStack
    return ScannedLayerStack(blocks, has_dropout=False,
                             recompute=config.recompute)


class LlamaModel(FromPretrainedMixin, Layer):
    """ref: llama/modeling.py LlamaModel."""

    def __init__(self, config: LlamaConfig = None, **kwargs):
        super().__init__()
        if config is None:
            config = LlamaConfig(**kwargs)
        elif isinstance(config, dict):
            config = LlamaConfig(**config)
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=_init_attr(config))
        self.layers = _build_layers(config)
        self.norm = RMSNorm(config.hidden_size,
                            epsilon=config.rms_norm_eps)

    @classmethod
    def from_config_name(cls, name, **overrides):
        return cls(_resolve_config(name, **overrides))

    def forward(self, input_ids, attention_mask=None, use_cache=False,
                cache=None, cache_index=None):
        from .gpt import _recompute_block
        if cache_index is not None and cache is None:
            raise ValueError(
                "cache_index was given without cache: decode-by-index "
                "needs the preallocated static KV buffers (run a "
                "use_cache=True prefill / generation.init_cache first, "
                "or drop cache_index)")
        mask = normalize_attention_mask(attention_mask)
        x = self.embed_tokens(input_ids)
        if self.config.scan_layers:
            if use_cache or cache is not None or cache_index is not None:
                raise NotImplementedError(
                    "scan_layers=True serves training/no-cache forward "
                    "only; build with scan_layers=False for cached "
                    "decode (stack_layer_state converts checkpoints)")
            x = self.layers(x, mask)
            return self.norm(x)
        if use_cache and cache is None:
            cache = [(Tensor(jnp.zeros(
                (x.shape[0], 0, self.config.num_key_value_heads,
                 self.config.head_dim), jnp.float32)),) * 2
                for _ in range(self.config.num_hidden_layers)]
        new_caches = [] if (cache is not None) else None
        for i, blk in enumerate(self.layers):
            if cache is not None or cache_index is not None:
                layer_cache = cache[i] if cache is not None else None
                x, c = blk(x, mask, layer_cache, cache_index=cache_index)
                new_caches.append(c)
            elif self.config.recompute and self.training:
                x = _recompute_block(blk, x, mask)
            else:
                x = blk(x, mask)
        x = self.norm(x)
        return (x, new_caches) if new_caches is not None else x


class LlamaPretrainingCriterion(GPTPretrainingCriterion):
    """ref: llama/modeling.py LlamaPretrainingCriterion — same masked
    CLM cross entropy (and the same fused chunked head+CE contract)."""


class LlamaForCausalLM(FromPretrainedMixin, Layer):
    """ref: llama/modeling.py LlamaForCausalLM (untied lm_head by
    default; tie_word_embeddings=True reuses the embedding)."""

    def __init__(self, config: LlamaConfig = None, **kwargs):
        super().__init__()
        self.llama = LlamaModel(config, **kwargs)
        self.config = self.llama.config
        if not self.config.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                self.config.hidden_size, self.config.vocab_size,
                weight_attr=_init_attr(self.config), has_bias=False,
                gather_output=False)

    @classmethod
    def from_config_name(cls, name, **overrides):
        return cls(_resolve_config(name, **overrides))

    def _head_weight(self):
        if self.config.tie_word_embeddings:
            return self.llama.embed_tokens.weight, True
        return self.lm_head.weight, False

    def forward(self, input_ids, attention_mask=None, use_cache=False,
                cache=None, cache_index=None):
        out = self.llama(input_ids, attention_mask, use_cache=use_cache,
                         cache=cache, cache_index=cache_index)
        if use_cache or cache is not None or cache_index is not None:
            hidden, new_cache = out
        else:
            hidden, new_cache = out, None
        if (getattr(self.config, "chunked_ce", 0) and self.training
                and new_cache is None):
            w, tied = self._head_weight()
            # the criterion's chunked einsum wants [vocab, hidden]; the
            # untied lm_head stores the Linear [in, out] layout — hand
            # it the TRANSPOSE (a layout op XLA folds into the
            # per-chunk matmul, not a copy). Under a trace use the
            # traced value, not the Parameter (functional_call restores
            # _value post-forward — the Parameter would bake a stale
            # constant); EAGERLY pass the Parameter / a tape-linked
            # transpose, else loss.backward() drops the head grad on a
            # detached leaf (ADVICE r5 #1).
            from ..autograd import in_jax_trace
            if in_jax_trace((w._value,)):
                wv = w._value if tied else w._value.T
                lm_w = Tensor(wv, stop_gradient=w.stop_gradient)
            else:
                lm_w = w if tied else w.transpose([1, 0])
            return {"_loss_only_aux": True, "hidden": hidden,
                    "lm_weight": lm_w,
                    "chunked_ce": int(self.config.chunked_ce)}
        w, tied = self._head_weight()
        with jax.named_scope("lm_head"):
            if tied:
                logits = parallel_matmul(hidden, w, transpose_y=True,
                                         gather_output=False)
            else:
                # lm_head weight is [in, out] — the Linear layout
                logits = self.lm_head(hidden)
        if new_cache is not None:
            return logits, new_cache
        return logits

    def generate(self, input_ids, **kwargs):
        from .generation import generate as _generate
        return _generate(self, input_ids, **kwargs)
