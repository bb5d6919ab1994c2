"""GPT — the flagship decoder-only LM.

ref parity: PaddleNLP paddlenlp/transformers/gpt/modeling.py (GPTModel,
GPTForCausalLM/GPTLMHeadModel, GPTPretrainingCriterion) and the fleet GPT-3
pretrain configs (hidden 2048 x 24 layers = 1.3B).

TPU-native design:
- attention/MLP projections are mpu Column/RowParallelLinear: dense on one
  chip, tensor-parallel (GSPMD or shard_map) under a Mesh with an 'mp' axis.
- word embedding is VocabParallelEmbedding; the LM head ties its weight via
  parallel_matmul (ref: GPTForCausalLM's shared word_embeddings).
- attention core routes through F.scaled_dot_product_attention -> Pallas
  flash attention on TPU; causal masking via is_causal (no materialised
  [S,S] mask in the hot path).
- pre-LayerNorm residual blocks (the reference GPT's normalize_before=True).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..nn import functional as F
from ..nn.initializer import Normal, ParamAttr
from ..nn.layer import Layer
from ..nn.scan_stack import (ScannedLayerStack, stack_layer_state,
                             unstack_layer_state)
from ..nn.layers_common import Dropout, Embedding, LayerList
from ..nn.layers_norm import LayerNorm
from ..tensor import Tensor
from ..distributed.fleet.mpu import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    ParallelCrossEntropy, parallel_matmul, annotate)
from .modeling_utils import FromPretrainedMixin


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 0  # 0 -> 4*hidden
    hidden_act: str = "gelu"
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    max_position_embeddings: int = 1024
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    use_flash_attention: bool = True
    tie_word_embeddings: bool = True
    # rematerialize each decoder block in backward (ref: fleet GPT-3
    # configs train with recompute on) — ~1/3 more FLOPs for O(1)-block
    # activation memory, the enabler for large batch/seq on one chip
    recompute: bool = False
    # store the L decoder blocks as stacked [L, ...] parameters and run
    # them with ONE lax.scan: the traced/compiled HLO is O(1 block)
    # instead of O(L). TPU-native compile-time lever (the unrolled
    # 24-layer step takes over a minute to compile); composes with recompute
    # as the standard remat-scan. Training/no-cache path only — cached decode
    # keeps the unrolled blocks (see ScannedGPTLayers.forward).
    scan_layers: bool = False
    # one [h, 3h] qkv matmul (Megatron head-interleaved layout) instead
    # of three [h, h]: fewer launches + fewer activation reads. Weight
    # layout differs from the separate projections — convert checkpoints
    # with fuse_qkv_state / split_qkv_state.
    fused_qkv: bool = False
    # interleaved ('virtual') pipeline stages for GPTForCausalLMPipe:
    # each pp rank holds v chunks and activations ride a ring ppermute,
    # shrinking the bubble to (S-1)/(m*v+S-1). ref: fleet
    # num_virtual_pipeline_stages (Megatron interleaved schedule).
    num_virtual_pipeline_stages: int = 1
    # fuse the tied LM head matmul into the loss, computed over token
    # CHUNKS of this size (lax.scan + jax.checkpoint): the [N, vocab]
    # logits tensor — 824 MB fp32 at 1.3B b4 s1024 — never exists in
    # HBM; each chunk's logits live only inside one scan step and are
    # recomputed in backward. The Liger-kernel/Megatron fused-CE idea
    # in XLA-native form. Training path only; 0 disables.
    # ref: paddlenlp parallel_cross_entropy + fused head variants.
    chunked_ce: int = 0
    # fuse the block's residual add into the following LayerNorm with
    # one Pallas pass (y=LN(x+r) and s=x+r in a single read of the
    # operands — the add->reduce boundary XLA keeps as a kernel break;
    # step anatomy r4 put the MFU gap in exactly these elementwise HBM
    # passes). Default off, not measured on the chip. ref:
    # paddle/phi/kernels/fusion/fused_layernorm_residual_dropout_bias.
    fused_ln: bool = False
    # sequence/context parallelism for long sequences: '' (off), 'ring'
    # (KV blocks rotate by ppermute with an online-softmax accumulator;
    # arXiv:2310.01889) or 'ulysses' (all_to_all seq<->heads swap;
    # arXiv:2309.14509). Takes effect when the active mesh has an 'sp'
    # axis of size > 1; attention then runs sequence-sharded via
    # shard_map while everything pointwise in S stays GSPMD-partitioned.
    # ref: fleet sep_parallel / RingFlashAttention (meta_parallel).
    sequence_parallel: str = ""

    def __post_init__(self):
        if not self.intermediate_size:
            self.intermediate_size = 4 * self.hidden_size
        if self.sequence_parallel not in ("", "ring", "ulysses"):
            raise ValueError(
                f"sequence_parallel={self.sequence_parallel!r}: expected "
                "'', 'ring' or 'ulysses'")
        if self.sequence_parallel and self.attention_probs_dropout_prob:
            raise ValueError(
                "sequence_parallel requires attention_probs_dropout_prob"
                "=0 (the sp attention kernels carry no dropout stream; "
                "hidden_dropout_prob is fine — it is pointwise in S)")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


# ref: PaddleNLP gpt/configuration.py pretrained init configurations +
# fleet gpt-3 1.3B yaml (hidden 2048, 24L, 16 heads, seq 2048/1024 pos 1024*2)
GPT_CONFIGS = {
    "gpt3-1.3B": dict(vocab_size=50304, hidden_size=2048,
                      num_hidden_layers=24, num_attention_heads=16,
                      max_position_embeddings=2048),
    "gpt3-345M": dict(vocab_size=50304, hidden_size=1024,
                      num_hidden_layers=24, num_attention_heads=16,
                      max_position_embeddings=1024),
    "gpt2-en": dict(vocab_size=50304, hidden_size=768,
                    num_hidden_layers=12, num_attention_heads=12,
                    max_position_embeddings=1024),
    "gpt-tiny": dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=128,
                     hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0),
}


def _init_attr(cfg):
    return ParamAttr(initializer=Normal(mean=0.0, std=cfg.initializer_range))


class GPTAttention(Layer):
    """Causal self-attention. Separate q/k/v column-parallel projections
    (head dim sharded over mp) + row-parallel output projection — the
    layout of the reference's fused_attention mp path."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.cfg = config
        h = config.hidden_size
        wa = _init_attr(config)
        if config.fused_qkv:
            # one [h, 3h] matmul instead of three [h, h]: two fewer
            # kernel launches and two fewer reads of the activation per
            # layer. Out-dim layout is the Megatron INTERLEAVE
            # [H, 3, head_dim] so an mp shard (a contiguous head range)
            # holds its own q,k,v — correct under GSPMD and shard_map
            # alike. fuse_qkv_state converts separate checkpoints.
            self.qkv_proj = ColumnParallelLinear(h, 3 * h, weight_attr=wa,
                                                 gather_output=False)
        else:
            self.q_proj = ColumnParallelLinear(h, h, weight_attr=wa,
                                               gather_output=False)
            self.k_proj = ColumnParallelLinear(h, h, weight_attr=wa,
                                               gather_output=False)
            self.v_proj = ColumnParallelLinear(h, h, weight_attr=wa,
                                               gather_output=False)
        self.out_proj = RowParallelLinear(h, h, weight_attr=wa,
                                          input_is_parallel=True)

    def _heads(self, x):
        b, s = x.shape[0], x.shape[1]
        return x.reshape([b, s, -1, self.cfg.head_dim])

    def _qkv(self, x):
        if self.cfg.fused_qkv:
            qkv = self.qkv_proj(x)               # [b, s, 3h] interleaved
            b, s = qkv.shape[0], qkv.shape[1]
            d = self.cfg.head_dim
            qkv = qkv.reshape([b, s, -1, 3, d])  # [b, s, H(local), 3, d]
            return qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
        return (self._heads(self.q_proj(x)), self._heads(self.k_proj(x)),
                self._heads(self.v_proj(x)))

    def forward(self, x, attn_mask=None, cache=None, cache_index=None):
        q, k, v = self._qkv(x)
        from .paged_cache import PagedLayerCache, paged_layer_forward
        if isinstance(cache, PagedLayerCache):
            # serving path (nlp/serving.py): paged block cache, one
            # token per slot, per-slot positions — shared contract with
            # Llama (nlp/paged_cache.py)
            return paged_layer_forward(q, k, v, cache, self.out_proj)
        if cache_index is not None:
            # STATIC cache (jit decode fast path, nlp/generation.py):
            # fixed [B, S_max, H, D] buffers written in place at
            # cache_index — shapes never change across scan steps, so one
            # compiled program decodes every token
            return self._forward_static_cache(q, k, v, cache, cache_index)
        if cache is not None:
            # skip the concat for the zero-length initial cache: under
            # shard_map tensor parallelism k/v carry num_heads/mp LOCAL
            # heads while the pre-built empty cache has global heads
            if cache[0].shape[1]:
                from ..tensor_ops.manip import concat
                k = concat([cache[0], k], axis=1)
                v = concat([cache[1], v], axis=1)
            cache = (k, v)
        sp_out = self._maybe_sequence_parallel(q, k, v, attn_mask,
                                               cache)
        if sp_out is not None:
            return sp_out
        # causal ALWAYS applies (decoder-only LM): a user attention_mask is
        # a padding mask combined ON TOP of the causal structure (ref:
        # GPTModel builds causal&padding jointly in modeling.py's
        # _prepare_decoder_attention_mask); SDPA's tril is bottom-right
        # aligned so cached decode (sq < sk) stays correct
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask,
            dropout_p=self.cfg.attention_probs_dropout_prob
            if self.training else 0.0,
            is_causal=True, training=self.training,
            use_flash=self.cfg.use_flash_attention)
        b, s = out.shape[0], out.shape[1]
        out = self.out_proj(out.reshape([b, s, -1]))
        return (out, cache) if cache is not None else out

    def _maybe_sequence_parallel(self, q, k, v, attn_mask, cache):
        """Route attention through ring/Ulysses sequence parallelism when
        config asks for it AND the active mesh has an 'sp' axis (>1).
        Returns the projected output, or None to fall through to SDPA.
        Training/no-cache path only: cached decode grows S dynamically,
        which a static sequence shard cannot host."""
        mode = getattr(self.cfg, "sequence_parallel", "")
        if not mode or cache is not None:
            return None
        from ..distributed.mesh import get_mesh
        mesh = get_mesh()
        if mesh is None or "sp" not in mesh.axis_names or \
                mesh.shape["sp"] <= 1:
            return None
        if attn_mask is not None:
            raise ValueError(
                "sequence_parallel attention does not take a padding "
                "attention_mask (pad to full blocks or mask the loss "
                "instead — ref: fleet sep_parallel has the same "
                "contract)")
        from ..autograd import apply_op
        from ..distributed.fleet.sequence_parallel import (
            ring_attention_spmd, ulysses_attention_spmd)
        fn = (ring_attention_spmd if mode == "ring"
              else ulysses_attention_spmd)
        out = apply_op(
            lambda qq, kk, vv: fn(qq, kk, vv, mesh, causal=True), q, k, v)
        b, s = out.shape[0], out.shape[1]
        return self.out_proj(out.reshape([b, s, -1]))

    def _forward_static_cache(self, q, k, v, cache, cache_index):
        from ..autograd import apply_op

        import math as _math

        def run(qv, kv, vv, kbuf, vbuf, idx):
            idx = jnp.asarray(idx, jnp.int32)
            zero = jnp.int32(0)
            kbuf = jax.lax.dynamic_update_slice(
                kbuf, kv.astype(kbuf.dtype), (zero, idx, zero, zero))
            vbuf = jax.lax.dynamic_update_slice(
                vbuf, vv.astype(vbuf.dtype), (zero, idx, zero, zero))
            sq, s_max = qv.shape[1], kbuf.shape[1]
            if sq == 1:
                # decode step: flash-decode kernel over the padded cache
                # (causal == "first idx+1 keys are valid" when sq == 1)
                from ..ops.attention import flash_decode
                lens = jnp.full((qv.shape[0],), idx + 1, jnp.int32)
                # a reduced-precision cache (cache_dtype='bfloat16')
                # must not break the kernel: dot_general needs matching
                # dtypes, so run the attention in the cache dtype
                out = flash_decode(qv.astype(kbuf.dtype), kbuf, vbuf,
                                   lens)
                return out.astype(qv.dtype), kbuf, vbuf
            # causal validity against absolute positions: query row r sits
            # at position idx+r and may attend keys at positions <= idx+r
            kpos = jnp.arange(s_max, dtype=jnp.int32)[None, :]
            qpos = idx + jnp.arange(sq, dtype=jnp.int32)[:, None]
            mask = (kpos <= qpos)[None, None]        # [1, 1, sq, S_max]
            qh, kh, vh = (jnp.swapaxes(a, 1, 2) for a in (qv, kbuf, vbuf))
            scale = 1.0 / _math.sqrt(qh.shape[-1])
            logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
            logits = jnp.where(mask, logits, -jnp.inf)
            probs = jax.nn.softmax(logits.astype(jnp.float32),
                                   axis=-1).astype(qh.dtype)
            out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
            return jnp.swapaxes(out, 1, 2), kbuf, vbuf

        idx = cache_index._value if isinstance(cache_index, Tensor) \
            else cache_index
        out, kbuf, vbuf = apply_op(run, q, k, v, cache[0], cache[1], idx)
        b, s = out.shape[0], out.shape[1]
        return self.out_proj(out.reshape([b, s, -1])), (kbuf, vbuf)


def fuse_qkv_state(state_dict, num_attention_heads):
    """Convert separate q/k/v projection leaves to the fused
    head-interleaved layout (attn.qkv_proj.*). Weight convention is
    [in, out]; fused out-dim layout is [H, 3, head_dim] flattened.
    Inverse: split_qkv_state."""
    import numpy as np
    out, groups = {}, {}
    for k, v in state_dict.items():
        for part in ("q_proj", "k_proj", "v_proj"):
            if f".{part}." in k:
                base, leaf = k.split(f".{part}.")
                groups.setdefault((base, leaf), {})[part[0]] = v
                break
        else:
            out[k] = v
    if not groups:
        hint = ""
        if any("__" in k and "q_proj" in k for k in state_dict):
            hint = (" (keys look scan_layers-stacked: unstack with "
                    "unstack_layer_state first, fuse, then re-stack)")
        raise ValueError(
            "fuse_qkv_state converted 0 q/k/v trios — no '.q_proj.' / "
            "'.k_proj.' / '.v_proj.' keys found" + hint)
    for (base, leaf), g in groups.items():
        if set(g) != {"q", "k", "v"}:
            raise ValueError(f"incomplete q/k/v trio at {base}.*.{leaf}")
        arrs = [np.asarray(g[p]._value if hasattr(g[p], "_value") else g[p])
                for p in "qkv"]
        H = num_attention_heads
        if arrs[0].ndim == 2:                       # weight [in, h]
            inn, h = arrs[0].shape
            stacked = np.stack([a.reshape(inn, H, h // H) for a in arrs],
                               axis=2)              # [in, H, 3, d]
            out[f"{base}.qkv_proj.{leaf}"] = stacked.reshape(inn, 3 * h)
        else:                                       # bias [h]
            h = arrs[0].shape[0]
            stacked = np.stack([a.reshape(H, h // H) for a in arrs],
                               axis=1)              # [H, 3, d]
            out[f"{base}.qkv_proj.{leaf}"] = stacked.reshape(3 * h)
    return out


def split_qkv_state(state_dict, num_attention_heads):
    """Inverse of fuse_qkv_state."""
    import numpy as np
    if not any(".qkv_proj." in k for k in state_dict):
        raise ValueError("split_qkv_state converted 0 fused leaves — no "
                         "'.qkv_proj.' keys found (already separate, or "
                         "scan_layers-stacked: unstack first)")
    out = {}
    for k, v in state_dict.items():
        if ".qkv_proj." not in k:
            out[k] = v
            continue
        base, leaf = k.split(".qkv_proj.")
        arr = np.asarray(v._value if hasattr(v, "_value") else v)
        H = num_attention_heads
        if arr.ndim == 2:
            inn, h3 = arr.shape
            h = h3 // 3
            sp = arr.reshape(inn, H, 3, h // H)
            parts = [sp[:, :, i].reshape(inn, h) for i in range(3)]
        else:
            h = arr.shape[0] // 3
            sp = arr.reshape(H, 3, h // H)
            parts = [sp[:, i].reshape(h) for i in range(3)]
        for name, a in zip(("q_proj", "k_proj", "v_proj"), parts):
            out[f"{base}.{name}.{leaf}"] = a
    return out


class GPTMLP(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        wa = _init_attr(config)
        self.fc1 = ColumnParallelLinear(
            config.hidden_size, config.intermediate_size, weight_attr=wa,
            gather_output=False)
        self.fc2 = RowParallelLinear(
            config.intermediate_size, config.hidden_size, weight_attr=wa,
            input_is_parallel=True)
        self.act = getattr(F, config.hidden_act)
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, x):
        return self.dropout(self.fc2(self.act(self.fc1(x))))


class GPTDecoderLayer(Layer):
    """Pre-LN block (ref: gpt/modeling.py TransformerDecoderLayer with
    normalize_before=True)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.cfg = config
        eps = config.layer_norm_epsilon
        self.ln_1 = LayerNorm(config.hidden_size, epsilon=eps)
        self.attn = GPTAttention(config)
        self.dropout1 = Dropout(config.hidden_dropout_prob)
        self.ln_2 = LayerNorm(config.hidden_size, epsilon=eps)
        self.mlp = GPTMLP(config)

    def forward(self, x, attn_mask=None, cache=None, cache_index=None):
        residual = x
        h = self.ln_1(x)
        if cache is not None:
            h, cache = self.attn(h, attn_mask, cache,
                                 cache_index=cache_index)
        else:
            h = self.attn(h, attn_mask)
        h = self.dropout1(h)
        if getattr(self.cfg, "fused_ln", False):
            # one Pallas pass: s = residual + h AND ln_2(s) — saves a
            # full re-read of s between the add and the norm
            from .modeling_utils import fused_residual_ln
            y, s = fused_residual_ln(residual, h, self.ln_2)
            x = s + self.mlp(y)
        else:
            x = residual + h
            x = x + self.mlp(self.ln_2(x))
        return (x, cache) if cache is not None else x


def _recompute_block(blk, x, attention_mask):
    """jax.checkpoint around one decoder block (array-level function; layer
    params are closed-over tracers, which checkpoint treats as implicit
    inputs). Full recompute: only the block INPUT is saved — saving dot
    outputs (dots_saveable) keeps ~300MB/layer of qkv/mlp activations
    alive and defeats the point on a 16GB chip."""
    from ..autograd import in_jax_trace

    def f(xa):
        out = blk(Tensor(xa), attention_mask)
        return out._value if isinstance(out, Tensor) else out

    xa = x._value if isinstance(x, Tensor) else x
    if not in_jax_trace((xa,)):
        return blk(x, attention_mask)  # eager: nothing to rematerialize
    return Tensor(jax.checkpoint(f)(xa), stop_gradient=False)


class ScannedGPTLayers(ScannedLayerStack):
    """GPT's L decoder blocks through the generic scan-over-layers stack
    (nn/scan_stack.py — O(1-block) compiled program)."""

    def __init__(self, config: GPTConfig):
        super().__init__(
            [GPTDecoderLayer(config)
             for _ in range(config.num_hidden_layers)],
            has_dropout=(config.hidden_dropout_prob > 0
                         or config.attention_probs_dropout_prob > 0),
            recompute=config.recompute)


class GPTEmbeddings(Layer):
    """word (vocab-parallel) + learned position embeddings."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.word_embeddings = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=_init_attr(config))
        self.position_embeddings = Embedding(
            config.max_position_embeddings, config.hidden_size,
            weight_attr=_init_attr(config))
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, input_ids, position_ids=None):
        if position_ids is None:
            s = input_ids.shape[1]
            position_ids = Tensor(jnp.arange(s, dtype=jnp.int32)[None, :])
        return self.dropout(self.word_embeddings(input_ids)
                            + self.position_embeddings(position_ids))


def _resolve_config(name, **overrides):
    cfg = dict(GPT_CONFIGS[name])
    cfg.update(overrides)
    return GPTConfig(**cfg)


def _coerce_config(config, kwargs):
    if config is None:
        return GPTConfig(**kwargs)
    if isinstance(config, dict):
        return GPTConfig(**config)
    return config


class GPTModel(FromPretrainedMixin, Layer):
    """ref: paddlenlp/transformers/gpt/modeling.py GPTModel."""

    def __init__(self, config: GPTConfig = None, **kwargs):
        super().__init__()
        config = _coerce_config(config, kwargs)
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        if config.scan_layers:
            self.h = ScannedGPTLayers(config)
        else:
            self.h = LayerList([GPTDecoderLayer(config)
                                for _ in range(config.num_hidden_layers)])
        self.ln_f = LayerNorm(config.hidden_size,
                              epsilon=config.layer_norm_epsilon)

    @classmethod
    def from_config_name(cls, name, **overrides):
        return cls(_resolve_config(name, **overrides))


    def forward(self, input_ids, position_ids=None, attention_mask=None,
                use_cache=False, cache=None, cache_index=None):
        if position_ids is None and cache_index is not None:
            idx = cache_index._value if isinstance(cache_index, Tensor) \
                else cache_index
            idx = jnp.asarray(idx)
            s = input_ids.shape[1]
            if idx.ndim:
                # per-slot positions (paged serving decode): [B] index
                # vector -> [B, s] position grid
                position_ids = Tensor(
                    idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :])
            else:
                position_ids = Tensor(
                    (idx + jnp.arange(s, dtype=jnp.int32))[None, :])
        elif position_ids is None and cache is not None:
            # cached decode: positions continue after the cache length
            # (ref: GPTModel.forward's past_length offset)
            past = cache[0][0].shape[1]
            s = input_ids.shape[1]
            position_ids = Tensor(
                (past + jnp.arange(s, dtype=jnp.int32))[None, :])
        # causal structure is added by the attention op itself; the user
        # mask is padding-only (ref paddlenlp GPTModel's
        # _prepare_decoder_attention_mask)
        from .modeling_utils import normalize_attention_mask
        attention_mask = normalize_attention_mask(attention_mask)
        x = self.embeddings(input_ids, position_ids)
        x = annotate(x, "dp", None, None)
        new_caches = [] if (use_cache or cache is not None) else None
        if self.config.scan_layers:
            if new_caches is not None:
                raise NotImplementedError(
                    "scan_layers=True does not support the KV-cache "
                    "decode path (the static per-layer cache contract "
                    "rides the unrolled blocks). Build the serving "
                    "model with scan_layers=False — checkpoints convert "
                    "via unstack_layer_state().")
            x = self.h(x, attention_mask)
            return self.ln_f(x)
        for i, blk in enumerate(self.h):
            if new_caches is not None:
                layer_cache = cache[i] if cache is not None else (
                    Tensor(jnp.zeros((x.shape[0], 0,
                                      self.config.num_attention_heads,
                                      self.config.head_dim),
                                     dtype=x.dtype)),) * 2
                x, c = blk(x, attention_mask, layer_cache,
                           cache_index=cache_index)
                new_caches.append(c)
            elif self.config.recompute and self.training:
                x = _recompute_block(blk, x, attention_mask)
            else:
                x = blk(x, attention_mask)
        x = self.ln_f(x)
        if new_caches is not None:
            return x, new_caches
        return x


class GPTForCausalLM(FromPretrainedMixin, Layer):
    """GPTModel + tied vocab-parallel LM head (ref: GPTForCausalLM /
    GPTLMHeadModel in gpt/modeling.py)."""

    def __init__(self, config: GPTConfig = None, **kwargs):
        super().__init__()
        self.gpt = GPTModel(config, **kwargs)
        self.config = self.gpt.config

    @classmethod
    def from_config_name(cls, name, **overrides):
        return cls(_resolve_config(name, **overrides))


    def forward(self, input_ids, position_ids=None, attention_mask=None,
                use_cache=False, cache=None, cache_index=None):
        out = self.gpt(input_ids, position_ids, attention_mask,
                       use_cache=use_cache, cache=cache,
                       cache_index=cache_index)
        if use_cache or cache is not None:
            hidden, new_cache = out
        else:
            hidden, new_cache = out, None
        if (getattr(self.config, "chunked_ce", 0) and self.training
                and new_cache is None):
            # fused head+loss: hand the criterion the HIDDEN states and
            # the tied embedding weight — GPTPretrainingCriterion runs
            # the head matmul chunk-by-chunk inside the loss so the
            # full [N, vocab] logits never materialize (config docs).
            # Under a trace, snapshot the weight's CURRENT (traced,
            # AMP-cast) value into a fresh Tensor: functional_call
            # restores the Parameter object's _value after forward
            # returns, so passing the Parameter itself would bake the
            # stale concrete array into the jit as a constant (no grads
            # to the tied weight through the head). EAGERLY the reverse
            # holds: a fresh Tensor is a detached tape leaf that would
            # silently swallow the tied-embedding grad under
            # loss.backward() — pass the Parameter itself there
            # (ADVICE r5 #1).
            from ..autograd import in_jax_trace
            w = self.gpt.embeddings.word_embeddings.weight
            lm_w = (Tensor(w._value, stop_gradient=w.stop_gradient)
                    if in_jax_trace((w._value,)) else w)
            return {"_loss_only_aux": True,
                    "hidden": hidden,
                    "lm_weight": lm_w,
                    "chunked_ce": int(self.config.chunked_ce)}
        # vocab stays sharded under shard_map: GPTPretrainingCriterion's
        # ParallelCrossEntropy consumes vocab-LOCAL logits (Megatron-style)
        with jax.named_scope("lm_head"):
            logits = parallel_matmul(
                hidden, self.gpt.embeddings.word_embeddings.weight,
                transpose_y=True, gather_output=False)
        if new_cache is not None:
            return logits, new_cache
        return logits

    # -- generation ---------------------------------------------------------
    def generate(self, input_ids, max_new_tokens=20, temperature=1.0,
                 top_k=0, top_p=1.0, repetition_penalty=1.0, num_beams=1,
                 length_penalty=1.0, eos_token_id=None, pad_token_id=0,
                 decode_strategy=None, seed=None, cache_dtype="float32"):
        """ref: paddlenlp.generation.GenerationMixin. Greedy
        (temperature=0/top_k=0) or top-k sampled decode runs the eager KV-
        cache loop below (parity surface); top_p / repetition_penalty /
        eos early-stop / beam search delegate to the jit-compiled decode
        in paddle_tpu.nlp.generation (one XLA program, the fast path)."""
        if (num_beams > 1 or top_p < 1.0 or repetition_penalty != 1.0
                or eos_token_id is not None or decode_strategy is not None
                or str(cache_dtype) != "float32"):
            from .generation import generate as _jit_generate
            return _jit_generate(
                self, input_ids, max_new_tokens=max_new_tokens,
                temperature=temperature, top_k=top_k, top_p=top_p,
                repetition_penalty=repetition_penalty, num_beams=num_beams,
                length_penalty=length_penalty, eos_token_id=eos_token_id,
                pad_token_id=pad_token_id, decode_strategy=decode_strategy,
                seed=0 if seed is None else seed, cache_dtype=cache_dtype)
        was_training = self.training
        self.eval()
        ids = input_ids if isinstance(input_ids, Tensor) else Tensor(input_ids)
        key = jax.random.PRNGKey(0 if seed is None else seed)
        logits, cache = self.forward(ids, use_cache=True)
        out_ids = ids._value
        for _ in range(max_new_tokens):
            last = logits._value[:, -1, :].astype(jnp.float32)
            if top_k and temperature > 0:
                vals, idx = jax.lax.top_k(last / temperature, top_k)
                key, sub = jax.random.split(key)
                pick = jax.random.categorical(sub, vals)
                nxt = jnp.take_along_axis(idx, pick[:, None], axis=-1)[:, 0]
            else:
                nxt = jnp.argmax(last, axis=-1)
            nxt = nxt.astype(out_ids.dtype)
            out_ids = jnp.concatenate([out_ids, nxt[:, None]], axis=1)
            pos = Tensor(jnp.full((ids.shape[0], 1), out_ids.shape[1] - 1,
                                  dtype=jnp.int32))
            logits, cache = self.forward(
                Tensor(nxt[:, None]), position_ids=pos, cache=cache)
        if was_training:
            self.train()
        return Tensor(out_ids)


GPTLMHeadModel = GPTForCausalLM


class GPTPretrainingCriterion(Layer):
    """Masked LM loss (ref: gpt/modeling.py GPTPretrainingCriterion):
    mean of token CE where loss_mask==1, vocab-parallel safe."""

    def __init__(self, config=None):
        super().__init__()
        self.ce = ParallelCrossEntropy()

    def forward(self, prediction_scores, masked_lm_labels, loss_mask=None):
        if isinstance(prediction_scores, dict) and \
                "chunked_ce" in prediction_scores:
            loss = self._chunked_head_ce(
                prediction_scores["hidden"],
                prediction_scores["lm_weight"],
                masked_lm_labels, prediction_scores["chunked_ce"])
        else:
            loss = self.ce(prediction_scores, masked_lm_labels)
        if loss_mask is not None:
            m = loss_mask if isinstance(loss_mask, Tensor) else Tensor(loss_mask)
            num = (loss * m.astype(loss.dtype)).sum()
            den = m.astype(loss.dtype).sum()
            return num / den
        return loss.mean()

    @staticmethod
    def _chunked_head_ce(hidden, weight, labels, chunk):
        """Per-token CE with the tied-head matmul fused into the loss,
        lax.scan over token chunks + jax.checkpoint: each chunk's
        [chunk, vocab] logits live only inside one scan step (and are
        recomputed in backward), so peak HBM holds chunk*vocab instead
        of B*S*vocab. Grads to hidden and weight flow through the scan
        transpose (weight cotangents accumulate across chunks)."""
        from ..autograd import apply_op
        from ..distributed.fleet.mpu import axis_bound
        if axis_bound("mp"):
            # inside shard_map the weight is the vocab-LOCAL shard: the
            # chunked lse/gather would silently cover one shard's
            # partition function. ParallelCrossEntropy owns that path.
            raise NotImplementedError(
                "chunked_ce does not run inside shard_map tensor "
                "parallelism (vocab-sharded weight) — use the default "
                "head + ParallelCrossEntropy there; under GSPMD "
                "annotation-based mp, chunked_ce is fine (XLA "
                "partitions the per-chunk matmul globally)")

        def run(h, w, y):
            b, s, hd = h.shape
            n = b * s
            h2 = h.reshape(n, hd)
            y2 = y.reshape(n)
            c = max(1, min(int(chunk), n))
            pad = (-n) % c
            if pad:
                h2 = jnp.concatenate(
                    [h2, jnp.zeros((pad, hd), h2.dtype)])
                y2 = jnp.concatenate(  # pad rows count as ignored
                    [y2, jnp.full((pad,), -100, y2.dtype)])
            hc = h2.reshape(-1, c, hd)
            yc = y2.reshape(-1, c)

            @jax.checkpoint
            def body(carry, xs):
                h_c, y_c = xs
                logits = jnp.einsum(
                    "ch,vh->cv", h_c, w,
                    preferred_element_type=jnp.float32)
                lse = jax.scipy.special.logsumexp(logits, axis=-1)
                # ignore_index=-100 parity with ParallelCrossEntropy:
                # ignored positions contribute EXACTLY 0 loss
                ok = y_c != -100
                safe = jnp.clip(y_c.astype(jnp.int32), 0, None)
                picked = jnp.take_along_axis(
                    logits, safe[:, None], axis=-1)[:, 0]
                return carry, jnp.where(ok, lse - picked, 0.0)
            _, losses = jax.lax.scan(body, 0.0, (hc, yc))
            return losses.reshape(-1)[:n].reshape(b, s)

        return apply_op(run, hidden, weight,
                        labels if isinstance(labels, Tensor)
                        else Tensor(labels))


class GPTForCausalLMPipe(Layer):
    """Pipeline-parallel GPT (ref: paddlenlp/transformers/gpt/modeling_pp.py
    GPTForCausalLMPipe — PipelineLayer of [embedding, N decoder LayerDescs,
    ln_f, tied lm-head]).

    TPU-native split of responsibilities: the decoder trunk — where the
    per-layer weights live — runs through the shard_map+ppermute pipeline
    over the 'pp' mesh axis (equal-structure stages of
    num_hidden_layers/pp blocks each); embeddings, final LN and the tied
    LM head run outside the pipelined region, partitioned by GSPMD over
    dp/mp like any other op (the reference pins them to the first/last
    stage rank instead — under one SPMD program there is no rank to pin
    to, and XLA already shards the vocab matmul over 'mp').

    Composes dp x mp x pp: batch sharded over 'dp', weights over 'mp'
    (shard_model), trunk stages over 'pp'. Dropout must be 0 inside the
    trunk (stage_fn runs without a traced rng stream).
    """

    def __init__(self, config: GPTConfig = None, mesh=None, n_micro=None,
                 **kwargs):
        super().__init__()
        from ..distributed.fleet.pipeline import PipelineLayer
        config = _coerce_config(config, kwargs)
        if config.hidden_dropout_prob or config.attention_probs_dropout_prob:
            # inside the pipelined shard_map+scan there is no traced rng
            # stream: one mask would be baked in at trace time and reused
            # for every microbatch/stage/tick — silently wrong, so refuse
            raise ValueError(
                "GPTForCausalLMPipe requires hidden_dropout_prob=0 and "
                "attention_probs_dropout_prob=0 (dropout masks cannot vary "
                "across pipeline microbatches)")
        if getattr(config, "chunked_ce", 0):
            raise NotImplementedError(
                "chunked_ce is not wired through GPTForCausalLMPipe "
                "(its head computes full logits after the pipelined "
                "trunk) — set chunked_ce=0 for pipeline parallelism, or "
                "use GPTForCausalLM")
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.pipe = PipelineLayer(
            [GPTDecoderLayer(config)
             for _ in range(config.num_hidden_layers)],
            num_virtual_pipeline_stages=
            config.num_virtual_pipeline_stages)
        self.ln_f = LayerNorm(config.hidden_size,
                              epsilon=config.layer_norm_epsilon)
        self.mesh = mesh
        self.n_micro = n_micro

    @classmethod
    def from_config_name(cls, name, mesh=None, n_micro=None, **overrides):
        return cls(_resolve_config(name, **overrides), mesh=mesh,
                   n_micro=n_micro)

    def forward(self, input_ids, position_ids=None):
        x = self.embeddings(input_ids, position_ids)
        x = annotate(x, "dp", None, None)
        x = self.pipe(x, n_micro=self.n_micro, mesh=self.mesh)
        x = self.ln_f(x)
        with jax.named_scope("lm_head"):
            return parallel_matmul(
                x, self.embeddings.word_embeddings.weight,
                transpose_y=True, gather_output=False)
