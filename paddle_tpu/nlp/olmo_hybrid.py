"""Olmo-Hybrid-7B (allenai; `model_type: olmo_hybrid`), TPU-native, for
serving.

Source: https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json.
`layer_types` is (`linear_attention` x3, `full_attention`) x8. Every layer
puts its norms after the sublayer, as Olmo 2 and 3 do:
`h = x + RMSNorm(Mixer(x))`, `y = h + RMSNorm(MLP(h))`, no biases, the MLP
a SwiGLU. Where the config is silent the readings are those of the
configuration file's `assumed` list:

- `full_attention`: `q = RMSNorm(x W_q)`, `k = RMSNorm(x W_k)` over the
  whole projection (Olmo's QK-norm), `v = x W_v`, heads of
  `hidden / heads`, causal softmax attention, `W_o`. No rotary embedding:
  the config's `rope_parameters.rope_theta` is null, and positions reach
  these layers through the recurrent ones. The prefill goes through
  ops/attention.flash_attention, the decode step through the paged K/V
  cache (`paged_update_and_attend`).
- `linear_attention`, Gated DeltaNet (Yang, Kautz and Hatamizadeh,
  arXiv:2412.06464, in flash-linear-attention's `GatedDeltaNet` form):
  `[q, k, v] = SiLU(conv([x W_q, x W_k, x W_v]))`, a depthwise causal
  convolution of `linear_conv_kernel_dim` taps; per head
  `q = l2norm(q) / sqrt(dk)`, `k = l2norm(k)`; `beta = 2 sigmoid(x W_b)`
  (`linear_allow_neg_eigval`: beta in (0, 2)); `g = -exp(A_log)
  softplus(x W_a + dt_bias)`, `alpha = exp(g)`;
  `S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T`
  with S [dk, dv] a head; `o_t = S_t^T q_t`; the output
  `W_o [RMSNorm(o_t) * SiLU(x W_z)]`, one gain of dv for every head.

  A prompt runs the chunkwise form (`chunked_gated_delta`): chunks of 64
  positions, the WY/UT transform within a chunk (one unit-lower
  triangular solve), a `lax.scan` over the chunks for the state. A
  decode step runs the recurrence on each slot's row: on a TPU one Pallas
  kernel that reads and writes each slot's state once
  (ops/pallas/gated_delta_decode.py), elsewhere `gated_delta_step`, the
  written specification the kernel is held to (`gated_delta_path`).
  What a sequence carries from token to token is the convolution's last
  `taps - 1` inputs and S, one fixed-size state per serving slot and no
  pages, stored with two heads side by side in a row where dv = 192
  (`paged_cache.DeltaStateSpec`).

Weights are stored in `dtype`; products with them take operands in that
dtype and accumulate in float32; the residual stream, norms, softmax and
logits are float32, and so is everything of the recurrence (`gated_delta`
scope): the state, decays, beta, the triangular solve, and its products
at `Precision.HIGHEST`.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from ..nn.layer import Layer
from ..nn.layers_common import LayerList
from ..nn.layers_norm import RMSNorm
from ..tensor import Tensor
from .axk1 import _mm, _param, _swiglu
from .llama import _repeat_kv
from .paged_cache import (DeltaStateCache, DeltaStateRows, DeltaStateSpec,
                          KVCacheSpec, PagedLayerCache, conv_state_at,
                          delta_heads_paired, delta_heads_unpaired,
                          paged_update_and_attend, record_delta_path)

__all__ = ["OlmoHybridConfig", "OlmoHybridModel", "OlmoHybridForCausalLM",
           "OLMO_HYBRID_CONFIGS", "chunked_gated_delta", "gated_delta_step",
           "gated_delta_path"]

_PUBLISHED_LAYERS = tuple(
    "full_attention" if i % 4 == 3 else "linear_attention"
    for i in range(32))
HI = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6
# positions a chunk of the prefill's scan: one triangular solve of this
# size a chunk and head
CHUNK = 64


@dataclass
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    layer_types: tuple = field(default_factory=lambda: _PUBLISHED_LAYERS)
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        for what, got, want in (
                ("attention_bias", self.attention_bias, False),
                ("tie_word_embeddings", self.tie_word_embeddings, False),
                ("hidden_act", self.hidden_act, "silu"),
                ("linear_num_value_heads", self.linear_num_value_heads,
                 self.linear_num_key_heads)):
            if got != want:
                raise ValueError(f"OlmoHybridConfig: {what} = {got!r}; "
                                 f"this model implements {want!r} only")
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {"linear_attention",
                                         "full_attention"}:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of "
                f"{self.num_hidden_layers}, each 'linear_attention' or "
                f"'full_attention': {self.layer_types}")
        if self.num_attention_heads % self.num_key_value_heads or \
                self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"{self.num_attention_heads} heads over "
                f"{self.num_key_value_heads} K/V heads and hidden "
                f"{self.hidden_size}")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def conv_channels(self):
        """The convolution's channels: q and k, then v, of every head."""
        return self.linear_num_key_heads * (2 * self.linear_key_head_dim
                                            + self.linear_value_head_dim)


OLMO_HYBRID_CONFIGS = {
    # the CPU tests' size: every mechanism, nothing published
    "olmo-hybrid-tiny": dict(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
        layer_types=("linear_attention", "linear_attention",
                     "full_attention", "linear_attention"),
        linear_num_key_heads=2, linear_num_value_heads=2,
        linear_key_head_dim=16, linear_value_head_dim=32,
        max_position_embeddings=4096),
}


def _resolve_config(name, **overrides):
    cfg = dict(OLMO_HYBRID_CONFIGS[name])
    cfg.update(overrides)
    return OlmoHybridConfig(**cfg)


def _norm(n, cfg):
    norm = RMSNorm(n, epsilon=cfg.rms_norm_eps)
    norm.weight._value = norm.weight._value.astype(cfg.dtype)
    return norm


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _mix(x, y, eq):
    return jnp.einsum(eq, x, y, precision=HI)


def chunked_gated_delta(q, k, v, g, beta):
    """The delta rule over whole prompts in chunks. q, k [B, S, H, dk]
    (q scaled), v [B, S, H, dv], g (log decay) and beta [B, S, H], all
    float32. Returns (o [B, S, H, dv], the state after the last position
    [B, H, dk, dv]). A position with g = beta = 0 leaves the state as it
    was, which is how right padding is kept out of it.

    Within a chunk, with G the cumulative sum of g and
    `A[i, j] = beta_i exp(G_i - G_j) k_i.k_j` for j < i, the chunk's
    corrected values are `v' = u - w S0`, with `[u | w] = (I + A)^-1
    [beta v | beta exp(G) k]` (one unit-lower triangular solve);
    `o_i = exp(G_i) q_i S0 + sum_{j<=i} exp(G_i - G_j) (q_i.k_j) v'_j` and
    `S = exp(G_C) S0 + sum_j exp(G_C - G_j) k_j v'_j^T`. Every exponent is
    at most 0: decays near 0 underflow to an exact 0, nothing overflows."""
    b, s, h, dk = k.shape
    dv = v.shape[-1]
    chunk = CHUNK
    n = -(-s // chunk)

    def blocks(x):           # [B, S, H, ...] -> [B, H, N, C, ...]
        x = jnp.pad(x, ((0, 0), (0, n * chunk - s))
                    + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = (blocks(t) for t in (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=-1)                       # [B, H, N, C]
    i = jnp.arange(chunk)
    lower = i[:, None] >= i[None, :]
    diff = cum[..., :, None] - cum[..., None, :]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))  # [.., C, C]
    strict = lower & ~jnp.eye(chunk, dtype=bool)
    a = jnp.where(strict, beta[..., :, None] * decay
                  * _mix(k, k, "...id,...jd->...ij"), 0.0)
    rhs = jnp.concatenate([beta[..., None] * v,
                           (beta * jnp.exp(cum))[..., None] * k], axis=-1)
    uw = jax.lax.linalg.triangular_solve(
        a + jnp.eye(chunk, dtype=a.dtype), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    u, w = uw[..., :dv], uw[..., dv:]
    qk = _mix(q, k, "...id,...jd->...ij") * decay
    q_in = q * jnp.exp(cum)[..., None]
    last = cum[..., -1:]
    k_out = k * jnp.exp(last - cum)[..., None]

    def one(state, xs):
        u, w, qk, q_in, k_out, last = xs
        new_v = u - _mix(w, state, "bhck,bhkv->bhcv")
        o = _mix(q_in, state, "bhck,bhkv->bhcv") + \
            _mix(qk, new_v, "bhij,bhjv->bhiv")
        state = jnp.exp(last)[..., None] * state + \
            _mix(k_out, new_v, "bhck,bhcv->bhkv")
        return state, o

    def chunk_major(x):      # [B, H, N, ...] -> [N, B, H, ...]
        return jnp.moveaxis(x, 2, 0)

    state0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    state, o = jax.lax.scan(one, state0, tuple(
        chunk_major(t) for t in (u, w, qk, q_in, k_out, last)))
    o = jnp.moveaxis(o, 0, 2)                          # [B, H, N, C, dv]
    o = jnp.moveaxis(o, 1, 3).reshape(b, n * chunk, h, dv)[:, :s]
    return o, state


def gated_delta_step(q, k, v, g, beta, state, live=None):
    """One token per slot. q, k [B, H, dk] (q scaled), v [B, H, dv], g and
    beta [B, H], state [B, H, dk, dv], all float32. Returns (o [B, H, dv],
    the new state: of the live slots only). The state is read twice and
    written once: `S^T k` and `S^T q` in one pass, then the update, and
    the readout from those two products, `S'^T q = alpha S^T q +
    (k.q) delta`, so that S' is never read again."""
    alpha = jnp.exp(g)[..., None]                      # [B, H, 1]
    kq = jnp.stack([k, q], axis=-1)                    # [B, H, dk, 2]
    s_kq = jnp.sum(state[..., None] * kq[..., None, :], axis=-3)
    kv_mem, q_mem = s_kq[..., 0], s_kq[..., 1]         # [B, H, dv]
    delta = beta[..., None] * (v - alpha * kv_mem)
    new = alpha[..., None] * state + k[..., :, None] * delta[..., None, :]
    o = alpha * q_mem + jnp.sum(k * q, -1, keepdims=True) * delta
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, state)
    return o, new


def gated_delta_path():
    """How a decode step runs: "kernel" (ops/pallas/gated_delta_decode.py)
    where Pallas kernels compile natively, else "jnp" (`gated_delta_step`,
    on the same storage)."""
    from ..ops.pallas import _common
    return "jnp" if _common.interpret_default() else "kernel"


def _decode_step(q, k, v, g, beta, state, live):
    """One token per slot over the stored state [B, H / r, dk, r * dv]
    (r read off its width): (o [B, H, dv], the new state as stored)."""
    r = state.shape[-1] // v.shape[-1]
    path = gated_delta_path()
    record_delta_path(path, r)
    if path == "kernel":
        from ..ops.pallas.gated_delta_decode import gated_delta_decode
        return gated_delta_decode(q, k, v, g, beta, state, live)
    o, new = gated_delta_step(q, k, v, g, beta,
                              delta_heads_unpaired(state, r), live)
    return o, delta_heads_paired(new, r)


class OlmoHybridGatedDeltaNet(Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        h, heads = cfg.hidden_size, cfg.linear_num_key_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        self.q_proj = _param(self, cfg, h, heads * dk)
        self.k_proj = _param(self, cfg, h, heads * dk)
        self.v_proj = _param(self, cfg, h, heads * dv)
        self.z_proj = _param(self, cfg, h, heads * dv)
        self.a_proj = _param(self, cfg, h, heads)
        self.b_proj = _param(self, cfg, h, heads)
        # [taps, channels]: tap j multiplies the input of `taps - 1 - j`
        # tokens ago, over q, k and v side by side
        self.conv = _param(self, cfg, cfg.linear_conv_kernel_dim,
                           cfg.conv_channels)
        self.A_log = _param(self, cfg, heads)
        self.dt_bias = _param(self, cfg, heads)
        self.o_norm = _norm(dv, cfg)
        self.o_proj = _param(self, cfg, heads * dv, h)

    def _heads(self, mixed, a, b):
        """SiLU of the convolved channels split into q, k, v by heads, and
        the per-head decay and beta. mixed [..., C]; a, b [..., H]."""
        cfg = self.cfg
        heads, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
        act = jax.nn.silu(mixed)
        q, k, v = jnp.split(act, [heads * dk, 2 * heads * dk], axis=-1)
        lead = act.shape[:-1]
        q = _l2norm(q.reshape(lead + (heads, dk))) * dk ** -0.5
        k = _l2norm(k.reshape(lead + (heads, dk)))
        v = v.reshape(lead + (heads, -1))
        beta = jax.nn.sigmoid(b)
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(self.A_log._value.astype(jnp.float32)) * \
            jax.nn.softplus(a + self.dt_bias._value.astype(jnp.float32))
        return q, k, v, g, beta

    def _gated_norm(self, o, z):
        gain = self.o_norm.weight._value.astype(jnp.float32)
        ms = jnp.mean(o * o, -1, keepdims=True)
        o = o * jax.lax.rsqrt(ms + self.cfg.rms_norm_eps) * gain
        return o * jax.nn.silu(z.reshape(o.shape))

    def forward(self, x, cache=None, kv_lens=None):
        """x [B, S, h] Tensor. cache None: (out, DeltaStateRows of the
        state after each row's last true token); a DeltaStateCache
        (S = 1): (out, (the new convolution rows, the new state))."""
        cfg = self.cfg
        u = x._value
        bsz, s = u.shape[0], u.shape[1]
        mixed = jnp.concatenate([_mm(u, self.q_proj._value),
                                 _mm(u, self.k_proj._value),
                                 _mm(u, self.v_proj._value)], axis=-1)
        z = _mm(u, self.z_proj._value)
        a = _mm(u, self.a_proj._value)
        b = _mm(u, self.b_proj._value)
        taps = self.conv._value.astype(jnp.float32)
        n = taps.shape[0]
        with jax.named_scope("gated_delta"):
            if cache is None:
                padded = jnp.pad(mixed, ((0, 0), (n - 1, 0), (0, 0)))
                conv = sum(taps[j] * padded[:, j:j + s] for j in range(n))
                q, k, v, g, beta = self._heads(conv, a, b)
                if kv_lens is not None:
                    true = (jnp.arange(s)[None, :] < kv_lens[:, None])
                    g = jnp.where(true[..., None], g, 0.0)
                    beta = jnp.where(true[..., None], beta, 0.0)
                o, state = chunked_gated_delta(q, k, v, g, beta)
                kept = DeltaStateRows(conv_state_at(mixed, kv_lens, n - 1),
                                      state)
            else:
                old = cache.conv
                window = jnp.concatenate(
                    [old.astype(jnp.float32), mixed[:, -1:]], axis=1)
                conv = jnp.sum(taps[None] * window, axis=1)
                new_conv = window[:, 1:].astype(old.dtype)
                if cache.live is not None:
                    new_conv = jnp.where(cache.live[:, None, None],
                                         new_conv, old)
                q, k, v, g, beta = self._heads(conv, a[:, 0], b[:, 0])
                o, state = _decode_step(q, k, v, g, beta, cache.state,
                                        cache.live)
                o = o[:, None]
                kept = (new_conv, state)
            o = self._gated_norm(o, z)
        out = _mm(o.reshape(bsz, s, -1), self.o_proj._value)
        return Tensor(out), kept


class OlmoHybridAttention(Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        self.q_proj = _param(self, cfg, h, cfg.num_attention_heads * d)
        self.k_proj = _param(self, cfg, h, cfg.num_key_value_heads * d)
        self.v_proj = _param(self, cfg, h, cfg.num_key_value_heads * d)
        self.o_proj = _param(self, cfg, cfg.num_attention_heads * d, h)
        self.q_norm = _norm(cfg.num_attention_heads * d, cfg)
        self.k_norm = _norm(cfg.num_key_value_heads * d, cfg)

    def forward(self, x, cache=None, kv_lens=None):
        """x [B, S, h] Tensor. cache None: (out, (k, v) of [B, S, Hkv, D]:
        what the pages hold); a PagedLayerCache (S = 1): (out, the new
        page arrays)."""
        cfg = self.cfg
        u = x._value
        b, s = u.shape[0], u.shape[1]
        d = cfg.head_dim
        groups = cfg.num_attention_heads // cfg.num_key_value_heads
        q = self.q_norm(Tensor(_mm(u, self.q_proj._value)))._value
        k = self.k_norm(Tensor(_mm(u, self.k_proj._value)))._value
        v = _mm(u, self.v_proj._value)
        q, k, v = (t.reshape(b, s, -1, d) for t in (q, k, v))
        if cache is not None:
            o, kept = paged_update_and_attend(q, k, v, cache, groups=groups)
        else:
            from ..ops.attention import flash_attention
            dt = self.q_proj._value.dtype
            o = flash_attention(
                q.astype(dt), _repeat_kv(k, groups).astype(dt),
                _repeat_kv(v, groups).astype(dt), causal=True,
                kv_lens=kv_lens)
            kept = (k, v)
        return Tensor(_mm(o.reshape(b, s, -1), self.o_proj._value)), kept


class OlmoHybridMLP(Layer):
    """SwiGLU: down(silu(gate(x)) * up(x))."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        h, f = cfg.hidden_size, cfg.intermediate_size
        self.gate_proj = _param(self, cfg, h, f)
        self.up_proj = _param(self, cfg, h, f)
        self.down_proj = _param(self, cfg, f, h)

    def forward(self, x):
        return Tensor(_swiglu(x._value, self.gate_proj._value,
                              self.up_proj._value, self.down_proj._value))


class OlmoHybridDecoderLayer(Layer):
    def __init__(self, cfg: OlmoHybridConfig, index):
        super().__init__()
        self.is_attention = cfg.layer_types[index] == "full_attention"
        if self.is_attention:
            self.self_attn = OlmoHybridAttention(cfg)
        else:
            self.linear_attn = OlmoHybridGatedDeltaNet(cfg)
        self.post_attention_layernorm = _norm(cfg.hidden_size, cfg)
        self.mlp = OlmoHybridMLP(cfg)
        self.post_feedforward_layernorm = _norm(cfg.hidden_size, cfg)

    def forward(self, x, cache=None, kv_lens=None):
        """(y, what the mixer keeps: the prompt's rows or state, or the
        layer's new cache arrays)."""
        mixer = self.self_attn if self.is_attention else self.linear_attn
        a, kept = mixer(x, cache, kv_lens)
        h = Tensor(x._value + self.post_attention_layernorm(a)._value)
        f = self.post_feedforward_layernorm(self.mlp(h))
        return Tensor(h._value + f._value), kept


class OlmoHybridModel(Layer):
    def __init__(self, config: OlmoHybridConfig = None, **kwargs):
        super().__init__()
        if config is None:
            config = OlmoHybridConfig(**kwargs)
        elif isinstance(config, dict):
            config = OlmoHybridConfig(**config)
        self.config = config
        self.embed_tokens = _param(self, config, config.vocab_size,
                                   config.hidden_size)
        self.layers = LayerList([OlmoHybridDecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = _norm(config.hidden_size, config)

    def forward(self, input_ids, attention_mask=None, use_cache=False,
                cache=None, cache_index=None):
        """Three paths of one contract (nlp/serving.py calls the last
        two): no cache -> hidden; `use_cache=True` -> (hidden, per layer a
        (k, v) pair or DeltaStateRows: what the prompt leaves in that
        layer's cache); `cache` a list of PagedLayerCache / DeltaStateCache
        by the layer's kind (one token per slot) -> (hidden, the new
        caches). `attention_mask` [B, S] of ones then zeros marks right
        padding."""
        del cache_index     # the paged caches carry their positions
        if cache is not None:
            for layer, c in zip(self.layers, cache):
                want = PagedLayerCache if layer.is_attention \
                    else DeltaStateCache
                if not isinstance(c, want):
                    raise ValueError(
                        "Olmo-Hybrid decodes through the caches its "
                        "cache_spec() names (nlp/serving.py) only; it has "
                        "no dense static-cache path")
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        kv_lens = None
        if attention_mask is not None and cache is None:
            m = attention_mask._value if isinstance(attention_mask, Tensor) \
                else jnp.asarray(attention_mask)
            kv_lens = jnp.sum(m, axis=-1).astype(jnp.int32)
        x = Tensor(self.embed_tokens._value[ids].astype(jnp.float32))
        kept = []
        for i, layer in enumerate(self.layers):
            x, keep = layer(x, None if cache is None else cache[i], kv_lens)
            kept.append(keep if cache is None else cache[i].replaced(*keep))
        x = self.norm(x)
        return (x, kept) if (use_cache or cache is not None) else x


class OlmoHybridForCausalLM(Layer):
    """OlmoHybridModel and its own output head (untied); float32 logits."""

    def __init__(self, config: OlmoHybridConfig = None, **kwargs):
        super().__init__()
        self.model = OlmoHybridModel(config, **kwargs)
        self.config = self.model.config
        self.lm_head = _param(self, self.config, self.config.hidden_size,
                              self.config.vocab_size)

    @classmethod
    def from_config_name(cls, name, **overrides):
        return cls(_resolve_config(name, **overrides))

    def cache_spec(self):
        """What nlp/serving.py holds for each layer: K/V pages for a full
        attention layer, a per-slot convolution and recurrent state for a
        Gated DeltaNet layer."""
        cfg = self.config
        kv = KVCacheSpec(cfg.num_key_value_heads, cfg.head_dim)
        state = DeltaStateSpec(cfg.conv_channels, cfg.linear_conv_kernel_dim,
                               cfg.linear_num_key_heads,
                               cfg.linear_key_head_dim,
                               cfg.linear_value_head_dim)
        return [kv if t == "full_attention" else state
                for t in cfg.layer_types]

    def forward(self, input_ids, attention_mask=None, use_cache=False,
                cache=None, cache_index=None):
        out = self.model(input_ids, attention_mask, use_cache=use_cache,
                         cache=cache, cache_index=cache_index)
        hidden, kept = out if isinstance(out, tuple) else (out, None)
        with jax.named_scope("lm_head"):
            logits = Tensor(_mm(hidden._value, self.lm_head._value))
        return logits if kept is None else (logits, kept)
