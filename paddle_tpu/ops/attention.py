"""Flash attention for TPU.

ref parity: paddle.nn.functional.flash_attention (CUDA flash-attn v2 in the
reference). Here: a Pallas TPU kernel (ops/pallas/flash_attention.py) tiled
for the MXU, with an XLA-fusable jnp fallback. The public entry keeps the
reference's [batch, seq, heads, head_dim] layout.

In-kernel coverage (matching the reference's flash_attn feature set):
causal, per-sequence KV padding lengths (kv_lens), attention dropout
(mask regenerated in backward). Arbitrary dense attn_mask tensors still
fall back to the jnp path — the reference routes those off flash too.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_PALLAS_MIN_SEQ = 128
_PALLAS_HEAD_DIMS = (64, 128, 256)


def _on_tpu():
    # no try/except: a backend that fails to initialise must surface,
    # not read as "cpu" and silently switch the kernels off
    return jax.default_backend() == "tpu"


def flash_attention_available(q_shape, k_shape, attn_mask, dropout_p) -> bool:
    """Pallas kernel handles: TPU, no explicit dense mask (padding lengths
    and dropout ARE supported in-kernel), seq multiple of block, supported
    head dims."""
    if attn_mask is not None:
        return False
    if not _on_tpu():
        return False
    if len(q_shape) != 4:
        return False
    b, sq, h, d = q_shape
    sk = k_shape[1]
    return (d in _PALLAS_HEAD_DIMS and sq % _PALLAS_MIN_SEQ == 0
            and sk % _PALLAS_MIN_SEQ == 0)


def flash_attention(q, k, v, causal=False, sm_scale=None, kv_lens=None,
                    dropout_p=0.0, dropout_seed=0):
    """[B, S, H, D] flash attention. Uses the Pallas kernel on TPU, jnp
    reference otherwise."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if flash_attention_available(q.shape, k.shape, None, dropout_p):
        return _flash_per_shard(q, k, v, kv_lens, dropout_seed,
                                causal=causal, sm_scale=sm_scale,
                                dropout_p=dropout_p)
    return reference_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               kv_lens=kv_lens, dropout_p=dropout_p,
                               dropout_seed=dropout_seed)


def _flash_per_shard(q, k, v, kv_lens, dropout_seed, **static):
    """The Pallas kernel, run per shard when a device mesh is active.

    Mosaic kernels cannot be partitioned by GSPMD ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map" — what every mesh layout raised on a 4-chip v5e host),
    so under an explicitly set mesh the call is wrapped in a shard_map
    that makes every not-yet-manual mesh axis manual: the batch splits
    over 'dp' and the heads over 'mp' where they divide (attention is
    independent per batch row and per head), any other axis just
    replicates. Inside the pipeline's 'pp'-manual region this nests and
    only the remaining axes are taken. Off-mesh it is a plain call."""
    from ..distributed import mesh as mesh_mod
    from .pallas.flash_attention import flash_attention as pallas_flash
    mesh = mesh_mod._global_mesh
    context = jax.sharding.get_abstract_mesh()
    bound = set(context.manual_axes) if not context.empty else set()
    free = [] if mesh is None else \
        [a for a in mesh.axis_names if a not in bound]
    if not free:
        return pallas_flash(q, k, v, kv_lens=kv_lens,
                            dropout_seed=dropout_seed, **static)

    def split(axis, *dims):
        ok = axis in free and all(d % mesh.shape[axis] == 0 for d in dims)
        return axis if ok else None
    b_axis = split("dp", q.shape[0])
    h_axis = split("mp", q.shape[2], k.shape[2])
    from jax.sharding import PartitionSpec as P
    qkv = P(b_axis, None, h_axis, None)
    use_lens = kv_lens is not None
    seed = jnp.asarray(dropout_seed, jnp.int32)

    def local(q, k, v, seed, *lens):
        if static["dropout_p"]:
            # distinct masks per shard: the kernel hashes LOCAL positions
            for a in free:
                seed = seed * jnp.int32(31) + jax.lax.axis_index(a)
        return pallas_flash(q, k, v, kv_lens=lens[0] if use_lens else None,
                            dropout_seed=seed, **static)

    args = (q, k, v, seed) + ((jnp.asarray(kv_lens, jnp.int32),)
                              if use_lens else ())
    specs = (qkv, qkv, qkv, P()) + ((P(b_axis),) if use_lens else ())
    return jax.shard_map(
        local, mesh=None if bound else mesh, in_specs=specs, out_specs=qkv,
        axis_names=frozenset(free), check_vma=False)(*args)


def flash_decode(q, k_cache, v_cache, kv_lens, sm_scale=None):
    """Single-query decode against a padded KV cache ([B, 1, H, D] x
    [B, S, H, D] + kv_lens [B]). Pallas on TPU (opt-in), jnp elsewhere.

    The Pallas decode kernel sits behind PADDLE_TPU_FLASH_DECODE=1. It
    compiles natively on a v5e and matches the jnp path (chip_smoke.py
    kernels phase: 2.5e-3 f32 / 6e-4 bf16 normalised max error at b8
    s1024 h12 d64, PR 21); whether it is FASTER than the jnp path has
    not been measured, so the jnp path stays the default until a
    benchmark cell decides."""
    import os
    d = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    sk = k_cache.shape[1]
    if (os.environ.get("PADDLE_TPU_FLASH_DECODE") == "1"
            and _on_tpu() and d in _PALLAS_HEAD_DIMS
            and sk % _PALLAS_MIN_SEQ == 0):
        from .pallas.flash_attention import flash_decode as pallas_decode
        return pallas_decode(q, k_cache, v_cache, kv_lens,
                             sm_scale=sm_scale)
    return reference_attention(q, k_cache, v_cache, sm_scale=sm_scale,
                               kv_lens=kv_lens)


def paged_flash_available(head_dim, page_size, use_flash=None):
    """Gate for the paged GQA decode kernel (serving engine /
    nlp/paged_cache.py). The kernel compiles natively inside the
    engine's decode scan on a v5e and agrees with the jnp reference
    path (7e-3 normalised max error on decode-step logits, gpt2-en,
    page_size 128, bf16 cache — chip_smoke.py serve phase, PR 21).
    Which path is faster is unmeasured, so auto mode keeps the
    reference path unless PADDLE_TPU_FLASH_DECODE=1.

    use_flash: True -> the kernel, anywhere the SHAPE supports it
    (interpret mode off-TPU — the CPU tests exercise the identical
    kernel), ValueError where it does not: an explicit request is
    never silently served by the reference. False -> off. None ->
    auto (TPU + env gate + supported shape)."""
    shape_ok = head_dim in _PALLAS_HEAD_DIMS and page_size % 8 == 0
    if use_flash is False:
        return False
    if use_flash is True:
        if not shape_ok:
            raise ValueError(
                f"use_flash=True: the paged flash-decode kernel needs "
                f"head_dim in {_PALLAS_HEAD_DIMS} and page_size % 8 == 0, "
                f"got head_dim={head_dim} page_size={page_size}; pass "
                "use_flash=False for the jnp reference path")
        return True
    import os
    return (shape_ok and _on_tpu()
            and os.environ.get("PADDLE_TPU_FLASH_DECODE") == "1")


def paged_flash_decode(q, k_pages, v_pages, page_table, lens,
                       k_scale=None, v_scale=None, sm_scale=None):
    """Paged GQA decode attention — Pallas kernel entry used by
    paged_cache.paged_update_and_attend when the layer cache is built
    with use_flash=True (the caller owns the gate via
    paged_flash_available). Runs the kernel natively on TPU, in
    interpret mode elsewhere so CPU tests/ladder rungs execute the
    identical kernel."""
    from .pallas.flash_decode import paged_flash_decode as kernel
    return kernel(q, k_pages, v_pages, page_table, lens,
                  k_scale=k_scale, v_scale=v_scale, sm_scale=sm_scale)


def reference_attention(q, k, v, causal=False, sm_scale=None, kv_lens=None,
                        dropout_p=0.0, dropout_seed=0):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    qh, kh, vh = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * sm_scale
    sq, sk = logits.shape[-2], logits.shape[-1]
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(mask, logits, -jnp.inf)
    if kv_lens is not None:
        lm = jnp.arange(sk)[None, None, None, :] < \
            jnp.asarray(kv_lens)[:, None, None, None]
        logits = jnp.where(lm, logits, -jnp.inf)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    # fully-masked rows produce NaN softmax -> zero them (kernel outputs 0)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs).astype(q.dtype)
    if dropout_p:
        key = jax.random.PRNGKey(dropout_seed)
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)
