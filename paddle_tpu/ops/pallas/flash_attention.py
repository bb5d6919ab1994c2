"""Flash attention, Pallas TPU.

ref parity: paddle/phi/kernels/gpu/flash_attn_kernel.cu (flash-attn v2:
causal + padding masks + dropout, fwd and bwd).
TPU-native: online-softmax tiles sized for the MXU (128x128 blocks held in
VMEM, fp32 accumulators in scratch), grid (batch*heads, q_blocks, k_blocks)
with the k dimension innermost so the running (m, l, acc) state lives in
VMEM scratch across k iterations. Backward is the standard two-kernel
recompute split (dq; then dk/dv) using the saved row logsumexp — no S x S
probability matrix ever hits HBM.

Feature set (all in-kernel, static shapes):
- causal masking (bottom-right aligned for uneven q/kv lengths);
- per-sequence KV padding lengths (`kv_lens` [B] int32, read from SMEM) —
  the TPU shape of the reference's varlen/padding mask support;
- dropout on the attention probabilities, flash-attn v2 style (the softmax
  denominator uses the un-dropped p; the same mask is REGENERATED in the
  backward kernels from a counter-based hash of (seed, batch-head,
  element position) — no mask tensor is ever stored);
- flash decode: single-query attention against a long padded KV cache
  (`flash_decode`), the generation-time path.

Layout: public entry takes [B, S, H, D] (the reference's layout) and runs
kernels on [B*H, S, D].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import pallas_call


#   measured on v5e (b8 h16 d64, fwd+bwd, causal): 512x512 blocks beat both
#   128x128 (2.2-4.5x) and XLA's fused attention (1.2x @1k ... 1.8x @4k) —
#   large tiles keep the MXU busy across the k-scan and amortize the
#   per-block rescale
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30


def _fit_block(seq, want, head_dim):
    """Pick the kernel block for one sequence axis.

    seq <= want: the whole sequence is one block. Otherwise: halve `want`
    (scaled down for wide heads so bwd tiles stay within VMEM — the 512
    default was measured at d=64) until it divides seq, floored at 128;
    if nothing >= 128 divides seq the caller's validity check rejects the
    shape (tiny tiles would silently run orders of magnitude slower than
    the XLA fallback)."""
    want = max(128, (want * 64) // max(head_dim, 64))
    if seq <= want:
        return seq
    b = want
    while b > 128 and seq % b:
        b //= 2
    return b
# trailing lane dim for per-row stats (lse, delta): Mosaic requires the last
# block dim to be 128-divisible or equal to the array dim, so per-row vectors
# are carried as [bh, sq, 8] with the value replicated over the 8 lanes.
_LSE_LANES = 8


def _positions(shape, qi, ki, block_q, block_k):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return q_pos, k_pos


def _mask_s(s, qi, ki, block_q, block_k, offset, causal, kv_len):
    """Apply causal and/or kv-length masking to the score tile."""
    q_pos, k_pos = _positions(s.shape, qi, ki, block_q, block_k)
    neg = jnp.asarray(_NEG_INF, s.dtype)
    if causal:
        s = jnp.where(q_pos + offset >= k_pos, s, neg)
    if kv_len is not None:
        s = jnp.where(k_pos < kv_len, s, neg)
    return s


def _dropout_keep(seed, b, qi, ki, shape, block_q, block_k, sk, rate):
    """Deterministic keep-mask tile from a murmur3-finalizer hash of the
    GLOBAL element position — bwd kernels regenerate the identical mask
    from the same (seed, b, position) regardless of their grid order.
    Plain uint32 vector ops: lowers on Mosaic AND runs in interpret mode
    (pltpu.prng_* has no interpret path)."""
    q_pos, k_pos = _positions(shape, qi, ki, block_q, block_k)
    gid = (q_pos * sk + k_pos).astype(jnp.uint32)
    x = gid ^ (seed.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
               + jnp.uint32(b).astype(jnp.uint32) * jnp.uint32(0x85EBCA6B))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    # 24-bit threshold compare
    thresh = jnp.uint32(int(rate * (1 << 24)))
    return (x >> 8) >= thresh


def _fwd_kernel(lens_ref, seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, sm_scale, causal, block_q,
                block_k, offset, use_lens, dropout_p, sk):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = (ki * block_k < (qi + 1) * block_q + offset) if causal else True
    if use_lens:
        # skip key blocks that are entirely padding (decode over a long
        # padded cache would otherwise burn full MXU work per dead block)
        run = run & (ki * block_k < lens_ref[b])

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        kv_len = lens_ref[b] if use_lens else None
        s = _mask_s(s, qi, ki, block_q, block_k, offset, causal, kv_len)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        # hard-masked entries must contribute exactly 0 even in a fully
        # masked row (where m_new == _NEG_INF would otherwise make p = 1);
        # with l = 0 the final tick's safe_l guard then emits a 0 output row
        p = jnp.where(s > _NEG_INF / 2, p, jnp.float32(0.0))
        alpha = jnp.exp(m_prev - m_new)
        # denominator from the UN-dropped p (flash-attn v2 dropout order)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_p:
            keep = _dropout_keep(seed_ref[0], b, qi, ki, p.shape,
                                 block_q, block_k, sk, dropout_p)
            p = jnp.where(keep, p / (1.0 - dropout_p), jnp.float32(0.0))
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, jnp.float32(1.0), l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        # lse is stored [bh, sq, 8]: the trailing size-8 lane dim exists only
        # to satisfy Mosaic's block-shape rules (a (1, block_q) block is not
        # lowerable); the row value is replicated across it.
        lse_ref[0] = jnp.broadcast_to(
            m_scr[:, :1] + jnp.log(safe_l), (m_scr.shape[0], _LSE_LANES))


def _recompute_p(q_ref, k_ref, lse_ref, qi, ki, *, sm_scale, causal,
                 block_q, block_k, offset, kv_len):
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    s = _mask_s(s, qi, ki, block_q, block_k, offset, causal, kv_len)
    p = jnp.exp(s - lse_ref[0][:, :1])
    # masked entries contribute no gradient (matches fwd's hard zero)
    return jnp.where(s > _NEG_INF / 2, p, jnp.float32(0.0))


def _dq_kernel(lens_ref, seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, acc_scr, *, sm_scale, causal, block_q,
               block_k, offset, use_lens, dropout_p, sk):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = (ki * block_k < (qi + 1) * block_q + offset) if causal else True
    if use_lens:
        run = run & (ki * block_k < lens_ref[b])

    @pl.when(run)
    def _():
        kv_len = lens_ref[b] if use_lens else None
        p = _recompute_p(q_ref, k_ref, lse_ref, qi, ki, sm_scale=sm_scale,
                         causal=causal, block_q=block_q, block_k=block_k,
                         offset=offset, kv_len=kv_len)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout_p:
            keep = _dropout_keep(seed_ref[0], b, qi, ki, p.shape,
                                 block_q, block_k, sk, dropout_p)
            dp = jnp.where(keep, dp / (1.0 - dropout_p), jnp.float32(0.0))
        ds = p * (dp - delta_ref[0][:, :1])
        acc_scr[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(lens_ref, seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale,
                causal, block_q, block_k, offset, use_lens, dropout_p, sk):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = ((qi + 1) * block_q + offset > ki * block_k) if causal else True
    if use_lens:
        run = run & (ki * block_k < lens_ref[b])

    @pl.when(run)
    def _():
        kv_len = lens_ref[b] if use_lens else None
        p = _recompute_p(q_ref, k_ref, lse_ref, qi, ki, sm_scale=sm_scale,
                         causal=causal, block_q=block_q, block_k=block_k,
                         offset=offset, kv_len=kv_len)
        if dropout_p:
            keep = _dropout_keep(seed_ref[0], b, qi, ki, p.shape,
                                 block_q, block_k, sk, dropout_p)
            scale = 1.0 / (1.0 - dropout_p)
            p_d = jnp.where(keep, p * scale, jnp.float32(0.0))
        else:
            p_d = p
        # dV += P_dropped^T dO
        dv_scr[:] += jax.lax.dot_general(
            p_d.astype(do_ref.dtype), do_ref[0],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout_p:
            dp = jnp.where(keep, dp / (1.0 - dropout_p), jnp.float32(0.0))
        ds = p * (dp - delta_ref[0][:, :1])
        # dK += dS^T Q * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _row_specs(block_q, index=lambda b, i, j: (b, i, 0)):
    return pl.BlockSpec((1, block_q, _LSE_LANES), index)


def _smem_full(n):
    # rank-1 SMEM blocks must cover the whole array on real TPU lowering;
    # kernels index by their batch-head program id
    return pl.BlockSpec((n,), lambda *_: (0,), memory_space=pltpu.SMEM)


def _fwd_call(q, k, v, lens, seed, causal, sm_scale, dropout_p, block_q,
              block_k, interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
    grid = (bh, sq // block_q, sk // block_k)
    use_lens = lens is not None
    kern = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, offset=sk - sq, use_lens=use_lens,
        dropout_p=dropout_p, sk=sk)
    lens_in = lens if use_lens else jnp.zeros((bh,), jnp.int32)
    seed_in = seed if seed is not None else jnp.zeros((1,), jnp.int32)
    return pallas_call(
        kern,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            _smem_full(bh),
            _smem_full(1),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            _row_specs(block_q),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, _LSE_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(lens_in, seed_in, q, k, v)


def _bwd_call(res, g, causal, sm_scale, dropout_p, block_q, block_k,
              interpret):
    q, k, v, o, lse, lens, seed = res
    do = g
    bh, sq, d = q.shape
    sk = k.shape[1]
    use_lens = lens is not None
    lens_in = lens if use_lens else jnp.zeros((bh,), jnp.int32)
    seed_in = seed if seed is not None else jnp.zeros((1,), jnp.int32)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (bh, sq, _LSE_LANES))

    common = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, offset=sk - sq, use_lens=use_lens,
                  dropout_p=dropout_p, sk=sk)
    dq_kern = functools.partial(_dq_kernel, **common)
    dq = pallas_call(
        dq_kern,
        name="flash_bwd_dq",
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[
            _smem_full(bh),
            _smem_full(1),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            _row_specs(block_q),
            _row_specs(block_q),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(lens_in, seed_in, q, k, v, do, lse, delta)

    dkv_kern = functools.partial(_dkv_kernel, **common)
    dk, dv = pallas_call(
        dkv_kern,
        name="flash_bwd_dkv",
        grid=(bh, sk // block_k, sq // block_q),
        in_specs=[
            _smem_full(bh),
            _smem_full(1),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            _row_specs(block_q, lambda b, j, i: (b, i, 0)),
            _row_specs(block_q, lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(lens_in, seed_in, q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_bhsd(q, k, v, lens, seed, causal, sm_scale, dropout_p, block_q,
                block_k, interpret):
    o, _ = _fwd_call(q, k, v, lens, seed, causal, sm_scale, dropout_p,
                     block_q, block_k, interpret)
    return o


def _flash_fwd_rule(q, k, v, lens, seed, causal, sm_scale, dropout_p,
                    block_q, block_k, interpret):
    o, lse = _fwd_call(q, k, v, lens, seed, causal, sm_scale, dropout_p,
                       block_q, block_k, interpret)
    return o, (q, k, v, o, lse, lens, seed)


def _flash_bwd_rule(causal, sm_scale, dropout_p, block_q, block_k,
                    interpret, res, g):
    dq, dk, dv = _bwd_call(res, g, causal, sm_scale, dropout_p, block_q,
                           block_k, interpret)
    lens, seed = res[5], res[6]
    zlens = (np.zeros(lens.shape, jax.dtypes.float0)
             if lens is not None else None)
    zseed = (np.zeros(seed.shape, jax.dtypes.float0)
             if seed is not None else None)
    return dq, dk, dv, zlens, zseed


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal=False, sm_scale=None, kv_lens=None,
                    dropout_p=0.0, dropout_seed=0,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=None):
    """[B, S, H, D] differentiable flash attention.

    kv_lens: optional [B] int32 — key positions >= kv_lens[b] are masked
    (padding). dropout_p/dropout_seed: in-kernel attention dropout
    (training); masks are regenerated in backward, nothing stored.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_q = _fit_block(sq, block_q, d)
    block_k = _fit_block(sk, block_k, d)
    if sq % block_q or sk % block_k or block_q % 8 or block_k % 8:
        raise ValueError(
            f"flash_attention requires seq lens tileable into 8-row blocks "
            f"of at least 128, got sq={sq} (block {block_q}), sk={sk} "
            f"(block {block_k}); pad or use F.scaled_dot_product_attention")

    def fold(x):
        return jnp.swapaxes(x, 1, 2).reshape(x.shape[0] * x.shape[2],
                                             x.shape[1], x.shape[3])

    lens = None
    if kv_lens is not None:
        lens = jnp.repeat(jnp.asarray(kv_lens, jnp.int32), h)
    seed = None
    if dropout_p:
        seed = jnp.asarray([dropout_seed], jnp.int32).reshape((1,))
    o = _flash_bhsd(fold(q), fold(k), fold(v), lens, seed, causal,
                    sm_scale, float(dropout_p), block_q, block_k, interpret)
    return jnp.swapaxes(o.reshape(b, h, sq, d), 1, 2)


_DECODE_Q_ROWS = 8  # Mosaic minimum sublane tile for f32


def flash_decode(q, k_cache, v_cache, kv_lens, sm_scale=None,
                 block_k=DEFAULT_BLOCK_K, interpret=None):
    """Single-step decode attention against a padded KV cache.

    q [B, 1, H, D]; k_cache/v_cache [B, S, H, D] (S static, padded);
    kv_lens [B] int32 — entries at positions >= kv_lens[b] are padding.
    Returns [B, 1, H, D]. ref: the reference's flash decode / paged
    attention path for generation; here the fwd kernel runs with the query
    padded to the 8-sublane minimum tile, masked by kv_lens.
    """
    b, sq, h, d = q.shape
    assert sq == 1, "flash_decode is the single-query path"
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    qp = jnp.concatenate(
        [q, jnp.zeros((b, _DECODE_Q_ROWS - 1) + q.shape[2:], q.dtype)],
        axis=1)

    def fold(x):
        return jnp.swapaxes(x, 1, 2).reshape(x.shape[0] * x.shape[2],
                                             x.shape[1], x.shape[3])

    sk = k_cache.shape[1]
    block_k = min(block_k, sk)
    if sk % block_k:
        raise ValueError(
            f"flash_decode requires the cache length to be divisible by "
            f"block_k, got S={sk} (block {block_k}); pad the cache")
    lens = jnp.repeat(jnp.asarray(kv_lens, jnp.int32), h)
    o, _ = _fwd_call(fold(qp), fold(k_cache), fold(v_cache), lens, None,
                     False, sm_scale, 0.0, _DECODE_Q_ROWS, block_k,
                     interpret)
    o = jnp.swapaxes(o.reshape(b, h, _DECODE_Q_ROWS, d), 1, 2)
    return o[:, :1]
