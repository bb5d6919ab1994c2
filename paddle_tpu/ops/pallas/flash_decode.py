"""Paged GQA flash-decode, Pallas TPU.

ref parity: the reference's PagedAttention decode kernels
(paddle/fluid/operators/fused/ block-wise attention; vLLM
arXiv:2309.06180) and FlashAttention-class single-row decode.

One grid step = one (slot, kv head, page): the kernel walks a slot's
page list innermost, carrying the online-softmax state (m, l, acc) in
VMEM scratch, so a query row attends its whole paged history without
the [B, S_cap, ...] gather the jnp reference pays. TPU-native points:

- the page table rides scalar prefetch (PrefetchScalarGridSpec): the
  k/v BlockSpec index maps read `pt_ref[b, i]` to pick the page each
  grid step DMAs — HBM pages are read in place, nothing is gathered;
- pages are head-major `[Hkv, P, ps, D]` so one (head, page) block is
  a legal (ps, D) Mosaic tile;
- GQA is free: the query block carries all G query heads of one kv
  head as sublanes (padded to the f32 minimum of 8), so K/V stream
  from HBM exactly once per kv head — the repeat_kv broadcast never
  materializes;
- int8 caches dequantize in-VMEM with the f32 scale sidecar
  `[Hkv, P, ps, 1]` (trailing singleton = legal lane dim);
- dead pages are skipped via the per-slot length in SMEM (same trick
  as flash_attention.py's kv_lens): a slot whose history ends before
  page i contributes no MXU work for it. Unused page-table entries
  point at the trash page (paged_cache.TRASH_PAGE), so skipped blocks
  still DMA a valid page.

All shapes static; per-step state updates happen OUTSIDE the kernel
(paged_cache.write_token_kv) — the kernel is read-only attention.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import pallas_call

_NEG_INF = -1e30
_Q_SUBLANES = 8  # f32 minimum sublane tile; G query heads pad up to it


def _decode_kernel(pt_ref, lens_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                   o_ref, m_scr, l_scr, acc_scr, *, sm_scale, page_size,
                   quantized):
    b = pl.program_id(0)
    i = pl.program_id(2)
    np_ = pl.num_programs(2)

    @pl.when(i == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # skip pages wholly past the slot's history (and the all-trash rows
    # of inactive slots, whose lens is 0 — they produce a zero row)
    @pl.when(i * page_size < lens_ref[b])
    def _():
        q = q_ref[0, 0].astype(jnp.float32)            # [Gp, D]
        k = k_ref[0, 0].astype(jnp.float32)            # [ps, D]
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            k = k * ks_ref[0, 0]                       # [ps, 1] broadcast
            v = v * vs_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [Gp, ps]
        kpos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < lens_ref[b], s, jnp.float32(_NEG_INF))
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(s > _NEG_INF / 2, p, jnp.float32(0.0))
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(i == np_ - 1)
    def _():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, jnp.float32(1.0), l)
        o_ref[0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def paged_flash_decode(q, k_pages, v_pages, page_table, lens,
                       k_scale=None, v_scale=None, sm_scale=None,
                       interpret=None):
    """q [B, Hkv, G, D] f32/bf16; k_pages/v_pages [Hkv, P, ps, D]
    (f32/bf16, or int8 with k_scale/v_scale [Hkv, P, ps, 1] f32);
    page_table [B, MP] int32 (every entry a valid page id — unused
    rows point at the trash page); lens [B] int32 valid key counts.
    Returns [B, Hkv, G, D] in q's dtype."""
    b, hkv, g, d = q.shape
    ps = k_pages.shape[2]
    mp = page_table.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    quantized = k_scale is not None
    gp = max(_Q_SUBLANES, g)
    if gp % _Q_SUBLANES:
        gp = (gp // _Q_SUBLANES + 1) * _Q_SUBLANES
    qp = q.astype(jnp.float32)
    if gp != g:
        qp = jnp.concatenate(
            [qp, jnp.zeros((b, hkv, gp - g, d), jnp.float32)], axis=2)

    pt = jnp.asarray(page_table, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    if not quantized:
        # a dummy scale block keeps the kernel signature uniform (the
        # branch is static, the refs unread; 1 page avoids dead weight)
        k_scale = jnp.zeros((hkv, 1, ps, 1), jnp.float32)
        v_scale = k_scale
    scale_idx = (lambda b_, h_, i_, pt_, lens_:
                 (h_, pt_[b_, i_], 0, 0)) if quantized else \
                (lambda b_, h_, i_, pt_, lens_: (h_, 0, 0, 0))

    kern = functools.partial(_decode_kernel, sm_scale=sm_scale,
                             page_size=ps, quantized=quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, mp),
        in_specs=[
            pl.BlockSpec((1, 1, gp, d),
                         lambda b_, h_, i_, pt_, lens_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda b_, h_, i_, pt_, lens_:
                         (h_, pt_[b_, i_], 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda b_, h_, i_, pt_, lens_:
                         (h_, pt_[b_, i_], 0, 0)),
            pl.BlockSpec((1, 1, ps, 1), scale_idx),
            pl.BlockSpec((1, 1, ps, 1), scale_idx),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, gp, d), lambda b_, h_, i_, pt_, lens_: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gp, 128), jnp.float32),
            pltpu.VMEM((gp, 128), jnp.float32),
            pltpu.VMEM((gp, d), jnp.float32),
        ],
    )
    out = pallas_call(
        kern,
        name="flash_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, d), q.dtype),
        interpret=interpret,
    )(pt, lens, qp, k_pages, v_pages, k_scale, v_scale)
    return out[:, :, :g]
