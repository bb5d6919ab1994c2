"""Paged decode attention for the absorbed latent form, Pallas TPU.

ref parity: DeepSeek-V2's absorbed multi-head latent attention
(arXiv:2405.04434, section 2.1) over a paged cache (vLLM
PagedAttention, arXiv:2309.06180).

Every head of a slot attends the same cached rows `[c_kv | k_rope |
zeros]`, and the rows are key and value at once: a head's score is its
`[q_nope W_UK^T | q_rope]` against the whole row, its output the
probabilities against the row's first `v_width` numbers. So one slot is
ONE "kv head" whose query block holds all H heads as sublanes, and a
page brought into VMEM once serves both products.

- The pool `[P, ps, Wp]` stays in HBM where it lies
  (`memory_space=ANY`); the page table and the lengths ride scalar
  prefetch (PrefetchScalarGridSpec). One grid step = one slot: the
  kernel walks the slot's LIVE table entries and copies each page into
  one of two VMEM buffers itself, the next page's copy in flight while
  this one is multiplied. The online-softmax state (m, l, acc) of all
  heads stays in VMEM scratch along the walk.
- Time follows the live pages alone: a table entry past the slot's
  length is never visited, so it costs no DMA, no MXU work and no grid
  step. (A grid over every table entry that skips the dead ones, as
  flash_decode.py's, was measured first: at tables of 32 entries with 8
  live the dead steps were a quarter to a third of its time, PERF.md
  PR 29.) A slot of length 0 (an all-trash table row) copies nothing
  and produces a zero row.
- The operand rule is paged_cache.latent_paged_attention's: rows enter
  both products in the cache's dtype, q and the unnormalised
  exponentials are rounded to it, scores, softmax state and sums are
  float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import pallas_call

_NEG_INF = -1e30
_LANES = 128


def _latent_kernel(pt_ref, lens_ref, q_ref, pool_ref, o_ref, buf, sem,
                   m_scr, l_scr, acc_scr, *, sm_scale, page_size, v_cols):
    b = pl.program_id(0)
    n = lens_ref[b]
    n_pages = (n + page_size - 1) // page_size

    def page_copy(t, half):
        return pltpu.make_async_copy(pool_ref.at[pt_ref[b, t]],
                                     buf.at[half], sem.at[half])

    m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(n_pages > 0)
    def _():
        page_copy(0, 0).start()

    def attend(t, _):
        half = t % 2

        @pl.when(t + 1 < n_pages)
        def _():
            page_copy(t + 1, 1 - half).start()

        page_copy(t, half).wait()
        rows = buf[half]                                    # [ps, Wp]
        s = jax.lax.dot_general(
            q_ref[0], rows, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [Hp, ps]
        kpos = t * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < n, s, jnp.float32(_NEG_INF))
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a visited page's first key is live, so m_new is a real score
        # and a masked key's exponential underflows to exactly 0
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_scr.shape)
        v = rows[:, :v_cols]
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        return 0

    jax.lax.fori_loop(0, n_pages, attend, 0)
    l = l_scr[:, :1]
    o_ref[0] = acc_scr[:] / jnp.where(l == 0.0, jnp.float32(1.0), l)


def latent_flash_decode(q, pages, page_table, lens, v_width, sm_scale,
                        interpret=None):
    """q [B, H, W] (each head's `[q_nope W_UK^T | q_rope]`, W <= Wp);
    pages [P, ps, Wp] (ps a multiple of 8, Wp of 128) rows `[c_kv |
    k_rope | zeros]`; page_table [B, MP] int32 (every entry below a
    slot's length a valid page id); lens [B] int32 valid key counts.
    Returns float32 [B, H, v_width]: `sum p c_kv`, before W_UV."""
    b, h, w = q.shape
    ps, wp = pages.shape[1], pages.shape[2]
    dt = pages.dtype
    if ps % 8 or wp % _LANES or w > wp:
        raise ValueError(
            f"latent_flash_decode needs page_size % 8 == 0 and rows of a "
            f"multiple of {_LANES} numbers at least as wide as q, got "
            f"pages {pages.shape}, q {q.shape}")
    # the values are the rows' first v_width numbers: sliced in VMEM where
    # that is whole lane tiles, else the product runs over the whole row
    # and the slice follows it, as the XLA form's does
    v_cols = v_width if v_width % _LANES == 0 else wp
    # all heads of a slot are the sublanes of one query block
    tile = 8 * 4 // dt.itemsize
    hp = -(-h // tile) * tile
    qp = jnp.pad(q.astype(dt), ((0, 0), (0, hp - h), (0, wp - w)))

    def slot_idx(b_, pt_, lens_):
        return (b_, 0, 0)

    kern = functools.partial(_latent_kernel, sm_scale=sm_scale,
                             page_size=ps, v_cols=v_cols)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hp, wp), slot_idx),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, hp, v_cols), slot_idx),
        scratch_shapes=[
            pltpu.VMEM((2, ps, wp), dt),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((hp, _LANES), jnp.float32),
            pltpu.VMEM((hp, _LANES), jnp.float32),
            pltpu.VMEM((hp, v_cols), jnp.float32),
        ],
    )
    out = pallas_call(
        kern,
        name="latent_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hp, v_cols), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(page_table, jnp.int32), jnp.asarray(lens, jnp.int32),
      qp, pages)
    return out[:, :h, :v_width]
