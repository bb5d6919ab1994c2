"""Flash attention, Pallas TPU.

ref parity: paddle/phi/kernels/gpu/flash_attn_kernel.cu (flash-attn v2:
causal + padding masks + dropout, fwd and bwd). No S x S probability
matrix ever hits HBM; backward recomputes from the saved row logsumexp.

Two paths, chosen by the static head size of the input and by nothing else
(`_resident(head_dim)`; no flag, no environment variable):

- head_dim <= 128: the RESIDENT kernels. A head's queries, keys and values
  stay in VMEM in spans of up to `_SPAN_ROWS` rows (grid (batch*heads,
  q spans, k spans)) and the kernels loop inside a grid step over
  [block_k, block_q] score tiles, the online-softmax state (m, l, acc)
  carried as values. Scores are held TRANSPOSED, keys on sublanes and
  queries on lanes, so the row max and row sum reduce along sublanes
  (elementwise VPU work) and the per-row statistics are [1, block_q] lane
  vectors that broadcast along sublanes. A tile the causal diagonal or a
  sequence's `kv_lens` crosses takes the masked body; a tile wholly
  visible takes a body with no iota, compare, select or hard zero; a tile
  wholly hidden is never visited (`_key_span` / `_row_span` decide,
  `tile_counts` counts). `sm_scale` rides on an operand tile where it is
  a power of two (exact), else on the float32 scores. Backward is ONE
  kernel (`flash_bwd_dkv_dq`) that recomputes a tile's probabilities once
  and forms dP once: dK and dV accumulate per key tile, dQ in a float32
  [sq, d] scratch written once a head. Where that scratch would not fit
  (`_fused_bwd_fits`) the two-kernel split (dq; then dk/dv) runs, one
  tile a grid step, on the same tile math.
- head_dim > 128 (the A.X-K1 prefill, heads padded to 256): the TILED
  forward `_fwd_kernel`, one [block_q, block_k] tile a grid step (grid
  (batch*heads, q_blocks, k_blocks), k innermost, state in VMEM scratch),
  every visited tile masked, blocks 128 x 128. It is kept apart, sharing
  no kernel body with the resident forward, until the set-up seconds the
  resident forward cost that prefill's programs are explained (PERF.md
  section 6, PR 31 and PR 32). Its backward (no cell runs it) is the
  split form above. `flash_decode` (8 padded query rows) also runs it.

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


#   measured on v5e (bh128 s1024 d64 bf16 causal, ms per 24 calls; my chip
#   runs, PR 32): resident 512x512 fwd 9.1 / bwd 23.2 against 256x256
#   13.8 / 24.5 and the parent's tiled kernels' 25.1 / 50.9 at 512x512
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30
# widest head the resident kernels serve; wider heads take the tiled forward
_RESIDENT_HEAD_DIM = 128
# rows of one head that a resident grid step keeps in VMEM per operand
_SPAN_ROWS = 1024
# what the one-kernel backward may spend on dQ's float32 scratch and its
# output block (both a head's whole sq x d); above it the split form runs
_FUSED_DQ_BYTES = 8 * 2 ** 20
_VMEM_LIMIT = 32 * 2 ** 20
# trailing lane dim of the TILED forward's per-row logsumexp: Mosaic requires
# the last block dim to be 128-divisible or equal to the array dim, so it is
# carried as [bh, sq, 8] with the value replicated over the 8 lanes. The
# resident kernels carry row statistics as [bh, sq // block_q, 1, block_q].
_LSE_LANES = 8


# --------------------------------------------------------------------------
# pure functions of shapes (shared by both paths)
# --------------------------------------------------------------------------

def _resident(head_dim):
    """The rule that chooses the path: the input's static head size."""
    return head_dim <= _RESIDENT_HEAD_DIM


def _fit_block(seq, want, head_dim):
    """Pick the kernel block for one sequence axis.

    seq <= want: the whole sequence is one block. Otherwise: halve `want`
    (scaled down for heads wider than the resident kernels serve, so the
    tiled kernels' blocks stay within VMEM) until it divides seq, floored
    at 128; if nothing >= 128 divides seq the caller's validity check
    rejects the shape (tiny tiles would silently run orders of magnitude
    slower than the XLA fallback)."""
    if not _resident(head_dim):
        want = max(128, (want * 64) // head_dim)
    if seq <= want:
        return seq
    b = want
    while b > 128 and seq % b:
        b //= 2
    return b


def _fit_span(seq, block):
    """Rows of one head a resident grid step keeps in VMEM: the largest
    multiple of `block` within `_SPAN_ROWS` that divides seq."""
    for n in range(max(1, min(_SPAN_ROWS, seq) // block), 0, -1):
        if seq % (n * block) == 0:
            return n * block


def _fused_bwd_fits(sq, head_dim, itemsize):
    """Whether the one-kernel backward holds a head's dQ: the float32
    [sq // block_q, d, block_q] scratch and the two buffers of its
    [sq, d] output block (lanes padded to 128)."""
    out = 2 * max(head_dim, 128) * itemsize
    return (_resident(head_dim)
            and sq * (4 * head_dim + out) <= _FUSED_DQ_BYTES)


def _scale_on_operand(sm_scale):
    """A power of two scales a tile without rounding (1/8 for heads of 64),
    so the multiply moves from every score tile to an operand tile."""
    return math.frexp(sm_scale)[0] == 0.5


def _lo(a, b):
    both = isinstance(a, int) and isinstance(b, int)
    return min(a, b) if both else jnp.minimum(a, b)


def _hi(a, b):
    both = isinstance(a, int) and isinstance(b, int)
    return max(a, b) if both else jnp.maximum(a, b)


def _key_span(row0, col0, n, block_q, block_k, offset, causal, kv_len):
    """Of the n key tiles of block_k from col0, for the block_q query rows
    from row0: tiles [0, plain) need no mask, [plain, run) the masked body,
    [run, n) nothing. Python ints in, ints out; traced scalars likewise."""
    run_end = plain_end = col0 + n * block_k
    if causal:
        run_end = _lo(run_end, row0 + block_q + offset)
        plain_end = _lo(plain_end, row0 + offset + 1)
    if kv_len is not None:
        run_end = _lo(run_end, kv_len)
        plain_end = _lo(plain_end, kv_len)
    run = (_hi(run_end - col0, 0) + block_k - 1) // block_k
    plain = _hi(plain_end - col0, 0) // block_k
    return plain, run


def _row_span(row0, col0, n, block_q, block_k, offset, causal, kv_len):
    """The same from a key tile's side: of the n query tiles of block_q
    from row0, against the block_k keys from col0: tiles [0, run) see none
    of them, [run, plain) take the masked body, [plain, n) need no mask."""
    run = plain = 0
    if causal:
        run = _lo(_hi(col0 - offset - row0, 0) // block_q, n)
        plain = _lo((_hi(col0 + block_k - 1 - offset - row0, 0)
                     + block_q - 1) // block_q, n)
    if kv_len is not None:
        run = jnp.where(col0 >= kv_len, n, run)
        plain = jnp.where(col0 + block_k > kv_len, n, plain)
    return run, plain


def tile_counts(sq, sk, block_q, block_k, causal):
    """(plain, masked, skipped) score tiles of one head without `kv_lens`:
    how often each body of the resident and the split kernels engages
    (the tiled forward masks every tile it visits: plain + masked)."""
    nk = sk // block_k
    plain = masked = 0
    for row0 in range(0, sq, block_q):
        p, r = _key_span(row0, 0, nk, block_q, block_k, sk - sq, causal,
                         None)
        plain += p
        masked += r - p
    return plain, masked, (sq // block_q) * nk - plain - masked


# --------------------------------------------------------------------------
# masks and dropout
# --------------------------------------------------------------------------

def _positions(shape, qi, ki, block_q, block_k):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return q_pos, k_pos


def _positions_t(shape, row0, col0):
    """Global positions of a transposed [keys, queries] score tile."""
    k_pos = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    q_pos = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
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


def _visible_t(shape, row0, col0, offset, causal, kv_len):
    """Which scores of the transposed tile at (row0, col0) the causal
    diagonal and the kv length leave."""
    q_pos, k_pos = _positions_t(shape, row0, col0)
    keep = None
    if causal:
        keep = q_pos + offset >= k_pos
    if kv_len is not None:
        inside = k_pos < kv_len
        keep = inside if keep is None else keep & inside
    return keep


def _keep_hash(seed, b, q_pos, k_pos, sk, rate):
    """Deterministic keep-mask from a murmur3-finalizer hash of the GLOBAL
    element position — bwd kernels regenerate the identical mask from the
    same (seed, b, position) regardless of their tiling or its layout.
    Plain uint32 vector ops: lowers on Mosaic AND runs in interpret mode
    (pltpu.prng_* has no interpret path)."""
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


def _dropout_keep(seed, b, qi, ki, shape, block_q, block_k, sk, rate):
    """Keep-mask of the [queries, keys] tile (qi, ki)."""
    q_pos, k_pos = _positions(shape, qi, ki, block_q, block_k)
    return _keep_hash(seed, b, q_pos, k_pos, sk, rate)


def _dropout_keep_t(seed, b, row0, col0, shape, sk, rate):
    """Keep-mask of the transposed [keys, queries] tile at (row0, col0):
    the same element keeps or drops as in `_dropout_keep`."""
    q_pos, k_pos = _positions_t(shape, row0, col0)
    return _keep_hash(seed, b, q_pos, k_pos, sk, rate)


# --------------------------------------------------------------------------
# head_dim > 128 (and flash_decode): the tiled forward
# --------------------------------------------------------------------------

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


# --------------------------------------------------------------------------
# head_dim <= 128: the resident kernels (transposed score tiles)
# --------------------------------------------------------------------------

def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, dimension_numbers=((contract[:1], contract[1:]), ((), ())),
        preferred_element_type=jnp.float32)


def _rows(ref, i, block):
    """Rows [i * block, (i + 1) * block) of a [1, rows, lanes] block."""
    if isinstance(i, int):
        return ref[0, i * block:(i + 1) * block, :]
    return ref[0, pl.ds(pl.multiple_of(i * block, block), block), :]


def _when(cond):
    """`pl.when` that takes what is known while tracing as it is."""
    if isinstance(cond, bool):
        return (lambda f: f()) if cond else (lambda f: None)
    return pl.when(cond)


def _loop(lo, hi, body, carry):
    """`fori_loop`, or straight-line code where the bounds are known while
    tracing (one head span and no `kv_lens`; at most 8 x 8 tiles of 128 a
    span). The scheduler then overlaps one tile's products with another's
    vector work (the train cell's forward 12.1 -> 9.1 ms per 24 calls; my
    chip runs, PR 32), and no loop of static bounds is left for the
    compiler's MXU pass, which refuses one around the backward's transposed
    key tile at 256 x 256 tiles (tests/test_flash_tpu_compile.py)."""
    if isinstance(lo, int) and isinstance(hi, int):
        for i in range(lo, hi):
            carry = body(i, carry)
        return carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _span_ids(spans):
    """Program ids of a resident grid (batch-head, then `spans` grid steps
    along each sequence axis); an axis of one span is the Python int 0, so
    that tile positions stay known while tracing."""
    return (pl.program_id(0),) + tuple(
        pl.program_id(a + 1) if n > 1 else 0 for a, n in enumerate(spans))


def _fwd_resident_kernel(lens_ref, seed_ref, q_ref, k_ref, v_ref, o_ref,
                         lse_ref, m_scr, l_scr, acc_scr, *, sm_scale,
                         causal, block_q, block_k, offset, use_lens,
                         dropout_p, sk, spans):
    b, qm, km = _span_ids(spans)
    span_q, span_k = q_ref.shape[1], k_ref.shape[1]
    kv_len = lens_ref[b] if use_lens else None
    on_q = _scale_on_operand(sm_scale)
    # a row can be masked out entirely only under kv_lens or with more
    # queries than keys; only then must a masked score count exactly 0
    # whatever the running maximum
    hard_zero = use_lens or offset < 0

    @_when(km == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    for t in range(span_q // block_q):
        row0 = qm * span_q + t * block_q
        q = _rows(q_ref, t, block_q)
        if on_q:
            q = q * sm_scale
        plain, run = _key_span(row0, km * span_k, span_k // block_k,
                               block_q, block_k, offset, causal, kv_len)

        def key_tile(masked, row0=row0, q=q):
            def body(c, carry):
                m_prev, l_prev, acc = carry
                col0 = km * span_k + c * block_k
                s = _dot(_rows(k_ref, c, block_k), q, (1, 1))
                if not on_q:
                    s = s * sm_scale
                if masked:
                    keep = _visible_t(s.shape, row0, col0, offset, causal,
                                      kv_len)
                    s = jnp.where(keep, s, jnp.float32(_NEG_INF))
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=0, keepdims=True))
                p = jnp.exp(s - m_new)
                if masked and hard_zero:
                    # where m_new is still _NEG_INF, exp gave 1; with l = 0
                    # the safe_l guard below then emits a 0 output row
                    p = jnp.where(keep, p, jnp.float32(0.0))
                alpha = jnp.exp(m_prev - m_new)
                # denominator from the UN-dropped p (flash-attn v2 order)
                l_new = l_prev * alpha + jnp.sum(p, axis=0, keepdims=True)
                if dropout_p:
                    kept = _dropout_keep_t(seed_ref[0], b, row0, col0,
                                           p.shape, sk, dropout_p)
                    p = jnp.where(kept, p / (1.0 - dropout_p),
                                  jnp.float32(0.0))
                v = _rows(v_ref, c, block_k)
                acc = acc * alpha + _dot(v, p.astype(v.dtype), (0, 0))
                return m_new, l_new, acc
            return body

        carry = (m_scr[t], l_scr[t], acc_scr[t])
        carry = _loop(0, plain, key_tile(False), carry)
        if causal or use_lens:
            carry = _loop(plain, run, key_tile(True), carry)
        m, l, acc = carry
        m_scr[t], l_scr[t], acc_scr[t] = m, l, acc

        @_when(km == spans[1] - 1)
        def _():
            safe_l = jnp.where(l == 0.0, jnp.float32(1.0), l)
            o_ref[0, t * block_q:(t + 1) * block_q, :] = (
                acc / safe_l).T.astype(o_ref.dtype)
            lse_ref[0, t] = m + jnp.log(safe_l)


def _bwd_tile(q, k, v, do, lse, delta, row0, col0, seed, b, *, masked,
              sm_scale, causal, offset, kv_len, dropout_p, sk):
    """(p as dV's product takes it, dS) of the transposed score tile at
    (row0, col0), both float32 [block_k, block_q]; lse and delta are
    [1, block_q]; k comes scaled where `_scale_on_operand`."""
    s = _dot(k, q, (1, 1))
    if not _scale_on_operand(sm_scale):
        s = s * sm_scale
    if masked:
        keep = _visible_t(s.shape, row0, col0, offset, causal, kv_len)
        s = jnp.where(keep, s, jnp.float32(_NEG_INF))
    p = jnp.exp(s - lse)
    if masked and (kv_len is not None or offset < 0):
        # a fully masked row saved lse = _NEG_INF: no gradient (fwd's 0)
        p = jnp.where(keep, p, jnp.float32(0.0))
    dp = _dot(v, do, (1, 1))
    p_v = p
    if dropout_p:
        kept = _dropout_keep_t(seed, b, row0, col0, p.shape, sk, dropout_p)
        p_v = jnp.where(kept, p / (1.0 - dropout_p), jnp.float32(0.0))
        dp = jnp.where(kept, dp / (1.0 - dropout_p), jnp.float32(0.0))
    return p_v, p * (dp - delta)


def _dkv_dq_kernel(lens_ref, seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr,
                   dv_scr, *, sm_scale, causal, block_q, block_k, offset,
                   use_lens, dropout_p, sk, spans):
    b, km, qm = _span_ids(spans)
    span_q, span_k = q_ref.shape[1], k_ref.shape[1]
    tiles = span_q // block_q
    kv_len = lens_ref[b] if use_lens else None
    on_k = _scale_on_operand(sm_scale)
    tile = functools.partial(
        _bwd_tile, sm_scale=sm_scale, causal=causal, offset=offset,
        kv_len=kv_len, dropout_p=dropout_p, sk=sk)

    @_when(qm == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @_when((km == 0) & (qm == 0))
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    for j in range(span_k // block_k):
        cols = slice(j * block_k, (j + 1) * block_k)
        col0 = km * span_k + j * block_k
        k = k_ref[0, cols, :]
        if on_k:
            k = k * sm_scale
        # dQ^T = k^T dS^T accumulates [d, block_q] with k's small tile
        # transposed once a key tile, so no product transposes a score tile
        k_t = k.T
        v = v_ref[0, cols, :]
        run, plain = _row_span(qm * span_q, col0, tiles, block_q, block_k,
                               offset, causal, kv_len)

        def q_tile(masked, col0=col0, k=k, k_t=k_t, v=v):
            def body(t, carry):
                dk, dv = carry
                q = _rows(q_ref, t, block_q)
                do = _rows(do_ref, t, block_q)
                p, ds = tile(q, k, v, do, lse_ref[0, t], delta_ref[0, t],
                             qm * span_q + t * block_q, col0, seed_ref[0],
                             b, masked=masked)
                ds = ds.astype(q.dtype)
                i = qm * tiles + t
                dq_scr[i] = dq_scr[i] + _dot(k_t, ds, (1, 0))
                return (dk + _dot(ds, q, (1, 0)),
                        dv + _dot(p.astype(do.dtype), do, (1, 0)))
            return body

        carry = (dk_scr[cols, :], dv_scr[cols, :])
        if causal or use_lens:
            carry = _loop(run, plain, q_tile(True), carry)
        carry = _loop(plain, tiles, q_tile(False), carry)
        dk_scr[cols, :], dv_scr[cols, :] = carry

    last_q = qm == spans[1] - 1

    @_when(last_q)
    def _():
        dk_ref[0] = (dk_scr[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @_when(last_q & (km == spans[0] - 1))
    def _():
        def write(i, _):
            # dQ = dS (k * scale): the scale rode in on k where exact
            dq = dq_scr[i].T
            if not on_k:
                dq = dq * sm_scale
            dq_ref[0, pl.ds(pl.multiple_of(i * block_q, block_q), block_q),
                   :] = dq.astype(dq_ref.dtype)
            return 0
        jax.lax.fori_loop(0, dq_scr.shape[0], write, 0)


def _split_tile(run_tile, row0, col0, block_q, block_k, offset, causal,
                kv_len):
    """The split kernels visit one score tile a grid step: run its plain
    or its masked body, or neither."""
    plain, run = _key_span(row0, col0, 1, block_q, block_k, offset, causal,
                           kv_len)
    pl.when(plain == 1)(functools.partial(run_tile, False))
    if causal or kv_len is not None:
        pl.when(run - plain == 1)(functools.partial(run_tile, True))


def _dq_kernel(lens_ref, seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, acc_scr, *, sm_scale, causal, block_q,
               block_k, offset, use_lens, dropout_p, sk):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    kv_len = lens_ref[b] if use_lens else None
    on_k = _scale_on_operand(sm_scale)

    @pl.when(ki == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def run_tile(masked):
        k = k_ref[0] * sm_scale if on_k else k_ref[0]
        _, ds = _bwd_tile(
            q_ref[0], k, v_ref[0], do_ref[0], lse_ref[0, 0],
            delta_ref[0, 0], qi * block_q, ki * block_k, seed_ref[0], b,
            masked=masked, sm_scale=sm_scale, causal=causal, offset=offset,
            kv_len=kv_len, dropout_p=dropout_p, sk=sk)
        acc_scr[...] += _dot(k.T, ds.astype(k.dtype), (1, 0))

    _split_tile(run_tile, qi * block_q, ki * block_k, block_q, block_k,
                offset, causal, kv_len)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        dq = acc_scr[...].T
        if not on_k:
            dq = dq * sm_scale
        dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(lens_ref, seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale,
                causal, block_q, block_k, offset, use_lens, dropout_p, sk):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    kv_len = lens_ref[b] if use_lens else None
    on_k = _scale_on_operand(sm_scale)

    @pl.when(qi == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def run_tile(masked):
        k = k_ref[0] * sm_scale if on_k else k_ref[0]
        p, ds = _bwd_tile(
            q_ref[0], k, v_ref[0], do_ref[0], lse_ref[0, 0],
            delta_ref[0, 0], qi * block_q, ki * block_k, seed_ref[0], b,
            masked=masked, sm_scale=sm_scale, causal=causal, offset=offset,
            kv_len=kv_len, dropout_p=dropout_p, sk=sk)
        dv_scr[...] += _dot(p.astype(do_ref.dtype), do_ref[0], (1, 0))
        dk_scr[...] += _dot(ds.astype(q_ref.dtype), q_ref[0], (1, 0))

    _split_tile(run_tile, qi * block_q, ki * block_k, block_q, block_k,
                offset, causal, kv_len)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = (dk_scr[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _block(rows, d, index):
    return pl.BlockSpec((1, rows, d), index)


def _stat_block(tiles, block_q, index):
    """`tiles` rows of a [bh, sq // block_q, 1, block_q] row statistic."""
    return pl.BlockSpec((1, tiles, 1, block_q),
                        lambda *ids: index(*ids) + (0,))


def _scalars(bh, lens, seed):
    return (lens if lens is not None else jnp.zeros((bh,), jnp.int32),
            seed if seed is not None else jnp.zeros((1,), jnp.int32))


def _params():
    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _fwd_resident_call(q, k, v, lens, seed, causal, sm_scale, dropout_p,
                       block_q, block_k, interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
    span_q, span_k = _fit_span(sq, block_q), _fit_span(sk, block_k)
    tiles = span_q // block_q
    kern = functools.partial(
        _fwd_resident_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, offset=sk - sq,
        use_lens=lens is not None, dropout_p=dropout_p, sk=sk,
        spans=(sq // span_q, sk // span_k))
    by_q = lambda b, i, j: (b, i, 0)
    by_k = lambda b, i, j: (b, j, 0)
    return pallas_call(
        kern,
        name="flash_fwd",
        grid=(bh, sq // span_q, sk // span_k),
        in_specs=[
            _smem_full(bh),
            _smem_full(1),
            _block(span_q, d, by_q),
            _block(span_k, d, by_k),
            _block(span_k, d, by_k),
        ],
        out_specs=[
            _block(span_q, d, by_q),
            _stat_block(tiles, block_q, by_q),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq // block_q, 1, block_q),
                                 jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tiles, 1, block_q), jnp.float32),
            pltpu.VMEM((tiles, 1, block_q), jnp.float32),
            pltpu.VMEM((tiles, d, block_q), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=interpret,
    )(*_scalars(bh, lens, seed), q, k, v)


def _forward(q, k, v, lens, seed, causal, sm_scale, dropout_p, block_q,
             block_k, interpret):
    call = _fwd_resident_call if _resident(q.shape[-1]) else _fwd_call
    return call(q, k, v, lens, seed, causal, sm_scale, dropout_p, block_q,
                block_k, interpret)


def _bwd_call(res, g, causal, sm_scale, dropout_p, block_q, block_k,
              interpret):
    q, k, v, o, lse, lens, seed = res
    do = g
    bh, sq, d = q.shape
    sk = k.shape[1]
    stat = (bh, sq // block_q, 1, block_q)
    if not _resident(d):
        lse = lse[:, :, 0]
    lse = lse.reshape(stat)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(stat)
    common = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, offset=sk - sq, use_lens=lens is not None,
                  dropout_p=dropout_p, sk=sk)
    args = (*_scalars(bh, lens, seed), q, k, v, do, lse, delta)

    if _fused_bwd_fits(sq, d, q.dtype.itemsize):
        span_q, span_k = _fit_span(sq, block_q), _fit_span(sk, block_k)
        by_q = lambda b, j, i: (b, i, 0)
        by_k = lambda b, j, i: (b, j, 0)
        stats = _stat_block(span_q // block_q, block_q, by_q)
        return pallas_call(
            functools.partial(_dkv_dq_kernel, **common,
                              spans=(sk // span_k, sq // span_q)),
            name="flash_bwd_dkv_dq",
            grid=(bh, sk // span_k, sq // span_q),
            in_specs=[
                _smem_full(bh),
                _smem_full(1),
                _block(span_q, d, by_q),
                _block(span_k, d, by_k),
                _block(span_k, d, by_k),
                _block(span_q, d, by_q),
                stats,
                stats,
            ],
            out_specs=[
                _block(sq, d, lambda b, j, i: (b, 0, 0)),
                _block(span_k, d, by_k),
                _block(span_k, d, by_k),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((sq // block_q, d, block_q), jnp.float32),
                pltpu.VMEM((span_k, d), jnp.float32),
                pltpu.VMEM((span_k, d), jnp.float32),
            ],
            compiler_params=_params(),
            interpret=interpret,
        )(*args)

    def split(kernel, grid, by_q, by_k, outs, name):
        """One tile a grid step; outs: (like, block rows, index, float32
        scratch) each."""
        return pallas_call(
            functools.partial(kernel, **common),
            name=name,
            grid=grid,
            in_specs=[
                _smem_full(bh),
                _smem_full(1),
                _block(block_q, d, by_q),
                _block(block_k, d, by_k),
                _block(block_k, d, by_k),
                _block(block_q, d, by_q),
                _stat_block(1, block_q, by_q),
                _stat_block(1, block_q, by_q),
            ],
            out_specs=[_block(rows, d, by) for _, rows, by, _ in outs],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                       for x, _, _, _ in outs],
            scratch_shapes=[pltpu.VMEM(scr, jnp.float32)
                            for _, _, _, scr in outs],
            compiler_params=_params(),
            interpret=interpret,
        )(*args)

    by_q = lambda b, i, j: (b, i, 0)
    by_k = lambda b, i, j: (b, j, 0)
    dq, = split(_dq_kernel, (bh, sq // block_q, sk // block_k), by_q, by_k,
                [(q, block_q, by_q, (d, block_q))], name="flash_bwd_dq")
    by_q = lambda b, j, i: (b, i, 0)
    by_k = lambda b, j, i: (b, j, 0)
    dk, dv = split(_dkv_kernel, (bh, sk // block_k, sq // block_q), by_q,
                   by_k, [(k, block_k, by_k, (block_k, d)),
                          (v, block_k, by_k, (block_k, d))],
                   name="flash_bwd_dkv")
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_bhsd(q, k, v, lens, seed, causal, sm_scale, dropout_p, block_q,
                block_k, interpret):
    o, _ = _forward(q, k, v, lens, seed, causal, sm_scale, dropout_p,
                    block_q, block_k, interpret)
    return o


def _flash_fwd_rule(q, k, v, lens, seed, causal, sm_scale, dropout_p,
                    block_q, block_k, interpret):
    o, lse = _forward(q, k, v, lens, seed, causal, sm_scale, dropout_p,
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
    attention path for generation; here the tiled fwd kernel runs with the
    query padded to the 8-sublane minimum tile, masked by kv_lens.
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
