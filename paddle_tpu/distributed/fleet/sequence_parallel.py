"""Sequence / context parallelism over the 'sp' mesh axis.

ref parity: python/paddle/distributed/fleet/meta_parallel/pp_utils and the
sep_parallel / context-parallel utilities (RingFlashAttention in
paddle.distributed.fleet.meta_parallel.sep_utils, and the DeepSpeed-Ulysses
style all-to-all sequence parallelism used by fleet's sep group) — the
reference moves KV blocks between GPUs with NCCL send/recv and reshuffles
heads with all-to-all.

TPU-native design: both strategies are pure SPMD programs inside shard_map
over the 'sp' mesh axis, using XLA collectives over ICI:

- ring_attention: Q stays put; KV blocks rotate around the ring with
  lax.ppermute while an online-softmax accumulator (flash-attention style
  m/l/acc carry in a lax.scan) merges per-block partial attention. Causal
  blocks are masked by comparing the source block index against this
  rank's block index, so late blocks cost (masked) compute but the program
  stays static — XLA overlaps the ppermute with the matmuls, which is the
  whole point of ring attention (arXiv:2310.01889).
- ulysses_attention: lax.all_to_all swaps the sharded axis from sequence to
  heads ([B, S/sp, H, D] -> [B, S, H/sp, D]), runs ordinary (flash)
  attention on full sequences with a head subset, and swaps back
  (DeepSpeed-Ulysses, arXiv:2309.14509). Cheaper collectives than ring for
  moderate sp, but requires heads % sp == 0.

Both differentiate through jax.grad (ppermute/all_to_all transpose to the
reverse shift), so no hand-written backward schedule is needed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["ring_attention", "ulysses_attention", "split_sequence",
           "gather_sequence", "ring_attention_spmd", "ulysses_attention_spmd"]

_NEG = -1e30  # finite "minus infinity": keeps exp() NaN-free on masked blocks


def ring_attention(q, k, v, *, axis_name, causal=False, sm_scale=None):
    """Ring attention over sequence shards. Call INSIDE shard_map.

    q, k, v: [B, S_local, H, D] — this rank's sequence chunk; chunks are laid
    out in mesh-axis order (rank r holds positions [r*S_local, (r+1)*S_local)).
    Returns [B, S_local, H, D].
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    sp = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    orig_dtype = q.dtype

    # [B, H, S, D] with fp32 softmax state, MXU matmuls stay in input dtype
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    b, h, s_q, d = qh.shape

    tril = jnp.tril(jnp.ones((s_q, s_q), dtype=bool))

    def step(carry, t):
        k_t, v_t, m, l, acc = carry
        src = (idx - t) % sp  # which global block this rank holds at tick t
        logits = jnp.einsum("bhqd,bhkd->bhqk", qh, k_t,
                            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            bias = jnp.where(src < idx, 0.0,
                             jnp.where((src == idx) & tril, 0.0, _NEG))
            logits = logits + bias
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(k_t.dtype), v_t,
            preferred_element_type=jnp.float32)
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        k_t = lax.ppermute(k_t, axis_name, perm)
        v_t = lax.ppermute(v_t, axis_name, perm)
        return (k_t, v_t, m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, s_q), _NEG, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, s_q), dtype=jnp.float32)
    acc0 = jnp.zeros((b, h, s_q, d), dtype=jnp.float32)
    (_, _, _, l, acc), _ = lax.scan(
        step, (kh, vh, m0, l0, acc0), jnp.arange(sp))
    out = acc / l[..., None]
    return jnp.swapaxes(out, 1, 2).astype(orig_dtype)


def ulysses_attention(q, k, v, *, axis_name, causal=False, sm_scale=None,
                      attn_fn=None):
    """All-to-all (DeepSpeed-Ulysses) sequence parallelism. Call INSIDE
    shard_map.

    q, k, v: [B, S_local, H, D] with H % sp == 0. Swaps the sharded axis to
    heads, runs full-sequence attention (flash-capable via attn_fn), swaps
    back. Returns [B, S_local, H, D].
    """
    sp = lax.psum(1, axis_name)
    n_heads = q.shape[2]
    if n_heads % sp != 0:
        raise ValueError(
            f"ulysses needs heads ({n_heads}) divisible by sp ({sp})")
    if attn_fn is None:
        from ...ops.attention import flash_attention
        attn_fn = functools.partial(flash_attention, sm_scale=sm_scale)

    def seq_to_heads(x):  # [B, S/sp, H, D] -> [B, S, H/sp, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):  # [B, S, H/sp, D] -> [B, S/sp, H, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    out = attn_fn(seq_to_heads(q), seq_to_heads(k), seq_to_heads(v),
                  causal=causal)
    return heads_to_seq(out)


def split_sequence(x, axis_name, seq_axis=1):
    """Take this rank's sequence chunk of a replicated array (inside
    shard_map). ref: fleet's ScatterOp for sequence parallel."""
    sp = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    chunk = x.shape[seq_axis] // sp
    return lax.dynamic_slice_in_dim(x, idx * chunk, chunk, axis=seq_axis)


def gather_sequence(x, axis_name, seq_axis=1):
    """all_gather chunks back to the full sequence (inside shard_map).
    ref: fleet's GatherOp."""
    return lax.all_gather(x, axis_name, axis=seq_axis, tiled=True)


def _spmd(local_fn, mesh, axis):
    """shard_map over `axis` only; any OTHER mesh axes (dp/mp) stay
    *auto* so GSPMD keeps partitioning batch/heads inside the manual
    sequence-sharded body — this is what lets a dp x sp (or dp x mp x
    sp) train step compose with no extra code."""
    spec = P(None, axis, None, None)
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=frozenset({axis}), check_vma=False)


def ring_attention_spmd(q, k, v, mesh, *, axis="sp", causal=False,
                        sm_scale=None):
    """Top-level entry: q/k/v [B, S, H, D] (sharded or not) -> ring attention
    with S sharded over `axis`."""
    fn = functools.partial(ring_attention, axis_name=axis, causal=causal,
                           sm_scale=sm_scale)
    return _spmd(fn, mesh, axis)(q, k, v)


def ulysses_attention_spmd(q, k, v, mesh, *, axis="sp", causal=False,
                           sm_scale=None, attn_fn=None):
    fn = functools.partial(ulysses_attention, axis_name=axis, causal=causal,
                           sm_scale=sm_scale, attn_fn=attn_fn)
    return _spmd(fn, mesh, axis)(q, k, v)
