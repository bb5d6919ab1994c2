"""Quantized (8-bit) collectives — EQuARX-style gradient all-reduce.

ref: the reference's DistributedStrategy fp16/bf16 allreduce + the
EQuARX paper's int8 scheme (SURVEY §6 perf levers: "8-bit-collective
option"). Wire bytes are the scaling bottleneck once ICI is saturated:
an fp32 ring all-reduce moves 2·N·4 bytes per device; this moves
2·N·1 (+ scales), a ~4x cut, in exchange for bounded quantization error
on the gradient sync.

TPU-native shape: there is no NCCL hook to patch — the collective IS a
program op. `quantized_all_reduce` is written for use inside
`shard_map` over the dp axis (where our pipeline/tp kernels already
live), lowering to `all_to_all`/`all_gather` on int8 payloads that XLA
puts on ICI:

  stage 1 (reduce-scatter): quantize the local vector per rank-chunk
     (int8, per-block absmax scales), all_to_all so rank i holds every
     rank's chunk i, dequantize, sum -> rank i owns the reduced chunk i
     in full precision.
  stage 2 (gather): re-quantize the reduced chunk, all_gather, dequant.

Two quantization passes => error ~2 ulp(int8-block) — measured <1%
relative on gradient-like data across 8 ranks (tests); exact on integer-valued data
within the int8 range. Callers wanting bit-exact training keep the
default fp path; this is opt-in, like the reference's strategy flag.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["quantized_all_reduce", "quantize_int8_blockwise",
           "dequantize_int8_blockwise"]


def quantize_int8_blockwise(x, block=256):
    """[..., m] -> (int8 [..., m], f32 scales [..., m/block]).
    Per-block absmax scaling; m must divide by `block`."""
    lead = x.shape[:-1]
    m = x.shape[-1]
    xb = x.reshape(lead + (m // block, block)).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    return q.reshape(lead + (m,)), scale.squeeze(-1)


def dequantize_int8_blockwise(q, scale, block=256):
    lead = q.shape[:-1]
    m = q.shape[-1]
    qb = q.reshape(lead + (m // block, block)).astype(jnp.float32)
    return (qb * scale[..., None]).reshape(lead + (m,))


def quantized_all_reduce(x, axis_name, block=256):
    """All-reduce (sum) over `axis_name` with int8 wire format.

    Must run inside shard_map/pjit where `axis_name` is bound. Returns
    the summed array in x's dtype. Payload on the interconnect is int8
    plus one f32 scale per `block` elements (~x4 less than fp32).
    """
    n = jax.lax.axis_size(axis_name)
    orig_dtype = x.dtype
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    # pad so the vector splits into n rank-chunks of block-multiples
    unit = n * block
    pad = (-flat.size) % unit
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    chunks = flat.reshape(n, -1)                       # [n, m]
    q, s = quantize_int8_blockwise(chunks, block)      # [n, m], [n, m/b]
    # stage 1: all_to_all -> row j becomes rank j's version of MY chunk
    qt = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                            tiled=False)
    st = jax.lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0,
                            tiled=False)
    partial = jnp.sum(dequantize_int8_blockwise(qt, st, block), axis=0)
    # stage 2: re-quantize the reduced chunk and gather all chunks
    q2, s2 = quantize_int8_blockwise(partial, block)   # [m], [m/b]
    qg = jax.lax.all_gather(q2, axis_name, axis=0)     # [n, m]
    sg = jax.lax.all_gather(s2, axis_name, axis=0)
    out = dequantize_int8_blockwise(qg, sg, block).reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(shape).astype(orig_dtype)
