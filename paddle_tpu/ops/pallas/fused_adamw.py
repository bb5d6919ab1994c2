"""Fused Adam/AdamW parameter update in ONE HBM pass per tensor.

Why: the r4 step anatomy measured the isolated AdamW update at 22.8 ms
on gpt3-345M — ~2x the HBM-bandwidth floor of its 4-read/3-write
traffic (9.7 GB at fp32 -> ~11.8 ms on one v5e). XLA compiles the
per-leaf jnp chain into multiple loop fusions whose intermediate
re-reads pay that factor; this kernel performs the whole update —
moment EMAs, bias correction, coupled or decoupled weight decay,
parameter step — in a single read of (p, m, v, g) and a single write
of (p', m', v'), with input_output_aliasing so no fresh HBM buffers
are allocated. ref parity: paddle/phi/kernels/gpu/adamw_kernel.cu
(the reference fuses exactly this in CUDA).

Scalars that change per step (lr, bias corrections) ride a tiny SMEM
operand; hyperparameters (betas, eps, wd, decay mode) are compile-time
constants. fp32 moments only — bf16 stochastic-rounded moments keep
the jnp path (rounding noise needs the traced RNG stream).
Validated in interpret mode against the optimizer's own jnp math
(tests/test_fused_adamw.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import pallas_call

__all__ = ["fused_adamw_update", "fused_adamw_supported"]

_LANES = 512
_MIN_SIZE = 1 << 14  # smaller leaves: kernel launch overhead > win


def _kernel(s_ref, p_ref, m_ref, v_ref, g_ref, po_ref, mo_ref, vo_ref,
            *, b1, b2, eps, wd, decoupled):
    lr = s_ref[0]
    bc1 = s_ref[1]
    bc2 = s_ref[2]
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    if wd and not decoupled:
        g = g + wd * p
    m = b1 * m_ref[:].astype(jnp.float32) + (1.0 - b1) * g
    v = b2 * v_ref[:].astype(jnp.float32) + (1.0 - b2) * g * g
    denom = jnp.sqrt(v / bc2) + eps
    step = lr * (m / bc1) / denom
    if wd and decoupled:
        step = step + lr * wd * p
    po_ref[:] = (p - step).astype(po_ref.dtype)
    mo_ref[:] = m.astype(mo_ref.dtype)
    vo_ref[:] = v.astype(vo_ref.dtype)


def fused_adamw_supported(p, m, v):
    """Eligible leaf: large, fp32 throughout (a checkpoint-restored
    bf16 moment must fall back regardless of moment_dtype config),
    and already tiling to the 8x512 grid — a non-multiple leaf would
    pay four padded concatenate copies per step, defeating the
    one-pass aliasing the kernel exists for."""
    return (p.dtype == jnp.float32
            and m.dtype == jnp.float32 and v.dtype == jnp.float32
            and p.size >= _MIN_SIZE
            and p.size % (8 * _LANES) == 0)


def fused_adamw_update(p, m, v, g, lr, bc1, bc2, *, beta1, beta2, eps,
                       weight_decay, decoupled, block_rows=256,
                       interpret=None):
    """One-pass update; returns (p_new, m_new, v_new). lr/bc1/bc2 may
    be traced scalars (they ride SMEM); betas/eps/wd are static."""
    shape = p.shape
    n = p.size
    pad = (-n) % (8 * _LANES)
    total = n + pad

    def flat(x):
        x = x.reshape(-1)
        if pad:
            # reachable only when called directly with a non-tiling
            # size (fused_adamw_supported gates this path off in the
            # optimizer): four padded copies per step are the cost
            x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
        return x.reshape(-1, _LANES)

    rows = total // _LANES
    br = min(block_rows, rows)
    while rows % br:
        br //= 2
        if br < 8:
            br = rows  # tiny: single block
            break
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32),
                         jnp.asarray(bc1, jnp.float32),
                         jnp.asarray(bc2, jnp.float32)])
    kern = functools.partial(_kernel, b1=float(beta1), b2=float(beta2),
                             eps=float(eps),
                             wd=float(weight_decay or 0.0),
                             decoupled=bool(decoupled))
    row = lambda i: (i, 0)
    tile = pl.BlockSpec((br, _LANES), row)
    po, mo, vo = pallas_call(
        kern,
        name="fused_adamw",
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((3,), lambda i: (0,),
                         memory_space=pltpu.SMEM),
            tile, tile, tile, tile,
        ],
        out_specs=[tile, tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((rows, _LANES), p.dtype),
            jax.ShapeDtypeStruct((rows, _LANES), m.dtype),
            jax.ShapeDtypeStruct((rows, _LANES), v.dtype),
        ],
        # true in-place: p/m/v buffers are reused for the outputs —
        # no fresh HBM allocations for the optimizer state
        input_output_aliases={1: 0, 2: 1, 3: 2},
        interpret=interpret,
    )(scalars, flat(p), flat(m), flat(v), flat(g))

    def unflat(x):
        return x.reshape(-1)[:n].reshape(shape)
    return unflat(po), unflat(mo), unflat(vo)
