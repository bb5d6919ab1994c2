"""One decode step of the Gated DeltaNet recurrence over every serving slot,
Pallas TPU.

ref parity: Gated DeltaNet (Yang, Kautz and Hatamizadeh, arXiv:2412.06464)
in flash-linear-attention's recurrent form, one token a sequence; the
written specification is `nlp/olmo_hybrid.gated_delta_step`.

Per head, with S [dk, dv] the stored state and alpha = exp(g):
`delta = beta (v - alpha S^T k)`, `S' = alpha S + k delta^T` and
`o = S'^T q = alpha S^T q + (k.q) delta`, all float32. A decode step is
bound by the state's bytes (a few operations per number), so the kernel's
one job is to move each slot's state from HBM once and back once:

- One grid step = one slot. Its whole state block `[H / r, dk, r * dv]`
  comes into VMEM once (2.2 MB at Olmo-Hybrid's 30 heads of 96 x 192; in
  and out, each double-buffered, hold four such blocks, under the 16 MiB
  a kernel may use by default), both products over dk and the update run
  on it there, and it goes back where it came from: the state is aliased
  to the new state (`input_output_aliases`), so a donated carry is
  updated in place. A grid over slots alone keeps the fixed cost of a
  grid step off the heads (64 steps a layer, not 64 x 15); two or four
  slots a step were no faster on a v5e.
- The state is stored as `paged_cache.DeltaStateSpec` lays it out: r heads
  side by side in a row (dv = 192: r = 2, rows of 384 = three whole lane
  tiles; a row of 192 would occupy 256 lanes). A lane belongs to head
  `lane // dv` of its row; each head's k and q enter as a column over dk
  (the operands are handed in transposed, `[dk, 2H]`), its alpha, beta
  and k.q as one number, each selected by the lane's head.
- Both products are lane-wise multiplies summed over the sublanes (dk) on
  the VPU, in float32: the arithmetic of the `jnp` step, only the order of
  its sums differs.
- A slot that is not live keeps its rows bit for bit (they are written
  back as read); its output row is computed all the same and is the
  caller's to ignore, as the `jnp` step's is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import pallas_call


def _by_head(parts, lane, dv):
    """Of r values (each broadcastable to a row's lanes) the one of the
    lane's head: parts[j] in lanes `j*dv .. (j+1)*dv - 1`."""
    out = parts[-1]
    for j in range(len(parts) - 2, -1, -1):
        out = jnp.where(lane < (j + 1) * dv, parts[j], out)
    return out


def _gated_delta_kernel(live_ref, cols_ref, coef_ref, v_ref, s_ref, o_ref,
                        new_ref, *, heads, r, dv):
    live = live_ref[pl.program_id(0)] != 0
    cols = cols_ref[0]                                   # [dk, 2H]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, r * dv), 1)
    for p in range(heads // r):
        hs = range(p * r, (p + 1) * r)
        k = _by_head([cols[:, h:h + 1] for h in hs], lane, dv)
        q = _by_head([cols[:, heads + h:heads + h + 1] for h in hs],
                     lane, dv)
        alpha, beta, kq = (_by_head([coef_ref[0, h:h + 1, i:i + 1]
                                     for h in hs], lane, dv)
                           for i in range(3))
        s = s_ref[0, p]                                  # [dk, r * dv]
        kv_mem = jnp.sum(s * k, axis=0, keepdims=True)   # [1, r * dv]
        q_mem = jnp.sum(s * q, axis=0, keepdims=True)
        delta = beta * (v_ref[0, p:p + 1, :] - alpha * kv_mem)
        o_ref[0, p:p + 1, :] = alpha * q_mem + kq * delta
        new_ref[0, p] = jnp.where(live, alpha * s + k * delta, s)


def gated_delta_decode(q, k, v, g, beta, state, live=None, interpret=None):
    """q, k [B, H, dk] (q scaled), v [B, H, dv], g and beta [B, H], all
    float32; state [B, H / r, dk, r * dv] float32 with r heads side by side
    in a row (r read off the state's width); live [B] bool or None (all).
    Returns (o [B, H, dv], the new state in the state's layout: of the
    live slots only)."""
    b, h, dk = k.shape
    dv = v.shape[-1]
    rows, w = state.shape[1], state.shape[-1]
    r = w // dv
    if r * dv != w or rows * r != h or state.shape[2] != dk:
        raise ValueError(f"a state {state.shape} does not hold {h} heads "
                         f"of {dk} x {dv} in rows of whole heads")
    if live is None:
        live = jnp.ones((b,), bool)
    cols = jnp.swapaxes(jnp.concatenate([k, q], axis=1), 1, 2)  # [B, dk, 2H]
    coef = jnp.stack([jnp.exp(g), beta, jnp.sum(k * q, -1)], axis=-1)
    kern = functools.partial(_gated_delta_kernel, heads=h, r=r, dv=dv)

    def slot(n):
        return lambda b_, live_: (b_,) + (0,) * (n - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, dk, 2 * h), slot(3)),
                  pl.BlockSpec((1, h, 3), slot(3)),
                  pl.BlockSpec((1, rows, w), slot(3)),
                  pl.BlockSpec((1, rows, dk, w), slot(4))],
        out_specs=[pl.BlockSpec((1, rows, w), slot(3)),
                   pl.BlockSpec((1, rows, dk, w), slot(4))],
    )
    o, new = pallas_call(
        kern,
        name="gated_delta_decode",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, rows, w), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # operand 4 counts the scalar-prefetched `live`
        input_output_aliases={4: 1},
        interpret=interpret,
    )(live.astype(jnp.int32), cols, coef, v.reshape(b, rows, w), state)
    return o.reshape(b, h, dv), new
