"""Fused residual-add + LayerNorm Pallas TPU kernel (fwd + bwd).

Why: step anatomy on the 345M GPT (2026-07-30) put the MFU gap in
elementwise HBM passes — the pre-LN block's `s = x + drop(h);
ln_2(s)` chain costs an extra full read of s when the add and the
norm compile to separate HBM round trips. This kernel computes

    s = x + res        (returned: the next residual branch needs it)
    y = (s - mean)/sqrt(var + eps) * gamma + beta

in ONE pass over the rows (2 reads + 2 writes instead of 3 reads +
2 writes), saving per-row mean/rstd for an equally fused backward.
ref parity: paddle/phi/kernels/fusion/fused_layernorm_residual_
dropout_bias (the reference fuses the same chain in CUDA); dropout
stays outside this kernel (it is pointwise and XLA fuses it into the
producing matmul — the win here is the add->reduce boundary XLA keeps
as a kernel break).

Grid: rows are tiled [block_rows, H] per step; the weight grads are
accumulated across the sequential TPU grid into fp32 [1, H] outputs.
Validated in interpret mode on CPU (tests/test_fused_ln.py) and
compiled natively on a v5e against the jnp reference (chip_smoke.py);
bf16/fp32 both supported, softmax-free so tolerance is tight.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import pallas_call

__all__ = ["fused_add_layer_norm", "fused_add_layer_norm_y"]

_STAT_LANES = 128  # row stats stored [N, 128] to satisfy TPU tiling


def _fwd_kernel(x_ref, r_ref, g_ref, b_ref, y_ref, s_ref, mu_ref,
                rs_ref, *, eps):
    s = x_ref[:].astype(jnp.float32) + r_ref[:].astype(jnp.float32)
    mu = jnp.mean(s, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(s - mu), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (s - mu) * rstd
    y = xhat * g_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    s_ref[:] = s.astype(s_ref.dtype)
    mu_ref[:] = jnp.broadcast_to(mu, mu_ref.shape)
    rs_ref[:] = jnp.broadcast_to(rstd, rs_ref.shape)


def _bwd_kernel(dy_ref, ds_ref, s_ref, mu_ref, rs_ref, g_ref,
                dx_ref, dg_ref, db_ref):
    i = pl.program_id(0)
    dy = dy_ref[:].astype(jnp.float32)
    ds = ds_ref[:].astype(jnp.float32)
    s = s_ref[:].astype(jnp.float32)
    mu = mu_ref[:, :1]
    rstd = rs_ref[:, :1]
    g = g_ref[:].astype(jnp.float32)
    xhat = (s - mu) * rstd
    dxhat = dy * g
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx = rstd * (dxhat - m1 - xhat * m2) + ds
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dg_part = jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_part = jnp.sum(dy, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _():
        dg_ref[:] = dg_part
        db_ref[:] = db_part

    @pl.when(i > 0)
    def _():
        dg_ref[:] += dg_part
        db_ref[:] += db_part


def _fwd_kernel_y(x_ref, r_ref, g_ref, b_ref, y_ref, mu_ref, rs_ref, *,
                  eps):
    """y-only forward (post-LN blocks discard the sum): one write
    fewer per call; backward recomputes s from (x, res)."""
    s = x_ref[:].astype(jnp.float32) + r_ref[:].astype(jnp.float32)
    mu = jnp.mean(s, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(s - mu), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (s - mu) * rstd * g_ref[:].astype(jnp.float32) \
        + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mu_ref[:] = jnp.broadcast_to(mu, mu_ref.shape)
    rs_ref[:] = jnp.broadcast_to(rstd, rs_ref.shape)


def _bwd_kernel_y(dy_ref, x_ref, r_ref, mu_ref, rs_ref, g_ref,
                  dx_ref, dg_ref, db_ref):
    i = pl.program_id(0)
    dy = dy_ref[:].astype(jnp.float32)
    s = x_ref[:].astype(jnp.float32) + r_ref[:].astype(jnp.float32)
    mu = mu_ref[:, :1]
    rstd = rs_ref[:, :1]
    g = g_ref[:].astype(jnp.float32)
    xhat = (s - mu) * rstd
    dxhat = dy * g
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx = rstd * (dxhat - m1 - xhat * m2)
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dg_part = jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_part = jnp.sum(dy, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _():
        dg_ref[:] = dg_part
        db_ref[:] = db_part

    @pl.when(i > 0)
    def _():
        dg_ref[:] += dg_part
        db_ref[:] += db_part


def _pick_block_rows(n, h):
    # ~4 fp32 row tiles must sit in VMEM (~16 MB); keep tiles <= ~2 MB
    # each and rows a multiple of 8 (fp32 sublane)
    cap = max(8, min(256, (2 << 20) // max(1, 4 * h) // 8 * 8))
    while n % cap:
        cap //= 2
        if cap < 8:
            return 0
    return cap


def _fwd_call(x2, r2, gamma, beta, eps, block_rows, interpret):
    n, h = x2.shape
    grid = (n // block_rows,)
    row = lambda i: (i, 0)
    vec = lambda i: (0, 0)
    kern = functools.partial(_fwd_kernel, eps=eps)
    return pallas_call(
        kern,
        name="fused_add_ln_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, h), row),
            pl.BlockSpec((block_rows, h), row),
            pl.BlockSpec((1, h), vec),
            pl.BlockSpec((1, h), vec),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, h), row),
            pl.BlockSpec((block_rows, h), row),
            pl.BlockSpec((block_rows, _STAT_LANES), row),
            pl.BlockSpec((block_rows, _STAT_LANES), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x2.dtype),
            jax.ShapeDtypeStruct((n, h), x2.dtype),
            jax.ShapeDtypeStruct((n, _STAT_LANES), jnp.float32),
            jax.ShapeDtypeStruct((n, _STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(x2, r2, gamma[None, :], beta[None, :])


def _bwd_call(dy2, ds2, s2, mu, rstd, gamma, block_rows, interpret):
    n, h = dy2.shape
    grid = (n // block_rows,)
    row = lambda i: (i, 0)
    vec = lambda i: (0, 0)
    return pallas_call(
        _bwd_kernel,
        name="fused_add_ln_bwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, h), row),
            pl.BlockSpec((block_rows, h), row),
            pl.BlockSpec((block_rows, h), row),
            pl.BlockSpec((block_rows, _STAT_LANES), row),
            pl.BlockSpec((block_rows, _STAT_LANES), row),
            pl.BlockSpec((1, h), vec),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, h), row),
            pl.BlockSpec((1, h), vec),
            pl.BlockSpec((1, h), vec),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), dy2.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
        interpret=interpret,
    )(dy2, ds2, s2, mu, rstd, gamma[None, :])


def _reference(x, res, gamma, beta, eps):
    s = x.astype(jnp.float32) + res.astype(jnp.float32)
    mu = jnp.mean(s, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(s - mu), axis=-1, keepdims=True)
    y = (s - mu) * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32) \
        + beta.astype(jnp.float32)
    return y.astype(x.dtype), s.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def fused_add_layer_norm(x, res, gamma, beta, eps=1e-5, block_rows=0,
                         interpret=None):
    """(y, s): y = LayerNorm(x + res) * gamma + beta, s = x + res.

    x, res: [..., H]; gamma/beta: [H]. Both outputs differentiable
    (s feeds the next residual branch). Falls back to the jnp
    reference (same math, XLA-fused) when the row count doesn't tile.
    """
    y, s, _, _ = _fused_fwd_impl(x, res, gamma, beta, eps, block_rows,
                                 interpret)
    return y, s


def _fused_fwd_impl(x, res, gamma, beta, eps, block_rows, interpret):
    h = x.shape[-1]
    lead = x.shape[:-1]
    n = 1
    for d in lead:
        n *= d
    br = block_rows or _pick_block_rows(n, h)
    if not br or n % br:
        y, s = _reference(x, res, gamma, beta, eps)
        return y, s, None, None
    x2 = x.reshape(n, h)
    r2 = res.reshape(n, h)
    y2, s2, mu, rstd = _fwd_call(x2, r2, gamma, beta, eps, br, interpret)
    return (y2.reshape(*lead, h), s2.reshape(*lead, h),
            mu, rstd)


def _fused_fwd(x, res, gamma, beta, eps, block_rows, interpret):
    y, s, mu, rstd = _fused_fwd_impl(x, res, gamma, beta, eps,
                                     block_rows, interpret)
    return (y, s), (s, mu, rstd, gamma, beta)


def _fused_bwd(eps, block_rows, interpret, saved, cts):
    s, mu, rstd, gamma, beta = saved
    dy, ds = cts
    h = s.shape[-1]
    lead = s.shape[:-1]
    n = 1
    for d in lead:
        n *= d
    if mu is None:  # forward took the jnp fallback — mirror it
        def ref_fn(x_, r_, g_, b_):
            return _reference(x_, r_, g_, b_, eps)
        zeros = jnp.zeros_like(s)
        _, vjp = jax.vjp(ref_fn, s, zeros, gamma, beta)
        dx, _, dg, db = vjp((dy, ds))
        return dx, dx, dg, db
    br = block_rows or _pick_block_rows(n, h)
    dx2, dg, db = _bwd_call(dy.reshape(n, h), ds.reshape(n, h),
                            s.reshape(n, h), mu, rstd, gamma, br,
                            interpret)
    dx = dx2.reshape(*lead, h)
    return dx, dx, dg[0].astype(gamma.dtype), db[0].astype(beta.dtype)


fused_add_layer_norm.defvjp(_fused_fwd, _fused_bwd)


def _fwd_call_y(x2, r2, gamma, beta, eps, block_rows, interpret):
    n, h = x2.shape
    row = lambda i: (i, 0)
    vec = lambda i: (0, 0)
    return pallas_call(
        functools.partial(_fwd_kernel_y, eps=eps),
        name="fused_add_ln_y_fwd",
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, h), row),
            pl.BlockSpec((block_rows, h), row),
            pl.BlockSpec((1, h), vec),
            pl.BlockSpec((1, h), vec),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, h), row),
            pl.BlockSpec((block_rows, _STAT_LANES), row),
            pl.BlockSpec((block_rows, _STAT_LANES), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x2.dtype),
            jax.ShapeDtypeStruct((n, _STAT_LANES), jnp.float32),
            jax.ShapeDtypeStruct((n, _STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(x2, r2, gamma[None, :], beta[None, :])


def _bwd_call_y(dy2, x2, r2, mu, rstd, gamma, block_rows, interpret):
    n, h = dy2.shape
    row = lambda i: (i, 0)
    vec = lambda i: (0, 0)
    return pallas_call(
        _bwd_kernel_y,
        name="fused_add_ln_y_bwd",
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, h), row),
            pl.BlockSpec((block_rows, h), row),
            pl.BlockSpec((block_rows, h), row),
            pl.BlockSpec((block_rows, _STAT_LANES), row),
            pl.BlockSpec((block_rows, _STAT_LANES), row),
            pl.BlockSpec((1, h), vec),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, h), row),
            pl.BlockSpec((1, h), vec),
            pl.BlockSpec((1, h), vec),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), dy2.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
        interpret=interpret,
    )(dy2, x2, r2, mu, rstd, gamma[None, :])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def fused_add_layer_norm_y(x, res, gamma, beta, eps=1e-5, block_rows=0,
                           interpret=None):
    """y = LayerNorm(x + res) * gamma + beta, WITHOUT materializing the
    sum (post-LN blocks discard it): one HBM write fewer per call than
    fused_add_layer_norm, and backward re-adds x+res in-kernel."""
    y, _, _ = _fused_fwd_impl_y(x, res, gamma, beta, eps, block_rows,
                                interpret)
    return y


def _fused_fwd_impl_y(x, res, gamma, beta, eps, block_rows, interpret):
    h = x.shape[-1]
    n = 1
    for d in x.shape[:-1]:
        n *= d
    br = block_rows or _pick_block_rows(n, h)
    if not br or n % br:
        y, _ = _reference(x, res, gamma, beta, eps)
        return y, None, None
    y2, mu, rstd = _fwd_call_y(x.reshape(n, h), res.reshape(n, h),
                               gamma, beta, eps, br, interpret)
    return y2.reshape(x.shape), mu, rstd


def _fused_fwd_y(x, res, gamma, beta, eps, block_rows, interpret):
    y, mu, rstd = _fused_fwd_impl_y(x, res, gamma, beta, eps,
                                    block_rows, interpret)
    return y, (x, res, mu, rstd, gamma, beta)


def _fused_bwd_y(eps, block_rows, interpret, saved, dy):
    x, res, mu, rstd, gamma, beta = saved
    h = x.shape[-1]
    n = 1
    for d in x.shape[:-1]:
        n *= d
    if mu is None:  # forward took the jnp fallback — mirror it
        def ref_y(x_, r_, g_, b_):
            return _reference(x_, r_, g_, b_, eps)[0]
        _, vjp = jax.vjp(ref_y, x, res, gamma, beta)
        return vjp(dy)
    br = block_rows or _pick_block_rows(n, h)
    dx2, dg, db = _bwd_call_y(dy.reshape(n, h), x.reshape(n, h),
                              res.reshape(n, h), mu, rstd, gamma, br,
                              interpret)
    dx = dx2.reshape(x.shape)
    return dx, dx, dg[0].astype(gamma.dtype), db[0].astype(beta.dtype)


fused_add_layer_norm_y.defvjp(_fused_fwd_y, _fused_bwd_y)
