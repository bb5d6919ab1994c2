"""Fused 1x1-conv + BatchNorm scale/shift + ReLU (+ residual add) Pallas
TPU kernel — the diagnosed ResNet-50 HBM-bandwidth wall.

Why (ResNet-50 on the chip: not measured; the argument is from the
shapes): the bottleneck 1x1 convs are skinny matmuls, each of whose
output makes extra full HBM round trips through the BN normalize, the
ReLU, and the residual add.
In NHWC a 1x1 conv IS a [M, Cin] @ [Cin, Cout] matmul (M = N*H*W), so
this kernel computes

    y = relu((x @ w) * scale + shift [+ res])

in ONE pass: the [M, Cout] conv output never round-trips between the
matmul and the pointwise tail. `scale`/`shift` are the BN affine folded
per channel:

    scale_c = gamma_c / sqrt(var_c + eps)
    shift_c = beta_c  - mean_c * scale_c

with (mean, var) either the running stats (inference / use_global_stats)
or the batch stats of the conv output. For train mode the batch stats
are obtained WITHOUT materializing the conv output via
:func:`conv1x1_batch_stats`: mean is linear (mean_M(x) @ w) and the
second moment comes from the Gram matrix G = X^T X / M as
w_o^T G w_o — an extra M*Cin^2 FLOPs, i.e. Cin/Cout of the conv itself
(cheap exactly where the bottleneck expands, Cout = 4*Cin).

Backward is plain jnp under jax.custom_vjp (XLA-fused; the matmul
grads dominate anyway) and recomputes x@w instead of saving it — the
whole point is that the forward never wrote it.

Grid: M is tiled [block_m, :]; the weight [Cin, Cout] and the folded
[1, Cout] vectors are resident per step. Falls back to the jnp
reference whenever the shape doesn't tile (M % 8, Cin/Cout % 128, or a
weight too big for VMEM). Validated in interpret mode on CPU
(tests/test_fused_conv_bn_act.py).
ref parity: the reference serves this fusion via conv_bn_fuse_pass +
cuDNN fused conv epilogues; training-side it is CINN's job. Here it is
one Pallas kernel on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import pallas_call

__all__ = ["fused_conv1x1_bn_act", "conv1x1_batch_stats"]

_VMEM_W_CAP = 4 << 20  # fp32 bytes the resident [Cin, Cout] tile may take


def _fwd_kernel(x_ref, w_ref, s_ref, b_ref, y_ref, *, relu):
    acc = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)
    y = acc * s_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    y_ref[:] = y.astype(y_ref.dtype)


def _fwd_kernel_res(x_ref, w_ref, s_ref, b_ref, r_ref, y_ref, *, relu):
    acc = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)
    y = acc * s_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    y = y + r_ref[:].astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    y_ref[:] = y.astype(y_ref.dtype)


def _pick_block_m(m, cin, cout):
    """Rows per grid step: x/out/res tiles <= ~2 MB fp32 each, rows a
    multiple of 8 (fp32 sublane), and the row count must tile."""
    per_row = 4 * max(cin, cout)
    cap = max(8, min(1024, (2 << 20) // max(1, per_row) // 8 * 8))
    while m % cap:
        # re-round after halving: an odd-multiple cap (e.g. 336 -> 168
        # -> 84) would otherwise violate the sublane constraint
        cap = (cap // 2) // 8 * 8
        if cap < 8:
            return 0
    return cap


def _supported(m, cin, cout):
    return (cin % 128 == 0 and cout % 128 == 0
            and 4 * cin * cout <= _VMEM_W_CAP)


def _reference(x2, w, scale, shift, res2, relu):
    acc = jnp.dot(x2.astype(jnp.float32), w.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    y = acc * scale.astype(jnp.float32) + shift.astype(jnp.float32)
    if res2 is not None:
        y = y + res2.astype(jnp.float32)
    if relu:
        y = jnp.where(y > 0, y, 0.0)
    return y.astype(x2.dtype)


def _fwd_call(x2, w, scale, shift, res2, relu, block_m, interpret):
    m, cin = x2.shape
    cout = w.shape[1]
    grid = (m // block_m,)
    row = lambda i: (i, 0)
    full = lambda i: (0, 0)
    in_specs = [
        pl.BlockSpec((block_m, cin), row),
        pl.BlockSpec((cin, cout), full),
        pl.BlockSpec((1, cout), full),
        pl.BlockSpec((1, cout), full),
    ]
    if res2 is not None:
        in_specs.append(pl.BlockSpec((block_m, cout), row))
        kern = functools.partial(_fwd_kernel_res, relu=relu)
        args = (x2, w, scale[None, :], shift[None, :], res2)
    else:
        kern = functools.partial(_fwd_kernel, relu=relu)
        args = (x2, w, scale[None, :], shift[None, :])
    return pallas_call(
        kern,
        name="conv1x1_bn_act",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, cout), row),
        out_shape=jax.ShapeDtypeStruct((m, cout), x2.dtype),
        interpret=interpret,
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def fused_conv1x1_bn_act(x2, w, scale, shift, res2=None, relu=True,
                         block_m=0, interpret=None):
    """y = relu((x2 @ w) * scale + shift [+ res2]) in one HBM pass.

    x2: [M, Cin] (NHWC flattened over N*H*W); w: [Cin, Cout];
    scale/shift: [Cout] folded BN affine; res2: optional [M, Cout]
    residual added before the ReLU. Falls back to the jnp reference
    (same math, XLA-fused) when the shape doesn't tile.
    """
    return _fwd_impl(x2, w, scale, shift, res2, relu, block_m, interpret)


def _fwd_impl(x2, w, scale, shift, res2, relu, block_m, interpret):
    m, cin = x2.shape
    cout = w.shape[1]
    bm = block_m or _pick_block_m(m, cin, cout)
    if not bm or not _supported(m, cin, cout):
        return _reference(x2, w, scale, shift, res2, relu)
    return _fwd_call(x2, w, scale, shift, res2, relu, bm, interpret)


def _fused_fwd(x2, w, scale, shift, res2, relu, block_m, interpret):
    y = _fwd_impl(x2, w, scale, shift, res2, relu, block_m, interpret)
    # xw is deliberately NOT saved (never materialized in forward);
    # backward recomputes it with one extra matmul. y carries the ReLU
    # mask: y > 0 <=> pre-activation > 0 for the kept elements. The
    # empty dtype token stands in for res2 so bwd can emit a cotangent
    # of the RESIDUAL'S dtype without keeping the [M, Cout] array alive.
    res_tok = None if res2 is None else jnp.zeros((0,), res2.dtype)
    return y, (x2, w, scale, shift, y, res_tok)


def _fused_bwd(relu, block_m, interpret, saved, dy):
    x2, w, scale, shift, y, res_tok = saved
    dy = dy.astype(jnp.float32)
    if relu:
        dz = jnp.where(y > 0, dy, 0.0)
    else:
        dz = dy
    xw = jnp.dot(x2.astype(jnp.float32), w.astype(jnp.float32),
                 preferred_element_type=jnp.float32)
    dscale = jnp.sum(dz * xw, axis=0)
    dshift = jnp.sum(dz, axis=0)
    dxw = dz * scale.astype(jnp.float32)
    dx = jnp.dot(dxw, w.astype(jnp.float32).T,
                 preferred_element_type=jnp.float32)
    dw = jnp.dot(x2.astype(jnp.float32).T, dxw,
                 preferred_element_type=jnp.float32)
    # custom_vjp checks cotangent avals against the PRIMAL dtypes
    dres = None if res_tok is None else dz.astype(res_tok.dtype)
    return (dx.astype(x2.dtype), dw.astype(w.dtype),
            dscale.astype(scale.dtype), dshift.astype(shift.dtype), dres)


fused_conv1x1_bn_act.defvjp(_fused_fwd, _fused_bwd)


def conv1x1_batch_stats(x2, w):
    """(mean, var) per out-channel of x2 @ w over the M rows, WITHOUT
    materializing the [M, Cout] product:

        mean  = mean_M(x2) @ w                      (linearity)
        E[y²] = diag(wᵀ G w),  G = x2ᵀ x2 / M       (Gram matrix)
        var   = E[y²] - mean²

    Extra FLOPs are M*Cin² for G — Cin/Cout of the conv itself, so this
    is armed only where the 1x1 expands channels (Cout >= Cin: the
    bottleneck's conv3). All fp32; differentiable jnp (the custom-vjp
    kernel chains through scale/shift into these stats).
    """
    xf = x2.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    m = x2.shape[0]
    mean = jnp.dot(jnp.mean(xf, axis=0), wf,
                   preferred_element_type=jnp.float32)
    g = jnp.dot(xf.T, xf, preferred_element_type=jnp.float32) / m
    ex2 = jnp.sum(wf * jnp.dot(g, wf, preferred_element_type=jnp.float32),
                  axis=0)
    var = jnp.maximum(ex2 - jnp.square(mean), 0.0)
    return mean, var
