"""The held experts' feed-forward at decode widths, Pallas TPU.

ref parity: the dropless grouped expert products of MegaBlocks
(arXiv:2211.15841), for the regime a decode step is in: a few token rows
against many experts, far under the MXU's ridge, so an expert's time is
the read of its three matrices and a kernel has one job, to keep that
read going.

Token-major: the `T` token rows stay as they are, resident in VMEM, and
every hit expert computes all of them; a float32 combine matrix
`C [T, held]` (the router's weight of token t for held expert e, 0 where
t did not pick e) selects what is added into one `[T, h]` float32
accumulator. No sort, no gather, no scatter back; the MXU work on rows
that did not pick the expert hides under the weight read.

- Grid `(held, n1 + n2)`. An expert's first `n1` steps walk
  `w_gate_up[e]` in blocks of whole rows `[th, 2m]` and add into
  `gu [T, 2m]` float32; the last of them makes `act = silu(gate) * up`
  in float32 and rounds it to the stored width; the next `n2` steps walk
  `w_down[e]` in blocks `[tm, h]` and add into `y [T, h]`; the last adds
  `where(C[:, e] != 0, C[:, e] * y, 0)` to the output (a select: an
  expert a token did not pick cannot poison it).
- Every step issues the copy of exactly one weight block, the next
  step's, and the stream runs on from one expert into the next: the
  index map of the matrix that is not in use keeps the block it had, so
  nothing is fetched twice, and `w_down`'s first block is fetched during
  the first product's last step, not with the first product's first.
- Experts that got no row are not read: the hit experts' ids come first
  in a scalar-prefetch vector, and the grid entries past the last hit
  keep every block index (no copy) and do nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import pallas_call

_LANES = 128
# a weight block: 4-5 us of HBM time against a grid step's 0.35 us
_BLOCK_BYTES = 4 << 20
# beside the buffers counted below: the products' float32 results before
# they are added, and the compiler's own scratch
_VMEM_ROOM = 12 << 20


def _block(n, row_bytes):
    """The largest multiple of 128 that divides `n` and whose block of
    rows is at most `_BLOCK_BYTES` (128 where none is)."""
    fit = [b for b in range(_LANES, n + 1, _LANES)
           if n % b == 0 and b * row_bytes <= _BLOCK_BYTES]
    return max(fit, default=_LANES)


def _kernel(hit_ref, n_ref, x_ref, c_ref, wgu_ref, wd_ref, o_ref,
            gu, act, y, *, n1, n2, m, tm):
    i, p = pl.program_id(0), pl.program_id(1)
    live = i < n_ref[0]

    @pl.when((i == 0) & (p == 0))
    def _():
        o_ref[:] = jnp.zeros_like(o_ref)

    @pl.when(live & (p < n1))
    def _():
        part = jnp.dot(x_ref[p], wgu_ref[0],
                       preferred_element_type=jnp.float32)

        @pl.when(p == 0)
        def _():
            gu[:] = part

        @pl.when(p > 0)
        def _():
            gu[:] += part

    @pl.when(live & (p == n1 - 1))
    def _():
        for j in range(n2):
            gate = gu[:, j * tm:(j + 1) * tm]
            up = gu[:, m + j * tm:m + (j + 1) * tm]
            act[j] = (jax.nn.silu(gate) * up).astype(act.dtype)

    @pl.when(live & (p >= n1))
    def _():
        j = p - n1
        part = jnp.dot(act[j], wd_ref[0],
                       preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _():
            y[:] = part

        @pl.when(j > 0)
        def _():
            y[:] += part

        @pl.when(j == n2 - 1)
        def _():
            c = c_ref[0]                                    # [T, 1]
            o_ref[:] += jnp.where(c != 0.0, c * y[:], 0.0)


def _plan(t, h, m, dtype, block_h, block_m):
    """(padded rows, th, tm, vmem_limit_bytes) from the shapes alone."""
    size = jnp.dtype(dtype).itemsize
    tile = 8 * 4 // size
    tp = -(-t // tile) * tile
    th = block_h or _block(h, 2 * m * size)
    tm = block_m or _block(m, h * size)
    if h % th or m % tm or th % _LANES or tm % _LANES:
        raise ValueError(
            f"grouped_experts needs blocks that are multiples of {_LANES} "
            f"and divide the widths, got h {h} / {th}, m {m} / {tm}")
    buffers = (2 * (th * 2 * m + tm * h) * size       # the two weight blocks
               + 2 * tp * h * size + 2 * tp * h * 4   # x, out
               + 2 * tp * _LANES * 4                  # C's column
               + tp * 2 * m * 4 + tp * m * size + tp * h * 4)   # gu, act, y
    return tp, th, tm, buffers + _VMEM_ROOM


def grouped_experts(x, combine, hit, w_gate_up, w_down, *, block_h=None,
                    block_m=None, interpret=None):
    """`sum_e where(C[:, e] != 0, C[:, e] * Expert_e(x), 0)` over the hit
    experts, float32 [T, h].

    x [T, h] (rounded to the weights' dtype here); combine [T, held]
    float32; hit [held] bool, the experts to read (one that is not hit is
    neither read nor added, whatever `combine` says); w_gate_up
    [held, h, 2m] (gate | up), w_down [held, m, h]; h and m multiples of
    128. block_h / block_m: rows of `w_gate_up` / `w_down` a block (by
    default from the widths, `_BLOCK_BYTES` a block)."""
    h = x.shape[1]
    held, m = w_down.shape[0], w_down.shape[1]
    dt = w_gate_up.dtype
    if h % _LANES or m % _LANES or w_gate_up.shape != (held, h, 2 * m) \
            or w_down.shape != (held, m, h) or w_down.dtype != dt:
        raise ValueError(
            f"grouped_experts needs w_gate_up [held, h, 2m] and w_down "
            f"[held, m, h] of one dtype with h and m multiples of "
            f"{_LANES}, got {w_gate_up.shape} {dt}, {w_down.shape} "
            f"{w_down.dtype}, x {x.shape}")
    return _run(x, combine, hit, w_gate_up, w_down, block_h=block_h,
                block_m=block_m, interpret=interpret)


# A jit inside the caller's: a program's expert layers of one shape are one
# traced and lowered function called once a layer, not a kernel body traced
# and lowered anew for each (PERF.md PR 35: it took 3 s off the warm-up of
# three programs of 14 expert layers). The scopes a call runs under still
# reach the kernel's instruction; the caller's program is what compiles.
# tpulint: disable-next-line=TRC01
@functools.partial(jax.jit,
                   static_argnames=("block_h", "block_m", "interpret"))
def _run(x, combine, hit, w_gate_up, w_down, *, block_h, block_m, interpret):
    t, h = x.shape
    held, m = w_down.shape[0], w_down.shape[1]
    dt = w_gate_up.dtype
    tp, th, tm, limit = _plan(t, h, m, dt, block_h, block_m)
    n1, n2 = h // th, m // tm

    xs = jnp.pad(x.astype(dt), ((0, tp - t), (0, 0)))
    xs = xs.reshape(tp, n1, th).transpose(1, 0, 2)          # [n1, T, th]
    cols = jnp.pad(combine.astype(jnp.float32), ((0, tp - t), (0, 0)))
    cols = cols.T[:, :, None]                               # [held, T, 1]
    n_hit = jnp.sum(hit, dtype=jnp.int32)
    ids = jnp.nonzero(hit, size=held, fill_value=0)[0].astype(jnp.int32)
    # past the last hit: its id again, so that no block index changes
    ids = jnp.where(jnp.arange(held, dtype=jnp.int32) < n_hit, ids,
                    ids[jnp.maximum(n_hit - 1, 0)])

    def gate_up_block(i, p, ids_, n_):
        return (ids_[i], jnp.where(i < n_[0], jnp.minimum(p, n1 - 1),
                                   n1 - 1), 0)

    def down_block(i, p, ids_, n_):
        # during an expert's first product: the block the expert before
        # it ended on, so that block 0 is fetched in that product's last
        # step (the first expert has none before it and starts with 0)
        first = (i < n_[0]) & (p < n1)
        e = jnp.where(first, ids_[jnp.maximum(i - 1, 0)], ids_[i])
        j = jnp.where(first, jnp.where(i == 0, 0, n2 - 1),
                      jnp.where(i < n_[0], p - n1, n2 - 1))
        return (e, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(held, n1 + n2),
        in_specs=[
            pl.BlockSpec((n1, tp, th), lambda i, p, ids_, n_: (0, 0, 0)),
            pl.BlockSpec((1, tp, 1), lambda i, p, ids_, n_: (ids_[i], 0, 0)),
            pl.BlockSpec((1, th, 2 * m), gate_up_block),
            pl.BlockSpec((1, tm, h), down_block),
        ],
        out_specs=pl.BlockSpec((tp, h), lambda i, p, ids_, n_: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((tp, 2 * m), jnp.float32),
            pltpu.VMEM((n2, tp, tm), dt),
            pltpu.VMEM((tp, h), jnp.float32),
        ],
    )
    out = pallas_call(
        functools.partial(_kernel, n1=n1, n2=n2, m=m, tm=tm),
        name="grouped_experts",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tp, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=limit),
        interpret=interpret,
    )(ids, n_hit.reshape(1), xs, cols, w_gate_up, w_down)
    return out[:t]
