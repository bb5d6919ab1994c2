"""The routed half of an expert layer, shared by the models that have one
(nlp/axk1.py: a chip's share of 192 experts; nlp/lfm2.py: all 32 held):
the choice of experts from the router's scores, and the held experts'
part of the weighted sum, dropless, static shapes. Two paths by what the
code can see (`experts_path`): at decode widths on a TPU one Pallas
kernel that streams each hit expert's matrices once over the token rows
as they are (ops/pallas/grouped_experts.py); else two grouped products
over the rows sorted by expert (`jax.lax.ragged_dot`). Both run under the
scopes the benchmark reads (`moe_router` is the caller's, `moe_experts`
is `held_experts`'s own) and count what `paged_cache.AUX_COUNTERS`
names."""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

__all__ = ["select_experts", "held_experts", "experts_path",
           "recorded_paths", "STREAMED_MAX_ROWS"]

# Up to this many token rows the held experts run as the streaming kernel.
# The MXU holds a 128 x 128 weight tile as long for 8 rows as for 128, so
# up to 128 rows an expert's time is the read of its matrices and nothing
# else, which the kernel does once at 87% of the chip's bandwidth.
# Measured on a v5e against the sorted grouped products at LFM2's widths
# (PERF.md, PR 35): 32 rows 0.98 against 1.33 ms a layer, 64 rows 0.98 /
# 1.61, 128 rows 0.99 / 2.44. Past 128 its MXU work on rows that did not
# pick the expert begins to show (256 rows 1.21 / 2.60, 512 rows 2.31 /
# 2.94), and every program that holds the kernel pays about 0.2 s a layer
# of warm set-up, so the buckets past 128 stay with the sorted products.
STREAMED_MAX_ROWS = 128

_recorders = []


@contextlib.contextmanager
def recorded_paths():
    """Collects, while a program is traced inside it, the path each
    `held_experts` call took: a list of "streamed" / "ragged"."""
    seen = []
    _recorders.append(seen)
    try:
        yield seen
    finally:
        _recorders.remove(seen)


def experts_path(t, h, m, backend=None):
    """"streamed" (the Pallas kernel) or "ragged" (the sorted grouped
    products) for `t` token rows through experts `h` -> `m` -> `h`, from
    the shapes and the backend alone: few rows, widths in whole lane
    tiles, a TPU."""
    backend = backend or jax.default_backend()
    streamed = (backend == "tpu" and t <= STREAMED_MAX_ROWS
                and h % 128 == 0 and m % 128 == 0)
    return "streamed" if streamed else "ragged"


def select_experts(scores, k, norm, scale, bias=None, eps=1e-20):
    """(ids int32 [T, k], weights float32 [T, k]) from the router's scores
    [T, E]: the k highest by `scores + bias` (a stored selection bias [E]
    takes part in the choice only; None: by the scores alone), weighted by
    their scores, over their sum + `eps` where `norm`, times `scale`."""
    if bias is None:
        w, idx = jax.lax.top_k(scores, k)
    else:
        _, idx = jax.lax.top_k(scores + bias, k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return idx.astype(jnp.int32), w * scale


@jax.named_scope("moe_experts")
def held_experts(u, idx, w, w_gate_up, w_down, offset, rows_live=None):
    """The held experts' part of `sum_e w_e Expert_e(u)`, dropless.

    u [T, h] float32; idx, w [T, k] the router's picks; w_gate_up
    [held, h, 2m], w_down [held, m, h] experts `offset .. offset+held`;
    rows_live [T] bool leaves padding rows out. Operands in the stored
    width, float32 sums, float32 `silu`, the activation rounded to the
    stored width before the second product, `w` applied in float32, on
    both paths (`experts_path`); they differ in the order of the float32
    sum over a token's experts alone. Returns (out [T, h] float32,
    counters int32 [3] in AUX_COUNTERS' order)."""
    t, k = idx.shape
    held, m = w_down.shape[0], w_down.shape[1]
    local = idx - offset
    mine = (local >= 0) & (local < held)
    if rows_live is not None:
        mine = mine & rows_live[:, None]
    key = jnp.where(mine, local, held).reshape(t * k)
    path = experts_path(t, u.shape[-1], m)
    for seen in _recorders:
        seen.append(path)
    run = _streamed if path == "streamed" else _ragged
    # each path counts its own sizes: the order of the sorted path's
    # operations is what tests/test_lfm2.py's program digests pin
    out, sizes, n_mine = run(u, key, w, w_gate_up, w_down)
    routed = jnp.int32(t) if rows_live is None \
        else jnp.sum(rows_live, dtype=jnp.int32)
    aux = jnp.stack([n_mine, jnp.sum(sizes > 0, dtype=jnp.int32), routed])
    return out, aux


def _sizes(key, held):
    """Assignments of each held expert, int32 [held]; key [T*k] is the
    held expert's index, `held` for an assignment that is not held."""
    return jnp.sum(key[:, None] == jnp.arange(held, dtype=jnp.int32)[None],
                   axis=0, dtype=jnp.int32)


def _streamed(u, key, w, w_gate_up, w_down, **kernel_kw):
    """Token-major: the rows as they are against every hit expert, chosen
    by the combine matrix C[t, e] = token t's weight for held expert e (0
    where it did not pick it, or the row is padding). Returns (out, sizes,
    their sum), as `_ragged` does."""
    from ..ops.pallas.grouped_experts import grouped_experts
    held = w_down.shape[0]
    sizes = _sizes(key, held)
    picked = key.reshape(w.shape)[:, :, None] \
        == jnp.arange(held, dtype=jnp.int32)
    combine = jnp.sum(jnp.where(picked, w[:, :, None], 0.0), axis=1)
    out = grouped_experts(u, combine, sizes > 0, w_gate_up, w_down,
                          **kernel_kw)
    return out, sizes, jnp.sum(sizes)


def _ragged(u, key, w, w_gate_up, w_down):
    """The T*k assignments sorted by held expert (those of absent experts
    last), the three products as two grouped products over the rows of
    each expert, and each token's rows brought back and summed. Shapes
    are static at the worst case (every assignment held here); rows past
    the held ones belong to no group and count as zero."""
    t, k = w.shape
    held, m = w_down.shape[0], w_down.shape[1]
    a = t * k
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = _sizes(key, held)
    n_mine = jnp.sum(sizes)
    xs = u.astype(w_gate_up.dtype)[order // k]                   # [A, h]
    gu = jax.lax.ragged_dot(xs, w_gate_up, sizes,
                            preferred_element_type=jnp.float32)
    act = (jax.nn.silu(gu[:, :m]) * gu[:, m:]).astype(w_down.dtype)
    y = jax.lax.ragged_dot(act, w_down, sizes,
                           preferred_element_type=jnp.float32)   # [A, h]
    held_row = jnp.arange(a, dtype=jnp.int32) < n_mine
    y = jnp.where(held_row[:, None], y * w.reshape(a)[order][:, None], 0.0)
    back = jnp.zeros((a,), jnp.int32).at[order].set(
        jnp.arange(a, dtype=jnp.int32))
    return jnp.sum(y[back].reshape(t, k, -1), axis=1), sizes, n_mine
