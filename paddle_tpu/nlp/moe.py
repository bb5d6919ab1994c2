"""The routed half of an expert layer, shared by the models that have one
(nlp/axk1.py: a chip's share of 192 experts; nlp/lfm2.py: all 32 held):
the choice of experts from the router's scores, and the held experts'
part of the weighted sum as two grouped products over the rows sorted by
expert (`jax.lax.ragged_dot`): dropless, static shapes. Both run under
the scopes the benchmark reads (`moe_router` is the caller's,
`moe_experts` is `held_experts`'s own) and count what
`paged_cache.AUX_COUNTERS` names."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["select_experts", "held_experts"]


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
    rows_live [T] bool leaves padding rows out. The T*k assignments are
    sorted by held expert (those of absent experts last), the three
    products run as two grouped products over the rows of each expert,
    and each token's rows are brought back and summed. Shapes are static
    at the worst case (every assignment held here); rows past the held
    ones belong to no group and count as zero. Returns (out [T, h]
    float32, counters int32 [3] in AUX_COUNTERS' order)."""
    t, k = idx.shape
    held, m = w_down.shape[0], w_down.shape[1]
    a = t * k
    local = idx - offset
    mine = (local >= 0) & (local < held)
    if rows_live is not None:
        mine = mine & rows_live[:, None]
    key = jnp.where(mine, local, held).reshape(a)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=jnp.int32)[None],
                    axis=0, dtype=jnp.int32)
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
    out = jnp.sum(y[back].reshape(t, k, -1), axis=1)
    routed = jnp.int32(t) if rows_live is None \
        else jnp.sum(rows_live, dtype=jnp.int32)
    aux = jnp.stack([n_mine, jnp.sum(sizes > 0, dtype=jnp.int32), routed])
    return out, aux
