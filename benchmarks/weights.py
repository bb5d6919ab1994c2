"""Weights from the seed, made by the benchmark and given to the program
and to the reference alike: one jitted call on the device, float32.

Matrices and embeddings are N(0, std), biases N(0, std) and the
LayerNorm gains 1 + N(0, std): every leaf takes part in the result, so
a fault in any of them shows (the published init has zero biases, under
which a dropped bias could not be seen)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _is_gain(name, shape):
    return len(shape) == 1 and name.endswith(".weight")


@functools.partial(jax.jit, static_argnames=("spec", "std"))
def _make(key, spec, std):
    out = {}
    for i, (name, shape) in enumerate(spec):
        x = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        out[name] = 1.0 + x if _is_gain(name, shape) else x
    return out


def make_weights(shapes, seed, std=0.02):
    """shapes: {leaf name: shape}. The same shapes and seed give the same
    arrays, so the reference makes its own copy after the program's is
    freed."""
    spec = tuple(sorted((n, tuple(int(d) for d in s))
                        for n, s in shapes.items()))
    return _make(seed_key(seed), spec, float(std))
