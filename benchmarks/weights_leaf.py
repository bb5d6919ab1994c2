"""Weights from the seed a leaf at a time, for a configuration whose
leaves do not fit the device together in float32 (`weights.make_weights`
makes them all in one float32 call). The same rule as there: matrices and
embeddings N(0, std), norm gains 1 + N(0, std); each leaf's stream is the
seed's key folded with a hash of its name, so a leaf is the same whenever
and in whatever order it is made. Every leaf is rounded to bfloat16 once,
here: the program gets it as bfloat16 and the reference as float32 of the
same values, so the comparison sees the computation and not the storage."""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

from benchmarks.weights import _is_gain, seed_key


@functools.partial(jax.jit, static_argnames=("shape", "gain", "std", "dtype"))
def _make(key, shape, gain, std, dtype):
    def one(k, shape=shape):
        x = std * jax.random.normal(k, shape, jnp.float32)
        if gain:
            x = 1.0 + x
        return x.astype(jnp.bfloat16).astype(dtype)

    if len(shape) < 3:
        return one(key)
    # a stack (of experts): a member at a time, or the float32 draws of
    # the whole stack lie beside a device that is already full
    return jax.lax.map(lambda k: one(k, shape[1:]),
                       jax.random.split(key, shape[0]))


def make_leaf(name, shape, seed, std=0.02, dtype="bfloat16"):
    shape = tuple(int(d) for d in shape)
    key = jax.random.fold_in(seed_key(seed),
                             zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return _make(key, shape, _is_gain(name, shape), float(std),
                 jnp.dtype(dtype))
