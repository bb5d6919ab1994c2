"""int8 quantized all-reduce on the virtual 8-device mesh (SURVEY §6
"8-bit-collective option", now implemented — see distributed/quantized.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from paddle_tpu.distributed.quantized import (
    dequantize_int8_blockwise, quantize_int8_blockwise,
    quantized_all_reduce)


def _mesh():
    return Mesh(np.array(jax.devices()), ("dp",))


def test_quantize_roundtrip_exact_on_int_grid():
    x = jnp.asarray(np.random.default_rng(0).integers(
        -127, 128, (4, 512)).astype(np.float32))
    q, s = quantize_int8_blockwise(x, block=256)
    back = dequantize_int8_blockwise(q, s, block=256)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=1e-4)


def test_quantize_relative_error_bounded():
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (8, 1024)).astype(np.float32))
    q, s = quantize_int8_blockwise(x, block=256)
    back = dequantize_int8_blockwise(q, s, block=256)
    err = np.abs(np.asarray(back) - np.asarray(x)).max()
    amax = np.abs(np.asarray(x)).max()
    assert err <= amax / 127.0 + 1e-6


def _qar(mesh, x, block=256):
    fn = shard_map(
        lambda v: quantized_all_reduce(v, "dp", block=block),
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False)
    return fn(x)


def test_quantized_all_reduce_matches_psum():
    mesh = _mesh()
    rng = np.random.default_rng(2)
    # gradient-like magnitudes, one independent slice per device
    x = jnp.asarray(rng.standard_normal((8, 4096)).astype(np.float32) * 1e-2)
    got = np.asarray(_qar(mesh, x))
    want = np.broadcast_to(np.asarray(x).sum(0, keepdims=True), x.shape)
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 1.5e-2, rel
    # every shard must hold the same reduced value (it IS an all-reduce)
    assert np.allclose(got[0], got[3], atol=1e-6)


def test_quantized_all_reduce_exact_on_small_ints():
    mesh = _mesh()
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(-7, 8, (8, 2048)).astype(np.float32))
    got = np.asarray(_qar(mesh, x))
    want = np.broadcast_to(np.asarray(x).sum(0, keepdims=True), x.shape)
    # per-rank chunks are int-valued and within int8 range; stage-2 sums
    # are <= 8*127 but re-scaled — allow one quantization step
    assert np.abs(got - want).max() <= np.abs(want).max() / 127.0 + 1e-5


def test_quantized_all_reduce_ragged_and_nd():
    """Non-block-multiple sizes are padded internally; ND shapes and
    non-f32 dtypes round-trip."""
    mesh = _mesh()
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((8, 3, 37)).astype(np.float32))
    got = np.asarray(_qar(mesh, x, block=64))
    want = np.broadcast_to(np.asarray(x).sum(0, keepdims=True), x.shape)
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 2e-2, rel
    xb = x.astype(jnp.bfloat16)
    got_b = _qar(mesh, xb)
    assert got_b.dtype == jnp.bfloat16
