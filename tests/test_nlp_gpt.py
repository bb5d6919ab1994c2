"""GPT flagship tests (parity: PaddleNLP tests/transformers/gpt)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.fleet.mpu import shard_model
from paddle_tpu.nlp import (GPTConfig, GPTModel, GPTForCausalLM,
                            GPTPretrainingCriterion, GPT_CONFIGS)
from paddle_tpu.nn.layer import functional_call


def tiny():
    return GPTConfig(**GPT_CONFIGS["gpt-tiny"])


def test_forward_shape():
    m = GPTForCausalLM(tiny())
    m.eval()
    ids = paddle.to_tensor(np.arange(2 * 16).reshape(2, 16) % 256)
    logits = m(ids)
    assert logits.shape == [2, 16, 256]


def test_causality():
    """logits at position t must not depend on tokens > t."""
    m = GPTForCausalLM(tiny())
    m.eval()
    a = np.random.RandomState(0).randint(0, 256, (1, 12))
    b = a.copy()
    b[0, 8:] = (b[0, 8:] + 7) % 256  # perturb the future
    la = m(paddle.to_tensor(a)).numpy()
    lb = m(paddle.to_tensor(b)).numpy()
    np.testing.assert_allclose(la[0, :8], lb[0, :8], rtol=1e-4, atol=1e-4)
    assert np.abs(la[0, 8:] - lb[0, 8:]).max() > 1e-3


def test_cached_decode_matches_full_forward():
    m = GPTForCausalLM(tiny())
    m.eval()
    ids = np.random.RandomState(1).randint(0, 256, (2, 10))
    full = m(paddle.to_tensor(ids)).numpy()
    # prefill on first 9 tokens, then decode token 10 with the cache
    logits, cache = m(paddle.to_tensor(ids[:, :9]), use_cache=True)
    pos = paddle.to_tensor(np.full((2, 1), 9, dtype=np.int32))
    step, _ = m(paddle.to_tensor(ids[:, 9:10]), position_ids=pos, cache=cache)
    np.testing.assert_allclose(step.numpy()[:, 0], full[:, 9],
                               rtol=1e-4, atol=1e-4)


def test_generate():
    m = GPTForCausalLM(tiny())
    ids = paddle.to_tensor(np.array([[1, 2, 3]], dtype=np.int64))
    out = m.generate(ids, max_new_tokens=5)
    assert out.shape == [1, 8]
    out2 = m.generate(ids, max_new_tokens=5)
    np.testing.assert_array_equal(out.numpy(), out2.numpy())  # greedy determinism


def test_pretraining_criterion():
    crit = GPTPretrainingCriterion()
    logits = np.random.RandomState(2).randn(2, 4, 16).astype(np.float32)
    labels = np.random.RandomState(3).randint(0, 16, (2, 4))
    mask = np.array([[1, 1, 0, 1], [1, 0, 1, 1]], dtype=np.float32)
    got = float(crit(paddle.to_tensor(logits), paddle.to_tensor(labels),
                     paddle.to_tensor(mask)))
    m = logits.max(-1, keepdims=True)
    lse = np.log(np.exp(logits - m).sum(-1)) + m[..., 0]
    ce = lse - np.take_along_axis(logits, labels[..., None], -1)[..., 0]
    want = (ce * mask).sum() / mask.sum()
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_gspmd_dp_mp_matches_dense():
    """Sharded (dp=2, mp=4) jitted forward == dense single-device forward."""
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "mp"))
    old = mesh_mod._global_mesh
    try:
        m = GPTForCausalLM(tiny())
        m.eval()
        ids = np.random.RandomState(4).randint(0, 256, (4, 16))
        dense = m(paddle.to_tensor(ids)).numpy()
        mesh_mod.set_mesh(mesh)
        shard_model(m, mesh)
        params, buffers = m.raw_state()

        @jax.jit
        def fwd(params, ids):
            out = functional_call(m, params, buffers, paddle.Tensor(ids))
            return out._value

        got = np.asarray(fwd(params, jnp.asarray(ids)))
        np.testing.assert_allclose(got, dense, rtol=2e-4, atol=2e-4)
    finally:
        mesh_mod._global_mesh = old


def test_grad_step_decreases_loss():
    """One fused train step on the tiny config lowers the LM loss."""
    m = GPTForCausalLM(tiny())
    crit = GPTPretrainingCriterion()
    m.train()
    ids = np.random.RandomState(5).randint(0, 256, (4, 16))
    inp, lab = ids[:, :-1], ids[:, 1:]
    params, buffers = m.raw_state()

    def loss_fn(p):
        logits = functional_call(m, p, buffers, paddle.Tensor(inp))
        return crit(logits, paddle.Tensor(lab))._value

    l0, g = jax.value_and_grad(loss_fn)(params)
    p1 = jax.tree_util.tree_map(lambda p, gg: p - 0.1 * gg, params, g)
    l1 = loss_fn(p1)
    assert float(l1) < float(l0)


def test_shard_map_mp_loss_matches_dense():
    """Explicit shard_map TP: vocab-local logits + ParallelCrossEntropy
    must give the SAME loss as the dense model (regression: gathering
    logits before the parallel CE double-counted the partition function)."""
    from jax.sharding import Mesh
    from jax import shard_map
    m = GPTForCausalLM(tiny())
    crit = GPTPretrainingCriterion()
    m.eval()
    ids = np.random.RandomState(6).randint(0, 256, (2, 16))
    inp, lab = ids[:, :-1], ids[:, 1:]
    dense_logits = m(paddle.to_tensor(inp))
    dense_loss = float(crit(dense_logits, paddle.to_tensor(lab)))

    mesh = Mesh(np.array(jax.devices()[:4]), ("mp",))
    params, buffers = m.raw_state()

    def step(inp, lab, params):
        logits = functional_call(m, params, buffers, paddle.Tensor(inp))
        return crit(logits, paddle.Tensor(lab))._value

    specs = {}
    for n, p in m.named_parameters():
        sp = getattr(p, "sharding_spec", None)
        specs[n] = sp if sp is not None else P()
    fn = shard_map(step, mesh=mesh, in_specs=(P(), P(), specs),
                   out_specs=P(), check_vma=False)
    got = float(jax.jit(fn)(inp, lab, params))
    np.testing.assert_allclose(got, dense_loss, rtol=1e-4)


def test_float_padding_mask_matches_bool_mask():
    """Regression: 0/1 int/float padding masks (tokenizer convention) must
    mask, not act as a +1 additive bias."""
    import jax.numpy as jnp
    from paddle_tpu.nlp.gpt import GPTModel, GPTConfig
    import paddle_tpu as paddle

    paddle.seed(0)
    m = GPTModel(GPTConfig(vocab_size=64, hidden_size=32,
                           num_hidden_layers=1, num_attention_heads=2,
                           max_position_embeddings=16,
                           hidden_dropout_prob=0.0,
                           attention_probs_dropout_prob=0.0))
    m.eval()
    ids = paddle.to_tensor(np.arange(8, dtype=np.int32)[None, :] % 64)
    pad = np.array([[1, 1, 1, 1, 1, 0, 0, 0]])
    out_bool = m(ids, attention_mask=paddle.to_tensor(pad.astype(bool)))
    out_f32 = m(ids, attention_mask=paddle.to_tensor(pad.astype(np.float32)))
    out_i64 = m(ids, attention_mask=paddle.to_tensor(pad.astype(np.int64)))
    np.testing.assert_allclose(np.asarray(out_f32._value),
                               np.asarray(out_bool._value), atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_i64._value),
                               np.asarray(out_bool._value), atol=1e-6)
