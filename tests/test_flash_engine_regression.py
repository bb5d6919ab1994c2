"""Regression: the Pallas flash-attention kernel must be trainable through
the PRODUCTION path — F.scaled_dot_product_attention -> apply_op -> Engine's
jitted value_and_grad step.

Round 1 shipped with apply_op building a nested jax.vjp tape inside the
Engine's outer jax.grad trace; for jnp ops that was only compile bloat, but
for the custom_vjp Pallas kernel it crashed (_pallas_call_jvp_rule assert),
killing the TPU bench. On CPU the availability gate hid the bug because the
Pallas route is TPU-only. This test forces the gate on (the kernel then runs
in interpret mode on CPU, same trace/AD structure) and trains real Engine
steps.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.hapi.engine import Engine


@pytest.fixture
def force_flash(monkeypatch):
    import paddle_tpu.ops as ops_pkg
    import paddle_tpu.ops.attention as att

    def available(q_shape, k_shape, attn_mask, dropout_p):
        return attn_mask is None and not dropout_p and len(q_shape) == 4

    monkeypatch.setattr(att, "flash_attention_available", available)
    monkeypatch.setattr(ops_pkg, "flash_attention_available", available)


class TinyAttn(nn.Layer):
    def __init__(self, d_model=64, n_heads=2, seq=128):
        super().__init__()
        self.n_heads = n_heads
        self.qkv = nn.Linear(d_model, 3 * d_model)
        self.out = nn.Linear(d_model, d_model)
        self.head = nn.Linear(d_model, 1)

    def forward(self, x):
        import paddle_tpu.nn.functional as F
        b, s, d = x.shape
        qkv = self.qkv(x).reshape([b, s, 3, self.n_heads,
                                   d // self.n_heads])
        q, k, v = (qkv[:, :, i] for i in range(3))
        o = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        o = o.reshape([b, s, d])
        return self.head(self.out(o)).mean(axis=[1, 2])


def test_engine_train_step_through_pallas_flash(force_flash):
    paddle.seed(0)
    net = TinyAttn()
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    eng = Engine(net, loss=nn.MSELoss(), optimizer=opt)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(2, 128, 64).astype("float32"))
    y = paddle.to_tensor(rng.randn(2).astype("float32"))
    losses = [float(eng.train_batch([x], [y])[0]) for _ in range(4)]
    assert np.isfinite(losses).all()
    assert min(losses[1:]) < losses[0]


def test_eager_backward_through_pallas_flash(force_flash):
    """The eager tape path (outside any jax trace) must also differentiate
    the custom_vjp kernel."""
    import jax.numpy as jnp
    import paddle_tpu.nn.functional as F
    q = paddle.to_tensor(
        np.random.RandomState(1).randn(1, 128, 2, 64).astype("float32"),
        stop_gradient=False)
    out = F.scaled_dot_product_attention(q, q, q, is_causal=True)
    out.sum().backward()
    assert q.grad is not None
    assert bool(jnp.isfinite(q.grad._value).all())


def _gpt_first_losses(force_flash, mesh_axes=None, pipe=False, steps=2):
    """First losses of a tiny GPT (head_dim 64) through the Engine with
    the flash gate forced on, optionally under a 4-device mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from paddle_tpu.distributed.fleet.mpu import shard_model
    from paddle_tpu.nlp.gpt import (GPTConfig, GPTForCausalLM,
                                    GPTForCausalLMPipe,
                                    GPTPretrainingCriterion)
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_hidden_layers=2,
                    num_attention_heads=2, max_position_embeddings=128,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    mesh = None
    if mesh_axes:
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), mesh_axes)
    paddle.seed(0)
    net = (GPTForCausalLMPipe(cfg, mesh=mesh, n_micro=2) if pipe
           else GPTForCausalLM(cfg))
    net.train()
    if mesh is not None:
        shard_model(net, mesh)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=net.parameters())
    eng = Engine(net, loss=GPTPretrainingCriterion(), optimizer=opt,
                 mesh=mesh)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (4, 128)),
                      jnp.int32)
    losses = [float(eng.train_batch([ids], [ids])[0]) for _ in range(steps)]
    assert eng.tracer.counts() == {"train_step": 1}
    return losses


def _assert_mesh_matches_one_device(force_flash, axes, pipe):
    calls = []
    from jax.experimental import pallas as pl
    real = pl.pallas_call

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)
    pl.pallas_call = counting
    try:
        want = _gpt_first_losses(force_flash)
        assert calls, "the flash kernel was not on the path"
        got = _gpt_first_losses(force_flash, axes, pipe)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    finally:
        pl.pallas_call = real


def test_flash_kernel_runs_per_shard_under_a_mesh(force_flash):
    """Mosaic kernels cannot be partitioned by GSPMD (every mesh layout
    raised NotImplementedError on a 4-chip host): under a mesh the
    kernel must run inside a shard_map — batch over dp, heads over mp —
    and reproduce the one-device losses, with one compile."""
    _assert_mesh_matches_one_device(force_flash, ("dp", "mp"), pipe=False)


@pytest.mark.slow
def test_flash_kernel_nests_inside_the_pipeline(force_flash):
    """Same, nested inside the pipeline's pp-manual region (heads over
    the remaining mp axis). chip_smoke.py's mesh phase covers this
    layout natively."""
    _assert_mesh_matches_one_device(force_flash, ("mp", "pp"), pipe=True)
