"""Engine.train_batch_multi — K optimizer steps in one dispatch
(amortizes per-dispatch latency on remote backends).

Defining property: EXACTLY equal to K sequential train_batch calls
(same rng folding, same counters, same updates).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.hapi.engine import Engine


def _make(lr=0.01):
    paddle.seed(3)
    net = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.Tanh(),
                               paddle.nn.Linear(16, 4))
    return net, Engine(net, loss=paddle.nn.CrossEntropyLoss(),
                       optimizer=paddle.optimizer.AdamW(
                           lr, parameters=net.parameters()))


def _data(k=4, b=8):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((k, b, 8)).astype(np.float32)
    y = rng.integers(0, 4, (k, b)).astype(np.int64)
    return x, y


def test_multi_equals_sequential():
    x, y = _data()
    _, eng_a = _make()
    seq = [float(eng_a.train_batch([jnp.asarray(x[i])],
                                   [jnp.asarray(y[i])])[0])
           for i in range(4)]
    _, eng_b = _make()
    losses, _ = eng_b.train_batch_multi([jnp.asarray(x)], [jnp.asarray(y)])
    np.testing.assert_allclose(np.asarray(losses), seq, rtol=1e-6)
    for k in eng_a._params:
        np.testing.assert_allclose(np.asarray(eng_a._params[k]),
                                   np.asarray(eng_b._params[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)
    assert eng_b._step == 4 and eng_b._opt_step == 4


def test_multi_then_single_continues_exactly():
    """Counters and rng line up so multi(4) + single == 5 singles."""
    x, y = _data(5)
    _, eng_a = _make()
    for i in range(5):
        last_a, _ = eng_a.train_batch([jnp.asarray(x[i])],
                                      [jnp.asarray(y[i])])
    _, eng_b = _make()
    eng_b.train_batch_multi([jnp.asarray(x[:4])], [jnp.asarray(y[:4])])
    last_b, _ = eng_b.train_batch([jnp.asarray(x[4])], [jnp.asarray(y[4])])
    np.testing.assert_allclose(float(last_b), float(last_a), rtol=1e-6)


def test_multi_lr_values_schedule_matches_sequential():
    x, y = _data(3)
    lrs = np.asarray([0.05, 0.02, 0.01], np.float32)
    # sequential reference: inject each lr before its step
    _, eng_a = _make(lr=1.0)
    for i in range(3):
        eng_a.optimizer._lr = float(lrs[i])
        eng_a.train_batch([jnp.asarray(x[i])], [jnp.asarray(y[i])])
    _, eng_b = _make(lr=1.0)
    losses, _ = eng_b.train_batch_multi([jnp.asarray(x)], [jnp.asarray(y)],
                                        lr_values=lrs)
    assert losses.shape == (3,)
    for k in eng_a._params:
        np.testing.assert_allclose(np.asarray(eng_a._params[k]),
                                   np.asarray(eng_b._params[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)
    with pytest.raises(ValueError, match="lr_values"):
        eng_b.train_batch_multi([jnp.asarray(x)], [jnp.asarray(y)],
                                lr_values=np.ones((2,), np.float32))


def test_multi_mismatched_k_fails_before_counters_move():
    x, y = _data(4)
    _, eng = _make()
    with pytest.raises(ValueError, match="disagree on K"):
        eng.train_batch_multi([jnp.asarray(x)], [jnp.asarray(y[:3])])
    assert eng._step == 0 and eng._opt_step == 0   # counters untouched


def test_multi_flushes_pending_accum_window():
    x, y = _data(2)
    _, eng = _make()
    eng.train_batch_accum([jnp.asarray(x[0])], [jnp.asarray(y[0])],
                          apply_update=False)
    assert eng._micro_count == 1
    eng.train_batch_multi([jnp.asarray(x)], [jnp.asarray(y)])
    assert eng._micro_count == 0


def test_multi_dp_sharded():
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    x, y = _data(3, b=16)
    paddle.seed(3)
    net = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.Tanh(),
                               paddle.nn.Linear(16, 4))
    eng = Engine(net, loss=paddle.nn.CrossEntropyLoss(),
                 optimizer=paddle.optimizer.AdamW(
                     0.01, parameters=net.parameters()), mesh=mesh)
    losses, _ = eng.train_batch_multi([jnp.asarray(x)], [jnp.asarray(y)])
    assert losses.shape == (3,)
    # ragged stacked batch is a loud error
    with pytest.raises(ValueError, match="not divisible"):
        eng.train_batch_multi([jnp.asarray(x[:, :10])],
                              [jnp.asarray(y[:, :10])])
