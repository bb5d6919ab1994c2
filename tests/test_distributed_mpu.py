"""Tensor-parallel layer tests on the virtual 8-device CPU mesh.

Parity target: test/collective/fleet test_parallel_dygraph_mp_layers —
tp linear == dense linear, vocab-parallel embedding == dense embedding,
parallel CE == dense CE.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

import paddle_tpu as paddle
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.fleet.mpu import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    ParallelCrossEntropy, shard_model, param_specs)
from paddle_tpu.nn.layer import functional_call


@pytest.fixture
def mp_mesh():
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "mp"))
    old = mesh_mod._global_mesh
    mesh_mod.set_mesh(mesh)
    yield mesh
    mesh_mod._global_mesh = old


def test_column_row_gspmd_matches_dense(mp_mesh):
    """col(gather=False) -> row(parallel-in) under jit == dense 2-layer MLP."""
    rng = np.random.RandomState(0)
    x = rng.randn(8, 16).astype(np.float32)
    col = ColumnParallelLinear(16, 32, gather_output=False)
    row = RowParallelLinear(32, 16, input_is_parallel=True)
    shard_model(col, mp_mesh)
    shard_model(row, mp_mesh)

    params = {**{f"c.{n}": p._value for n, p in col.named_parameters()},
              **{f"r.{n}": p._value for n, p in row.named_parameters()}}

    @jax.jit
    def fwd(params, x):
        cp = {n[2:]: v for n, v in params.items() if n.startswith("c.")}
        rp = {n[2:]: v for n, v in params.items() if n.startswith("r.")}
        h = functional_call(col, cp, {}, paddle.Tensor(x))
        y = functional_call(row, rp, {}, h)
        return y._value

    got = np.asarray(fwd(params, x))
    w1, b1 = np.asarray(col.weight), np.asarray(col.bias)
    w2, b2 = np.asarray(row.weight), np.asarray(row.bias)
    want = (x @ w1 + b1) @ w2 + b2
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_column_row_shard_map_matches_dense(mp_mesh):
    """Explicit shard_map path: local weight shards + psum == dense."""
    rng = np.random.RandomState(1)
    x = rng.randn(4, 16).astype(np.float32)
    col = ColumnParallelLinear(16, 32, gather_output=False)
    row = RowParallelLinear(32, 16, input_is_parallel=True)
    w1 = np.asarray(col.weight)
    b1 = np.asarray(col.bias)
    w2 = np.asarray(row.weight)
    b2 = np.asarray(row.bias)

    def stage(x, w1, b1, w2, b2):
        h = functional_call(col, {"weight": w1, "bias": b1}, {},
                            paddle.Tensor(x))
        y = functional_call(row, {"weight": w2, "bias": b2}, {}, h)
        return y._value

    fn = shard_map(
        stage, mesh=mp_mesh,
        in_specs=(P(), P(None, "mp"), P("mp"), P("mp", None), P()),
        out_specs=P(),
        check_vma=False)
    got = np.asarray(jax.jit(fn)(x, w1, b1, w2, b2))
    want = (x @ w1 + b1) @ w2 + b2
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_vocab_parallel_embedding_shard_map(mp_mesh):
    vocab, dim = 64, 8
    emb = VocabParallelEmbedding(vocab, dim)
    w = np.asarray(emb.weight)
    ids = np.array([[0, 5, 63, 17], [33, 2, 48, 31]], dtype=np.int32)

    def stage(ids, w):
        out = functional_call(emb, {"weight": w}, {}, paddle.Tensor(ids))
        return out._value

    fn = shard_map(stage, mesh=mp_mesh,
                   in_specs=(P(), P("mp", None)), out_specs=P(),
                   check_vma=False)
    got = np.asarray(jax.jit(fn)(ids, w))
    np.testing.assert_allclose(got, w[ids], rtol=1e-6, atol=1e-6)


def test_parallel_cross_entropy_shard_map(mp_mesh):
    rng = np.random.RandomState(2)
    logits = rng.randn(4, 64).astype(np.float32)
    labels = np.array([3, 60, 17, 42], dtype=np.int32)
    ce = ParallelCrossEntropy()

    def stage(lg, lb):
        out = ce(paddle.Tensor(lg), paddle.Tensor(lb))
        return out._value

    fn = shard_map(stage, mesh=mp_mesh,
                   in_specs=(P(None, "mp"), P()), out_specs=P(),
                   check_vma=False)
    got = np.asarray(jax.jit(fn)(logits, labels))
    m = logits.max(-1, keepdims=True)
    lse = np.log(np.exp(logits - m).sum(-1)) + m[:, 0]
    want = lse - logits[np.arange(4), labels]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_parallel_ce_dense_path_matches():
    logits = np.random.RandomState(3).randn(6, 33).astype(np.float32)
    labels = np.array([0, 5, 32, 7, 9, 11], dtype=np.int32)
    ce = ParallelCrossEntropy()
    got = np.asarray(ce(paddle.Tensor(logits), paddle.Tensor(labels)))
    m = logits.max(-1, keepdims=True)
    lse = np.log(np.exp(logits - m).sum(-1)) + m[:, 0]
    want = lse - logits[np.arange(6), labels]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_param_specs_and_shard_model_placement(mp_mesh):
    col = ColumnParallelLinear(16, 32, gather_output=False)
    shard_model(col, mp_mesh)
    specs = param_specs(col)
    assert specs["weight"] == P(None, "mp")
    sh = col.weight._value.sharding
    assert isinstance(sh, NamedSharding) and sh.spec == P(None, "mp")


def test_grad_through_tp_stack_matches_dense(mp_mesh):
    """value_and_grad through GSPMD tp layers == dense grads."""
    paddle.seed(4)  # pin layer init: fd-vs-grad tolerance depends on it
    rng = np.random.RandomState(4)
    x = rng.randn(8, 16).astype(np.float32)
    col = ColumnParallelLinear(16, 32, gather_output=False)
    row = RowParallelLinear(32, 16, input_is_parallel=True)
    shard_model(col, mp_mesh)
    shard_model(row, mp_mesh)
    params = {"cw": col.weight._value, "cb": col.bias._value,
              "rw": row.weight._value, "rb": row.bias._value}

    @jax.jit
    def loss_fn(params, x):
        h = functional_call(col, {"weight": params["cw"],
                                  "bias": params["cb"]}, {},
                            paddle.Tensor(x))
        y = functional_call(row, {"weight": params["rw"],
                                  "bias": params["rb"]}, {}, h)
        return jnp.mean(y._value ** 2)

    g = jax.jit(jax.grad(loss_fn))(params, x)

    w1, b1 = np.asarray(col.weight), np.asarray(col.bias)
    w2, b2 = np.asarray(row.weight), np.asarray(row.bias)

    def dense_loss(w1):
        return jnp.mean(((x @ w1 + b1) @ w2 + b2) ** 2)

    ref = jax.grad(dense_loss)(jnp.asarray(w1))
    np.testing.assert_allclose(np.asarray(g["cw"]), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


class TestRNGStateTracker:
    def test_eager_streams_decorrelated_and_deterministic(self):
        from paddle_tpu.distributed.fleet.mpu import get_rng_state_tracker
        from paddle_tpu.framework import next_rng_key
        tr = get_rng_state_tracker()
        tr.reset()
        tr.add("global_seed", 100)
        tr.add("local_seed", 200)
        with tr.rng_state("global_seed"):
            g1 = next_rng_key()
        with tr.rng_state("local_seed"):
            l1 = next_rng_key()
        assert not np.array_equal(np.asarray(g1), np.asarray(l1))
        # re-adding the same seeds replays the same stream
        tr.add("global_seed", 100)
        with tr.rng_state("global_seed"):
            g1b = next_rng_key()
        assert np.array_equal(np.asarray(g1), np.asarray(g1b))

    def test_shard_map_local_stream_decorrelates_ranks(self):
        from paddle_tpu.distributed.fleet.mpu import get_rng_state_tracker
        from paddle_tpu.framework import next_rng_key, _rng_scope_ctx, RNGScope
        tr = get_rng_state_tracker()
        mesh = Mesh(np.array(jax.devices()[:4]), ("mp",))

        def draw(stream):
            def f():
                with _rng_scope_ctx(RNGScope(jax.random.PRNGKey(7))):
                    with tr.rng_state(stream):
                        k = next_rng_key()
                return jax.random.uniform(k, (1, 4))
            return shard_map(f, mesh=mesh, in_specs=(),
                             out_specs=P("mp"))()

        local = np.asarray(draw("local_seed"))    # [4, 4]
        glob = np.asarray(draw("global_seed"))
        # local stream: every rank draws a different row
        assert len({tuple(r) for r in local.round(6).tolist()}) == 4
        # global stream: identical rows on all ranks
        for r in glob[1:]:
            np.testing.assert_allclose(r, glob[0])


def test_fleet_ps_mode_gated():
    """SURVEY §2.6 descope: parameter-server mode raises a loud gate with
    a TPU migration recipe; the COLLECTIVE role_maker idiom still works."""
    import pytest as _pytest
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.base import PaddleCloudRoleMaker
    with _pytest.raises(NotImplementedError, match="parameter-server"):
        fleet.init(role_maker=PaddleCloudRoleMaker(is_collective=False))
    with _pytest.raises(NotImplementedError, match="VocabParallelEmbedding"):
        fleet.init(is_collective=False)
    # reference collective idiom must NOT be gated
    fleet.init(role_maker=PaddleCloudRoleMaker(is_collective=True))
