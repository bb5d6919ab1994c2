"""Weights held at the width the products read them.

A TPU at the default matmul precision multiplies float32 operands as
bfloat16 with float32 accumulation. The serving engine then holds each
float32 matrix that only linear-family layers read as its bfloat16
rounding, once (`serving.hold_weights`), for its decode scan; the
prefills read the model's own leaves. `F.linear` is unchanged: it
widens a narrower weight to the input's dtype, and the TPU's compiler
folds that into the product, which reads the bfloat16 matrix
(`test_flash_tpu_compile.py` holds it to that). No chip is here: the
platform check is forced, and the engine is compared with one whose
products round both operands to bfloat16, as the chip's would."""
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import framework
from paddle_tpu.autograd import apply_op
from paddle_tpu.nlp import serving
from paddle_tpu.nlp.gpt import GPTForCausalLM
from paddle_tpu.nn.layer import functional_call
from paddle_tpu.tensor import Tensor

ENGINE = dict(max_slots=3, page_size=16, max_seq_len=64, prefix_cache=False,
              steps_per_dispatch=4)


def _linear_jaxpr(x, w, b):
    return jax.make_jaxpr(
        lambda a, w, b: F.linear(Tensor(a), Tensor(w), Tensor(b))._value)(
            x, w, b)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_linear_of_one_dtype_is_the_plain_product(dtype):
    rng = np.random.default_rng(0)
    x, w, b = (jnp.asarray(rng.standard_normal(s), dtype)
               for s in ((2, 3, 8), (8, 5), (5,)))
    plain = jax.make_jaxpr(lambda a, w, b: a @ w + b)(x, w, b)
    assert str(_linear_jaxpr(x, w, b)) == str(plain)
    got = F.linear(Tensor(x), Tensor(w), Tensor(b))._value
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x @ w + b))


def test_a_narrower_weight_meets_the_plain_product():
    """A float32 input against a bfloat16 weight: the weight is widened
    and the product is the plain one, as it always was, on every backend
    and at every precision."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 3, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((8, 5)), jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((5,)), jnp.float32)
    plain = jax.make_jaxpr(lambda a, w, b: a @ w + b)(x, w, b)
    assert str(_linear_jaxpr(x, w, b)) == str(plain)
    got = F.linear(Tensor(x), Tensor(w), Tensor(b))._value
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x @ w + b))
    gw = jax.grad(lambda w: F.linear(Tensor(x), Tensor(w))._value.sum())(w)
    assert gw.dtype == jnp.bfloat16 and gw.shape == w.shape


def _model(fused_qkv):
    paddle.seed(0)
    model = GPTForCausalLM.from_config_name("gpt-tiny", fused_qkv=fused_qkv)
    model.eval()
    return model


def _serve(eng, prompts):
    rids = [eng.submit(np.asarray(p, np.int32), max_new_tokens=10)
            for p in prompts]
    got = {}
    while len(got) < len(rids):
        for res in eng.step():
            assert res["status"] == "ok"
            got[res["id"]] = res["tokens"]
    return [got[r] for r in rids]


def _chip_linear(x, weight, bias=None, name=None):
    """`F.linear` as the chip's default precision computes it: both
    operands rounded to bfloat16, float32 accumulation."""
    def product(a, w, *b):
        out = jax.lax.dot_general(
            a.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
            (((a.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return out + b[0] if b else out
    args = (x, weight) if bias is None else (x, weight, bias)
    return apply_op(product, *(F._t(t) for t in args))


@pytest.mark.parametrize("fused_qkv,per_layer", [(False, 6), (True, 4)])
def test_the_engine_holds_the_linear_matrices_and_serves_as_the_chip_would(
        monkeypatch, fused_qkv, per_layer):
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, (n,)).tolist() for n in (5, 17, 30, 9)]
    model = _model(fused_qkv)
    monkeypatch.setattr(F, "linear", _chip_linear)
    plain = serving.ServingEngine(model, **ENGINE)
    assert plain.health()["held_weights"] == {
        "leaves": 0, "gb": 0.0, "dtype": "bfloat16"}
    want_tokens = _serve(plain, prompts)
    ids = Tensor(jnp.asarray([prompts[1] + want_tokens[1]], jnp.int32))
    want_logits = functional_call(model, plain._params, plain._buffers,
                                  ids)._value[0, -1]

    monkeypatch.setattr(framework, "products_round_to_bfloat16",
                        lambda: True)
    eng = serving.ServingEngine(model, **ENGINE)
    layers = model.config.num_hidden_layers
    held = sorted(n for n, a in eng._decode_params.items()
                  if a.dtype == jnp.bfloat16)
    names = (["attn.qkv_proj"] if fused_qkv else
             ["attn.q_proj", "attn.k_proj", "attn.v_proj"]) + [
        "attn.out_proj", "mlp.fc1", "mlp.fc2"]
    assert held == sorted(f"gpt.h.{i}.{n}.weight" for i in range(layers)
                          for n in names)
    assert len(held) == per_layer * layers
    # the embeddings (the tied head's gather reads them exactly), the
    # LayerNorms and every bias stay float32, and so do the model's own
    # Parameters
    assert all(eng._decode_params[n].dtype == jnp.float32
               for n in eng._decode_params if n not in held)
    assert all(p._value.dtype == jnp.float32 for p in model.parameters())
    # the prefills read the model's own leaves, as the parent's did
    assert all(a.dtype == jnp.float32 for a in eng._params.values())
    assert eng.health()["held_weights"] == {
        "leaves": per_layer * layers,
        "gb": sum(eng._decode_params[n].nbytes for n in held) / 1e9,
        "dtype": "bfloat16"}

    assert _serve(eng, prompts) == want_tokens
    got_logits = functional_call(model, eng._decode_params, eng._buffers,
                                 ids)._value[0, -1]
    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(want_logits), rtol=0, atol=1e-5)


def test_the_first_wave_builds_no_program_the_warm_up_built(monkeypatch,
                                                            caplog):
    """A held leaf keeps the committed-ness of the leaf it replaces: one
    committed argument commits the page pool each program returns, and
    every program warmed on the fresh pool then compiles once more at its
    first real call, inside the traffic it should serve."""
    monkeypatch.setattr(framework, "products_round_to_bfloat16",
                        lambda: True)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, (n,)).tolist() for n in (5, 17, 30)]
    eng = serving.ServingEngine(_model(False), **ENGINE)
    params = eng.model.raw_state()[0]
    assert {n: getattr(a, "_committed", None)
            for n, a in eng._decode_params.items()} == {
        n: getattr(a, "_committed", None) for n, a in params.items()}
    eng.warmup(buckets=[len(p) for p in prompts])
    with jax.log_compiles(), caplog.at_level(logging.WARNING):
        _serve(eng, prompts)
    built = [r.getMessage().split(" with ")[0] for r in caplog.records
             if r.getMessage().startswith("Compiling jit(")]
    assert not [b for b in built
                if re.fullmatch(r"Compiling jit\((decode|prefill_\d+)\)", b)]


@pytest.mark.parametrize("precision,held", [
    ("default", 12), ("high", 0), ("highest", 0)])
def test_weights_are_held_only_where_products_round_them(
        monkeypatch, precision, held):
    """On a TPU asked for float32 products, rounding the weights would
    change the answer: they are kept as they are."""
    model = _model(False)
    params = model.raw_state()[0]
    assert serving.hold_weights(model, params)[1]["leaves"] == 0  # a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prev = paddle.get_flags("matmul_precision")["matmul_precision"]
    paddle.set_flags({"matmul_precision": precision})
    try:
        out, info = serving.hold_weights(model, params)
    finally:
        paddle.set_flags({"matmul_precision": prev})
    assert info["leaves"] == held
    assert sum(a.dtype == jnp.bfloat16 for a in out.values()) == held


def test_a_matrix_another_layer_also_reads_is_not_held(monkeypatch):
    """A linear layer whose weight is shared with a layer of another kind
    keeps it: that layer may need the exact values."""
    monkeypatch.setattr(framework, "products_round_to_bfloat16",
                        lambda: True)

    class Tied(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = paddle.nn.Embedding(8, 8)
            self.head = paddle.nn.Linear(8, 8)
            self.head.weight = self.emb.weight
            self.proj = paddle.nn.Linear(8, 4)

    model = Tied()
    params, held = serving.hold_weights(model, model.raw_state()[0])
    assert held["leaves"] == 1
    assert {n for n, a in params.items() if a.dtype == jnp.bfloat16} == {
        "proj.weight"}


# sha256 (16 hex digits) of the jaxpr of `Engine.train_step` for gpt-tiny
# under the train cell's setting (bench.build_engine, bf16 AMP, flash),
# batch 2 x 32, object addresses blanked, at commit 9da9ec8: the AMP step
# casts every parameter to bfloat16 and never meets a mixed product
PARENT_TRAIN_STEP = "c99df71bbc6db8b8"


def test_the_train_step_is_the_parent_s():
    import hashlib
    import os
    import re
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench
    paddle.seed(0)
    eng = bench.build_engine("gpt-tiny", 2, 32, amp=True)
    eng._ensure_opt_state()
    ids = jnp.zeros((2, 32), jnp.int32)
    text = str(jax.make_jaxpr(eng._build_train_fn())(
        eng._params, eng._buffers, eng._opt_state, np.float32(1e-4),
        np.int32(1), np.int32(1), eng._rng_key, [ids], [ids]))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_TRAIN_STEP


def test_an_artifact_of_held_weights_is_not_one_of_float32_weights(
        monkeypatch):
    """The held leaves' dtype is in every serving program's signature: an
    engine that holds none must not take the programs of one that does."""
    from paddle_tpu.jit.serving_artifact import artifact_fingerprint
    model = _model(False)
    plain = artifact_fingerprint(serving.ServingEngine(model, **ENGINE))
    monkeypatch.setattr(framework, "products_round_to_bfloat16",
                        lambda: True)
    held = artifact_fingerprint(serving.ServingEngine(model, **ENGINE))
    assert plain["held_weights"] == {"leaves": 0, "dtype": "bfloat16"}
    assert held["held_weights"] == {"leaves": 12, "dtype": "bfloat16"}
    assert {k for k in held if held[k] != plain[k]} == {"held_weights"}


def test_the_memory_ledger_counts_the_float32_originals_and_the_held(
        monkeypatch):
    """The model keeps its float32 Parameters beside the held copies:
    both are resident, and a leaf they share counts once."""
    monkeypatch.setattr(framework, "products_round_to_bfloat16",
                        lambda: True)
    model = _model(False)
    eng = serving.ServingEngine(model, mem_ledger=True, **ENGINE)
    params, buffers = model.raw_state()
    held = sum(a.nbytes for a in eng._decode_params.values()
               if a.dtype == jnp.bfloat16)
    want = (sum(a.nbytes for a in params.values()) + held
            + sum(a.nbytes for a in buffers.values()))
    assert eng.held_weights["gb"] == held / 1e9 > 0
    assert eng.ledger.segments()["weights"] == want
