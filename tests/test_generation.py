"""Jit decode fast path == eager cached generate (SURVEY §3.7 decode)."""
import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.nlp import GPTForCausalLM, GPTConfig
from paddle_tpu.nlp.generation import generate, build_decode_fn
from paddle_tpu.tensor import Tensor


def _model():
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        use_flash_attention=False))
    m.eval()
    return m


def test_jit_greedy_matches_eager_generate():
    m = _model()
    ids = Tensor(jnp.asarray([[5, 17, 3, 42], [9, 9, 1, 0]], jnp.int32))
    want = m.generate(ids, max_new_tokens=8, temperature=0.0)
    got = generate(m, ids, max_new_tokens=8, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(got._value),
                                  np.asarray(want._value))


def test_jit_decode_single_compile_reuse():
    m = _model()
    fn = build_decode_fn(m, max_new_tokens=4, temperature=0.0)
    params, buffers = m.raw_state()
    ids = jnp.asarray([[1, 2, 3]], jnp.int32)
    out1 = fn(params, buffers, ids, jax.random.PRNGKey(0))
    out2 = fn(params, buffers, jnp.asarray([[4, 5, 6]], jnp.int32),
              jax.random.PRNGKey(1))
    assert out1.shape == out2.shape == (1, 7)


def test_sampled_decode_valid_tokens():
    m = _model()
    out = generate(m, jnp.asarray([[1, 2]], jnp.int32), max_new_tokens=6,
                   temperature=1.0, top_k=5, seed=3)
    arr = np.asarray(out._value)
    assert arr.shape == (1, 8)
    assert (arr >= 0).all() and (arr < 97).all()


def test_static_cache_prefill_matches_full_forward():
    """logits from the cache_index path must equal the plain forward."""
    m = _model()
    ids = Tensor(jnp.asarray([[7, 11, 13, 17, 19]], jnp.int32))
    want = m(ids)  # plain causal forward
    caches = [(Tensor(jnp.zeros((1, 5, 4, 8), jnp.float32)),) * 2
              for _ in range(2)]
    got, _ = m(ids, cache=caches, cache_index=0)
    np.testing.assert_allclose(np.asarray(got._value),
                               np.asarray(want._value),
                               atol=1e-5, rtol=1e-5)


def test_top_p_masks_tail():
    from paddle_tpu.nlp.generation import _mask_top_p
    logits = jnp.asarray([[3.0, 2.0, 1.0, 0.0, -5.0]])
    out = np.asarray(_mask_top_p(logits, 0.6))
    # softmax([3,2,1,0,-5]) ~ [.66,.24,.09,...]: 0.66 >= 0.6 -> only top kept
    assert np.isfinite(out[0, 0])
    assert not np.isfinite(out[0, 2:]).any()
    # top_p=1.0 keeps everything
    full = np.asarray(_mask_top_p(logits, 1.0))
    assert np.isfinite(full).all()


def test_top_p_decode_valid_and_deterministic_seed():
    m = _model()
    ids = Tensor(jnp.asarray([[5, 17, 3, 42]], jnp.int32))
    a = generate(m, ids, max_new_tokens=6, temperature=1.0, top_p=0.9,
                 seed=7)
    b = generate(m, ids, max_new_tokens=6, temperature=1.0, top_p=0.9,
                 seed=7)
    np.testing.assert_array_equal(np.asarray(a._value), np.asarray(b._value))
    assert (np.asarray(a._value) < 97).all()


def test_repetition_penalty_suppresses_repeats():
    from paddle_tpu.nlp.generation import _apply_repetition_penalty
    logits = jnp.asarray([[2.0, -1.0, 0.5]])
    seen = jnp.asarray([[True, True, False]])
    out = np.asarray(_apply_repetition_penalty(logits, seen, 2.0))
    np.testing.assert_allclose(out, [[1.0, -2.0, 0.5]])


def test_eos_early_stop_pads_tail():
    """Once a row emits eos, the remainder of that row is pad."""
    m = _model()
    ids = Tensor(jnp.asarray([[5, 17, 3, 42]], jnp.int32))
    # find what greedy emits, then rerun declaring that token as eos
    base = np.asarray(generate(m, ids, max_new_tokens=6,
                               temperature=0.0)._value)
    eos = int(base[0, 4])  # first generated token
    out = np.asarray(generate(m, ids, max_new_tokens=6, temperature=0.0,
                              eos_token_id=eos, pad_token_id=0)._value)
    assert out[0, 4] == eos
    assert (out[0, 5:] == 0).all()


def test_beam_search_beats_or_equals_greedy_logprob():
    """Beam search's selected sequence must score >= greedy's under the
    model (same start, same length, sum log p) — the defining property."""
    m = _model()
    ids = jnp.asarray([[5, 17, 3, 42]], jnp.int32)
    T = 5

    def seq_logprob(full):
        params, buffers = m.raw_state()
        from paddle_tpu.nn.layer import functional_call
        out = functional_call(m, params, buffers, Tensor(full))
        logits = out[0] if isinstance(out, tuple) else out
        logits = logits._value if hasattr(logits, "_value") else logits
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
        tgt = full[:, 1:]
        pick = jnp.take_along_axis(lp, tgt[:, :, None], -1)[:, :, 0]
        return float(pick[:, -T:].sum())

    greedy = generate(m, ids, max_new_tokens=T, temperature=0.0)
    beam = generate(m, ids, max_new_tokens=T, num_beams=4,
                    length_penalty=0.0)
    lp_g = seq_logprob(np.asarray(greedy._value))
    lp_b = seq_logprob(np.asarray(beam._value))
    assert lp_b >= lp_g - 1e-4, (lp_b, lp_g)


def test_beam_search_shapes_and_batch():
    m = _model()
    ids = jnp.asarray([[5, 17, 3], [2, 8, 11]], jnp.int32)
    out = generate(m, ids, max_new_tokens=4, num_beams=3)
    assert np.asarray(out._value).shape == (2, 7)
    assert (np.asarray(out._value)[:, :3] == np.asarray(ids)).all()


def test_model_generate_delegates_advanced_options():
    m = _model()
    ids = Tensor(jnp.asarray([[5, 17, 3]], jnp.int32))
    out = m.generate(ids, max_new_tokens=4, num_beams=3)
    assert np.asarray(out._value).shape == (1, 7)
    out2 = m.generate(ids, max_new_tokens=4, temperature=1.0, top_p=0.8,
                      seed=3)
    assert np.asarray(out2._value).shape == (1, 7)


def test_sampling_strategy_actually_samples():
    """decode_strategy='sampling' with no filters must NOT be argmax
    (review fix: pure temperature sampling was unreachable)."""
    m = _model()
    ids = Tensor(jnp.asarray([[5, 17, 3, 42]], jnp.int32))
    greedy = np.asarray(generate(m, ids, max_new_tokens=8,
                                 temperature=0.0)._value)
    outs = [np.asarray(generate(m, ids, max_new_tokens=8, temperature=1.5,
                                decode_strategy="sampling",
                                seed=s)._value) for s in range(4)]
    assert any(not np.array_equal(o, greedy) for o in outs)
    assert any(not np.array_equal(outs[0], o) for o in outs[1:])


def test_beam_rejects_topk_topp():
    import pytest
    m = _model()
    ids = Tensor(jnp.asarray([[5, 17, 3]], jnp.int32))
    with pytest.raises(ValueError, match="beam_search"):
        generate(m, ids, num_beams=3, top_k=5)


def test_beam_one_equals_greedy():
    m = _model()
    ids = jnp.asarray([[5, 17, 3, 42]], jnp.int32)
    g = np.asarray(generate(m, ids, max_new_tokens=5,
                            temperature=0.0)._value)
    b1 = np.asarray(generate(m, ids, max_new_tokens=5,
                             decode_strategy="beam_search",
                             num_beams=1)._value)
    np.testing.assert_array_equal(g, b1)


def test_bf16_kv_cache_matches_fp32_greedy():
    """cache_dtype='bfloat16' halves decode HBM traffic (the decode
    bottleneck); greedy token ids must match the fp32 cache on a small
    model (logit gaps >> bf16 cache rounding)."""
    paddle.seed(21)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, max_position_embeddings=64,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    ids = jnp.asarray(np.array([[3, 5, 7, 9]], dtype=np.int64))
    a = np.asarray(generate(m, ids, max_new_tokens=8, temperature=0.0))
    b = np.asarray(generate(m, ids, max_new_tokens=8, temperature=0.0,
                            cache_dtype="bfloat16"))
    np.testing.assert_array_equal(a, b)


def test_bf16_kv_cache_beam_path_runs():
    paddle.seed(22)
    cfg = GPTConfig(vocab_size=48, hidden_size=16, num_hidden_layers=1,
                    num_attention_heads=2, max_position_embeddings=48,
                    intermediate_size=32)
    m = GPTForCausalLM(cfg)
    ids = jnp.asarray(np.array([[1, 2, 3]], dtype=np.int64))
    out = generate(m, ids, max_new_tokens=5, num_beams=3,
                   decode_strategy="beam_search", cache_dtype="bfloat16")
    assert np.asarray(out).shape == (1, 8)


def test_generate_memoizes_compiled_decode_fn():
    """Repeat generate() must reuse the compiled program (every call
    would otherwise pay a full re-compile), stay bounded, and keep the
    model collectable."""
    import gc
    import time
    import weakref

    import paddle_tpu.nlp.generation as gen
    from paddle_tpu.nlp.generation import _MEMO_ATTR, clear_decode_cache
    m = _model()
    ids = Tensor(jnp.asarray([[5, 17, 3, 42], [9, 9, 1, 0]], jnp.int32))
    t0 = time.perf_counter()
    first = generate(m, ids, max_new_tokens=4, temperature=0.0)
    t1 = time.perf_counter()
    second = generate(m, ids, max_new_tokens=4, temperature=0.0)
    t2 = time.perf_counter()
    np.testing.assert_array_equal(np.asarray(first._value),
                                  np.asarray(second._value))
    assert (t2 - t1) < (t1 - t0) / 5, "warm call re-traced"
    # numpy/jax scalar args are coerced into hashable key entries
    generate(m, ids, max_new_tokens=np.int64(4), temperature=jnp.float32(0.5),
             top_k=jnp.int32(2), seed=1)
    # distinct arg combos stay bounded by the LRU cap (cap shrunk to
    # keep the test at 5 compiles instead of _MEMO_MAX+3=11)
    monkey_max = 3
    orig_max = gen._MEMO_MAX
    gen._MEMO_MAX = monkey_max
    try:
        for i in range(monkey_max + 2):
            generate(m, ids, max_new_tokens=2, temperature=0.5 + 0.01 * i,
                     top_k=2, seed=i)
        memo = getattr(m, _MEMO_ATTR)
        assert 0 < len(memo) <= monkey_max
    finally:
        gen._MEMO_MAX = orig_max
    clear_decode_cache(m)
    assert len(memo) == 0
    # memo must not leak into checkpoints, nor pin the model in memory
    assert not any("decode_fn_memo" in k for k in m.state_dict())
    ref = weakref.ref(m)
    del m, memo
    gc.collect()
    assert ref() is None, "decode memo kept the model alive"


def test_generate_threadsafe_on_shared_model():
    """Concurrent generate() on one model must not leak tracers or race
    the LRU (functional_call swaps state into the shared model, so the
    whole call is serialized under the module lock)."""
    import threading

    m = _model()
    ids = Tensor(jnp.asarray([[5, 17, 3, 42]], jnp.int32))
    errs = []

    def worker(i):
        try:
            for j in range(3):
                generate(m, ids, max_new_tokens=2,
                         temperature=0.5 + 0.05 * ((i * 3 + j) % 4),
                         top_k=2, seed=j)
        except Exception as e:  # pragma: no cover - failure diagnostics
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs[:1]
