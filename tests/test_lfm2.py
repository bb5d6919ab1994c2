"""LFM2-8B-A1B at a tiny size of the same architecture (6 layers
`c c A c c A`, 2 dense, hidden 64, 4 heads over 2 K/V heads, 8 experts
top-2 of width 32, vocabulary 128; seeded weights of std 0.2 so that
nothing is near-linear, a selection bias of std 1 so that it changes
choices), each path against the plain reference `benchmarks/reference/
lfm2.py` at logit level. The engine hands back tokens, not logits; a
served token is judged by how far its REFERENCE logit lies below the
reference's best at its position (`served_gaps`): 0 when the engine's
logits order the vocabulary as the reference's do."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from benchmarks.reference import lfm2 as reference  # noqa: E402
from paddle_tpu.nlp import lfm2, moe, paged_cache  # noqa: E402
from paddle_tpu.nlp.lfm2 import LFM2ForCausalLM  # noqa: E402
from paddle_tpu.nlp.serving import ServingEngine  # noqa: E402
from paddle_tpu.tensor import Tensor  # noqa: E402

# float32 engine against the float32 reference: the same mathematics in
# another order of sums (grouped products over sorted rows, the paged
# softmax); logits of size 5-10 agree to 1e-5, so a served token's
# reference logit lies within 1e-4 of the reference's best
F32_GAP = 1e-4
# bfloat16 weights, pages and state: operands rounded to 8 bits of
# mantissa move logits of this size by up to 0.1-0.2 and flip a token
# where two logits lie closer than that; measured here 0.06 at most
BF16_GAP = 0.5
ENGINE = dict(max_slots=3, page_size=16, max_seq_len=64,
              prefix_cache=False, steps_per_dispatch=4)


def _weights(model, seed=0, std=0.2):
    rng = np.random.default_rng(seed)
    w = {}
    for name, p in model.named_parameters():
        x = std * rng.standard_normal(tuple(p.shape)).astype(np.float32)
        if len(p.shape) == 1 and name.endswith(".weight"):
            x = 1.0 + x
        if name.endswith("expert_bias"):
            x = rng.standard_normal(tuple(p.shape)).astype(np.float32)
        w[name] = jnp.asarray(x)
    return w


def _model(dtype="float32", **overrides):
    paddle.seed(0)
    model = LFM2ForCausalLM.from_config_name("lfm2-tiny", dtype=dtype,
                                             **overrides)
    model.eval()
    w = _weights(model)
    if dtype != "float32":
        # rounded once, the reference gets the same values as float32
        w = {n: v.astype(dtype).astype(jnp.float32) for n, v in w.items()}
    model.load_raw_state({n: v.astype(dtype) for n, v in w.items()})
    return model, w, dataclasses.asdict(model.config)


def _serve(eng, requests):
    """Submit all, step until all are back; [tokens] in submit order."""
    rids = [eng.submit(np.asarray(p, np.int32), max_new_tokens=n)
            for p, n in requests]
    got = {}
    while len(got) < len(rids):
        for res in eng.step():
            assert res["status"] == "ok"
            got[res["id"]] = res["tokens"]
    return [got[r] for r in rids]


def _worst_gap(w, cfg, requests, served):
    gaps = reference.served_gaps(
        lambda names: {n: w[n] for n in names}, cfg,
        [(list(p), t) for (p, _), t in zip(requests, served)])
    return max(float(jnp.max(g)) for g in gaps)


def _prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 128, (n,)).tolist() for n in lengths]


def test_the_leaves_are_the_reference_s_and_the_full_forward_agrees():
    model, w, cfg = _model()
    assert {n: tuple(p.shape) for n, p in model.named_parameters()} == \
        reference.leaf_shapes(cfg)
    ids = np.random.default_rng(1).integers(0, 128, (2, 24))
    got = model(Tensor(jnp.asarray(ids)))._value
    want = reference.forward(w, ids, cfg)
    # float32 on both sides, logits up to 6: rounding of another order
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    # right padding under a mask leaves the true rows as they were
    padded = np.concatenate([ids, np.zeros((2, 8), ids.dtype)], axis=1)
    mask = (np.arange(32)[None, :] < 24).astype(np.int32).repeat(2, 0)
    again = model(Tensor(jnp.asarray(padded)),
                  attention_mask=Tensor(jnp.asarray(mask)))._value
    assert float(jnp.max(jnp.abs(again[:, :24] - want))) < 2e-5


def _padded_prefill_then_decode():
    model, w, cfg = _model()
    eng = ServingEngine(model, cache_dtype="float32", **ENGINE)
    requests = [(_prompts(2, 21)[0], 13)]       # bucket 32, 12 decode steps
    served = _serve(eng, requests)
    assert eng.health()["conv_state_prefill_writes"] == 4
    return _worst_gap(w, cfg, requests, served)


def test_a_prompt_shorter_than_its_bucket_then_twelve_decode_steps():
    assert _padded_prefill_then_decode() < F32_GAP


def test_it_fails_if_the_state_is_taken_at_the_bucket_s_end(monkeypatch):
    at = paged_cache.conv_state_at
    monkeypatch.setattr(lfm2, "conv_state_at",
                        lambda g, lens, taps: at(g, None, taps))
    assert _padded_prefill_then_decode() > 100 * F32_GAP


def _same_slot_twice():
    model, w, cfg = _model()
    one = dict(ENGINE, max_slots=1)
    first, second = [(p, 9) for p in _prompts(3, 19, 11)]
    eng = ServingEngine(model, cache_dtype="float32", **one)
    _serve(eng, [first])
    again = _serve(eng, [second])
    fresh = _serve(ServingEngine(model, cache_dtype="float32", **one),
                   [second])
    return again, fresh, _worst_gap(w, cfg, [second], again)


def test_two_requests_in_turn_through_one_slot_leave_no_state_over():
    again, fresh, gap = _same_slot_twice()
    assert again == fresh and gap < F32_GAP


def test_it_fails_if_a_slot_s_state_survives_its_request(monkeypatch):
    monkeypatch.setattr(paged_cache, "write_prompt_state",
                        lambda state, rows, slot: state)
    again, fresh, gap = _same_slot_twice()
    assert again != fresh and gap > 100 * F32_GAP


def test_a_batch_with_dead_slots_and_unequal_lengths():
    """Three slots: two requests of unequal lengths start together, one
    ends early and its slot lies dead for the rest of a dispatch, a third
    takes the free slot and a fourth the one that ended."""
    model, w, cfg = _model()
    eng = ServingEngine(model, cache_dtype="float32", **ENGINE)
    requests = list(zip(_prompts(4, 30, 7, 17, 12), (14, 3, 10, 6)))
    served = _serve(eng, requests)
    assert [len(t) for t in served] == [14, 3, 10, 6]
    assert _worst_gap(w, cfg, requests, served) < F32_GAP


def test_a_step_shifts_the_state_of_live_slots_only():
    state = jnp.arange(2 * 3 * 4, dtype=jnp.float32).reshape(2, 3, 4)
    g = jnp.full((2, 4), -1.0)
    cache = paged_cache.ConvStateCache(state, jnp.asarray([True, False]))
    window, new = paged_cache.conv_state_step(cache, g)
    assert jnp.array_equal(window[:, :2], state[:, 1:]) and \
        jnp.array_equal(window[:, 2], g)
    assert jnp.array_equal(new[0], window[0]) and \
        jnp.array_equal(new[1], state[1])
    # a prefill's write lands in its slot alone; the warm-up's (the slot
    # past the last) nowhere
    rows = jnp.full((1, 3, 4), 7.0)
    put = paged_cache.write_prompt_state(state, rows, jnp.int32(1))
    assert jnp.array_equal(put[1], rows[0]) and \
        jnp.array_equal(put[0], state[0])
    assert jnp.array_equal(
        paged_cache.write_prompt_state(state, rows, jnp.int32(2)), state)


def test_the_bias_changes_which_experts_are_chosen_and_not_their_weights():
    rng = np.random.default_rng(5)
    scores = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((16, 8)),
                                        jnp.float32))
    bias = jnp.asarray(rng.standard_normal((8,)), jnp.float32)
    plain_idx, plain_w = moe.select_experts(scores, 2, True, 1.0)
    idx, w = moe.select_experts(scores, 2, True, 1.0, bias=bias, eps=1e-6)
    assert not jnp.array_equal(jnp.sort(idx, -1), jnp.sort(plain_idx, -1))
    want_idx = jnp.argsort(-(scores + bias), axis=-1)[:, :2]
    assert jnp.array_equal(idx, want_idx)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    assert jnp.allclose(w, picked / (picked.sum(-1, keepdims=True) + 1e-6),
                        rtol=1e-6)
    # the defaults are A.X-K1's: no bias, 1e-20
    assert jnp.allclose(plain_w.sum(-1), 1.0, atol=1e-6)
    # and the model's logits move with the bias
    model, w0, cfg = _model()
    ids = rng.integers(0, 128, (1, 16))
    before = model(Tensor(jnp.asarray(ids)))._value
    model.load_raw_state({n: jnp.zeros_like(v) for n, v in w0.items()
                          if n.endswith("expert_bias")})
    assert float(jnp.max(jnp.abs(model(Tensor(jnp.asarray(ids)))._value
                                 - before))) > 1e-2


def test_bfloat16_is_what_the_looser_limit_is_for():
    model, w, cfg = _model("bfloat16")
    eng = ServingEngine(model, cache_dtype="bfloat16", **ENGINE)
    requests = list(zip(_prompts(6, 21, 9), (13, 8)))
    gap = _worst_gap(w, cfg, requests, _serve(eng, requests))
    assert gap < BF16_GAP
    state = eng._pages[0][0]
    assert state.shape == (3, 3, 64) and state.dtype == jnp.bfloat16


def test_the_paged_kernel_at_head_size_64_agrees_with_the_reference():
    """`use_flash=True` (the cell's engine argument) needs a head size the
    kernel takes: hidden 128 over 2 heads of 64, 1 K/V head."""
    model, w, cfg = _model(hidden_size=128, num_attention_heads=2,
                           num_key_value_heads=1, num_hidden_layers=3,
                           layer_types=("conv", "full_attention", "conv"))
    eng = ServingEngine(model, cache_dtype="float32", use_flash=True,
                        **ENGINE)
    assert eng.health()["decode_attention"] == "paged_kernel"
    requests = list(zip(_prompts(7, 19, 6), (9, 5)))
    assert _worst_gap(w, cfg, requests, _serve(eng, requests)) < F32_GAP


def test_the_paged_kernel_on_paired_pools_agrees_with_the_reference():
    """The cell's layout at a tiny size: 4 query heads over 2 K/V heads of
    64, so that a row of each pool holds both K/V heads (rows of 128)."""
    model, w, cfg = _model(hidden_size=256, num_attention_heads=4,
                           num_key_value_heads=2, num_hidden_layers=3,
                           layer_types=("conv", "full_attention", "conv"))
    eng = ServingEngine(model, cache_dtype="float32", use_flash=True,
                        **ENGINE)
    h = eng.health()
    assert h["decode_attention"] == "paged_kernel"
    assert h["kv_heads_per_row"] == 2
    assert eng._pages[1][0].shape == (1, eng.num_pages, 16, 128)
    requests = list(zip(_prompts(8, 19, 6), (9, 5)))
    assert _worst_gap(w, cfg, requests, _serve(eng, requests)) < F32_GAP


def test_health_names_what_the_engine_holds_by_kind_of_layer():
    model, _, _ = _model()
    eng = ServingEngine(model, cache_dtype="bfloat16", **ENGINE)
    h = eng.health()
    assert h["cache_layers"] == {"conv_state": 4, "kv": 2}
    assert h["conv_state_prefill_writes"] == 0
    kinds = [type(s).__name__ for s in eng.cache_specs]
    assert kinds == ["ConvStateSpec", "ConvStateSpec", "KVCacheSpec"] * 2
    # pages are counted for the attention layers alone
    kv = sum(a.nbytes for arrays in eng._pages[2::3] for a in arrays[:2])
    assert eng._page_bytes == kv // eng.num_pages
    from paddle_tpu.nlp.gpt import GPTForCausalLM
    gpt = ServingEngine(GPTForCausalLM.from_config_name("gpt-tiny"),
                        **ENGINE)
    assert gpt.health()["cache_layers"] == {"kv": 2}
    assert gpt.health()["kv_heads_per_row"] == 1
    assert h["kv_heads_per_row"] == 1
    assert "conv_state_prefill_writes" not in gpt.health()


@pytest.mark.parametrize("kwargs,what", [
    (dict(prefix_cache=True), "prefix_cache=True"),
    (dict(cache_dtype="int8"), "cache_dtype='int8'"),
    (dict(spec_decode=True), "spec_decode=True"),
])
def test_what_a_state_layer_cannot_serve_is_refused_by_name(kwargs, what):
    model, _, _ = _model()
    kw = dict(ENGINE, **kwargs)
    with pytest.raises(ValueError) as e:
        ServingEngine(model, **kw)
    assert "per-slot state" in str(e.value) and what in str(e.value)


def test_aot_export_refuses_a_state_layer(tmp_path):
    from paddle_tpu.jit.serving_artifact import export_artifact
    model, _, _ = _model()
    eng = ServingEngine(model, **ENGINE)
    eng.warmup(buckets=(16,))
    with pytest.raises(ValueError, match="per-slot state"):
        export_artifact(eng, str(tmp_path))


def test_a_model_may_not_name_fewer_specs_than_layers():
    model, _, _ = _model()
    model.cache_spec = lambda: [paged_cache.KVCacheSpec(2, 16)] * 5
    with pytest.raises(ValueError, match="5 layers of 6"):
        ServingEngine(model, **ENGINE)


# -- the models the benchmark already serves trace what they traced ---------

# sha256 (16 hex digits) of `str(jax.make_jaxpr(program)(*warm arguments))`
# at the parent of this change (commit db72838), object addresses blanked:
# engine of 3 slots, pages of 16, 64 positions, bfloat16 cache, no prefix
# cache; made by the same `_program_digests` below in a checkout of it.
# lfm2-tiny's were recorded so at commit 9da9ec8.
PARENT_PROGRAMS = {
    "gpt-tiny": {"decode": "20c74b91f6482067",
                 "prefill_32": "7d3ef6e68c8a51d5"},
    "llama-tiny": {"decode": "3fcf88c26f42fabf",
                   "prefill_32": "41b1905f26484f61"},
    "axk1-tiny": {"decode": "7476c1d7d6f2c3fd",
                  "prefill_32": "19d6ae16da613172"},
    "lfm2-tiny": {"decode": "022e4a965bbf09ea",
                  "prefill_32": "0a9b48ff0ad447e6"},
}


def _tiny(name):
    from paddle_tpu.nlp.axk1 import AXK1ForCausalLM
    from paddle_tpu.nlp.gpt import GPTForCausalLM
    from paddle_tpu.nlp.llama import LlamaForCausalLM
    cls = {"gpt-tiny": GPTForCausalLM, "llama-tiny": LlamaForCausalLM,
           "axk1-tiny": AXK1ForCausalLM, "lfm2-tiny": LFM2ForCausalLM}[name]
    paddle.seed(0)
    return cls.from_config_name(name)


def _program_digests(model, cache_dtype="bfloat16", buckets=(32,), **kw):
    import hashlib
    import re
    eng = ServingEngine(model, max_slots=3, page_size=16, max_seq_len=64,
                        cache_dtype=cache_dtype, prefix_cache=False, **kw)
    for bucket in buckets:
        eng._prefill_fn(bucket)
    out = {}
    for site in ("decode",) + tuple(f"prefill_{b}" for b in buckets):
        fn, _ = eng._aot_programs[site]
        text = str(jax.make_jaxpr(fn)(*eng._warm_args(site)))
        text = re.sub(r"0x[0-9a-f]+", "0x", text)
        out[site] = hashlib.sha256(text.encode()).hexdigest()[:16]
    return eng, out


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_the_accepted_models_answer_and_trace_what_they_did(name):
    eng, digests = _program_digests(_tiny(name))
    assert digests == PARENT_PROGRAMS[name]
    # nothing is held where products keep float32, as on this CPU
    assert eng.health()["held_weights"]["leaves"] == 0
    spec = eng.cache_spec
    if name == "lfm2-tiny":
        assert eng.cache_layers == {"conv_state": 4, "kv": 2}
        return
    if name == "axk1-tiny":
        assert type(spec) is paged_cache.LatentCacheSpec
        assert eng.cache_layers == {"latent": 3}
    else:
        cfg = eng.cfg
        assert type(spec) is paged_cache.KVCacheSpec
        assert (spec.kv_heads, spec.head_dim) == (
            getattr(cfg, "num_key_value_heads", 0)
            or cfg.num_attention_heads, cfg.head_dim)
        assert eng.cache_layers == {"kv": cfg.num_hidden_layers}
    assert eng.cache_specs == [spec] * eng.num_layers


# the same digests of another engine: float32 pages, 4 steps a dispatch,
# the prefill buckets 16 and 64 beside the decode scan; recorded so at
# commit a599bf9 with `_program_digests(..., cache_dtype="float32",
# steps_per_dispatch=4, buckets=(16, 64))`
PARENT_PROGRAMS_F32 = {
    "gpt-tiny": {"decode": "55082eb1f70a3d63",
                 "prefill_16": "30abb7ac950b64a3",
                 "prefill_64": "dd07695b012e6efc"},
    "llama-tiny": {"decode": "1ae0d444b33d7241",
                   "prefill_16": "1cc0fd910160c71e",
                   "prefill_64": "a5f342dbcf7f084e"},
    "axk1-tiny": {"decode": "7f940db96306cb3a",
                  "prefill_16": "4a7160d579a4f8c5",
                  "prefill_64": "048824d10827ee4f"},
    "lfm2-tiny": {"decode": "49c61182c585d6e9",
                  "prefill_16": "9c3d56c28ff7d26c",
                  "prefill_64": "34d8471e0bc9348b"},
}


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS_F32))
def test_the_accepted_models_trace_what_they_did_in_a_float32_engine(name):
    _, digests = _program_digests(_tiny(name), cache_dtype="float32",
                                  steps_per_dispatch=4, buckets=(16, 64))
    assert digests == PARENT_PROGRAMS_F32[name]


# the linear-family matrices held where a TPU's products would round them:
# 2 layers of q, k, v, out, fc1, fc2; 2 layers of q, k, v, o, gate, up,
# down and the untied head
HELD = {"gpt-tiny": 12, "llama-tiny": 15, "axk1-tiny": 0, "lfm2-tiny": 0}


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_with_weights_held_only_the_dense_models_programs_change(
        monkeypatch, name):
    """The expert models multiply through products of their own, no
    linear-family layer's: nothing is held and their programs stay the
    parent's. The dense ones hold their projections for the decode scan,
    whose program reads them at bfloat16 by design; the prefills keep
    the parent's."""
    from paddle_tpu import framework
    monkeypatch.setattr(framework, "products_round_to_bfloat16",
                        lambda: True)
    eng, digests = _program_digests(_tiny(name))
    assert eng.health()["held_weights"]["leaves"] == HELD[name]
    assert digests["prefill_32"] == PARENT_PROGRAMS[name]["prefill_32"]
    assert (digests["decode"] != PARENT_PROGRAMS[name]["decode"]) \
        == bool(HELD[name])
