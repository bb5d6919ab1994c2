"""Olmo-Hybrid-7B at a tiny size of the same architecture (4 layers
`L L A L`, hidden 64, 4 attention heads of 16, 2 Gated DeltaNet heads of
key width 16 and value width 32, vocabulary 128; seeded weights of std 0.2
so that nothing is near-linear, and decays drawn as the benchmark draws
them), each path against the plain reference `benchmarks/reference/
olmo_hybrid.py` at logit level: the chunked prefill, the cached decode
through the serving engine, slot reuse, dead slots, and the refusals by
name."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from benchmarks.reference import olmo_hybrid as reference  # noqa: E402
from paddle_tpu.nlp import olmo_hybrid, paged_cache  # noqa: E402
from paddle_tpu.nlp.olmo_hybrid import OlmoHybridForCausalLM  # noqa: E402
from paddle_tpu.nlp.serving import ServingEngine  # noqa: E402
from paddle_tpu.tensor import Tensor  # noqa: E402

# float32 engine against the float32 reference: the same mathematics in
# another order of sums (the chunked solve against a token at a time, the
# paged softmax); logits of size 5-10 agree to about 1e-5, so a served
# token's reference logit lies within 1e-4 of the reference's best
F32_GAP = 1e-4
# logits at position level: the chunked prefill and the token recurrence
# in float32 differ by rounding alone
F32_LOGITS = 5e-5
ENGINE = dict(max_slots=3, page_size=16, max_seq_len=128,
              prefix_cache=False, steps_per_dispatch=4)


def _weights(model, seed=0, std=0.2):
    """N(0, std) matrices, gains 1 + N(0, std); A_log and dt_bias as the
    benchmark draws them (A uniform in [1, 16], dt log-uniform in
    [1e-3, 0.1]), so that the decays span near 0 to near 1."""
    rng = np.random.default_rng(seed)
    w = {}
    for name, p in model.named_parameters():
        shape = tuple(p.shape)
        x = std * rng.standard_normal(shape).astype(np.float32)
        if len(shape) == 1 and name.endswith(".weight"):
            x = 1.0 + x
        if name.endswith("A_log"):
            x = np.log(rng.uniform(1.0, 16.0, shape)).astype(np.float32)
        if name.endswith("dt_bias"):
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), shape))
            x = (dt + np.log(-np.expm1(-dt))).astype(np.float32)
        w[name] = jnp.asarray(x)
    return w


def _model(dtype="float32", seed=0, **overrides):
    paddle.seed(0)
    model = OlmoHybridForCausalLM.from_config_name("olmo-hybrid-tiny",
                                                   dtype=dtype, **overrides)
    model.eval()
    w = _weights(model, seed)
    if dtype != "float32":
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


# -- the chunked scan against the token recurrence ---------------------------

def _recurrence(q, k, v, g, beta):
    """Token by token, one sequence: q, k [S, H, dk], ... -> (o, S)."""
    def token(state, xs):
        qt, kt, vt, gt, bt = xs
        state = jnp.exp(gt)[:, None, None] * state
        mem = jnp.einsum("hkv,hk->hv", state, kt)
        state = state + jnp.einsum("hk,hv->hkv", kt,
                                   bt[:, None] * (vt - mem))
        return state, jnp.einsum("hkv,hk->hv", state, qt)
    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    state, o = jax.lax.scan(token, jnp.zeros((h, dk, dv), jnp.float32),
                            (q, k, v, g, beta))
    return o, state


def _inputs(seed, s, h=3, dk=16, dv=24, g_range=(-6.0, 0.0),
            beta_range=(0.0, 2.0)):
    rng = np.random.default_rng(seed)

    def l2(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = l2(rng.standard_normal((s, h, dk))) / np.sqrt(dk)
    k = l2(rng.standard_normal((s, h, dk)))
    v = rng.standard_normal((s, h, dv))
    g = rng.uniform(*g_range, (s, h))
    beta = rng.uniform(*beta_range, (s, h))
    return [jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta)]


@pytest.mark.parametrize("s,g_range,beta_range", [
    (37, (-6.0, 0.0), (0.0, 2.0)),        # shorter than a chunk
    (150, (-6.0, 0.0), (0.0, 2.0)),       # two chunks and a part
    (130, (-1e-4, 0.0), (0.0, 2.0)),      # decays near 1
    (130, (-80.0, -20.0), (0.0, 2.0)),    # decays near 0
    (130, (-1e-3, 0.0), (1.99, 2.0)),     # beta near 2, decays near 1
])
def test_the_chunked_scan_is_the_token_recurrence(s, g_range, beta_range):
    q, k, v, g, beta = _inputs(s, s, g_range=g_range, beta_range=beta_range)
    want_o, want_s = _recurrence(q, k, v, g, beta)
    o, state = olmo_hybrid.chunked_gated_delta(
        *(t[None] for t in (q, k, v, g, beta)))
    scale = float(jnp.max(jnp.abs(want_o))) + 1.0
    assert float(jnp.max(jnp.abs(o[0] - want_o))) < 1e-4 * scale
    assert float(jnp.max(jnp.abs(state[0] - want_s))) < 1e-4 * (
        float(jnp.max(jnp.abs(want_s))) + 1.0)


def test_right_padding_leaves_the_state_of_the_last_true_token():
    """Positions past a row's length carry g = beta = 0: the state the
    scan hands back is the one after the last true token, and the true
    rows' outputs are untouched by what the padding holds."""
    s, true = 96, 71
    q, k, v, g, beta = _inputs(3, s)
    _, want_s = _recurrence(q[:true], k[:true], v[:true], g[:true],
                            beta[:true])
    live = (jnp.arange(s) < true)[:, None]
    o, state = olmo_hybrid.chunked_gated_delta(
        q[None], k[None], v[None], jnp.where(live, g, 0.0)[None],
        jnp.where(live, beta, 0.0)[None])
    assert float(jnp.max(jnp.abs(state[0] - want_s))) < 1e-4
    # and without the mask the state would be another
    _, unmasked = olmo_hybrid.chunked_gated_delta(
        *(t[None] for t in (q, k, v, g, beta)))
    assert float(jnp.max(jnp.abs(unmasked[0] - want_s))) > 1e-2


def test_a_decode_step_is_one_token_of_the_recurrence_for_live_slots():
    q, k, v, g, beta = _inputs(4, 2)
    state0 = jnp.asarray(np.random.default_rng(5).standard_normal(
        (2, 3, 16, 24)), jnp.float32)
    live = jnp.asarray([True, False])
    o, new = olmo_hybrid.gated_delta_step(q, k, v, g, beta, state0, live)

    def one(st, b):
        st = jnp.exp(g[b])[:, None, None] * st
        mem = jnp.einsum("hkv,hk->hv", st, k[b])
        st = st + jnp.einsum("hk,hv->hkv", k[b],
                             beta[b][:, None] * (v[b] - mem))
        return st, jnp.einsum("hkv,hk->hv", st, q[b])
    want_s, want_o = one(state0[0], 0)
    assert float(jnp.max(jnp.abs(new[0] - want_s))) < 1e-5
    assert float(jnp.max(jnp.abs(o[0] - want_o))) < 1e-5
    assert jnp.array_equal(new[1], state0[1])      # a dead slot keeps its row


# -- the model against the reference ------------------------------------------

def test_the_leaves_are_the_reference_s_and_the_full_forward_agrees():
    model, w, cfg = _model()
    assert {n: tuple(p.shape) for n, p in model.named_parameters()} == \
        reference.leaf_shapes(cfg)
    ids = np.random.default_rng(1).integers(0, 128, (2, 90))
    got = model(Tensor(jnp.asarray(ids)))._value
    want = reference.forward(w, ids, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < F32_LOGITS * max(
        1.0, float(jnp.max(jnp.abs(want))))
    # right padding under a mask leaves the true rows as they were
    padded = np.concatenate([ids, np.zeros((2, 38), ids.dtype)], axis=1)
    mask = (np.arange(128)[None, :] < 90).astype(np.int32).repeat(2, 0)
    again = model(Tensor(jnp.asarray(padded)),
                  attention_mask=Tensor(jnp.asarray(mask)))._value
    assert float(jnp.max(jnp.abs(again[:, :90] - want))) < F32_LOGITS * max(
        1.0, float(jnp.max(jnp.abs(want))))


def _padded_prefill_then_decode():
    model, w, cfg = _model()
    eng = ServingEngine(model, cache_dtype="float32", **ENGINE)
    requests = [(_prompts(2, 75)[0], 21)]       # bucket 80, 20 decode steps
    served = _serve(eng, requests)
    assert eng.health()["delta_state_prefill_writes"] == 3
    return _worst_gap(w, cfg, requests, served)


def test_a_prompt_shorter_than_its_bucket_then_twenty_decode_steps():
    assert _padded_prefill_then_decode() < F32_GAP


def test_it_fails_if_the_state_is_taken_at_the_bucket_s_end(monkeypatch):
    at = paged_cache.conv_state_at
    monkeypatch.setattr(olmo_hybrid, "conv_state_at",
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
    """The recurrent state is summed into, not overwritten, as tokens
    come: a prefill that left the last request's row would accumulate."""
    real = paged_cache.write_prompt_delta_state

    def keeps_the_old_state(conv, state, conv_rows, state_rows, slot):
        put = real(conv, state, conv_rows, state_rows, slot)
        return put[0], state.at[slot].add(state_rows[0], mode="drop")

    monkeypatch.setattr(paged_cache, "write_prompt_delta_state",
                        keeps_the_old_state)
    again, fresh, gap = _same_slot_twice()
    assert again != fresh and gap > 100 * F32_GAP


def test_a_batch_with_dead_slots_and_unequal_lengths():
    """Three slots: two requests of unequal lengths start together, one
    ends early and its slot lies dead for the rest of a dispatch, a third
    takes the free slot and a fourth the one that ended."""
    model, w, cfg = _model()
    eng = ServingEngine(model, cache_dtype="float32", **ENGINE)
    requests = list(zip(_prompts(4, 30, 7, 70, 12), (14, 3, 10, 6)))
    served = _serve(eng, requests)
    assert [len(t) for t in served] == [14, 3, 10, 6]
    assert _worst_gap(w, cfg, requests, served) < F32_GAP


def test_the_decode_scan_leaves_dead_slots_rows_as_they_were():
    model, _, _ = _model()
    eng = ServingEngine(model, cache_dtype="float32", **ENGINE)
    rid = eng.submit(np.asarray(_prompts(9, 20)[0], np.int32),
                     max_new_tokens=30)
    eng.step()                              # admitted, one dispatch run
    slot = next(i for i, s in enumerate(eng._slots) if s is not None)
    before = [np.asarray(a) for a in eng._pages[0]]
    eng.step()
    after = [np.asarray(a) for a in eng._pages[0]]
    dead = [i for i in range(3) if i != slot]
    for old, new in zip(before, after):
        assert np.array_equal(old[dead], new[dead])
        assert not np.array_equal(old[slot], new[slot])


def test_bfloat16_weights_and_a_float32_state():
    model, w, cfg = _model("bfloat16")
    eng = ServingEngine(model, cache_dtype="bfloat16", **ENGINE)
    requests = list(zip(_prompts(6, 21, 9), (13, 8)))
    gap = _worst_gap(w, cfg, requests, _serve(eng, requests))
    assert gap < 0.5
    conv, state = eng._pages[0]
    assert conv.shape == (3, 3, 128) and conv.dtype == jnp.bfloat16
    # two heads of 32 fill no lane tile together: one head a row
    assert state.shape == (3, 2, 16, 32) and state.dtype == jnp.float32
    assert eng.health()["delta_state"] == {"path": "jnp", "heads_per_row": 1}


def _slot_reused_by_two_requests(model):
    """One slot: a prompt then twenty decode steps, then a second request
    through the same slot; (tokens of both, health())."""
    eng = ServingEngine(model, cache_dtype="float32",
                        **dict(ENGINE, max_slots=1))
    served = [_serve(eng, [(p, 21)])[0] for p in _prompts(11, 37, 13)]
    return served, eng.health()


def test_the_decode_kernel_serves_the_tokens_of_the_jnp_step(monkeypatch):
    """Value heads of 64, so that the state is stored two heads a row of
    128 (`DeltaStateSpec.heads_per_row`); the decode program built with
    the kernel (interpreted here) against the one with `gated_delta_step`
    over the same storage."""
    model, w, cfg = _model(linear_value_head_dim=64)
    plain, h = _slot_reused_by_two_requests(model)
    assert h["delta_state"] == {"path": "jnp", "heads_per_row": 2}
    monkeypatch.setattr(olmo_hybrid, "gated_delta_path", lambda: "kernel")
    kernel, h = _slot_reused_by_two_requests(model)
    assert h["delta_state"] == {"path": "kernel", "heads_per_row": 2}
    assert kernel == plain and [len(t) for t in kernel] == [21, 21]
    requests = [(p, 21) for p in _prompts(11, 37, 13)]
    assert _worst_gap(w, cfg, requests, kernel) < F32_GAP


def test_the_paged_kernel_at_head_size_128_agrees_with_the_reference():
    """`use_flash=True` (the cell's engine argument) at the cell's head
    size: hidden 256 over 2 heads of 128, every head a K/V head."""
    model, w, cfg = _model(hidden_size=256, num_attention_heads=2,
                           num_key_value_heads=2)
    eng = ServingEngine(model, cache_dtype="float32", use_flash=True,
                        **ENGINE)
    assert eng.health()["decode_attention"] == "paged_kernel"
    requests = list(zip(_prompts(7, 19, 6), (9, 5)))
    assert _worst_gap(w, cfg, requests, _serve(eng, requests)) < F32_GAP


def test_health_names_what_the_engine_holds_by_kind_of_layer():
    model, _, _ = _model()
    eng = ServingEngine(model, cache_dtype="bfloat16", **ENGINE)
    h = eng.health()
    assert h["cache_layers"] == {"delta_state": 3, "kv": 1}
    assert h["delta_state_prefill_writes"] == 0
    assert "conv_state_prefill_writes" not in h
    # three layers of (3 slots x 3 x 128 bf16) + (3 x 2 x 16 x 32 f32)
    assert h["delta_state_gb"] == 3 * (3 * 3 * 128 * 2 + 3 * 2 * 16 * 32
                                       * 4) / 1e9
    kinds = [type(s).__name__ for s in eng.cache_specs]
    assert kinds == ["DeltaStateSpec"] * 2 + ["KVCacheSpec",
                                              "DeltaStateSpec"]
    # pages are counted for the attention layer alone
    kv = sum(a.nbytes for a in eng._pages[2][:2])
    assert eng._page_bytes == kv // eng.num_pages
    assert eng.health()["held_weights"]["leaves"] == 0


@pytest.mark.parametrize("kwargs,what", [
    (dict(prefix_cache=True), "prefix_cache=True"),
    (dict(cache_dtype="int8"), "cache_dtype='int8'"),
    (dict(spec_decode=True), "spec_decode=True"),
])
def test_what_a_delta_state_layer_cannot_serve_is_refused_by_name(kwargs,
                                                                   what):
    model, _, _ = _model()
    kw = dict(ENGINE, **kwargs)
    with pytest.raises(ValueError) as e:
        ServingEngine(model, **kw)
    assert "per-slot state" in str(e.value) and what in str(e.value)


def test_aot_export_refuses_a_delta_state_layer(tmp_path):
    from paddle_tpu.jit.serving_artifact import export_artifact
    model, _, _ = _model()
    eng = ServingEngine(model, **ENGINE)
    eng.warmup(buckets=(16,))
    with pytest.raises(ValueError, match="per-slot state"):
        export_artifact(eng, str(tmp_path))


def test_the_configuration_file_counts_its_parameters():
    """Layers 0-15 as published (12 Gated DeltaNet layers, 4 attention
    layers), the embedding, the final norm and the untied head:
    4,100,788,944 parameters, 8.20 GB in bfloat16."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "olmo-hybrid-7b-l16.json")) as f:
        cfg = json.load(f)
    shapes = reference.leaf_shapes(cfg)
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert total == 4_100_788_944
    gdn = sum(int(np.prod(s)) for n, s in shapes.items()
              if n.startswith("model.layers.0.linear_attn."))
    assert gdn == 88_750_332
    # and the program's leaves at those widths are the reference's, by
    # shape alone (nothing is made)
    model_cfg = olmo_hybrid.OlmoHybridConfig(**{
        k: v for k, v in cfg.items()
        if k in {f.name for f in dataclasses.fields(
            olmo_hybrid.OlmoHybridConfig)}})
    specs = OlmoHybridForCausalLM.cache_spec(
        type("M", (), {"config": model_cfg})())
    assert [s.kind for s in specs] == ["delta_state"] * 3 + ["kv"] \
        + ["delta_state"] * 3 + ["kv"] + ["delta_state"] * 3 + ["kv"] \
        + ["delta_state"] * 3 + ["kv"]
    st = specs[0]
    assert (st.channels, st.taps, st.heads, st.key_dim, st.value_dim) == \
        (11520, 4, 30, 96, 192)
    assert 64 * 30 * 96 * 192 * 4 * 12 == 1_698_693_120   # 1.70 GB
    # stored two heads a row of 384: three whole lane tiles, no padding
    assert st.heads_per_row == 2
    assert jax.eval_shape(lambda: st.alloc(None, None, "bfloat16", 64)[1]
                          ).shape == (64, 15, 96, 384)
