"""A.X-K1 at a tiny size of the same architecture (3 layers, hidden 64, 4
heads, kv_lora_rank 16, 16 experts top-4; float32 weights and cache), every
case against `benchmarks/reference/axk1.py`, which shares no code with the
program."""
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.reference import axk1 as reference  # noqa: E402
from paddle_tpu.nlp import axk1, paged_cache  # noqa: E402
from paddle_tpu.nlp.axk1 import AXK1Config, AXK1ForCausalLM  # noqa: E402
from paddle_tpu.nlp.serving import ServingEngine  # noqa: E402
from paddle_tpu.tensor import Tensor  # noqa: E402

DATA = os.path.join(ROOT, "tests", "bench_harness", "data", "axk1-tiny.json")


def file_config(layer_chips=4, chip_rank=1):
    """The harness's tiny configuration file, cut for one chip of
    `layer_chips` (16 routed experts published)."""
    with open(DATA) as f:
        cfg = json.load(f)
    cfg["n_routed_experts"] = 16 // layer_chips
    cfg["deployment"] = dict(cfg["deployment"], layer_chips=layer_chips,
                             chip_rank=chip_rank)
    cfg["serve"] = dict(cfg["serve"], weight_dtype="float32")
    return cfg


def seeded(cfg, seed=3):
    """(model, float32 leaves) with the same seeded weights."""
    from benchmarks.drivers.serve_axk1 import model_config
    from benchmarks.weights_leaf import make_leaf
    model = AXK1ForCausalLM(model_config(cfg))
    model.eval()
    w = {n: make_leaf(n, s, seed, cfg["initializer_range"], "float32")
         for n, s in reference.leaf_shapes(cfg).items()}
    assert {n: tuple(p.shape) for n, p in model.named_parameters()} == \
        reference.leaf_shapes(cfg)
    model.load_raw_state(w)
    return model, w


def close(got, want, rel):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= rel * np.max(np.abs(want)), \
        (np.max(np.abs(got - want)), np.max(np.abs(want)))


@pytest.mark.parametrize("layer_chips,chip_rank", [(4, 1), (1, 0), (16, 15)])
def test_full_forward_logits_equal_the_reference(layer_chips, chip_rank):
    cfg = file_config(layer_chips, chip_rank)
    model, w = seeded(cfg)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 40),
                                            dtype=np.int32)
    # float32 both sides: what is left is the order of the sums
    close(model(Tensor(jnp.asarray(ids)))._value,
          reference.forward(w, ids, cfg), 2e-5)


def test_prefill_then_paged_decode_equals_the_reference_full_forward(
        monkeypatch):
    """Logits, not tokens, at every served position: the prompt (37 tokens,
    bucket 64) crosses two page boundaries of 16, and decoding runs from 37
    to 85, over the bucket's end and three more pages."""
    cfg = file_config()
    model, w = seeded(cfg)
    seen = []

    def spy(name):
        real = getattr(ServingEngine, name)

        def wrapped(self, logits, key):
            jax.debug.callback(lambda lg: seen.append(np.array(lg)), logits,
                               ordered=True)
            return real(self, logits, key)
        monkeypatch.setattr(ServingEngine, name, wrapped)

    spy("_sample")
    spy("_sample_rows")
    eng = ServingEngine(model, max_slots=2, page_size=16, max_seq_len=128,
                        cache_dtype="float32", prefix_cache=False,
                        steps_per_dispatch=4)
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_size"], (37,),
                                               dtype=np.int32)
    eng.submit(prompt, max_new_tokens=48)
    tokens = eng.run_to_completion()[0]["tokens"]
    jax.effects_barrier()
    assert len(tokens) == 48
    served = np.stack([seen[0][0]] + [lg[0] for lg in seen[1:48]])
    ids = np.concatenate([prompt, tokens[:-1]])[None]
    want = np.asarray(reference.forward(w, ids, cfg))[0, 36:]
    # float32 weights, cache and probabilities on both sides: only the
    # order of the sums differs (absorbed against expanded, pages against
    # one sequence), which reads 1e-6 of the largest logit here. bfloat16
    # probabilities against this float32 cache read 2e-3 and fail.
    close(served, want, 1e-4)
    assert np.array_equal(np.argmax(want, -1), np.asarray(tokens))
    counts = eng.health()["moe"]
    assert counts["moe_routed_tokens"] == 2 * (37 + 12 * 4 * 2)


def test_absorbed_form_equals_expanded_form():
    cfg = axk1._resolve_config("axk1-tiny")
    attn = axk1.AXK1Attention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 21, cfg.hidden_size))
    want, rows = attn._expanded(x, None)
    ps = 8
    for t in (0, 7, 8, 20):
        # pages 1-3 of slot 0, 4-6 of slot 1 hold the rows before t
        pool = jnp.zeros((7, ps, cfg.latent_width))
        for b in range(2):
            for j in range(t):
                pool = pool.at[1 + 3 * b + j // ps, j % ps].set(rows[b, j])
        cache = paged_cache.PagedLatentCache(
            pool, jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32),
            jnp.full((2,), t, jnp.int32))
        got, pages = attn._absorbed(x[:, t:t + 1], cache)
        close(got[:, 0], want[:, t], 1e-5)
        close(pages[1 + t // ps, t % ps], rows[0, t], 1e-6)


def test_yarn_frequencies_and_softmax_scale_by_hand():
    """The published numbers: rope dim 64, theta 10000, factor 32, original
    context 4096, beta_fast 32, beta_slow 1."""
    cfg = AXK1Config()
    # dimensions that turn 32 times and once over 4096 positions
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    want = []
    for i in range(32):
        f = 10000 ** (-2 * i / 64)
        ramp = min(max((i - 10) / 13, 0), 1)
        want.append(f * (1 - ramp) + f / 32 * ramp)
    assert want[10] == 10000 ** (-20 / 64) and want[23] * 32 == \
        pytest.approx(10000 ** (-46 / 64))
    got = axk1.yarn_inv_freq(64, 10000.0, cfg.rope_scaling)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    with open(os.path.join(ROOT, "benchmarks/configs/axk1-ep16.json")) as f:
        sz = reference.sizes(json.load(f))
    np.testing.assert_allclose(reference.yarn_inv_freq(sz), want, rtol=1e-12)
    m = 0.1 * math.log(32) + 1
    assert m == pytest.approx(1.3466, abs=5e-5)
    assert axk1._softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    assert reference.softmax_scale(sz) == pytest.approx(192 ** -0.5 * m * m)


def test_router_picks_and_weights_by_hand():
    scores = jnp.asarray([[0.9, 0.1, 0.5, 0.7], [0.2, 0.8, 0.6, 0.4]])
    idx, w = axk1.select_experts(scores, 2, True, 2.5)
    assert idx.tolist() == [[0, 3], [1, 2]] and idx.dtype == jnp.int32
    np.testing.assert_allclose(
        w, [[0.9 / 1.6 * 2.5, 0.7 / 1.6 * 2.5],
            [0.8 / 1.4 * 2.5, 0.6 / 1.4 * 2.5]], rtol=1e-6)
    _, raw = axk1.select_experts(scores, 2, False, 1.0)
    np.testing.assert_allclose(raw, [[0.9, 0.7], [0.8, 0.6]], rtol=1e-6)
    # the reference's dense form of the same rule
    sz = {"num_experts_per_tok": 2, "norm_topk_prob": True,
          "routed_scaling_factor": 2.5}
    logit = jnp.log(scores / (1 - scores))      # sigmoid's inverse
    g = reference.route(jnp.eye(4)[:2], jnp.pad(logit, ((0, 2), (0, 0))), sz)
    np.testing.assert_allclose(
        g, [[0.9 / 1.6 * 2.5, 0, 0, 0.7 / 1.6 * 2.5],
            [0, 0.8 / 1.4 * 2.5, 0.6 / 1.4 * 2.5, 0]], rtol=1e-5, atol=1e-7)


def _moe_layers(layer_chips):
    """The expert layer whole and as each of `layer_chips` shares, with the
    same weights (every share's experts a slice of the whole's)."""
    whole = axk1.AXK1MoE(axk1._resolve_config("axk1-tiny"))
    shares = []
    for rank in range(layer_chips):
        cfg = axk1._resolve_config("axk1-tiny", layer_chips=layer_chips,
                                   chip_rank=rank)
        part = axk1.AXK1MoE(cfg)
        state, _ = whole.raw_state()
        lo, hi = cfg.expert_offset, cfg.expert_offset + cfg.experts_held
        for n in ("experts.gate_up_proj", "experts.down_proj"):
            state[n] = state[n][lo:hi]
        part.load_raw_state(state)
        shares.append(part)
    return whole, shares


def test_the_shares_add_up_to_the_uncut_layer():
    whole, shares = _moe_layers(4)
    u = Tensor(jax.random.normal(jax.random.PRNGKey(2), (2, 9, 64)))
    full, aux = whole(u)
    shared = whole.shared_experts(u)._value
    parts = [s(u) for s in shares]
    total = shared + sum(p[0]._value - shared for p in parts)
    close(total, full._value, 1e-5)
    assert sum(int(p[1][0]) for p in parts) == int(aux[0]) == 2 * 9 * 4
    # and the whole is the reference's uncut layer
    state, _ = whole.raw_state()
    lw = {"mlp." + n: v for n, v in state.items()}
    sz = {"moe_intermediate_size": 32, "num_experts_per_tok": 4,
          "norm_topk_prob": True, "routed_scaling_factor": 2.5,
          "expert_offset": 0, "n_routed_experts": 16}
    want = reference.expert_ffn(u._value.reshape(18, 64), lw, sz, "float32")
    close(full._value.reshape(18, 64), want, 1e-5)


def test_a_skewed_router_loses_no_row():
    """Every token's first pick is held expert 2, its other three are
    absent experts: one group holds all the rows."""
    cfg = axk1._resolve_config("axk1-tiny", layer_chips=4)
    layer = axk1.AXK1MoE(cfg)
    gate = np.zeros((64, 16), np.float32)
    gate[0, 2], gate[0, 9:12] = 40.0, 20.0
    layer.gate._value = jnp.asarray(gate)
    x = np.array(jax.random.normal(jax.random.PRNGKey(3), (1, 33, 64)))
    x[..., 0] = 1.0 + np.abs(x[..., 0])
    out, aux = layer(Tensor(jnp.asarray(x)))
    assert aux.tolist() == [33, 1, 33]
    state, _ = layer.raw_state()
    sz = {"moe_intermediate_size": 32, "num_experts_per_tok": 4,
          "norm_topk_prob": True, "routed_scaling_factor": 2.5,
          "expert_offset": 0, "n_routed_experts": 4}
    want = reference.expert_ffn(jnp.asarray(x[0]),
                                {"mlp." + n: v for n, v in state.items()},
                                sz, "float32")
    close(out._value[0], want, 1e-5)
    # rows marked as padding are routed nowhere and counted out
    live = jnp.arange(33) < 20
    out, aux = layer(Tensor(jnp.asarray(x)), live[None])
    assert aux.tolist() == [20, 1, 20]
    close(out._value[0, :20], want[:20], 1e-5)


@pytest.mark.parametrize("kwargs,names", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(), "prefix_cache"),                   # the engine's default is on
    (dict(prefix_cache=False, cache_dtype="int8"), "int8"),
    (dict(prefix_cache=False, spec_decode=True), "spec_decode"),
])
def test_the_engine_refuses_by_name_what_the_latent_cache_lacks(
        monkeypatch, kwargs, names):
    monkeypatch.delenv("PADDLE_TPU_PREFIX_CACHE", raising=False)
    model = AXK1ForCausalLM.from_config_name("axk1-tiny")
    with pytest.raises(ValueError, match=names):
        ServingEngine(model, max_slots=2, page_size=16, max_seq_len=64,
                      **kwargs)


def test_aot_export_refuses_a_latent_cache(tmp_path):
    from paddle_tpu.jit.serving_artifact import export_artifact
    eng = ServingEngine(AXK1ForCausalLM.from_config_name("axk1-tiny"),
                        max_slots=2, page_size=16, max_seq_len=64,
                        prefix_cache=False)
    eng.warmup(buckets=(16,))
    with pytest.raises(ValueError, match="latent"):
        export_artifact(eng, str(tmp_path))


def test_a_gpt_engine_cache_spec_pages_and_decode_signature_are_unchanged():
    from paddle_tpu.nlp.gpt import GPTForCausalLM
    eng = ServingEngine(GPTForCausalLM.from_config_name("gpt-tiny"),
                        max_slots=3, page_size=16, max_seq_len=64,
                        cache_dtype="bfloat16", prefix_cache=False)
    cfg = eng.cfg
    spec = eng.cache_spec
    assert type(spec) is paged_cache.KVCacheSpec and not spec.latent
    assert (spec.kv_heads, spec.head_dim) == \
        (cfg.num_attention_heads, cfg.head_dim) == \
        (eng.kv_heads, eng.head_dim)
    shape = (cfg.num_attention_heads, 13, 16, cfg.head_dim)
    assert len(eng._pages) == cfg.num_hidden_layers
    for k, v, ks, vs in eng._pages:
        assert k.shape == v.shape == shape and k.dtype == jnp.bfloat16
        assert ks is None and vs is None
    fn, kw = eng._aot_programs["decode"]
    assert kw == {"donate_argnums": (2,)}
    out = jax.eval_shape(fn, *eng._warm_args("decode"))
    toks, pages, seq_lens, last, done, emitted = out     # six, as before
    assert toks.shape == (eng.steps_per_dispatch, 3)
    assert [tuple(a.shape for a in layer[:2]) for layer in pages] == \
        [(shape, shape)] * cfg.num_hidden_layers
    assert eng.aux_counts == {} and "moe" not in eng.health()
