"""Continuous-batching serving engine (nlp/serving.py + paged_cache).

Pins the round-7 contracts:
- batched paged decode is TOKEN-EXACT vs the memoized sequential
  generate() under greedy, for GPT and Llama (GQA);
- seeded sampling stays inside the strategy's support (every emitted
  token is in the per-step top-k of the dense reference logits);
- pages are recycled across admission/eviction and the free list
  returns to its initial size (no leaks, no corruption across reuse);
- the steady state compiles NOTHING (trace counters frozen across a
  second wave of same-bucket requests);
- eos early-stop and back-pressure (more requests than slots/pages).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nlp.generation import generate
from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
from paddle_tpu.nlp.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.nlp.serving import ServingEngine


@pytest.fixture(scope="module")
def gpt_model():
    paddle.seed(0)
    m = GPTForCausalLM(_resolve_config("gpt-tiny"))
    m.eval()
    return m


@pytest.fixture(scope="module")
def llama_model():
    paddle.seed(0)
    # GQA: 4 query heads share 2 kv heads
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=128, max_position_embeddings=128))
    m.eval()
    return m


def _prompts(lens, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,)).astype(np.int32) for n in lens]


def _greedy_ref(model, prompts, new_tok):
    out = []
    for p in prompts:
        ids = generate(model, jnp.asarray(p)[None, :],
                       max_new_tokens=new_tok, temperature=0.0)
        out.append(np.asarray(ids._value)[0, len(p):].tolist())
    return out


class TestGreedyParity:
    def test_gpt_token_exact(self, gpt_model):
        # lengths straddle the 16-token page and the pow2 buckets
        prompts = _prompts((5, 12, 17, 30))
        refs = _greedy_ref(gpt_model, prompts, 10)
        eng = ServingEngine(gpt_model, max_slots=4, page_size=16,
                            max_seq_len=64, steps_per_dispatch=4)
        outs = eng.generate(prompts, max_new_tokens=10)
        assert outs == refs

    def test_llama_gqa_token_exact(self, llama_model):
        prompts = _prompts((6, 20), seed=1)
        refs = _greedy_ref(llama_model, prompts, 8)
        eng = ServingEngine(llama_model, max_slots=2, page_size=16,
                            max_seq_len=48, steps_per_dispatch=4)
        assert eng.generate(prompts, max_new_tokens=8) == refs

    def test_gpt_reduced_precision_caches_run(self, gpt_model):
        # bf16/int8 caches are throughput levers, not exactness
        # contracts — pin that they decode and stay near the fp32 path
        prompts = _prompts((5, 12))
        refs = _greedy_ref(gpt_model, prompts, 8)
        for dt in ("bfloat16", "int8"):
            eng = ServingEngine(gpt_model, max_slots=2, page_size=16,
                                max_seq_len=48, cache_dtype=dt)
            outs = eng.generate(prompts, max_new_tokens=8)
            agree = sum(a == b for r, o in zip(refs, outs)
                        for a, b in zip(r, o))
            total = sum(len(r) for r in refs)
            assert agree >= total * 0.75, (dt, refs, outs)


class TestSampling:
    def test_topk_tokens_in_reference_support(self, gpt_model):
        """Seeded top-k sampling: every emitted token must lie in the
        top-k of the dense model's logits for the exact same prefix —
        the distributional parity pin that survives rng-stream
        differences vs generate()."""
        k = 5
        prompt = _prompts((9,), seed=3)[0]
        eng = ServingEngine(gpt_model, max_slots=1, page_size=16,
                            max_seq_len=48, temperature=0.9, top_k=k,
                            seed=7)
        toks = eng.generate([prompt], max_new_tokens=6)[0]
        prefix = list(prompt)
        for t in toks:
            logits = gpt_model(paddle.to_tensor(
                np.asarray(prefix, np.int64)[None, :]))
            last = np.asarray(logits._value)[0, -1]
            topk = set(np.argsort(last)[-k:].tolist())
            assert t in topk, (t, sorted(topk))
            prefix.append(t)

    def test_greedy_is_temperature_zero(self, gpt_model):
        prompts = _prompts((7,))
        refs = _greedy_ref(gpt_model, prompts, 6)
        eng = ServingEngine(gpt_model, max_slots=1, page_size=16,
                            max_seq_len=48, temperature=0.0, top_k=3)
        assert eng.generate(prompts, max_new_tokens=6) == refs


class TestPagingAndScheduling:
    def test_page_recycling_and_backpressure(self, gpt_model):
        """More requests than slots AND a page pool too small to host
        them all at once: admission must back-pressure, finished
        sequences must return their pages, and every request must
        still decode token-exactly."""
        prompts = _prompts((5, 12, 17, 9, 21, 14), seed=5)
        refs = _greedy_ref(gpt_model, prompts, 8)
        # 2 slots, 7 usable pages: slot capacity is 2-3 pages/request
        eng = ServingEngine(gpt_model, max_slots=2, page_size=16,
                            max_seq_len=48, num_pages=8,
                            steps_per_dispatch=4)
        free0 = eng.free_page_count
        outs = eng.generate(prompts, max_new_tokens=8)
        assert outs == refs
        assert eng.free_page_count == free0, "page leak across recycle"

    def test_eos_early_stop(self, gpt_model):
        prompts = _prompts((5,))
        ref = _greedy_ref(gpt_model, prompts, 12)[0]
        eos = ref[2]
        first = ref.index(eos)  # greedy repeats: stop at FIRST hit
        eng = ServingEngine(gpt_model, max_slots=1, page_size=16,
                            max_seq_len=48)
        out = eng.generate(prompts, max_new_tokens=12,
                           eos_token_id=eos)[0]
        assert out == ref[:first + 1], \
            "must stop right after emitting eos"

    def test_non_pow2_page_size(self, gpt_model):
        """page_size=24 is a legal multiple of 8 but not a power of
        two: the prompt bucket must round up to whole pages (the
        write_prompt_kv block reshape) and still decode token-exactly."""
        prompts = _prompts((5, 30), seed=13)
        refs = _greedy_ref(gpt_model, prompts, 6)
        eng = ServingEngine(gpt_model, max_slots=2, page_size=24,
                            max_seq_len=72)
        assert eng.generate(prompts, max_new_tokens=6) == refs

    def test_submit_rejects_oversized(self, gpt_model):
        eng = ServingEngine(gpt_model, max_slots=1, page_size=16,
                            max_seq_len=32)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.submit(np.zeros(30, np.int32), max_new_tokens=10)


class TestZeroRecompile:
    def test_steady_state_compiles_nothing(self, gpt_model):
        eng = ServingEngine(gpt_model, max_slots=2, page_size=16,
                            max_seq_len=48, steps_per_dispatch=2)
        prompts = _prompts((5, 12))
        out1 = eng.generate(prompts, max_new_tokens=6)
        frozen = eng.compile_counts()
        assert frozen.get("decode") == 1
        # second wave: same buckets, new admissions/evictions — the
        # continuous-batching contract is ZERO new traces
        prompts2 = _prompts((6, 11, 13, 4), seed=9)
        eng.generate(prompts2, max_new_tokens=6)
        assert eng.compile_counts() == frozen
        # waves decoded something and parity held within the run
        assert eng.generate(prompts, max_new_tokens=6) == out1
        assert eng.compile_counts() == frozen

    def test_new_bucket_traces_prefill_only(self, gpt_model):
        eng = ServingEngine(gpt_model, max_slots=1, page_size=16,
                            max_seq_len=64, steps_per_dispatch=2)
        eng.generate(_prompts((5,)), max_new_tokens=4)     # bucket 16
        c = eng.compile_counts()
        eng.generate(_prompts((20,)), max_new_tokens=4)    # bucket 32
        c2 = eng.compile_counts()
        assert c2["decode"] == c["decode"], "decode must not retrace"
        assert c2.get("prefill_32") == 1


class TestPagedKernelRouting:
    def test_forced_flash_matches_reference(self):
        """use_flash=True routes the Pallas paged kernel (interpret
        mode on CPU) — greedy tokens must match the jnp reference
        path exactly (head_dim 64 so the gate accepts)."""
        paddle.seed(2)
        m = GPTForCausalLM(_resolve_config("gpt-tiny",
                                           num_attention_heads=1))
        m.eval()
        prompts = _prompts((5, 12), seed=11)
        ref_eng = ServingEngine(m, max_slots=2, page_size=16,
                                max_seq_len=48, use_flash=False)
        refs = ref_eng.generate(prompts, max_new_tokens=6)
        fl_eng = ServingEngine(m, max_slots=2, page_size=16,
                               max_seq_len=48, use_flash=True)
        assert fl_eng.use_flash, "gate should accept head_dim 64"
        assert fl_eng.generate(prompts, max_new_tokens=6) == refs

    def test_gate_rejects_unsupported_head_dim(self, gpt_model):
        # gpt-tiny head_dim=16: an explicit request the kernel cannot
        # serve is an error, never a silent reference run
        with pytest.raises(ValueError, match="head_dim=16"):
            ServingEngine(gpt_model, max_slots=1, page_size=16,
                          max_seq_len=48, use_flash=True)
