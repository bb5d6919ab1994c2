"""Speculative decoding: draft-propose / one-dispatch-verify (ISSUE 20).

Pins the round-20 contracts (docs/performance.md "Speculative
decoding"):

- THE invariant: speculation may change latency, never tokens — ON vs
  OFF streams are token-exact for GPT and Llama/GQA across greedy and
  top-k sampling and fp32/bf16/int8 KV dtypes (the full cross
  product, `EXACT_CASES`). The verify dispatch applies the target
  model's own per-(request, token-index) seeded sampler to every
  folded lane, so an accepted draft IS the token plain decode would
  have emitted;
- proposers: the zero-weight prompt-lookup (ngram) fallback
  self-extends through the match so tight cycles accept at 100%; the
  draft-model proposer runs a real tiny model one-behind the target
  (its state derived fresh from target state each round — rejected
  drafts need no draft-side rewind). Draft quality is a latency knob,
  never a correctness one;
- arming: PADDLE_TPU_SPEC_DECODE / spec_decode= arms the engine,
  warmup() pre-traces the folded verify program, and an armed-but-
  never-warmed engine takes the plain decode path for every dispatch
  — a never-armed engine is byte-identical to a spec-off one (no
  serve_spec_* series even registered);
- zero-recompile: a warmed spec engine serves accepting AND rejecting
  dispatches with frozen compile counts;
- fleet: fleet_spec_* counters delta-fold engine stats off heartbeats
  (restart-reset-safe), per-tenant draft/accepted-token accounting
  feeds fleet_top's SPEC_ACC column, and crash-mid-spec-decode
  failover stays token-exact with speculation ON everywhere.

`pytest -m chaos` selects the fleet classes.

Engine/warmup tracing dominates this module's wall time, so waves are
single-bucket and assertions share engines wherever the contracts
allow.
"""
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config as _gpt_cfg
from paddle_tpu.nlp.llama import LlamaForCausalLM, \
    _resolve_config as _llama_cfg
from paddle_tpu.nlp.serving import ServingEngine
from paddle_tpu.nlp.speculative import DraftModelProposer, \
    NgramProposer, _ngram_propose, make_proposer
from paddle_tpu.resilience import faults
from paddle_tpu.serving_fleet import FleetRouter, InprocReplica

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW_TOK = 8
PS = 16


@pytest.fixture(scope="module")
def gpt_model():
    paddle.seed(0)
    m = GPTForCausalLM(_gpt_cfg("gpt-tiny"))
    m.eval()
    return m


@pytest.fixture(scope="module")
def llama_model():
    paddle.seed(0)
    m = LlamaForCausalLM(_llama_cfg("llama-tiny"))
    m.eval()
    return m


def wave(n=6, seed=0, vocab=256, lo=20, hi=28):
    """Seeded random prompts, every length inside prefill bucket 32.
    Tiny greedy models collapse into short cycles within a few steps,
    which is what makes the ngram acceptance assertions non-vacuous."""
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab,
                         (int(rng.integers(lo, hi)),)).astype(np.int32)
            for _ in range(n)]


def _engine(model, spec=True, **kw):
    d = dict(max_slots=2, page_size=PS, max_seq_len=64,
             steps_per_dispatch=4, num_pages=64, spec_decode=spec,
             spec_k=4, spec_draft="ngram")
    d.update(kw)
    return ServingEngine(model, **d)


def _run(model, spec, prompts, new_tok=NEW_TOK, **kw):
    eng = _engine(model, spec, **kw)
    eng.warmup(buckets=[len(p) for p in prompts], decode=True)
    out = eng.generate(prompts, max_new_tokens=new_tok)
    sp = eng.health().get("spec")
    eng.close()
    return out, sp, eng


def _counter(reg, name, **labels):
    c = reg.get(name, labels or None)
    return 0 if c is None else int(c.value)


# -- ngram proposer (pure host lookup) -----------------------------------


class TestNgramPropose:
    def test_cycle_self_extends_to_full_k(self):
        # the drafted tokens join the working context, so one match
        # unrolls a short cycle out to the full K — this is what buys
        # ~100% acceptance once greedy decode settles into a loop
        ctx = [7, 1, 2, 3, 1, 2, 3, 1, 2, 3]
        assert _ngram_propose(ctx, 6, -1) == [1, 2, 3, 1, 2, 3]

    def test_most_recent_occurrence_wins(self):
        # [5, 9] occurred twice; the draft continues the LATER one
        ctx = [5, 9, 1, 5, 9, 2, 5, 9]
        assert _ngram_propose(ctx, 1, -1) == [2]

    def test_no_match_pads(self):
        assert _ngram_propose([1, 2, 3, 4], 3, -1) == [-1, -1, -1]
        assert _ngram_propose([], 2, -1) == [-1, -1]

    def test_proposer_pads_dead_slots(self, gpt_model):
        eng = _engine(gpt_model)
        try:
            p = eng._spec
            assert isinstance(p, NgramProposer) and p.kind == "ngram"
            drafts = p.propose(eng)
            assert drafts.shape == (eng.max_slots, eng.spec_k)
            assert (drafts == eng.pad_token_id).all()  # no live slots
        finally:
            eng.close()

    def test_make_proposer_rejects_unknown(self, gpt_model):
        eng = _engine(gpt_model)
        try:
            with pytest.raises(ValueError):
                make_proposer(eng, "not-a-draft")
        finally:
            eng.close()


# -- engine: the token-exactness invariant -------------------------------


# both models x both samplers x every KV dtype
EXACT_CASES = [
    (which, sampler, cache_dtype)
    for which in ("gpt", "llama")
    for sampler in ({}, dict(temperature=0.8, top_k=4, seed=11))
    for cache_dtype in (None, "bfloat16", "int8")]


class TestTokenExactness:
    @pytest.mark.parametrize(
        "which,sampler,cache_dtype", EXACT_CASES,
        ids=[f"{w}-{'topk' if s else 'greedy'}-{d or 'fp32'}"
             for w, s, d in EXACT_CASES])
    def test_on_vs_off_token_exact(self, which, sampler, cache_dtype,
                                   request):
        """Speculation may never change tokens — only latency.
        Llama-tiny is the GQA coverage (kv_heads < heads)."""
        model = request.getfixturevalue(f"{which}_model")
        kw = dict(sampler)
        if cache_dtype:
            kw["cache_dtype"] = cache_dtype
        prompts = wave()
        on, sp, _ = _run(model, True, prompts, **kw)
        off, _, _ = _run(model, False, prompts, **kw)
        assert on == off, "speculative decode changed tokens"
        assert sp["proposed"] > 0 and sp["dispatches"] > 0, \
            "wave never took the spec path — the check was vacuous"

    def test_acceptance_nonvacuous_frozen_counts_no_leaks(
            self, gpt_model):
        """Greedy long decode settles into cycles the prompt-lookup
        proposer predicts — acceptance must be genuinely nonzero (a
        rejecting-only run would pass exactness trivially), compile
        counts stay frozen across accepting AND rejecting dispatches,
        and every page returns to the free list after close()."""
        prompts = wave()
        eng = _engine(gpt_model, spec_k=8)
        eng.warmup(buckets=[len(p) for p in prompts], decode=True)
        frozen = eng.compile_counts()
        out1 = eng.generate(prompts, max_new_tokens=24)
        out2 = eng.generate(prompts, max_new_tokens=24)
        assert out1 == out2, "speculative decode is nondeterministic"
        assert eng.compile_counts() == frozen
        assert eng.tracer.unexpected_retraces() == 0
        sp = eng.health()["spec"]
        assert sp["accepted"] > 0 and sp["acceptance_rate"] > 0
        assert sp["armed"] and sp["k"] == 8 and sp["draft"] == "ngram"
        assert _counter(eng.registry, "serve_spec_accepted_total") \
            == sp["accepted"]
        eng.close()
        assert eng.free_page_count == eng.num_pages - 1, \
            "speculative rewind leaked pages"


# -- engine: draft-model proposer ----------------------------------------


class TestDraftModelProposer:
    def test_self_draft_token_exact_high_acceptance(self, gpt_model):
        """The target as its own draft: the propose pass predicts the
        verify pass near-perfectly (greedy), so acceptance lands high
        — and the streams are STILL bit-identical to plain decode
        (draft quality is a latency knob, never a correctness one)."""
        prompts = wave()
        on, sp, _ = _run(gpt_model, True, prompts, new_tok=12,
                         spec_draft=gpt_model)
        off, _, _ = _run(gpt_model, False, prompts, new_tok=12)
        assert on == off
        assert sp["draft"] == "draft"
        assert sp["acceptance_rate"] > 0.5, \
            "an identical-weight draft must accept heavily"

    def test_random_draft_still_token_exact(self, gpt_model):
        """A draft with UNRELATED weights (fresh random init) proposes
        junk — acceptance drops, tokens do not move."""
        paddle.seed(123)
        junk = GPTForCausalLM(_gpt_cfg("gpt-tiny"))
        junk.eval()
        prompts = wave(4)
        on, sp, _ = _run(gpt_model, True, prompts, spec_draft=junk)
        off, _, _ = _run(gpt_model, False, prompts)
        assert on == off, "a bad draft changed tokens"
        assert sp["proposed"] > 0

    def test_vocab_mismatch_rejected(self, gpt_model):
        eng = _engine(gpt_model)
        try:
            cfg = _gpt_cfg("gpt-tiny")
            cfg.vocab_size *= 2
            paddle.seed(0)
            bad = GPTForCausalLM(cfg)
            bad.eval()
            with pytest.raises(ValueError, match="vocab"):
                DraftModelProposer(eng, bad)
        finally:
            eng.close()


# -- engine: arming, kill switch, dormancy -------------------------------


class TestArming:
    def test_env_knobs_arm_and_configure(self, gpt_model, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SPEC_DECODE", "1")
        monkeypatch.setenv("PADDLE_TPU_SPEC_K", "3")
        eng = ServingEngine(gpt_model, max_slots=2, page_size=PS,
                            max_seq_len=64, steps_per_dispatch=4)
        try:
            assert eng._spec is not None and eng.spec_k == 3
            assert eng.health()["spec"]["armed"] is False  # no warmup
        finally:
            eng.close()

    def test_kill_switch_disables_cleanly(self, gpt_model, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SPEC_DECODE", "0")
        eng = ServingEngine(gpt_model, max_slots=2, page_size=PS,
                            max_seq_len=64, steps_per_dispatch=4)
        try:
            assert eng._spec is None
            assert eng.health().get("spec") is None
            # never-armed: no serve_spec_* series even registered, so
            # the metrics surface is byte-identical to pre-round-20
            assert eng.registry.get("serve_spec_proposed_total") is None
        finally:
            eng.close()

    def test_armed_unwarmed_takes_plain_path_token_exact(
            self, gpt_model):
        """Warmup that skips decode leaves _warmed_spec unset: every
        dispatch must route through plain decode (no verify trace
        mid-traffic) and still match the spec-off stream."""
        prompts = wave(3)
        eng = _engine(gpt_model)
        try:
            eng.warmup(buckets=[len(p) for p in prompts], decode=False)
            assert not eng._warmed_spec
            out = eng.generate(prompts, max_new_tokens=NEW_TOK)
            sp = eng.health()["spec"]
            assert sp["dispatches"] == 0 and sp["proposed"] == 0
        finally:
            eng.close()
        off, _, _ = _run(gpt_model, False, prompts)
        assert out == off

    def test_spec_k_validated(self, gpt_model):
        with pytest.raises(ValueError, match="spec_k"):
            _engine(gpt_model, spec_k=0)


# -- fleet: counters, tenancy, failover (chaos) --------------------------


def _spec_fleet(model, n=2, router_kw=None, **engine_kw):
    engines = [_engine(model, **engine_kw) for _ in range(n)]
    lens = sorted({len(p) for p in wave(9)})
    for e in engines:
        e.warmup(buckets=lens, decode=True)
    frozen = [e.compile_counts() for e in engines]
    reps = [InprocReplica(f"r{i}", e) for i, e in enumerate(engines)]
    router = FleetRouter(reps, **dict(router_kw or {}))
    return router, reps, engines, frozen


@pytest.mark.chaos
class TestFleetSpec:
    def test_counters_tenancy_and_restart_fold(self, gpt_model):
        """fleet_spec_* delta-folds off heartbeats (restart-safe), and
        per-tenant draft/accepted tokens account — the rows fleet_top
        renders as SPEC_ACC."""
        prompts = wave(6)
        router, reps, engines, frozen = _spec_fleet(gpt_model, n=2,
                                                    spec_k=8)
        try:
            rids = [router.submit(p, 24, tenant="team-s")
                    for p in prompts]
            res = {r["id"]: r for r in router.run_to_completion()}
            assert all(res[i]["status"] == "ok" for i in rids)
            router._scrape_all()
            reg = router.registry
            assert _counter(reg, "fleet_spec_proposed_total") > 0
            assert _counter(reg, "fleet_spec_accepted_total") > 0
            assert _counter(reg, "fleet_spec_dispatches_total") > 0
            drafted = _counter(reg, "fleet_spec_draft_tokens_total",
                               tenant="team-s")
            accepted = _counter(reg, "fleet_spec_accepted_tokens_total",
                                tenant="team-s")
            assert drafted > 0 and 0 < accepted <= drafted
            t = router.tenants.report()
            row = [r for r in t["tenants"]
                   if r["tenant"] == "team-s"][0]
            assert row["spec_proposed"] == drafted
            assert row["spec_accepted"] == accepted
            # restart-reset fold: a stat that went BACKWARDS means a
            # respawn — fold the new absolute value, never a negative
            p0 = _counter(reg, "fleet_spec_proposed_total")
            router._fold_spec("zz", {"spec": {"proposed": 5,
                                              "accepted": 2,
                                              "dispatches": 1}})
            assert _counter(reg, "fleet_spec_proposed_total") == p0 + 5
            router._fold_spec("zz", {})          # inventory cleared
            assert "zz" not in router._spec_seen
        finally:
            router.close()

    def test_failover_token_exact_mid_spec_decode(self, gpt_model):
        """Crash a replica mid-wave with speculation ON everywhere:
        every request completes token-exact vs a spec-OFF golden (the
        failover continuation re-proposes at its destination against
        rewound state), and compile counts stay frozen."""
        prompts = wave(6)
        refs, _, _ = _run(gpt_model, False, prompts)
        router, reps, engines, frozen = _spec_fleet(gpt_model, n=2)
        try:
            assert router.generate(prompts, max_new_tokens=NEW_TOK) \
                == refs
            with faults.scenario(("replica_crash", {"replica": "r1"})):
                outs = router.generate(prompts, max_new_tokens=NEW_TOK)
            assert outs == refs, \
                "failover with speculation ON must stay token-exact"
            assert reps[1].state == "dead"
            for i, eng in enumerate(engines):
                assert eng.compile_counts() == frozen[i]
            assert router.compile_report()["unexpected_retraces"] == 0
        finally:
            router.close()
