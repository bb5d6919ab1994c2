"""Copy-on-write prefix caching + fleet-affinity routing (ISSUE 16).

Pins the round-19 contracts (docs/performance.md "Prefix caching"):

- THE invariant: a cache hit may change TTFT, never tokens — ON vs
  OFF streams are token-exact for GPT and Llama/GQA across greedy and
  top-k sampling and fp32/bf16/int8 KV dtypes (the full cross
  product, `EXACT_CASES`);
- fingerprint chain: rolling per-page-boundary digests, page-size
  domain-separated, final prompt position always private (COW is
  structural, not best-effort);
- PrefixIndex refcounts: pages return to the free list only at
  owners==0 AND rc==0, eviction never frees a slot-pinned page, and
  after close() every page is back on the free list — under churn,
  capacity eviction, and repeated waves;
- zero-recompile: a warmed engine serves hit AND miss admissions with
  frozen compile counts (the tail-prefill ladder traces at warmup);
- fleet: heartbeat fingerprint inventories feed a prefix_affinity
  placement term (weight 0 — the default — places exactly as before),
  fleet_prefix_* counters delta-fold engine stats (restart-safe),
  "placed" journal records carry the gain fingerprint, per-tenant
  hit-page accounting conserves, and crash-mid-wave failover stays
  token-exact with caching ON (the continuation re-fingerprints at
  the destination);
- replay: fleet_replay.prefix_stats predicts the committed golden
  wave's (independently random) hit rate as zero, and a genuinely
  shared wave as nonzero — the measure-before-build number.

`pytest -m chaos` selects the fleet classes.

Engine/warmup tracing dominates this module's wall time, so waves are
single-bucket (every prompt lands in prefill bucket 32, tail ladder
{16, 32}) and assertions share engines wherever the contracts allow.
"""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config as _gpt_cfg
from paddle_tpu.nlp.llama import LlamaForCausalLM, \
    _resolve_config as _llama_cfg
from paddle_tpu.nlp.paged_cache import PrefixIndex, prefix_fingerprints
from paddle_tpu.nlp.serving import ServingEngine
from paddle_tpu.resilience import faults
from paddle_tpu.serving_fleet import FleetRouter, InprocReplica
from paddle_tpu.serving_fleet.journal import replay as journal_replay

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW_TOK = 6
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


def shared_wave(n=8, seed=0, vocab=256, base_lens=(24, 20)):
    """n requests over len(base_lens) shared "system prompt" bases,
    each with a short random tail — the traffic the cache exists for.
    Default lens keep every prompt inside prefill bucket 32."""
    rng = np.random.default_rng(seed)
    bases = [rng.integers(1, vocab, (L,)).astype(np.int32)
             for L in base_lens]
    return [np.concatenate([bases[i % len(bases)],
                            rng.integers(1, vocab,
                                         (3 + i % 5,)).astype(np.int32)])
            for i in range(n)]


def _engine(model, on=True, **kw):
    # num_pages=64: the default pool is deliberately tiny — hits need
    # room for the index to retain pages across admissions
    d = dict(max_slots=2, page_size=PS, max_seq_len=64,
             steps_per_dispatch=4, num_pages=64, prefix_cache=on)
    d.update(kw)
    return ServingEngine(model, **d)


def _run(model, on, prompts, waves=1, **kw):
    eng = _engine(model, on, **kw)
    eng.warmup(buckets=[len(p) for p in prompts], decode=True)
    out = [eng.generate(prompts, max_new_tokens=NEW_TOK)
           for _ in range(waves)]
    pc = (eng.health().get("prefix_cache") or {})
    eng.close()
    return out, pc, eng


def _counter(reg, name, **labels):
    c = reg.get(name, labels or None)
    return 0 if c is None else int(c.value)


# -- fingerprint chain (pure host hashing) -------------------------------


class TestPrefixFingerprints:
    def test_deterministic_rolling_chain(self):
        p = np.arange(100, 170).astype(np.int32)
        fps = prefix_fingerprints(p, PS)
        assert fps == prefix_fingerprints(p, PS)
        assert len(fps) == (len(p) - 1) // PS
        assert len(set(fps)) == len(fps)
        # rolling: a longer prompt's chain extends its prefix's chain
        assert prefix_fingerprints(p[:40], PS) == fps[:(40 - 1) // PS]

    def test_page_size_domain_separated(self):
        p = np.arange(64).astype(np.int32)
        assert set(prefix_fingerprints(p, 16)) \
            .isdisjoint(prefix_fingerprints(p, 32))

    def test_final_position_always_private(self):
        # a prompt that ends exactly on a page boundary must NOT
        # publish that page: its last position's forward pass samples
        # the first token, so the boundary is capped one short
        assert prefix_fingerprints(np.arange(PS), PS) == []
        assert len(prefix_fingerprints(np.arange(PS + 1), PS)) == 1
        assert prefix_fingerprints(np.arange(0), PS) == []

    def test_content_sensitivity(self):
        a = np.arange(40).astype(np.int32)
        b = a.copy()
        b[3] += 1   # first page differs -> whole chain differs
        fa, fb = prefix_fingerprints(a, PS), prefix_fingerprints(b, PS)
        assert all(x != y for x, y in zip(fa, fb))


# -- PrefixIndex refcount bookkeeping (no engine, no jax) ----------------


class TestPrefixIndex:
    def _fps(self, n, ps=4, seed=0):
        rng = np.random.default_rng(seed)
        return prefix_fingerprints(
            rng.integers(0, 99, (n,)).astype(np.int64), ps)

    def test_insert_match_acquire_release_evict_cycle(self):
        idx = PrefixIndex(4, min_pages=1, max_entries=8)
        fps = self._fps(13)                      # 3 boundaries
        adopted, freed = idx.insert(fps, [7, 8, 9], kv="sidecar")
        assert adopted == {7, 8, 9} and freed == []
        assert idx.entries == 3 and idx.owned_page_count == 3
        assert idx.adopted_pages == 3 and idx.covers(fps)
        # the donor pin blocks eviction until the slot releases
        assert idx.evict(3) == []
        idx.release([7, 8, 9])
        e, j = idx.match(fps)                    # longest boundary wins
        assert j == 3 and e.fp == fps[-1]
        assert idx.acquire(e) == [7, 8, 9] and idx.pinned(7)
        assert idx.evict(3) == []                # still pinned
        idx.release([7, 8, 9])
        got = idx.evict(3)
        assert sorted(got) == [7, 8, 9]
        assert idx.entries == 0 and idx.owned_page_count == 0
        assert idx.evictions == 3
        # re-registering the same chain adopts afresh (monotonic feed)
        idx.insert(fps, [1, 2, 3], kv="sidecar2", pin=False)
        assert idx.adopted_pages == 6

    def test_nested_boundaries_share_pages_and_kv(self):
        idx = PrefixIndex(4, min_pages=1, max_entries=8)
        fps = self._fps(13)
        sidecar = object()
        idx.insert(fps, [5, 6, 7], kv=sidecar, pin=False)
        ents = [idx.match(fps[:j + 1])[0] for j in range(3)]
        assert [len(e.pages) for e in ents] == [1, 2, 3]
        assert all(e.kv is sidecar for e in ents)
        # page 5 is covered by all three entries; evicting the deepest
        # entry must not free it
        assert idx._owners[5] == 3

    def test_min_pages_gates_short_prefixes(self):
        idx = PrefixIndex(4, min_pages=2, max_entries=8)
        fps = self._fps(13)
        idx.insert(fps, [1, 2, 3], kv=None, pin=False)
        assert idx.entries == 2                  # boundary 1 skipped
        assert idx.match(fps[:1]) is None
        assert idx.match(fps)[1] == 3

    def test_capacity_eviction_returns_freed_pages(self):
        idx = PrefixIndex(4, min_pages=1, max_entries=2)
        a = self._fps(9, seed=1)                 # 2 boundaries
        b = self._fps(9, seed=2)
        idx.insert(a, [1, 2], kv=None, pin=False)
        _, freed = idx.insert(b, [3, 4], kv=None, pin=False)
        # capacity 2: registering b's 2 boundaries evicted a's LRU
        # entries and handed their pages back to the caller
        assert idx.entries == 2
        assert set(freed) == {1, 2}
        assert idx.owned_pages == {3, 4}


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
        """Hits may never change tokens — only TTFT. Llama-tiny is the
        GQA coverage (kv_heads < heads)."""
        model = request.getfixturevalue(f"{which}_model")
        kw = dict(sampler)
        if cache_dtype:
            kw["cache_dtype"] = cache_dtype
        prompts = shared_wave()
        on, pc, _ = _run(model, True, prompts, **kw)
        off, _, _ = _run(model, False, prompts, **kw)
        assert on == off, "prefix-cache hits changed tokens"
        assert pc["hits"] > 0 and pc["hit_pages"] > 0, \
            "wave produced no hits — the exactness check was vacuous"

    def test_repeat_waves_identical_zero_recompile_cow_isolated(
            self, gpt_model):
        """Shared pages are immutable: if any hit wrote one, a later
        wave over the same prompts would diverge (two slots share an
        entry concurrently here — COW isolation). Also the no-new-
        traces contract with caching ON (hit + miss + extension paths
        all inside the warmed ladder), and refcount conservation:
        every page back on the free list after close()."""
        prompts = shared_wave()
        eng = _engine(gpt_model)
        eng.warmup(buckets=[len(p) for p in prompts], decode=True)
        frozen = eng.compile_counts()
        w1 = eng.generate(prompts, max_new_tokens=NEW_TOK)
        w2 = eng.generate(prompts, max_new_tokens=NEW_TOK)
        assert w1 == w2, "a hit mutated shared prefix state"
        assert eng.compile_counts() == frozen
        assert eng.tracer.unexpected_retraces() == 0
        pc = eng.health()["prefix_cache"]
        assert pc["hits"] >= len(prompts), "wave 2 must hit every time"
        assert pc["cow_copies"] > 0, "no private tail was materialized"
        eng.close()
        assert eng.free_page_count == eng.num_pages - 1, \
            "prefix refcounts leaked pages"


# -- engine: churn, telemetry, kill switch -------------------------------


class TestChurnAndTelemetry:
    def test_churn_eviction_occupancy_and_no_leaks(self, gpt_model):
        """Distinct waves through a capacity-starved index force LRU
        evictions mid-traffic; every page must still come back. The
        occupancy gauge is registered at 0 on a cold engine (DOC01
        catalogue contract) and tracks the index level."""
        eng = _engine(gpt_model, prefix_max_entries=3)
        g = eng.registry.get("prefix_cache_occupancy")
        assert g is not None and g.value == 0
        waves = [shared_wave(6, seed=s) for s in range(3)]
        lens = sorted({len(p) for w in waves for p in w})
        eng.warmup(buckets=lens, decode=True)
        for w in waves:
            eng.generate(w, max_new_tokens=NEW_TOK)
        pc = eng.health()["prefix_cache"]
        assert pc["evictions"] > 0, "capacity churn never evicted"
        assert pc["entries"] <= 3
        assert eng.registry.get("prefix_cache_occupancy").value > 0
        assert pc["fingerprints"] and pc["page_size"] == PS
        eng.close()
        assert eng.free_page_count == eng.num_pages - 1, \
            "prefix refcounts leaked pages under churn"

    def test_kill_switch_disables_cleanly(self, gpt_model, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PREFIX_CACHE", "0")
        eng = ServingEngine(gpt_model, max_slots=2, page_size=PS,
                            max_seq_len=64, steps_per_dispatch=4)
        assert eng.prefix is None
        assert eng.health().get("prefix_cache") is None
        eng.close()


# -- replay: the measure-before-build number -----------------------------


class TestReplayPrefixStats:
    def _stats(self, entries, **kw):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            from fleet_replay import prefix_stats
        finally:
            sys.path.pop(0)
        return prefix_stats(entries, **kw)

    def test_golden_wave_predicts_zero(self):
        """The committed replay wave's prompts are independently
        random — prefix_stats must predict a zero hit rate (which is
        also why the replay goldens stay byte-identical with caching
        ON by default)."""
        with open(os.path.join(REPO, "tools", "golden",
                               "replay_wave.json")) as f:
            entries = json.load(f)["entries"]
        assert len(entries) == 20
        for row in self._stats(entries).values():
            assert row["expected_hit_pages"] == 0
            assert row["requests"] == 20

    def test_shared_wave_predicts_hits_and_min_pages_gates(self):
        entries = [{"arrival_s": float(i), "prompt": p.tolist()}
                   for i, p in enumerate(shared_wave(8))]
        row = self._stats(entries, page_sizes=(PS,))[str(PS)]
        assert row["expected_hit_pages"] > 0
        assert 0.0 < row["expected_page_hit_rate"] <= 1.0
        assert row["expected_hit_requests"] >= 5     # all but seeds
        strict = self._stats(entries, page_sizes=(PS,),
                             min_pages=3)[str(PS)]
        assert strict["expected_hit_requests"] \
            <= row["expected_hit_requests"]


# -- fleet: affinity, counters, journal, failover (chaos) ----------------


def _prefix_fleet(model, n=2, router_kw=None, jdir=None, **engine_kw):
    engines = [_engine(model, **engine_kw) for _ in range(n)]
    lens = sorted({len(p) for p in shared_wave(9)})
    for e in engines:
        e.warmup(buckets=lens, decode=True)
    frozen = [e.compile_counts() for e in engines]
    reps = [InprocReplica(f"r{i}", e) for i, e in enumerate(engines)]
    kw = dict(router_kw or {})
    if jdir is not None:
        kw["journal_dir"] = str(jdir)
    router = FleetRouter(reps, **kw)
    return router, reps, engines, frozen


@pytest.mark.chaos
class TestFleetPrefix:
    def test_affinity_counters_tenancy_journal_and_zero_weight(
            self, gpt_model, tmp_path):
        """One fleet session, the full placement story: seed one
        replica with a base prefix, scrape, then place same-base
        requests with a dominant affinity weight — they must all land
        on the fingerprint holder; fleet_prefix_* counters fold off
        heartbeats (restart-reset-safe); per-tenant hit pages account;
        "placed" journal records carry the gain fingerprint; and with
        the weight dialed back to the default 0, a prefix-laden
        pending places exactly like no pending at all."""
        prompts = shared_wave(7, base_lens=(24,))
        router, reps, engines, frozen = _prefix_fleet(
            gpt_model, n=2, jdir=tmp_path / "journal",
            router_kw={"placement_weights": {"prefix_affinity": 1e6},
                       "replica_queue_limit": 16})
        try:
            router.generate(prompts[:1], max_new_tokens=NEW_TOK)
            router._scrape_all()
            holders = [name for name, (fs, ps) in router._fpsets.items()
                       if fs and ps == PS]
            assert len(holders) == 1
            holder = holders[0]
            before = _counter(router.registry, "fleet_routed_total",
                              replica=holder)
            rids = [router.submit(p, NEW_TOK, tenant="team-a")
                    for p in prompts[1:]]
            res = {r["id"]: r for r in router.run_to_completion()}
            assert all(res[i]["status"] == "ok" for i in rids)
            after = _counter(router.registry, "fleet_routed_total",
                             replica=holder)
            assert after - before == len(rids), \
                "affinity did not concentrate the shared prefix"
            router._scrape_all()
            reg = router.registry
            assert _counter(reg, "fleet_prefix_hits_total") > 0
            assert _counter(reg, "fleet_prefix_shared_pages_total") > 0
            assert _counter(reg, "fleet_prefix_cow_copies_total") > 0
            # per-tenant accounting: hit pages <= shareable pages
            pages = _counter(reg, "fleet_prefix_pages_total",
                             tenant="team-a")
            hitp = _counter(reg, "fleet_prefix_hit_pages_total",
                            tenant="team-a")
            assert pages > 0 and 0 < hitp <= pages
            # journal: placed records carry the prefix gain fingerprint
            records, _ = journal_replay(str(tmp_path / "journal"))
            placed = [r for r in records if r.get("kind") == "placed"]
            fps = [r.get("fingerprint") for r in placed
                   if r.get("fingerprint")]
            assert fps, "no placed record carried a fingerprint"
            assert prefix_fingerprints(prompts[1], PS)[-1] in fps
            # restart-reset fold: a stat that went BACKWARDS means a
            # respawn — fold the new absolute value, never a negative
            hits0 = _counter(reg, "fleet_prefix_hits_total")
            snap = {"page_size": PS,
                    "prefix_cache": {"fingerprints": ["ab" * 12],
                                     "hits": 2, "misses": 0,
                                     "adopted_pages": 0,
                                     "cow_copies": 0, "evictions": 0}}
            router._fold_prefix("zz", snap)      # fresh incarnation
            assert _counter(reg, "fleet_prefix_hits_total") \
                == hits0 + 2
            router._fold_prefix("zz", {"page_size": PS})
            assert "zz" not in router._fpsets    # inventory cleared
            # zero-weight kill path: affinity term skipped entirely —
            # identical pick with/without the pending, and its
            # fingerprint memo never even computes
            router.placement_weights["prefix_affinity"] = 0.0
            rid = router.submit(prompts[1], NEW_TOK)
            p = router._pending[rid]
            out = {name: 0 for name in router.replicas}
            assert router._pick_replica(out, pending=p) \
                == router._pick_replica(out, pending=None)
            assert p.prefix_fps is None, \
                "affinity memo computed despite weight 0"
            router.run_to_completion()
        finally:
            router.close()

    def test_failover_token_exact_with_caching_on(self, gpt_model):
        """Crash a replica mid-wave with caching ON everywhere: every
        request completes token-exact vs a cache-OFF golden (the
        failover continuation re-fingerprints at its destination),
        and compile counts stay frozen."""
        prompts = shared_wave(6)
        refs, _, _ = _run(gpt_model, False, prompts)
        router, reps, engines, frozen = _prefix_fleet(gpt_model, n=2)
        try:
            assert router.generate(prompts, max_new_tokens=NEW_TOK) \
                == refs[0]
            with faults.scenario(("replica_crash", {"replica": "r1"})):
                outs = router.generate(prompts, max_new_tokens=NEW_TOK)
            assert outs == refs[0], \
                "failover with caching ON must stay token-exact"
            assert reps[1].state == "dead"
            for i, eng in enumerate(engines):
                assert eng.compile_counts() == frozen[i]
            assert router.compile_report()["unexpected_retraces"] == 0
        finally:
            router.close()
