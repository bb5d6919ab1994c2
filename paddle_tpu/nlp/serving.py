"""Continuous-batching serving engine — paged KV cache + batched decode.

ref parity: FastDeploy / vLLM-style continuous batching over the
PaddleNLP generation surface (the reference serves GPT/Llama through
fused block-attention CUDA ops; see PAPERS.md on memory-efficient
attention serving). TPU-native design: EVERYTHING the chip executes is
one of a small, fixed set of compiled XLA programs —

- ONE batched decode program per sampling strategy: a `lax.scan` of
  `steps_per_dispatch` single-token steps over the whole slot pool
  (single dispatch per K tokens x B slots), paged-cache reads/writes
  inside (nlp/paged_cache.py; Pallas GQA flash-decode when armed);
- ONE prefill program per power-of-two length bucket: admission pads
  the prompt to the bucket, masks the tail, and scatters the prompt's
  K/V into the slot's pages — a new request NEVER triggers a fresh
  trace once its bucket is warm;
- page allocation, slot assignment, admission and eviction are
  host-side bookkeeping BETWEEN dispatches (a free-list of page ids
  and a [slots, max_pages] int32 table) — they change array CONTENTS,
  never shapes, so the steady state compiles nothing.

Zero-recompile is not aspirational: every jitted program runs under a
trace counter and `compile_counts()` exposes them; the benchmark's
serve cells refuse a window in which anything compiled, and
tests/test_serving.py asserts the counts freeze after warmup.

The cache is shared GPT/Llama (both models' attention layers route a
`PagedLayerCache` through `paged_update_and_attend`): GQA models cache
only their kv heads; `cache_dtype` float32/bfloat16/int8 trades HBM
decode bandwidth for precision (int8 carries per-token-per-head f32
scale sidecars).

Degradation under load is first-class (docs/robustness.md): per-
request deadlines and cancel() resolve at host step boundaries (never
mid-dispatch, never a recompile), admission back-pressure can reject
or evict-lowest-priority when KV pages run out, a resilience.Watchdog
flags wedged dispatches, transient dispatch errors ride a bounded
retry, and health() exposes the whole picture. Every path drills
deterministically via resilience.faults (page_exhaustion, slow_step,
dispatch_error).

Single-threaded by design (one engine owns one chip's decode loop);
wrap submissions in your own queue for multi-producer serving — or
run N engines as a fault-tolerant fleet behind
``serving_fleet.FleetRouter`` (health-routed balancing, failover with
token-exact prefix dedup, hedging, graceful drain/rejoin via
``drain()``/``resume()``/``export_inflight()`` below).
"""
from __future__ import annotations

import collections
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..nn.layer import functional_call
from ..observability.metrics import MetricsRegistry
from ..resilience import faults
from ..resilience.retry import call_with_retries
from ..tensor import Tensor
from .paged_cache import AUX_COUNTERS, PrefixIndex, cache_specs_of, \
    prefix_fingerprints, write_prompt_kv, xla_attention_form, TRASH_PAGE

__all__ = ["ServingEngine", "ServeRequest"]


class ServeRequest:
    """One queued generation request.

    deadline: absolute time.monotonic() seconds (None = no deadline) —
    checked at host step boundaries only, preserving zero-recompile.
    priority: larger = more important; the evict admission policy may
    preempt a strictly-lower-priority running request.
    """

    __slots__ = ("rid", "prompt", "max_new_tokens", "eos_token_id",
                 "deadline", "priority", "submitted_at", "submitted_pc",
                 "trace", "admitted_pc", "tenant", "queue_wait_s",
                 "prefix_fps")

    def __init__(self, rid, prompt, max_new_tokens, eos_token_id,
                 deadline=None, priority=0, trace=None, tenant=None):
        self.rid = rid
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.deadline = deadline
        self.priority = int(priority)
        self.submitted_at = time.monotonic()
        # span clock (perf_counter): the queue-wait span's start
        self.submitted_pc = time.perf_counter()
        # distributed-trace context (observability.dtrace wire form);
        # None for untraced (non-fleet) requests — zero overhead then
        self.trace = trace
        self.admitted_pc = None
        # tenancy label (observability.tenancy): None = untagged, no
        # accounting; set at admission so finish sees the real wait
        self.tenant = None if tenant is None else str(tenant)
        self.queue_wait_s = None
        # rolling per-page-boundary fingerprint chain (COW prefix
        # caching) — computed once at submit when the cache is on
        self.prefix_fps = None


class _Slot:
    __slots__ = ("req", "pages", "out_tokens", "status", "admit_seq",
                 "decode_t0", "shared", "prefix_hit_pages",
                 "prefix_pages", "spec_proposed", "spec_accepted")

    def __init__(self, req, pages, admit_seq=0):
        self.req = req
        self.pages = pages          # page ids owned by this sequence
        self.out_tokens = []        # generated tokens (host ints)
        self.status = "ok"          # ok | expired | cancelled | evicted
        self.admit_seq = admit_seq  # admission order (evict tie-break)
        self.decode_t0 = None       # perf_counter at prefill end (the
        #                             traced decode leg's start)
        self.shared = frozenset()   # pages owned by the prefix index
        #                             (release, don't free, on finish)
        self.prefix_hit_pages = 0   # prompt pages served from cache
        self.prefix_pages = 0       # shareable prompt pages (denom)
        self.spec_proposed = 0      # draft tokens dispatched to verify
        self.spec_accepted = 0      # draft tokens the target confirmed


def _next_pow2(n):
    return 1 << max(0, (int(n) - 1)).bit_length()


class ServingEngine:
    """Continuous-batching decode over a fixed slot pool.

    model: GPTForCausalLM / LlamaForCausalLM (anything whose attention
    layers understand the PagedLayerCache contract), or a model that
    names another cache through `cache_spec()`, for every layer or
    layer by layer (AXK1ForCausalLM: one latent pool a layer;
    LFM2ForCausalLM: K/V pages for its attention layers and a per-slot
    state, no pages, for its short convolutions; the prefix cache, an
    int8 cache, speculative verify and AOT export are refused for both
    by name). All requests share
    one sampling strategy (greedy when temperature==0, else
    temperature/top-k sampling) — the strategy is baked into the one
    compiled decode program.

    max_slots: decode batch width (the slot pool).
    page_size: tokens per KV page (multiple of 8).
    max_seq_len: per-sequence capacity (prompt + generated), rounded up
        to whole pages; fixes the page-table width.
    num_pages: total pool pages (page 0 is the reserved trash page).
        Default fully provisions every slot; smaller values exercise
        admission back-pressure/recycling.
    cache_dtype: 'float32' | 'bfloat16' | 'int8' KV storage.
    use_flash: how decode attention reads the paged cache. True the
        Pallas paged kernel (interpret mode off-TPU; ValueError when
        head_dim/page_size rule it out), False the plain-XLA path
        (paged_cache.paged_attention_ref: the pool read in place, or
        gathered where the pool outgrows what the tables can name),
        None what ops/attention.paged_flash_available resolves from
        the shapes (the XLA path today; no environment variable is
        read). A latent cache (ops/attention.latent_flash_available):
        True its paged kernel (ops/pallas/latent_decode.py), False the
        gathered XLA form, None the kernel on a TPU, where the chip
        measured it faster, and the gathered form elsewhere.
        health()["decode_attention"] names the one built
        ("paged_kernel" | "in_place" | "gathered" |
        "latent_paged_kernel" | "latent_gathered").
    steps_per_dispatch: decode tokens per compiled call (the scan
        length) — admission/eviction happen at dispatch boundaries.
    admission_policy: what to do with the queue head when pages run
        out — 'wait' (back-pressure, retry next boundary), 'reject'
        (finish it immediately with status='rejected'), or 'evict'
        (preempt the lowest-priority strictly-lower-priority running
        request, finishing it with status='evicted' and its partial
        tokens; falls back to waiting when no such victim exists).
    watchdog_timeout: seconds; when set, a resilience.Watchdog daemon
        monitors every decode/prefill dispatch and flags a wedge in
        health() when one stays in flight past the timeout (it cannot
        cancel a running XLA execute — detection only).
    dispatch_retries: bounded deterministic backoff for transient
        RESOURCE_EXHAUSTED-style dispatch errors (resilience.retry).
    registry: observability.MetricsRegistry the engine publishes its
        serve_* series into (docs/observability.md metric catalogue);
        default a PRIVATE per-engine registry, so two engines in one
        process never alias each other's counters and reset_counters()
        on one cannot zero another's window — pass
        observability.metrics.get_registry() (or merge
        engine.registry.snapshot()) to land the series in the
        process-global export. Everything is recorded at host step
        boundaries AFTER the dispatch's existing device sync —
        instrumentation adds no host sync and no trace inputs, so the
        zero-recompile contract is untouched. reset_counters() zeroes
        every serve_* series (incl. retry/watchdog counts) uniformly.
    donate: donate the page pool to the decode/prefill programs
        (in-place HBM updates).
    prefix_cache: copy-on-write prefix-page sharing (PrefixIndex):
        prompts sharing a page-aligned prefix with an earlier prompt
        map the already-written pages into their page table and run a
        short bucketed TAIL prefill only. Hits can change TTFT, never
        tokens (docs/performance.md round 19). Default ON; None reads
        PADDLE_TPU_PREFIX_CACHE (0/false/off disables — the kill
        switch). Hit admission additionally requires the tail bucket
        pre-traced by warmup() — a cold engine serves every request
        through the full-prefill path, so zero-recompile and token
        goldens hold unconditionally.
    min_prefix_pages: shortest prefix (in whole pages) worth sharing;
        None reads PADDLE_TPU_PREFIX_MIN_PAGES (default 1).
    prefix_max_entries: bound on registered fingerprint boundaries
        (LRU-evicted beyond it).
    spec_decode: speculative decoding (draft-propose / one-dispatch-
        verify): a proposer guesses spec_k tokens per live slot and the
        flagship verifies all spec_k+1 positions in ONE folded batched
        dispatch through the paged cache, applying its own per-position
        seeded sampler — accepted tokens are bit-identical to what
        non-speculative decode would have produced (greedy AND top-k;
        docs/performance.md round 20). Default OFF; None reads
        PADDLE_TPU_SPEC_DECODE (the kill switch — 1/true/on arms it).
        An armed engine additionally requires warmup() to pre-trace the
        verify program before any speculative dispatch runs, so a
        never-warmed engine is byte-identical to a spec-off one.
    spec_k: draft tokens proposed per slot per dispatch; None reads
        PADDLE_TPU_SPEC_K (default 4).
    spec_draft: 'ngram' (zero-weight prompt-lookup proposer — no second
        model) or a tiny GPT/Llama draft model instance sharing the
        tokenizer; None reads PADDLE_TPU_SPEC_DRAFT (default 'ngram').
    mem_ledger: device-memory ledger (observability.memledger): typed
        per-segment HBM attribution (kv_pages/prefix_sidecar/weights/
        ...), ground-truth cross-check with an unattributed residual,
        and headroom forecasting as engine_mem_* gauges. Default OFF;
        None reads PADDLE_TPU_MEM_LEDGER. A never-armed engine
        creates no ledger and registers no mem_* series (the profiler
        dormancy contract). Host-side accounting only: arming it
        leaves token streams and compile counts byte-identical.
    mem_admission: 'advisory' (would_fit consults are counters only)
        or 'hard' (submit() rejects a request whose full KV footprint
        would not fit the forecast headroom with a typed
        MemoryAdmissionError). None reads PADDLE_TPU_MEM_ADMISSION
        (default advisory). Hard mode needs a known capacity.
    mem_capacity_bytes: device-memory budget when the backend's
        memory_stats() has no bytes_limit (CPU, capped deployments);
        None reads PADDLE_TPU_MEM_CAPACITY_BYTES, else the ledger
        learns it from the device or runs capacity-blind.
    """

    def __init__(self, model, *, max_slots=8, page_size=16,
                 max_seq_len=256, num_pages=None, cache_dtype="float32",
                 use_flash=None, temperature=0.0, top_k=0, seed=0,
                 pad_token_id=0, steps_per_dispatch=8, donate=True,
                 admission_policy="wait", watchdog_timeout=None,
                 dispatch_retries=2, registry=None,
                 tenant_capacity=64, prefix_cache=None,
                 min_prefix_pages=None, prefix_max_entries=512,
                 spec_decode=None, spec_k=None, spec_draft=None,
                 profile=None, profile_hz=None, mem_ledger=None,
                 mem_admission=None, mem_capacity_bytes=None):
        if page_size % 8:
            raise ValueError(f"page_size must be a multiple of 8 "
                             f"(Mosaic sublane tiling), got {page_size}")
        model.eval()
        self.model = model
        cfg = model.config
        self.cfg = cfg
        # the cache layout comes from the model, layer by layer: keys and
        # values by head (GPT, Llama), one latent pool, or a per-slot state
        # and no pages (paged_cache.*Spec); `cache_spec` is the model's own
        # answer, one spec or a list
        self.cache_spec, self.cache_specs = cache_specs_of(model)
        specs = self.cache_specs
        self.num_layers = cfg.num_hidden_layers
        kinds = [s.kind for s in specs]
        self.cache_layers = {k: kinds.count(k) for k in dict.fromkeys(kinds)}
        self._state_layers = kinds.count("conv_state")
        latent = "latent" in kinds
        if not latent:
            kv = specs[kinds.index("kv")]
            self.kv_heads, self.head_dim = kv.kv_heads, kv.head_dim
            self.groups = cfg.num_attention_heads // self.kv_heads
        self.page_size = int(page_size)
        self.max_slots = int(max_slots)
        self.max_pages_per_seq = -(-int(max_seq_len) // self.page_size)
        self.max_seq_len = self.max_pages_per_seq * self.page_size
        max_pos = getattr(cfg, "max_position_embeddings", None)
        if max_pos and self.max_seq_len > max_pos:
            raise ValueError(
                f"max_seq_len={max_seq_len} exceeds the model's "
                f"max_position_embeddings={max_pos}")
        if num_pages is None:
            num_pages = 1 + self.max_slots * self.max_pages_per_seq
        self.num_pages = int(num_pages)
        self.cache_dtype = str(cache_dtype)
        if self.cache_dtype not in ("float32", "bfloat16", "int8"):
            raise ValueError(f"cache_dtype {cache_dtype!r}: expected "
                             "float32 | bfloat16 | int8")
        if latent:
            from ..ops.attention import latent_flash_available
            self.use_flash = latent_flash_available(use_flash)
            self.decode_attention = "latent_paged_kernel" \
                if self.use_flash else "latent_gathered"
        else:
            from ..ops.attention import paged_flash_available
            self.use_flash = paged_flash_available(
                self.head_dim, self.page_size, use_flash)
            # which attention the decode program is built with, as
            # health() names it: the Pallas kernel, or the plain-XLA form
            # the shapes select (paged_cache.xla_attention_form)
            self.decode_attention = "paged_kernel" if self.use_flash else \
                xla_attention_form(self.num_pages, self.max_slots,
                                   self.max_pages_per_seq)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.sampling_seed = int(seed)  # published in health() so the
        #                                 fleet capture archive records
        #                                 what replay must match for
        #                                 token-exact goldens
        self.pad_token_id = int(pad_token_id)
        self.steps_per_dispatch = int(steps_per_dispatch)
        self.donate = bool(donate)
        if prefix_cache is None:
            prefix_cache = os.environ.get(
                "PADDLE_TPU_PREFIX_CACHE", "1").lower() \
                not in ("0", "false", "off")
        if min_prefix_pages is None:
            min_prefix_pages = int(os.environ.get(
                "PADDLE_TPU_PREFIX_MIN_PAGES", "1"))
        self.prefix = PrefixIndex(
            self.page_size, min_pages=min_prefix_pages,
            max_entries=prefix_max_entries) if prefix_cache else None
        if spec_decode is None:
            spec_decode = os.environ.get(
                "PADDLE_TPU_SPEC_DECODE", "0").lower() \
                in ("1", "true", "on")
        if spec_k is None:
            spec_k = int(os.environ.get("PADDLE_TPU_SPEC_K", "4"))
        self.spec_k = int(spec_k)
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if spec_draft is None:
            spec_draft = os.environ.get("PADDLE_TPU_SPEC_DRAFT", "ngram")
        self.spec_draft = spec_draft
        # what a kind of cache does not serve yet is refused here, by
        # name: never silently served by another path
        if latent:
            for asked, what in (
                    (prefix_cache, "prefix_cache=True (no sharing of "
                     "latent pages yet; pass prefix_cache=False)"),
                    (self.cache_dtype == "int8", "cache_dtype='int8'"),
                    (spec_decode, "spec_decode=True (speculative verify)")):
                if asked:
                    raise ValueError(
                        f"{type(model).__name__} serves from a latent "
                        f"paged cache, which does not support {what}")
        if self._state_layers:
            for asked, what in (
                    (prefix_cache, "prefix_cache=True (a tail prefill "
                     "would need the state at the prefix's end, which no "
                     "page holds; pass prefix_cache=False)"),
                    (self.cache_dtype == "int8", "cache_dtype='int8' (the "
                     "state has no quantized form)"),
                    (spec_decode, "spec_decode=True (a rejected write to a "
                     "state overwritten in place cannot be taken back)")):
                if asked:
                    raise ValueError(
                        f"{type(model).__name__} has layers with a "
                        f"per-slot state, which does not support {what}")
        if profile is None:
            profile = os.environ.get(
                "PADDLE_TPU_PROFILE", "0").lower() in ("1", "true", "on")
        self._profile_enabled = bool(profile)
        self._profile_hz = profile_hz
        from ..observability import memledger as _memledger
        if mem_ledger is None:
            mem_ledger = _memledger.mem_ledger_enabled_from_env()
        self._mem_enabled = bool(mem_ledger)
        self.mem_admission = (_memledger.mem_admission_from_env()
                              if mem_admission is None
                              else str(mem_admission))
        if self.mem_admission not in _memledger.ADMISSION_MODES:
            raise ValueError(
                f"mem_admission {mem_admission!r}: expected "
                f"{' | '.join(_memledger.ADMISSION_MODES)}")
        if mem_capacity_bytes is None:
            mem_capacity_bytes = _memledger.mem_capacity_from_env()
        self._mem_capacity_bytes = mem_capacity_bytes

        self._params, self._buffers = model.raw_state()
        self._pages = [spec.alloc(self.num_pages, self.page_size,
                                  self.cache_dtype, self.max_slots)
                       for spec in specs]
        # prefills that wrote a layer's per-slot state (one count a state
        # layer and admission): health()["conv_state_prefill_writes"]
        self.conv_state_prefill_writes = 0
        self._quantized = self.cache_dtype == "int8"

        b = self.max_slots
        self._page_table = np.zeros((b, self.max_pages_per_seq), np.int32)
        self._seq_lens = np.zeros((b,), np.int32)
        self._last_tokens = np.zeros((b,), np.int32)
        self._emitted = np.zeros((b,), np.int32)
        self._max_new = np.ones((b,), np.int32)
        self._eos = np.full((b,), -1, np.int32)  # -1 = no eos for slot
        self._done = np.ones((b,), bool)
        self._active = np.zeros((b,), bool)
        self._rng = jax.random.PRNGKey(seed)
        # prime the eager split executable NOW (result discarded, RNG
        # state untouched): the per-admission split below must never
        # pay its one-time process-wide compile inside a request's
        # TTFT — the replay latency baselines treat admission as
        # microseconds of host work
        jax.random.split(self._rng)
        # per-slot sampling key base: one fresh split per ADMISSION,
        # folded with the token's emitted index inside the programs
        # (key = fold_in(base, index)). Token streams are therefore a
        # pure function of (request, admission order, index) — not of
        # how decode work is scheduled into dispatches — which is what
        # lets speculative verify reproduce non-speculative sampling
        # bit-for-bit at any acceptance pattern.
        self._key_base = np.zeros((b, 2), np.uint32)

        # device-resident mirror of the scheduling arrays: refreshed
        # from host only when admission/eviction mutates them, so a
        # steady full-pool decode pays zero host->device uploads per
        # dispatch (the compiled step's launch overhead is the serving
        # metric's denominator)
        self._dev_sched = None

        self._free_pages = list(range(1, self.num_pages))  # 0 = trash
        self._slots = [None] * b
        self._queue = collections.deque()
        self._finished = []
        self._next_rid = 0

        # -- resilience/degradation state (all host-side: deadlines,
        # cancellation, admission policy and the watchdog never touch
        # the compiled programs, so zero-recompile survives chaos)
        if admission_policy not in ("wait", "reject", "evict"):
            raise ValueError(f"admission_policy {admission_policy!r}: "
                             "expected wait | reject | evict")
        self.admission_policy = admission_policy
        self.dispatch_retries = int(dispatch_retries)
        from ..resilience.retry import RetryStats
        self.retry_stats = RetryStats()
        self._watchdog = None
        if watchdog_timeout is not None:
            from ..resilience.watchdog import Watchdog
            self._watchdog = Watchdog(timeout_s=watchdog_timeout).start()
        self._rounds = 0
        self._admit_seq = 0
        self._cancel_pending = set()
        self.last_dispatch_s = 0.0
        # lifecycle: serving -> (draining <-> serving) -> closed. A
        # router/LB reads this through health()["state"] to tell
        # "busy" from "going away" (docs/robustness.md fleet section)
        self._state = "serving"

        # -- observability: every counter the engine keeps lives in the
        # registry (status_counts/health() are snapshot VIEWS of it),
        # so reset_counters() has exactly one reset semantic. Default
        # is a private registry: series like serve_requests_total are
        # identified by name alone, so sharing the process-global one
        # between engines would alias their counters (and reset would
        # zero a sibling engine's measurement window)
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        reg = self.registry
        self._own_series = []

        def own(m):
            self._own_series.append(m)
            return m
        self._m_queue_wait = own(reg.histogram(
            "serve_queue_wait_seconds",
            help="submit -> admission (prefill start) wait"))
        self._m_ttft = own(reg.histogram(
            "serve_ttft_seconds",
            help="submit -> first generated token (incl. queue wait "
                 "and prefill)"))
        self._m_tok = own(reg.histogram(
            "serve_decode_token_seconds",
            help="per-token batched-decode latency (dispatch wall / "
                 "tokens, count-weighted)"))
        self._m_dispatch = own(reg.histogram(
            "serve_dispatch_seconds",
            help="batched decode dispatch wall time"))
        self._m_decode_tokens = own(reg.counter(
            "serve_decode_tokens_total",
            help="tokens generated by batched decode"))
        self._m_decode_dispatches = own(reg.counter(
            "serve_decode_dispatches_total",
            help="batched decode dispatches"))
        self._m_deadline = own(reg.counter(
            "serve_deadline_misses_total",
            help="requests finished with status=expired"))
        self._m_evictions = own(reg.counter(
            "serve_evictions_total",
            help="running requests preempted by the evict admission "
                 "policy"))
        self._m_retries = own(reg.counter(
            "serve_dispatch_retries_total",
            help="transient dispatch errors absorbed by the retry "
                 "wrapper"))
        self._m_wedges = own(reg.counter(
            "serve_watchdog_wedges_total",
            help="dispatches the watchdog flagged past its timeout"))
        self._g_free_pages = own(reg.gauge(
            "serve_free_pages", help="KV pages on the free list"))
        self._g_occupancy = own(reg.gauge(
            "serve_page_occupancy",
            help="fraction of usable KV pages in use"))
        self._g_queue_depth = own(reg.gauge(
            "serve_queue_depth", help="requests awaiting admission"))
        self._g_running = own(reg.gauge(
            "serve_running", help="requests occupying a slot"))
        self._g_prefix_occ = own(reg.gauge(
            "prefix_cache_occupancy",
            help="fraction of usable KV pages owned by the shared "
                 "prefix index (0 when the cache is off/empty)"))
        self._m_req = {}            # status -> serve_requests_total
        for status in ("ok", "expired", "cancelled", "rejected",
                       "evicted"):
            self._status_counter(status)
        # per-tenant usage attribution (observability.tenancy): a
        # bounded space-saving sketch of tokens in/out, queue-wait and
        # KV-page-seconds for tenant-tagged requests. Host-side dict
        # arithmetic at the finish boundary the engine already owns —
        # zero-recompile untouched; untagged requests skip it entirely
        from ..observability.tenancy import TenantAccountant
        self.tenants = TenantAccountant(capacity=tenant_capacity,
                                        registry=reg)
        self._seen_retries = 0
        self._seen_wedges = 0
        # _sync_registry runs on the step() thread AND (via health())
        # on metrics-exporter HTTP threads — the diff-and-increment
        # must not race
        self._sync_lock = threading.Lock()
        self._update_gauges()

        # the trace counters ARE a RecompileTracer's (same dict): the
        # zero-recompile assertion's ground truth and the queryable
        # recompile report (observability.trace.report_all) share one
        # source of truth
        from ..observability.trace import RecompileTracer
        self.tracer = RecompileTracer(name="serving",
                                      registry=self.registry)
        # per-request span timeline (queue -> prefill -> decode
        # dispatches -> finish, with page/eviction instants) — a
        # bounded ring of host timestamps recorded at the step
        # boundaries the engine already owns; export via
        # observability.spans.export_chrome (docs/observability.md)
        from ..observability.spans import SpanRecorder
        self.spans = SpanRecorder(name="serving")
        # continuous host sampling profiler (observability.contprof):
        # armed via PADDLE_TPU_PROFILE / the profile ctor knob. A
        # never-armed engine creates NO profiler object at all — the
        # same dormancy contract prefix caching and spec decode keep,
        # so legacy goldens stay byte-identical. Host-side only:
        # profiling ON leaves compile counts frozen (chaos-asserted).
        self.profiler = None
        if self._profile_enabled:
            from ..observability.contprof import ContinuousProfiler
            self.profiler = ContinuousProfiler(
                hz=self._profile_hz, registry=reg,
                name="engine").start()
        # device-memory ledger (observability.memledger): armed via
        # PADDLE_TPU_MEM_LEDGER / the mem_ledger ctor knob, same
        # dormancy contract as the profiler — a never-armed engine
        # creates NO ledger and registers NO mem_* series. track/
        # release are host dict arithmetic; the ground-truth sweep
        # runs at health() cadence, never the dispatch hot path.
        self.ledger = None
        # per-page KV bytes (all layers, incl. int8 scale sidecars):
        # the unit the admission hint prices a request in. Host attr
        # walk over pool metadata, computed once.
        self._page_bytes = (_memledger.nbytes_of(
            [a for a, spec in zip(self._pages, specs) if spec.paged])
                            // max(self.num_pages, 1))
        if self._mem_enabled:
            self.ledger = _memledger.MemoryLedger(
                registry=reg, name="engine",
                capacity_bytes=self._mem_capacity_bytes)
            model_tag = type(model).__name__
            self.ledger.track("weights", (self._params, self._buffers),
                              label=f"model={model_tag}")
            self.ledger.track(
                "kv_pages", self._pages,
                label=f"dtype={self.cache_dtype},model={model_tag}")
            self.ledger.add_audit(self._mem_audit)
        self._exporter = None
        self._trace_counts = self.tracer._counts
        # AOT export surface: every compiled serving program's RAW
        # (pre-tracer) body + jit kwargs, recorded by _counting as the
        # program is built. jit.serving_artifact lowers these through
        # jax.export so a respawned replica can boot from serialized
        # StableHLO instead of re-tracing Python (docs/robustness.md
        # "Artifact boot").
        self._aot_programs = {}
        # how THIS engine became serving-ready: "traced" (warmup) or
        # "aot" (artifact load). serving_artifact.warm_boot stamps
        # mode/boot_s/artifact; heartbeats carry it to fleet_top's
        # BOOT column.
        self.boot_info = {"mode": "traced", "boot_s": None,
                          "artifact": None}
        self._decode_fn = self._build_decode_fn()
        self._prefill_fns = {}
        self._tail_prefill_fns = {}
        # warm-boot bookkeeping (warmup()): which prefill buckets and
        # whether the decode program were pre-traced at boot. Tail
        # buckets gate the prefix-cache HIT path: a hit admission only
        # happens when its tail program is already traced, so caching
        # can never introduce a mid-traffic compile
        self._warmed_buckets = set()
        self._warmed_tail_buckets = set()
        self._warmed_decode = False
        # speculative decoding: proposer + folded verify program.
        # Dispatch routing is gated on _warmed_spec (set by warmup()),
        # mirroring the prefix-cache tail-bucket gate: an armed-but-
        # never-warmed engine takes the plain decode path for every
        # dispatch, so speculation can never introduce a mid-traffic
        # compile and a never-warmed engine is byte-identical to a
        # spec-off one
        self._spec = None
        self._spec_verify_fn = None
        self._warmed_spec = False
        if spec_decode:
            from .speculative import make_proposer
            self._spec = make_proposer(self, self.spec_draft)
            self._spec_verify_fn = self._build_spec_verify_fn()
            self._m_spec_proposed = own(reg.counter(
                "serve_spec_proposed_total",
                help="draft tokens dispatched to speculative verify"))
            self._m_spec_accepted = own(reg.counter(
                "serve_spec_accepted_total",
                help="draft tokens the target model confirmed "
                     "(committed bit-identical to plain decode)"))
            self._m_spec_dispatches = own(reg.counter(
                "serve_spec_dispatches_total",
                help="folded verify dispatches (each commits >= 1 "
                     "token per live slot)"))
        # decode-dispatch accounting: batched-decode throughput is THE
        # serving metric (wall time also pays per-request prefill,
        # which is batch-1 by construction); the benchmark's
        # decode_ms_per_step reads these
        self.decode_seconds = 0.0
        self.decode_tokens = 0
        self.decode_dispatches = 0
        # what a model's layers count per dispatch (an expert layer's
        # routing: AUX_COUNTERS), summed per kind of program; stays empty
        # for a model whose layers count nothing
        self.aux_counts = {}
        # program site -> "streamed" / "ragged", set when it is traced
        self.experts_path = {}

    def _add_aux(self, program, aux):
        """Add one dispatch's per-layer counters ([layers, 3] int32, read
        after the dispatch's own host sync) into `aux_counts[program]`."""
        total = np.asarray(aux, np.int64).sum(axis=0)
        self.aux_counts[program] = self.aux_counts.get(program, 0) + total

    def _status_counter(self, status):
        c = self._m_req.get(status)
        if c is None:
            c = self.registry.counter(
                "serve_requests_total",
                help="finished requests by terminal status",
                labels={"status": status})
            self._own_series.append(c)
            self._m_req[status] = c
        return c

    @property
    def status_counts(self):
        """Snapshot view of serve_requests_total{status=...}."""
        return {s: int(c.value) for s, c in self._m_req.items()}

    def _update_gauges(self):
        self._g_free_pages.set(len(self._free_pages))
        usable = max(self.num_pages - 1, 1)
        self._g_occupancy.set(
            round(1.0 - len(self._free_pages) / usable, 6))
        self._g_queue_depth.set(len(self._queue))
        self._g_running.set(
            sum(1 for s in self._slots if s is not None))
        if self.prefix is not None:
            self._g_prefix_occ.set(
                round(self.prefix.owned_page_count / usable, 6))

    def _sync_registry(self):
        """Fold the monotonic retry/watchdog sources into registry
        counters (diffed, so a registry reset restarts them at 0 —
        the uniform-reset semantics health() reports through).

        Locked: health() runs this from the metrics exporter's HTTP
        threads too (serve_metrics), and the _seen_* read-modify-write
        racing the step() thread would double-count a wedge/retry —
        and double-dump the wedge flight record."""
        with self._sync_lock:
            r = self.retry_stats.retries
            if r > self._seen_retries:
                self._m_retries.inc(r - self._seen_retries)
            self._seen_retries = r
            if self._watchdog is not None:
                w = self._watchdog.wedge_count
                if w > self._seen_wedges:
                    self._m_wedges.inc(w - self._seen_wedges)
                    # a wedged dispatch is a flight-recorder trigger:
                    # the recent dispatch/request ring + which op
                    # wedged
                    from ..observability import flightrec
                    flightrec.dump("wedge", extra={
                        "op": self._watchdog.last_wedge_op,
                        "wedge_count": int(w), "round": self._rounds})
                self._seen_wedges = w
            self._update_gauges()

    def reset_counters(self):
        """Zero EVERY serve counter uniformly: decode throughput, the
        per-status request totals, latency histograms, and the retry/
        watchdog counts (which previously survived a reset and made
        health() diverge from the window being measured)."""
        self.decode_seconds = 0.0
        self.decode_tokens = 0
        self.decode_dispatches = 0
        self._sync_registry()     # consume pending source increments
        for m in self._own_series:
            m.reset()
        self._update_gauges()     # gauges reflect live state, not 0

    # -- public API ---------------------------------------------------------

    def submit(self, prompt, max_new_tokens=16, eos_token_id=None,
               deadline_ms=None, priority=0, trace=None, tenant=None):
        """Queue one request; returns its id. Admitted at the next
        step() boundary (slot + pages permitting).

        deadline_ms: wall budget from NOW for the whole request
            (queueing + prefill + decode). Expiry is detected at host
            step boundaries; the request finishes with
            status='expired' and whatever tokens it produced.
        priority: larger = more important (evict admission policy).
        trace: distributed-trace context (observability.dtrace wire
            form, minted by a FleetRouter and propagated through the
            replica transport). The engine then records this
            request's queue/prefill/decode legs as child spans in the
            process-global trace store — pure host-side dict appends
            at the step boundaries the engine already owns, so the
            zero-recompile contract is untouched. None (the default)
            records nothing.
        tenant: usage-attribution label (observability.tenancy,
            threaded from ``FleetRouter.submit`` through the replica
            transports). Tagged requests accumulate tokens in/out,
            queue-wait and KV-page-seconds into ``engine.tenants``
            and stamp them on their result; None (the default) skips
            accounting entirely."""
        if self._state != "serving":
            if self._state == "closed":
                raise RuntimeError("ServingEngine is closed")
            raise RuntimeError(
                "ServingEngine is draining (not admitting); resume() "
                "re-opens admission")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if not len(prompt):
            raise ValueError("empty prompt")
        need = len(prompt) + int(max_new_tokens)
        if need > self.max_seq_len:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new_tokens"
                f"({max_new_tokens}) = {need} exceeds max_seq_len="
                f"{self.max_seq_len}")
        need_pages = -(-need // self.page_size)
        if need_pages > self.num_pages - 1:
            # would otherwise sit in the admission queue FOREVER:
            # back-pressure can free at most the whole pool (page 0 is
            # reserved), so this request can never be admitted
            raise ValueError(
                f"request needs {need_pages} KV pages (prompt "
                f"{len(prompt)} + {int(max_new_tokens)} new tokens @ "
                f"page_size={self.page_size}) but the pool only has "
                f"{self.num_pages - 1} usable — it would wedge the "
                "admission queue. Raise num_pages or shorten the "
                "request.")
        if self.ledger is not None and self.mem_admission == "hard":
            # hard admission (PADDLE_TPU_MEM_ADMISSION=hard): reject
            # a request whose full KV footprint would not fit the
            # forecast headroom with a typed error NOW, instead of
            # OOMing mid-decode. Conservative by design — judged
            # against current headroom, not what draining requests
            # may free (a kill switch, not a scheduler).
            need_bytes = need_pages * self._page_bytes
            if self.ledger.admission_check(need_bytes) is False:
                from ..observability.memledger import \
                    MemoryAdmissionError
                raise MemoryAdmissionError(
                    need_bytes, self.ledger.headroom_bytes(),
                    self.ledger.capacity_bytes)
        deadline = None if deadline_ms is None \
            else time.monotonic() + float(deadline_ms) / 1e3
        rid = self._next_rid
        self._next_rid += 1
        req = ServeRequest(rid, prompt, max_new_tokens,
                           eos_token_id, deadline=deadline,
                           priority=priority, trace=trace,
                           tenant=tenant)
        if self.prefix is not None:
            # rolling page-boundary fingerprints, once per request —
            # a failover continuation re-submitted here re-fingerprints
            # naturally (hit = cheap re-admission, miss = normal
            # continuation prefill)
            req.prefix_fps = prefix_fingerprints(prompt, self.page_size)
        self._queue.append(req)
        return rid

    @staticmethod
    def _dtrace_add(ctx, name, t0, t1=None, args=None, outcome=None):
        """Record one distributed-trace child span (no-op for
        untraced requests; never raises — tracing must not kill a
        step)."""
        if ctx is None:
            return
        try:
            from ..observability import dtrace
            dtrace.get_store().add_span(ctx, name, t0, t1, args=args,
                                        outcome=outcome)
        except Exception:  # noqa: BLE001 — accounting only
            pass

    def cancel(self, rid):
        """Request cancellation of a queued or running request. Takes
        effect at the next step() boundary (never mid-dispatch — a
        compiled decode program is never interrupted): the request
        finishes with status='cancelled' and its partial tokens.
        Returns True when `rid` is still queued or running, False when
        unknown or already finished."""
        if any(r.rid == rid for r in self._queue) or any(
                s is not None and s.req.rid == rid for s in self._slots):
            self._cancel_pending.add(rid)
            return True
        return False

    def step(self):
        """One scheduling round: apply cancellations and deadline
        expiry, evict finished slots, admit queued requests (per the
        admission policy), run ONE batched decode dispatch
        (steps_per_dispatch tokens x all live slots). Returns the list
        of requests finished this round as dicts
        {id, prompt, tokens, status} (tokens = generated only).

        An unhandled exception here is a flight-recorder trigger: the
        ring of recent dispatch/request records dumps to
        flight_serve_exception.json before the error propagates."""
        if self._state == "closed":
            raise RuntimeError("ServingEngine is closed")
        try:
            return self._step_impl()
        except Exception as e:
            from ..observability import flightrec
            flightrec.dump("serve_exception",
                           extra={"error": f"{type(e).__name__}: {e}",
                                  "round": self._rounds})
            raise

    def _step_impl(self):
        self._rounds += 1
        if self._state == "draining":
            # draining: nothing new admits, and anything still QUEUED
            # resolves as cancelled NOW (a router re-places it on a
            # healthy replica); in-flight slots keep decoding below
            # until they finish token-exactly
            while self._queue:
                self._finish_request(self._queue.popleft(), "cancelled")
        self._apply_cancels()
        self._expire_deadlines()
        self._evict()
        if self._state == "serving":
            self._admit()
        if self._active.any() and not (self._done | ~self._active).all():
            if self._spec is not None and self._warmed_spec:
                self._dispatch_spec()
            else:
                self._dispatch_decode()
        self._evict()
        self._sync_registry()
        out, self._finished = self._finished, []
        return out

    def run_to_completion(self, max_rounds=10_000):
        """Drive step() until queue and slots drain; returns all
        finished requests in completion order."""
        results = []
        rounds = 0
        while self._queue or any(s is not None for s in self._slots):
            results.extend(self.step())
            rounds += 1
            if rounds > max_rounds:
                raise RuntimeError("serving loop did not drain "
                                   f"within {max_rounds} rounds")
        return results

    def generate(self, prompts, max_new_tokens=16, eos_token_id=None):
        """Convenience batch API: submit all, drain, return generated
        token lists in submission order."""
        ids = [self.submit(p, max_new_tokens, eos_token_id)
               for p in prompts]
        res = {r["id"]: r for r in self.run_to_completion()}
        return [res[i]["tokens"] for i in ids]

    def compile_counts(self):
        """Trace counts per compiled program (name -> count). Steady
        state == this dict stops changing
        (tests/test_serving.py)."""
        return dict(self._trace_counts)

    @property
    def free_page_count(self):
        return len(self._free_pages)

    @property
    def state(self):
        """Lifecycle state: 'serving' | 'draining' | 'closed'. Also in
        health()/'/healthz' so an external LB can tell a busy replica
        from one that is going away."""
        return self._state

    @property
    def idle(self):
        """True when nothing is queued and no slot is occupied — the
        'drain complete' condition a replica worker polls."""
        return not self._queue and all(s is None for s in self._slots)

    def drain(self):
        """Stop admitting (graceful shutdown / preemption notice):
        queued requests resolve as status='cancelled' at the next
        step() boundary so a router can re-place them, while in-flight
        requests keep decoding to their normal finish, token-exactly.
        Idempotent; submit() during the drain raises. resume()
        re-opens admission (rejoin), close() retires the engine."""
        if self._state == "closed":
            raise RuntimeError("ServingEngine is closed")
        self._state = "draining"

    def resume(self):
        """Re-open admission after drain() (fleet rejoin). The engine
        keeps its compiled programs, so a drain/rejoin cycle costs
        zero recompiles."""
        if self._state == "closed":
            raise RuntimeError("ServingEngine is closed")
        self._state = "serving"

    def drain_to_completion(self, max_rounds=10_000):
        """drain(), then step() until every slot finishes; returns the
        finished-request dicts (in-flight complete token-exactly,
        queued come back cancelled). Bounded by max_rounds — the drain
        path never wedges."""
        self.drain()
        results = []
        rounds = 0
        while not self.idle:
            results.extend(self.step())
            rounds += 1
            if rounds > max_rounds:
                raise RuntimeError("drain did not complete within "
                                   f"{max_rounds} rounds")
        return results

    def _bucket_for(self, n):
        """The pow2, whole-page prefill bucket a prompt of length `n`
        lands in (the _admit_one formula, shared with warmup)."""
        ps = self.page_size
        bucket = min(max(_next_pow2(int(n)), ps), self.max_seq_len)
        return min(-(-bucket // ps) * ps, self.max_seq_len)

    def warmup(self, buckets=(), decode=True):
        """Pre-trace the serving programs BEFORE traffic: one prefill
        program per bucket plus the batched decode scan, driven with
        synthetic inputs whose shapes/dtypes are exactly what real
        admission passes — so the first real wave of those buckets
        compiles NOTHING. The traces count once, here, in the boot
        compile budget (`compile_counts()` shows them like any other
        trace); this is also the fix for the first-request TTFT cliff
        in single-replica serving (the first admission used to pay the
        prefill compile inside a request's latency), and the warm-boot
        contract a respawned fleet replica re-enters rotation under
        (serving-ready, frozen counts — docs/robustness.md "Process
        supervision").

        buckets: prompt lengths OR bucket sizes — each is normalized
            through the same pow2/whole-page formula admission uses,
            then traced once (already-warm buckets are skipped).
        decode: also trace the batched decode program (default True).

        Writes land exclusively in the reserved trash page (the
        synthetic page tables point every page there) and the sampling
        RNG state is NOT advanced, so a warmed engine generates
        token-for-token what an unwarmed one would. Requires an idle
        engine (warmup is a boot step, not a mid-traffic one).
        Returns the sorted list of buckets warmed by THIS call."""
        if self._state == "closed":
            raise RuntimeError("ServingEngine is closed")
        if not self.idle:
            raise RuntimeError("warmup() needs an idle engine — it is "
                               "a boot step, not a mid-traffic one")
        warmed = []
        norm = sorted({self._bucket_for(n) for n in buckets})
        for n in norm:
            if n in self._warmed_buckets:
                continue
            # the pool is donated to the program and the returned
            # buffers adopted (contents untouched outside the trash
            # page); the RNG rides along as a synthetic key only —
            # host state is NOT advanced (see docstring)
            self._prime(f"prefill_{n}", self._prefill_fn(n))
            self._warmed_buckets.add(n)
            warmed.append(n)
        if self.prefix is not None and norm:
            # tail-prefill ladder: a prefix HIT on a prompt of bucket n
            # runs a tail of 1..n tokens, whose bucket is one of the
            # pow2/whole-page values below n — trace them all now so a
            # hit never compiles mid-traffic (the hit path is gated on
            # exactly this set)
            tails = set()
            for n in norm:
                tails.update(self._bucket_for(t)
                             for t in range(1, n + 1))
            for t in sorted(tails):
                if t in self._warmed_tail_buckets:
                    continue
                self._prime(f"tail_prefill_{t}",
                            self._tail_prefill_fn(t))
                self._warmed_tail_buckets.add(t)
            self._warm_eager_ladder(norm)
        if decode and not self._warmed_decode:
            self._prime("decode", self._decode_fn)
            self._warmed_decode = True
        if self._spec is not None and decode:
            # speculative programs: the folded verify (all-trash table,
            # inactive slots — writes land in the trash page) plus the
            # proposer's own programs (draft prefill per warmed bucket
            # + the propose scan for a model draft; nothing for ngram).
            # _warmed_spec is the arming gate: until it flips, every
            # dispatch takes the plain decode path
            if not self._warmed_spec:
                self._prime("spec_verify", self._spec_verify_fn)
                self._warmed_spec = True
            self._spec.warmup(self, norm)
        from ..observability import flightrec
        flightrec.note("serve_warmup", buckets=warmed,
                       tail_buckets=sorted(self._warmed_tail_buckets),
                       decode=self._warmed_decode,
                       spec=self._warmed_spec)
        return warmed

    def _warm_args(self, name):
        """Synthetic boot-time arguments for serving program `name` —
        shapes and dtypes exactly what real dispatch passes, page
        tables pointing every write at the reserved trash page, the
        RNG riding along as a value only (host state not advanced).
        ONE builder shared by warmup() (tracing boot) and
        jit.serving_artifact (AOT export signatures + load-time
        priming), so the two boot paths can never drift apart."""
        if name == "decode":
            b = self.max_slots
            sched = (np.full((b, self.max_pages_per_seq), TRASH_PAGE,
                             np.int32),
                     np.zeros((b,), np.int32),      # seq_lens
                     np.zeros((b,), np.int32),      # last_tokens
                     np.zeros((b,), bool),          # active: none
                     np.ones((b,), bool),           # done: all
                     np.zeros((b,), np.int32),      # emitted
                     np.ones((b,), np.int32),       # max_new
                     np.full((b,), -1, np.int32),   # eos
                     np.zeros((b, 2), np.uint32))   # key_base
            return (self._params, self._buffers, self._pages,
                    *(jnp.asarray(a) for a in sched))
        if name == "spec_verify":
            b = self.max_slots
            return (self._params, self._buffers, self._pages,
                    jnp.asarray(np.full((b, self.max_pages_per_seq),
                                        TRASH_PAGE, np.int32)),
                    jnp.asarray(np.zeros((b,), np.int32)),
                    jnp.asarray(np.zeros((b,), np.int32)),
                    jnp.asarray(np.zeros((b, self.spec_k), np.int32)),
                    jnp.asarray(np.zeros((b, 2), np.uint32)),
                    jnp.asarray(np.zeros((b,), np.int32)))
        if name.startswith("tail_prefill_"):
            t = int(name.rsplit("_", 1)[1])
            pre = self.max_seq_len
            zero = jnp.zeros((1, pre, self.kv_heads, self.head_dim),
                             jnp.float32)
            ids = np.full((1, t), self.pad_token_id, np.int32)
            pages_vec = np.full((t // self.page_size,), TRASH_PAGE,
                                np.int32)
            return (self._params, self._buffers, self._pages,
                    [zero] * self.num_layers, [zero] * self.num_layers,
                    jnp.asarray(ids), jnp.int32(0), jnp.int32(1),
                    jnp.asarray(pages_vec), self._rng)
        if name.startswith("prefill_"):
            n = int(name.rsplit("_", 1)[1])
            ids = np.full((1, n), self.pad_token_id, np.int32)
            pages_vec = np.full((n // self.page_size,), TRASH_PAGE,
                                np.int32)
            # the slot past the last: a state layer's write is dropped
            return (self._params, self._buffers, self._pages,
                    jnp.asarray(ids), jnp.int32(1),
                    jnp.asarray(pages_vec), self._rng,
                    *self._slot_arg(self.max_slots))
        raise ValueError(f"unknown serving program {name!r}")

    def _prime(self, name, fn):
        """Run `fn` once with _warm_args(name) and adopt the returned
        page pool (the pool is donated in; every serving program
        returns its new pages at result index 1). Writes land only in
        the trash page and the RNG is not advanced, so a primed engine
        generates token-for-token what an unprimed one would."""
        out = fn(*self._warm_args(name))
        self._pages = out[1]

    def _warm_eager_ladder(self, norm):
        """Pre-run the prefix-REGISTRATION path's eager ops: jnp.pad
        at full prefill (bucket -> max_seq_len sidecar) and the
        extension splice at a hit are eager XLA ops whose executables
        key on shapes only (splice starts are dynamic operands) — run
        every shape combo the warmed buckets can produce so a
        registering wave never pays a backend compile mid-traffic."""
        pre = self.max_seq_len
        zero = jnp.zeros((1, pre, self.kv_heads, self.head_dim),
                         jnp.float32)
        for n in norm:
            if n < pre:
                jnp.pad(zero[:, :n],
                        ((0, 0), (0, pre - n), (0, 0), (0, 0)))
        for t in sorted(self._warmed_tail_buckets):
            src = zero[:, :t]
            for w in sorted({min(t, pre - jj * self.page_size)
                             for jj in range(1, pre //
                                             self.page_size)}):
                jax.lax.dynamic_update_slice(
                    zero, src if w == t else src[:, :w],
                    (0, 0, 0, 0))

    def _install_aot_program(self, name, call):
        """Install a pre-compiled (jax.export-restored) serving
        program under site `name`, replacing the build-on-first-use
        traced one. The caller (jit.serving_artifact.load_artifact)
        owns priming it and flipping the matching _warmed_* flag —
        installation alone must not claim warmth."""
        if name == "decode":
            self._decode_fn = call
        elif name == "spec_verify":
            if self._spec is None:
                raise ValueError(
                    "spec_verify program on a spec-off engine")
            self._spec_verify_fn = call
        elif name.startswith("tail_prefill_"):
            self._tail_prefill_fns[int(name.rsplit("_", 1)[1])] = call
        elif name.startswith("prefill_"):
            self._prefill_fns[int(name.rsplit("_", 1)[1])] = call
        else:
            raise ValueError(f"unknown serving program {name!r}")

    @property
    def warmed(self):
        """True once the batched decode program has been traced — by
        warmup() or by real traffic (a rejoined engine that already
        served is warm: its compiled programs carried over). The
        supervisor's boot gate reads this off the heartbeat;
        per-bucket detail in health()."""
        return self._warmed_decode \
            or bool(self._trace_counts.get("decode"))

    def export_inflight(self):
        """Host-side snapshot of every unfinished request: in-flight
        slots with their partial tokens (queued=False) and
        still-queued requests (queued=True, no tokens). The fleet
        failover path reads this off a dead/wedged replica to
        continuation-resubmit elsewhere with the completed prefix
        deduped; in a subprocess deployment the same facts arrive over
        the streaming token channel. Pure bookkeeping — no device
        sync, no compilation."""
        out = []
        for slot in self._slots:
            if slot is None:
                continue
            r = slot.req
            out.append({"rid": r.rid, "prompt": r.prompt.tolist(),
                        "tokens": list(slot.out_tokens),
                        "max_new_tokens": r.max_new_tokens,
                        "eos_token_id": r.eos_token_id,
                        "priority": r.priority, "queued": False})
        for r in self._queue:
            out.append({"rid": r.rid, "prompt": r.prompt.tolist(),
                        "tokens": [],
                        "max_new_tokens": r.max_new_tokens,
                        "eos_token_id": r.eos_token_id,
                        "priority": r.priority, "queued": True})
        return out

    def serve_metrics(self, port=0, host="127.0.0.1"):
        """Attach a live HTTP exporter to THIS engine: /metrics is the
        engine's registry, /healthz is health(), /report the
        recompile + cost reports. Returns the exporter (read .port
        when port=0); close() (and engine close()) shuts it down. A
        second call replaces the first."""
        from ..observability.exporter import MetricsExporter
        if self._exporter is not None:
            self._exporter.close()
        profile_fn = None
        if self.profiler is not None:
            profile_fn = lambda window: \
                self.profiler.report(window_s=window)  # noqa: E731

        def memory_fn(window):
            # /memory is always routable on an engine exporter: an
            # unarmed ledger answers a stub (HTTP 200) telling the
            # scraper how to arm it, instead of a route-shaped 404
            if self.ledger is not None:
                return self.ledger.report(window_s=window)
            return {"armed": False,
                    "note": "no ledger armed "
                            "(PADDLE_TPU_MEM_LEDGER=1 or "
                            "mem_ledger=True)"}
        self._exporter = MetricsExporter(
            registry=self.registry, port=port, host=host,
            health_fn=self.health,
            # span-ring overflow is never silent: the /report doc
            # carries each recorder's eviction count
            report_fn=lambda: {"spans_evicted": {
                self.spans.name: int(self.spans.evicted)}},
            tenants_fn=self.tenants.report,
            profile_fn=profile_fn,
            memory_fn=memory_fn)
        return self._exporter

    def close(self):
        """Retire the engine: every queued request resolves as
        status='cancelled', every running one finishes with its
        partial tokens as 'cancelled', ALL pages return to the free
        list, then host-side resources are released (the watchdog's
        polling thread, the metrics exporter's port + thread, the
        tracer's slot in the process-wide report set). Idempotent, and
        composes with the drain path: drain_to_completion() then
        close() is the graceful shutdown; a bare close() is the
        impatient one — neither wedges. Returns the finished-request
        dicts resolved by the close (cancelled work keeps its partial
        tokens) plus any earlier results not yet collected — step()
        raises after close, so this is the last chance to read them.
        After close(), submit()/step() raise
        RuntimeError('ServingEngine is closed'). Compiled programs and
        the page pool are plain GC'd objects."""
        if self._state == "closed":
            return []
        while self._queue:
            self._finish_request(self._queue.popleft(), "cancelled")
        for b in range(self.max_slots):
            if self._slots[b] is not None:
                # a done-but-unswept slot keeps its natural status;
                # live ones are cancelled with their partial tokens
                self._finish_slot(
                    b, None if self._done[b] else "cancelled")
        if self.prefix is not None:
            # every slot is gone, so nothing is pinned: a full evict
            # returns the index-owned pages and keeps the close()
            # contract (ALL pages back on the free list)
            self._free_pages.extend(self.prefix.evict(self.num_pages))
        self._state = "closed"
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None
        if self._exporter is not None:
            self._exporter.close()
            self._exporter = None
        if self.profiler is not None:
            self.profiler.stop()
        if self.ledger is not None:
            self.ledger.close()
        self.tracer.close()
        out, self._finished = self._finished, []
        return out

    def __del__(self):
        wd = getattr(self, "_watchdog", None)
        if wd is not None:
            # signal only — joining a thread from a finalizer can
            # deadlock interpreter shutdown
            wd._stop.set()
        ex = getattr(self, "_exporter", None)
        if ex is not None:
            try:
                ex.close()
            except Exception:  # noqa: BLE001 — finalizer safety
                pass
        pr = getattr(self, "profiler", None)
        if pr is not None:
            # signal only (the _watchdog convention): joining the
            # sampler thread from a finalizer can deadlock shutdown
            pr._stop.set()
        tr = getattr(self, "tracer", None)
        if tr is not None:
            # an engine retired without close() must not pin a live
            # tracer in the process-wide report set forever
            tr.close()

    def health(self):
        """One host-side snapshot of engine liveness and degradation
        state — the thing a load balancer or operator pages on. Pure
        bookkeeping reads: no device sync, no compilation. Counter
        fields are views of the registry's serve_* series, so this and
        metrics.json can never disagree and reset_counters() resets
        both at once."""
        self._sync_registry()
        running = sum(1 for s in self._slots if s is not None)
        now = time.monotonic()
        h = {"state": self._state,
             "running": running,
             "queued": len(self._queue),
             "oldest_queued_s": round(
                 max((now - r.submitted_at for r in self._queue),
                     default=0.0), 6),
             "free_pages": len(self._free_pages),
             "total_pages": self.num_pages - 1,
             "page_occupancy": self._g_occupancy.value,
             "rounds": self._rounds,
             "decode_dispatches": self.decode_dispatches,
             "decode_tokens": self.decode_tokens,
             "last_dispatch_s": round(self.last_dispatch_s, 6),
             "results_pending": len(self._finished),
             "cancels_pending": len(self._cancel_pending),
             "admission_policy": self.admission_policy,
             "decode_attention": self.decode_attention,
             # what the engine holds, by kind of layer cache
             "cache_layers": dict(self.cache_layers),
             "dispatch_retries": int(self._m_retries.value),
             "deadline_misses": int(self._m_deadline.value),
             "evictions": int(self._m_evictions.value),
             "status_counts": dict(self.status_counts),
             "warmed": self.warmed,
             "warmed_buckets": sorted(self._warmed_buckets),
             # how this engine became serving-ready: traced warmup or
             # an AOT artifact load (fleet_top's BOOT column)
             "boot": dict(self.boot_info),
             "tenants_tracked": self.tenants.tracked,
             # the decode-determinism fingerprint: replayed traffic is
             # token-exact only when these (and the weights) match —
             # the traffic-capture plane archives them per replica
             "sampling": {"temperature": self.temperature,
                          "top_k": self.top_k,
                          "seed": self.sampling_seed},
             "compile_counts": self.compile_counts()}
        if self._state_layers:
            h["conv_state_prefill_writes"] = self.conv_state_prefill_writes
        if self.aux_counts:
            def named(v):
                return dict(zip(AUX_COUNTERS, (int(x) for x in v)))
            h["moe"] = dict(named(sum(self.aux_counts.values())),
                            by_program={k: named(v) for k, v in
                                        self.aux_counts.items()},
                            experts_path=dict(self.experts_path))
        if self.prefix is not None:
            st = self.prefix.stats()
            st["occupancy"] = self._g_prefix_occ.value
            st["min_pages"] = self.prefix.min_pages
            st["page_size"] = self.page_size
            st["top"] = [{"fp": f, "pages": p, "hits": n}
                         for f, p, n in self.prefix.top_fingerprints()]
            # the full boundary inventory: the fleet router harvests
            # this off heartbeats for prefix-affinity placement
            st["fingerprints"] = sorted(self.prefix.fingerprint_set())
            h["prefix_cache"] = st
        if self._spec is not None:
            # the fleet router delta-folds proposed/accepted/dispatches
            # off heartbeats into fleet_spec_* (acceptance canary)
            prop = int(self._m_spec_proposed.value)
            acc = int(self._m_spec_accepted.value)
            h["spec"] = {"k": self.spec_k,
                         "draft": self._spec.kind,
                         "armed": self._warmed_spec,
                         "proposed": prop,
                         "accepted": acc,
                         "dispatches":
                             int(self._m_spec_dispatches.value),
                         "acceptance_rate":
                             round(acc / prop, 6) if prop else None}
        if self.profiler is not None:
            # bounded per-phase hotspot digest riding the heartbeat:
            # the fleet router folds samples/dropped deltas into
            # fleet_profile_* and rolls the tables up in health()
            h["profile"] = self.profiler.digest()
        if self.ledger is not None:
            # typed segment totals + headroom forecast riding the
            # heartbeat: the fleet router delta-folds the stats into
            # fleet_mem_* and rolls MEM%/HEADROOM up for fleet_top.
            # digest() sweeps (rate-limited) — health() cadence is
            # exactly where the ground-truth cross-check belongs.
            h["mem"] = self.ledger.digest()
        if self._watchdog is not None:
            h["watchdog"] = dict(self._watchdog.health(),
                                 wedge_count=int(self._m_wedges.value))
        return h

    # -- sampling (one strategy per engine == per compiled program) ---------

    @jax.named_scope("sample")
    def _sample(self, logits, key):
        logits = logits.astype(jnp.float32)
        # lax.argmax with an int32 index: jnp.argmax would reduce over
        # an s64 iota (framework.py turns x64 on), and 64-bit integer
        # compares are emulated on a TPU
        if self.temperature <= 0.0:
            return jax.lax.argmax(logits, logits.ndim - 1, jnp.int32)
        logits = logits / self.temperature
        if self.top_k:
            vals, cand = jax.lax.top_k(logits, self.top_k)
            pick = jax.random.categorical(key, vals)
            return jnp.take_along_axis(
                cand, pick[..., None], axis=-1)[..., 0].astype(jnp.int32)
        return jax.random.categorical(key, logits).astype(jnp.int32)

    @jax.named_scope("sample")
    def _sample_rows(self, logits, keys):
        """Batched sampling with ONE key per row: logits [N, V],
        keys [N, 2]. Every row's draw depends only on its own (key,
        logits) — `vmap` of the single-row sampler — so a row sampled
        inside a width-N batch is bit-identical to the same row
        sampled inside a width-M batch. That row independence is what
        makes speculative verify (which folds K+1 positions into the
        batch dim) reproduce the plain decode scan's tokens exactly;
        a single-key `categorical` over the whole batch would draw
        batch-shape-dependent noise and break it."""
        logits = logits.astype(jnp.float32)
        if self.temperature <= 0.0:
            return jax.lax.argmax(logits, logits.ndim - 1, jnp.int32)
        logits = logits / self.temperature
        if self.top_k:
            vals, cand = jax.lax.top_k(logits, self.top_k)
            pick = jax.vmap(jax.random.categorical)(keys, vals)
            return jnp.take_along_axis(
                cand, pick[:, None], axis=-1)[:, 0].astype(jnp.int32)
        return jax.vmap(jax.random.categorical)(keys,
                                                logits).astype(jnp.int32)

    # -- compiled programs --------------------------------------------------

    def _counting(self, name, fn, donate_argnums=()):
        """jit through the RecompileTracer: its per-site counter bumps
        exactly when jax (re)traces, i.e. on every compile — the
        zero-recompile assertion's ground truth — and each trace lands
        in the recompile report with its signature + compile wall time.
        Steady-state host overhead is two dict reads per call."""
        def wrapped(*args):
            from ..autograd import no_grad
            from .moe import recorded_paths
            with no_grad(), recorded_paths() as paths:
                out = fn(*args)
            if paths:
                # runs when the program is traced: which way its expert
                # layers went (moe.experts_path), health()["moe"]
                self.experts_path[name] = "+".join(sorted(set(paths)))
            return out

        kw = {"donate_argnums": donate_argnums} \
            if (self.donate and donate_argnums) else {}
        self._aot_programs[name] = (wrapped, kw)
        return self.tracer.jit(name, wrapped, **kw)

    def _layer_caches(self, pages, page_table, positions, live=None):
        return [spec.view(arrays, page_table, positions,
                          use_flash=self.use_flash, live=live)
                for spec, arrays in zip(self.cache_specs, pages)]

    def _slot_arg(self, slot):
        """The prefill programs' last argument, for a model with state
        layers only: the slot whose row of each state the prompt's is
        written to. Other models' programs take none."""
        return (jnp.int32(slot),) if self._state_layers else ()

    @staticmethod
    def _unwrap_pages(new_caches):
        def arr(x):
            return x._value if isinstance(x, Tensor) else x
        return [tuple(None if a is None else arr(a) for a in c.arrays())
                for c in new_caches]

    @staticmethod
    def _layer_aux(new_caches):
        """[layers with counters, 3] int32 of what the layers counted in
        this forward, or None for a model whose layers count nothing."""
        aux = [a for a in (getattr(c, "aux", None) for c in new_caches)
               if a is not None]
        return jnp.stack(aux) if aux else None

    def _model_token_step(self, params, buffers, tokens, pages,
                          page_table, positions, live=None):
        """One batched single-token forward through the paged cache.
        tokens [B] int32; live [B] bool, the slots whose per-slot state
        (where a layer has one) this step may overwrite; returns
        (last_logits [B, V] f32, new pages, the layers' counters or
        None)."""
        caches = self._layer_caches(pages, page_table, positions, live)
        out = functional_call(
            self.model, params, buffers, Tensor(tokens[:, None]),
            use_cache=False, cache=caches,
            cache_index=Tensor(positions))
        logits_t, new_caches = out
        logits = logits_t._value if isinstance(logits_t, Tensor) \
            else logits_t
        return (logits[:, -1].astype(jnp.float32),
                self._unwrap_pages(new_caches), self._layer_aux(new_caches))

    def _build_decode_fn(self):
        steps = self.steps_per_dispatch
        pad = self.pad_token_id

        def decode(params, buffers, pages, page_table, seq_lens,
                   last_tokens, active, done, emitted, max_new, eos,
                   key_base):
            def step(carry, _):
                (pages, seq_lens, last, done, emitted) = carry
                live = active & ~done
                logits, pages, aux = self._model_token_step(
                    params, buffers, last, pages, page_table, seq_lens,
                    live)
                # token index e = emitted-so-far keys the draw:
                # fold_in(base, e) — the stream is a function of the
                # request and index, never of dispatch scheduling
                keys = jax.vmap(jax.random.fold_in)(key_base, emitted)
                nxt = self._sample_rows(logits, keys)
                nxt = jnp.where(live, nxt, jnp.int32(pad))
                emitted = emitted + live.astype(jnp.int32)
                stop = (emitted >= max_new) | ((eos >= 0) & (nxt == eos))
                done = done | (live & stop)
                seq_lens = seq_lens + live.astype(jnp.int32)
                last = jnp.where(live, nxt, last)
                return (pages, seq_lens, last, done, emitted), (nxt, aux)

            carry = (pages, seq_lens, last_tokens, done, emitted)
            carry, (toks, aux) = jax.lax.scan(step, carry, None,
                                              length=steps)
            pages, seq_lens, last, done, emitted = carry
            out = (toks, pages, seq_lens, last, done, emitted)
            # a model whose layers count (AUX_COUNTERS) returns the
            # dispatch's sums as one more small array
            return out if aux is None else out + (aux.sum(axis=0),)

        # donate the page pool (arg 2): decode updates it in place
        return self._counting("decode", decode, donate_argnums=(2,))

    def _build_spec_verify_fn(self):
        """The speculative-verify program: ONE batched dispatch scores
        all spec_k+1 candidate positions of every slot by FOLDING them
        into the batch dimension — lane (b, j) = row b*(K+1)+j carries
        slot b's candidate token at position seq_lens[b]+j, with the
        slot's page-table row repeated across its lanes. Within each
        layer the paged cache writes every lane's K/V row first (one
        scatter, distinct (page, row) targets because positions are
        consecutive) and then attends with lens = position+1, so lane
        (b, j) sees exactly the context plain decode would have at that
        position. _model_token_step is the SAME function the decode
        scan calls, per-row computations are batch-width invariant, and
        each position samples with fold_in(key_base, emitted+j) — the
        identical key plain decode would use — so the returned tokens
        are bit-identical to non-speculative decode wherever the draft
        context matches (the host commits exactly that prefix + one
        correction, r19-tail-style: rows written past the commit point
        are masked by lens and overwritten by the next dispatch).

        Lanes whose position would exceed max_seq_len have their WHOLE
        table row redirected to the trash page (never a clamp into a
        real page): the table keeps the plain-decode width so attention
        reduction shapes — and therefore bitwise numerics — are
        untouched, and the host never commits such positions (submit()
        bounds prompt+max_new by max_seq_len)."""
        k1 = self.spec_k + 1
        b = self.max_slots

        def verify(params, buffers, pages, page_table, seq_lens,
                   last_tokens, drafts, key_base, emitted):
            toks_f = jnp.concatenate(
                [last_tokens[:, None], drafts], axis=1).reshape(-1)
            offs = jnp.arange(k1, dtype=jnp.int32)
            pos_f = (seq_lens[:, None] + offs[None, :]).reshape(-1)
            pt_f = jnp.repeat(page_table, k1, axis=0)
            pt_f = jnp.where((pos_f >= self.max_seq_len)[:, None],
                             jnp.int32(TRASH_PAGE), pt_f)
            logits, pages, _ = self._model_token_step(
                params, buffers, toks_f, pages, pt_f, pos_f)
            idx_f = (emitted[:, None] + offs[None, :]).reshape(-1)
            keys = jax.vmap(jax.random.fold_in)(
                jnp.repeat(key_base, k1, axis=0), idx_f)
            true = self._sample_rows(logits, keys)
            return true.reshape(b, k1), pages

        return self._counting("spec_verify", verify, donate_argnums=(2,))

    def _prefill_fn(self, bucket):
        fn = self._prefill_fns.get(bucket)
        if fn is not None:
            return fn

        def prefill(params, buffers, pages, ids, true_len, pages_vec,
                    key, *slot):
            s_b = ids.shape[1]
            mask = (jnp.arange(s_b, dtype=jnp.int32)[None, :]
                    < true_len).astype(jnp.int32)
            out = functional_call(self.model, params, buffers,
                                  Tensor(ids), attention_mask=Tensor(mask),
                                  use_cache=True)
            logits_t, caches = out
            logits = logits_t._value if isinstance(logits_t, Tensor) \
                else logits_t

            def arr(x):
                return x._value if isinstance(x, Tensor) else x

            new_pages, dense_kv = [], []
            for spec, arrays, layer in zip(self.cache_specs, pages,
                                           caches):
                rows = tuple(arr(r) for r in spec.prompt_rows(layer))
                new_pages.append(spec.write_prompt(arrays, rows,
                                                   pages_vec, *slot))
                # the dense prompt K/V ride back out so the prefix
                # index can pin host-side f32 copies of shareable
                # pages — device buffers, no extra compute
                dense_kv.append(rows)
            last = jax.lax.dynamic_index_in_dim(
                logits[0], true_len - 1, keepdims=False)
            tok = self._sample(last[None, :], key)[0]
            aux = self._layer_aux(caches)
            out = (tok, new_pages, dense_kv)
            return out if aux is None else out + (aux,)

        fn = self._counting(f"prefill_{bucket}", prefill,
                            donate_argnums=(2,))
        self._prefill_fns[bucket] = fn
        return fn

    def _tail_prefill_fn(self, tb):
        """The prefix-cache HIT program for tail bucket ``tb``: the
        matched prefix arrives as dense host-pinned f32 K/V buffers
        (padded to max_seq_len so the program is shape-stable across
        hits), the tail tokens run the models' static-cache multi-token
        forward at cache_index=cached_len — positions, RoPE and the
        causal mask all line up with what a full prefill computes for
        those rows — and only the tail K/V is written into (private)
        pages. One program per tail bucket, zero recompiles after
        warmup; donation matches the full-prefill contract."""
        fn = self._tail_prefill_fns.get(tb)
        if fn is not None:
            return fn

        def tail_prefill(params, buffers, pages, kpre, vpre, ids,
                         cached_len, true_tail, pages_vec, key):
            def arr(x):
                return x._value if isinstance(x, Tensor) else x

            caches = []
            for kp, vp in zip(kpre, vpre):
                pad = jnp.zeros(kp.shape[:1] + (tb,) + kp.shape[2:],
                                kp.dtype)
                caches.append((Tensor(jnp.concatenate([kp, pad], 1)),
                               Tensor(jnp.concatenate([vp, pad], 1))))
            out = functional_call(self.model, params, buffers,
                                  Tensor(ids), use_cache=False,
                                  cache=caches,
                                  cache_index=Tensor(cached_len))
            logits_t, new_caches = out
            logits = arr(logits_t)
            new_pages, tail_kv = [], []
            z0 = jnp.int32(0)
            for (k, v, ks, vs), layer in zip(pages, new_caches):
                kb, vb = arr(layer[0]), arr(layer[1])
                kt = jax.lax.dynamic_slice(
                    kb, (z0, cached_len, z0, z0),
                    (1, tb) + kb.shape[2:])
                vt = jax.lax.dynamic_slice(
                    vb, (z0, cached_len, z0, z0),
                    (1, tb) + vb.shape[2:])
                new_pages.append(write_prompt_kv(k, v, ks, vs, kt, vt,
                                                 pages_vec))
                tail_kv.append((kt, vt))
            last = jax.lax.dynamic_index_in_dim(
                logits[0], true_tail - 1, keepdims=False)
            tok = self._sample(last[None, :], key)[0]
            return tok, new_pages, tail_kv

        fn = self._counting(f"tail_prefill_{tb}", tail_prefill,
                            donate_argnums=(2,))
        self._tail_prefill_fns[tb] = fn
        return fn

    def _prefix_dense(self, entry, j):
        """A matched entry's padded [1, max_seq_len, Hkv, D] dense
        prefix K/V, ready for the tail program. Zero per-hit work:
        the index keeps the padded DEVICE buffers (built once at
        registration), and rows beyond the matched boundary are
        irrelevant by construction — the tail program overwrites
        [cached, cached+tb) with the tail's own K/V and causally
        masks everything past that, so the same buffers serve every
        nested boundary of the entry."""
        del j  # every boundary reads the same padded buffers
        return ([k for k, _ in entry.kv], [v for _, v in entry.kv])

    # -- host-side scheduling ----------------------------------------------

    def _finish_request(self, req, status, tokens=None, kv_page_s=0.0,
                        prefix_hit_pages=0, prefix_pages=0,
                        spec_proposed=0, spec_accepted=0):
        """Finish a request that never reached (or is leaving) a slot.
        age_s — submit-to-finish latency — rides the result so tail
        latency is measurable per request, not just per dispatch;
        tenant-tagged requests additionally carry their queue-wait and
        KV-page-seconds (what only the engine can see) and fold into
        the per-tenant usage sketch."""
        self._status_counter(status).inc()
        if status == "expired":
            self._m_deadline.inc()
        elif status == "evicted":
            self._m_evictions.inc()
        age = round(time.monotonic() - req.submitted_at, 6)
        qw = req.queue_wait_s
        if qw is None:   # never admitted: the whole age was queue wait
            qw = time.monotonic() - req.submitted_at
        # usage facts ride EVERY result (the router folds untagged
        # traffic under "anon", and its kv/queue numbers must be as
        # real as a tagged tenant's); the tenant key and the
        # engine-side sketch stay tagged-only
        result = {"id": req.rid,
                  "prompt": req.prompt.tolist(),
                  "tokens": list(tokens or []),
                  "status": status,
                  "queue_wait_s": round(qw, 6),
                  "kv_page_s": round(kv_page_s, 6),
                  "prefix_hit_pages": int(prefix_hit_pages),
                  "prefix_pages": int(prefix_pages),
                  "spec_proposed": int(spec_proposed),
                  "spec_accepted": int(spec_accepted),
                  "age_s": age}
        if req.tenant is not None:
            result["tenant"] = req.tenant
            self.tenants.account(req.tenant,
                                 tokens_in=len(req.prompt),
                                 tokens_out=len(tokens or []),
                                 queue_wait_s=qw,
                                 kv_page_s=kv_page_s, requests=1,
                                 prefix_hit_pages=int(prefix_hit_pages),
                                 prefix_pages=int(prefix_pages),
                                 spec_proposed=int(spec_proposed),
                                 spec_accepted=int(spec_accepted))
        self._finished.append(result)
        self._cancel_pending.discard(req.rid)
        if req.trace is not None and req.admitted_pc is None:
            # never admitted (cancelled/expired/shed in the queue):
            # the queue leg is the whole replica-side story
            self._dtrace_add(req.trace, "queue", req.submitted_pc,
                             outcome=status)
        self.spans.instant("finish", tid=f"req{req.rid}", cat="serve",
                           args={"status": status,
                                 "tokens": len(tokens or []),
                                 "age_s": age})
        from ..observability import flightrec
        flightrec.note("serve_finish", rid=req.rid, status=status,
                       tokens=len(tokens or []), age_s=age)

    def _finish_slot(self, b, status=None):
        """Release slot b and emit its result (status defaults to the
        slot's recorded degradation status, 'ok' for a natural
        finish). Pages return to the free list immediately."""
        slot = self._slots[b]
        req = slot.req
        if req.trace is not None and slot.decode_t0 is not None:
            self._dtrace_add(req.trace, "decode", slot.decode_t0,
                             args={"tokens": len(slot.out_tokens)},
                             outcome=status or slot.status)
        # KV-page-seconds: pages held x admission->release wall — the
        # HBM-residency cost this request charged the pool (tenancy)
        kv_page_s = 0.0
        if req.admitted_pc is not None:
            kv_page_s = len(slot.pages) * max(
                time.perf_counter() - req.admitted_pc, 0.0)
        self._finish_request(req, status or slot.status,
                             slot.out_tokens[:req.max_new_tokens],
                             kv_page_s=kv_page_s,
                             prefix_hit_pages=slot.prefix_hit_pages,
                             prefix_pages=slot.prefix_pages,
                             spec_proposed=slot.spec_proposed,
                             spec_accepted=slot.spec_accepted)
        self.spans.instant("release_pages", tid="sched", cat="serve",
                           args={"rid": req.rid, "slot": b,
                                 "pages": len(slot.pages),
                                 "shared": len(slot.shared),
                                 "status": status or slot.status})
        if slot.shared:
            # refcount-aware release: index-owned pages stay resident
            # for the next hit (they free only through LRU eviction at
            # refcount 0); only the private pages return to the pool
            self.prefix.release(slot.shared)
            self._free_pages.extend(p for p in slot.pages
                                    if p not in slot.shared)
        else:
            self._free_pages.extend(slot.pages)
        self._slots[b] = None
        self._active[b] = False
        self._done[b] = True
        self._page_table[b, :] = TRASH_PAGE
        self._seq_lens[b] = 0
        self._emitted[b] = 0
        self._eos[b] = -1
        self._dev_sched = None  # host state diverged from device

    def _evict(self):
        for b in range(self.max_slots):
            if self._slots[b] is not None and self._done[b]:
                self._finish_slot(b)

    def _apply_cancels(self):
        """Host boundary resolution of cancel(): queued requests leave
        the queue; running ones are marked done for the sweep."""
        if not self._cancel_pending:
            return
        kept = collections.deque()
        for req in self._queue:
            if req.rid in self._cancel_pending:
                self._finish_request(req, "cancelled")
            else:
                kept.append(req)
        self._queue = kept
        for b in range(self.max_slots):
            slot = self._slots[b]
            if slot is not None and slot.req.rid in self._cancel_pending:
                self._cancel_pending.discard(slot.req.rid)
                slot.status = "cancelled"
                self._done[b] = True
                self._dev_sched = None

    def _expire_deadlines(self):
        """Deadline expiry, host boundaries only (zero-recompile): a
        queued request past its deadline never admits; a running one
        stops decoding this round and returns its partial tokens."""
        now = time.monotonic()
        kept = collections.deque()
        for req in self._queue:
            if req.deadline is not None and now > req.deadline:
                self._finish_request(req, "expired")
            else:
                kept.append(req)
        self._queue = kept
        for b in range(self.max_slots):
            slot = self._slots[b]
            if slot is None or self._done[b]:
                continue
            dl = slot.req.deadline
            if dl is not None and now > dl:
                slot.status = "expired"
                self._done[b] = True
                self._dev_sched = None

    def _victim_slot(self, priority):
        """Lowest-priority running slot strictly below `priority`
        (ties: latest admission goes first — it has sunk the least
        decode work)."""
        best = None
        key = None
        for b in range(self.max_slots):
            slot = self._slots[b]
            if slot is None or self._done[b]:
                continue
            if slot.req.priority >= priority:
                continue
            k = (slot.req.priority, -slot.admit_seq)
            if key is None or k < key:
                best, key = b, k
        return best

    def _admit(self):
        # injected page exhaustion: the free list READS as empty for
        # this round (pages are not actually lost), driving the
        # admission policy exactly like a real shortage
        exhausted = faults.pull("page_exhaustion", self._rounds) \
            is not None
        while self._queue:
            req = self._queue[0]
            free_slot = next((b for b in range(self.max_slots)
                              if self._slots[b] is None), None)
            need_pages = -(-(len(req.prompt) + req.max_new_tokens)
                           // self.page_size)
            have = 0 if exhausted else len(self._free_pages)
            short_pages = have < need_pages
            if short_pages and not exhausted \
                    and self.prefix is not None:
                # reclaim BEFORE the admission policy bites: idle
                # shared prefixes (refcount 0) are cache, not load —
                # LRU-evict them instead of rejecting/preempting work.
                # Under INJECTED exhaustion the free list must keep
                # reading as empty, so no reclaim then.
                freed = self.prefix.evict(need_pages - have)
                if freed:
                    self._free_pages.extend(freed)
                    self._mem_sync_prefix()
                    self.spans.instant(
                        "prefix_evict", tid="sched", cat="serve",
                        args={"pages": len(freed)})
                    have = len(self._free_pages)
                    short_pages = have < need_pages
            if free_slot is not None and not short_pages:
                if self.ledger is not None:
                    # advisory admission consult before page
                    # allocation: counts checks and would-not-fit
                    # verdicts (engine_mem_admission_*); hard mode
                    # already screened at submit(), so admission
                    # itself never blocks here
                    self.ledger.admission_check(
                        need_pages * self._page_bytes)
                self._queue.popleft()
                self._admit_one(free_slot, req, need_pages)
                continue
            if self.admission_policy == "reject" and short_pages \
                    and free_slot is not None:
                # pages are the scarce resource here; a merely-full
                # slot pool turns over every round and is not worth a
                # rejection
                self._queue.popleft()
                self._finish_request(req, "rejected")
                continue
            if self.admission_policy == "evict" and not exhausted:
                # preemption frees a slot AND its pages, so it covers
                # both shortages; under INJECTED exhaustion freed
                # pages would still read as absent — evicting then
                # would be a death spiral, so fall through to wait
                victim = self._victim_slot(req.priority)
                if victim is None:
                    return  # nobody lower-priority: back-pressure
                self._finish_slot(victim, "evicted")
                continue  # re-check the head against freed capacity
            return  # back-pressure: retry next boundary

    def _prefix_lookup(self, req):
        """(entry, matched_pages) when the HIT path should run, else
        None — and fold the hit/miss accounting. A hit additionally
        requires its tail bucket pre-traced (warmup): caching must
        never introduce a mid-traffic compile, so a cold engine takes
        the full-prefill path unconditionally. An engine that never
        armed ANY tail bucket keeps the cache fully dormant (no
        accounting, no page retention): it could never serve a hit,
        so retained pages would only shrink the pool."""
        if self.prefix is None or not self._warmed_tail_buckets:
            return None
        fps = req.prefix_fps
        if fps is None:  # e.g. cache enabled after submit — recompute
            fps = prefix_fingerprints(req.prompt, self.page_size)
            req.prefix_fps = fps
        self.prefix.total_pages += len(fps)
        m = self.prefix.match(fps)
        if m is not None:
            tail = len(req.prompt) - m[1] * self.page_size
            if self._bucket_for(tail) in self._warmed_tail_buckets:
                self.prefix.hits += 1
                self.prefix.hit_pages += m[1]
                return m
        if len(fps) >= self.prefix.min_pages:
            self.prefix.misses += 1
        return None

    def _prefix_register(self, req, pages, kv_host_fn):
        """Register a prompt's boundary fingerprints after its pages
        were written (miss path: all of them; hit path: the extension
        beyond the matched boundary). kv_host_fn materializes the host
        f32 dense K/V lazily — only paid when something new registers.
        Returns the set of slot pages the index now owns. Dormant
        (never-armed) engines register nothing — see _prefix_lookup."""
        if self.prefix is None or not self._warmed_tail_buckets:
            return frozenset()
        fps = req.prefix_fps or []
        if len(fps) < self.prefix.min_pages or self.prefix.covers(fps):
            return frozenset()
        adopted, freed = self.prefix.insert(fps, pages, kv_host_fn(),
                                            pin=True)
        if freed:
            self._free_pages.extend(freed)
        self._mem_sync_prefix()
        return adopted

    def _mem_sync_prefix(self):
        """Refresh the ledger's prefix_sidecar level from the index's
        own sidecar inventory (the level channel: idempotent absolute
        sets at the seams that mutate it, re-asserted by every sweep's
        audit). No-op when either plane is dormant."""
        if self.ledger is not None and self.prefix is not None:
            self.ledger.set_level("prefix_sidecar",
                                  self.prefix.sidecar_bytes())

    def _mem_audit(self):
        """The ledger's periodic sweep hook: cross-check prefix-index
        refcounts against live page-table references (the release-on-
        failover leak class) and re-sync the sidecar level. Returns
        problem strings; sweep counts them into
        engine_mem_audit_failures_total."""
        if self.prefix is None:
            return []
        live = {}
        for slot in self._slots:
            if slot is None:
                continue
            for p in slot.shared:
                live[p] = live.get(p, 0) + 1
        problems = self.prefix.audit(live_refs=live)
        self._mem_sync_prefix()
        return problems

    def _admit_one(self, b, req, need_pages):
        req.queue_wait_s = time.monotonic() - req.submitted_at
        self._m_queue_wait.observe(req.queue_wait_s)
        # span: the queue-wait leg closes at admission (one lane per
        # request — Perfetto shows queue -> prefill -> finish stacked)
        self.spans.add("queue_wait", req.submitted_pc,
                       tid=f"req{req.rid}", cat="serve",
                       args={"rid": req.rid, "slot": b})
        # ONE host-side split per admission: `sub` seeds this request's
        # whole token stream (prefill samples with it directly; decode/
        # verify fold it with each token's emitted index). The split
        # order — admission order — is the only thing the stream
        # depends on, so replay and failover reproduce it exactly.
        self._rng, sub = jax.random.split(self._rng)
        with self._phase("prefix_admit"):
            hit = self._prefix_lookup(req)
        if hit is not None:
            tok, pages, shared, t_post = self._prefill_hit(
                b, req, need_pages, hit, sub)
        else:
            tok, pages, shared, t_post = self._prefill_full(
                b, req, need_pages, sub)
        self._key_base[b] = np.asarray(sub)
        if self._spec is not None and self._warmed_spec:
            self._spec.on_admit(self, b, req)

        self._admit_seq += 1
        slot = _Slot(req, pages, admit_seq=self._admit_seq)
        slot.shared = frozenset(shared)
        slot.prefix_hit_pages = 0 if hit is None else hit[1]
        slot.prefix_pages = len(req.prefix_fps or [])
        self._slots[b] = slot
        self._slots[b].decode_t0 = t_post
        self._slots[b].out_tokens.append(tok)
        row = np.full((self.max_pages_per_seq,), TRASH_PAGE, np.int32)
        row[:need_pages] = pages
        self._page_table[b] = row
        self._seq_lens[b] = len(req.prompt)
        self._last_tokens[b] = tok
        self._emitted[b] = 1
        self._max_new[b] = req.max_new_tokens
        self._eos[b] = -1 if req.eos_token_id is None \
            else int(req.eos_token_id)
        self._active[b] = True
        self._done[b] = bool(req.max_new_tokens <= 1
                             or (req.eos_token_id is not None
                                 and tok == req.eos_token_id))
        self._dev_sched = None  # host state diverged from device

    def _prefill_full(self, b, req, need_pages, key):
        """The miss path: full bucketed prefill (the pre-prefix-cache
        admission body, unchanged), plus prefix registration of the
        freshly written prompt pages. Returns (first token, pages,
        index-owned pages, prefill-end perf_counter)."""
        ps = self.page_size
        lp = len(req.prompt)
        # pow2 bucket, rounded UP to whole pages (_bucket_for — ONE
        # formula, shared with warmup so a pre-traced bucket is
        # exactly the one admission will ask for): write_prompt_kv
        # reshapes the bucket into page blocks, and a page_size that is
        # a multiple of 8 but not a power of two (e.g. 24) would
        # otherwise leave bucket % ps != 0. Bucket count stays bounded
        # (one per pow2 size), so the no-fresh-trace property holds.
        bucket = self._bucket_for(lp)
        nb = bucket // ps
        pages = [self._free_pages.pop() for _ in range(need_pages)]
        # bucket tail blocks beyond the allocation write to the trash
        # page (write_prompt_kv's contract)
        pages_vec = np.full((nb,), TRASH_PAGE, np.int32)
        pages_vec[:min(need_pages, nb)] = pages[:nb]
        ids = np.full((1, bucket), self.pad_token_id, np.int32)
        ids[0, :lp] = req.prompt

        fn = self._prefill_fn(bucket)
        t_pre = time.perf_counter()
        with self._phase(f"prefill_{bucket}"):
            with self._watch(f"prefill_{bucket}"):
                tok, new_pages, dense_kv, *aux = fn(
                    self._params, self._buffers, self._pages,
                    jnp.asarray(ids), jnp.int32(lp),
                    jnp.asarray(pages_vec), key, *self._slot_arg(b))
            self._pages = new_pages
            tok = int(tok)  # host sync: the first token exists NOW
            if aux:
                self._add_aux("prefill", aux[0])
            self.conv_state_prefill_writes += self._state_layers
        self._m_ttft.observe(time.monotonic() - req.submitted_at)
        # the int(tok) sync above bounds the span at real prefill work
        self.spans.add(f"prefill_{bucket}", t_pre, tid=f"req{req.rid}",
                       cat="serve", args={"rid": req.rid, "slot": b,
                                          "pages": need_pages})
        # distributed-trace legs: the queue-wait leg closed at t_pre,
        # the prefill leg at the sync above (dtrace no-ops untraced)
        req.admitted_pc = t_pre
        t_post = time.perf_counter()
        self._dtrace_add(req.trace, "queue", req.submitted_pc, t_pre,
                         args={"slot": b})
        self._dtrace_add(req.trace, f"prefill_{bucket}", t_pre, t_post,
                         args={"pages": need_pages,
                               "prompt_len": lp})
        def kv_dense():
            # padded [1, max_seq_len, Hkv, D] DEVICE buffers for the
            # index: no host round-trip, and jnp.pad on a fixed shape
            # set compiles once per bucket then replays — admission
            # never stalls on eager transfers
            pre = self.max_seq_len
            out = []
            for k, v in dense_kv:
                pad = pre - k.shape[1]
                if pad > 0:
                    k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                out.append((k, v))
            return out

        with self._phase("prefix_admit"):
            shared = self._prefix_register(req, pages, kv_dense)
        return tok, pages, shared, t_post

    def _prefill_hit(self, b, req, need_pages, hit, key):
        """The prefix-cache HIT path: map the matched entry's shared
        pages into this slot (COW — they are never written again),
        allocate private pages for the tail + decode, and run the
        short tail-prefill program. The admission key seeds the first
        token exactly like a full prefill, so the token stream is the
        OFF path's stream whenever logits agree. Returns like
        _prefill_full."""
        entry, j = hit
        ps = self.page_size
        lp = len(req.prompt)
        cached = j * ps
        tail = lp - cached      # >= 1: boundaries stop before the end
        tb = self._bucket_for(tail)
        nbt = tb // ps
        priv = [self._free_pages.pop()
                for _ in range(need_pages - j)]
        shared_pages = self.prefix.acquire(entry)
        pages = shared_pages + priv
        pages_vec = np.full((nbt,), TRASH_PAGE, np.int32)
        pages_vec[:min(len(priv), nbt)] = priv[:nbt]
        ids = np.full((1, tb), self.pad_token_id, np.int32)
        ids[0, :tail] = req.prompt[cached:]
        kpre, vpre = self._prefix_dense(entry, j)

        fn = self._tail_prefill_fn(tb)
        t_pre = time.perf_counter()
        with self._phase(f"prefill_{tb}"):
            with self._watch(f"tail_prefill_{tb}"):
                tok, new_pages, tail_kv = fn(
                    self._params, self._buffers, self._pages, kpre,
                    vpre, jnp.asarray(ids), jnp.int32(cached),
                    jnp.int32(tail), jnp.asarray(pages_vec), key)
            self._pages = new_pages
            tok = int(tok)  # host sync: the first token exists NOW
        self._m_ttft.observe(time.monotonic() - req.submitted_at)
        self.spans.add(f"tail_prefill_{tb}", t_pre,
                       tid=f"req{req.rid}", cat="serve",
                       args={"rid": req.rid, "slot": b,
                             "pages": need_pages, "cached_pages": j})
        req.admitted_pc = t_pre
        t_post = time.perf_counter()
        self._dtrace_add(req.trace, "queue", req.submitted_pc, t_pre,
                         args={"slot": b})
        self._dtrace_add(req.trace, f"tail_prefill_{tb}", t_pre,
                         t_post, args={"pages": need_pages,
                                       "prompt_len": lp,
                                       "cached_pages": j})
        # COW accounting: the partial-page tail re-materialized
        # privately instead of writing the shared pages
        self.prefix.cow_copies += min(-(-tail // ps), len(priv))
        shared = set(shared_pages)
        jm = len(req.prefix_fps or [])
        if jm > j:
            # extension-on-hit: this prompt proves longer boundaries —
            # splice the entry's prefix K/V with the tail rows just
            # computed and register them (prefix view + tail copy).
            # The splice width is the whole (clipped) tail bucket, not
            # the exact extension: every newly proven boundary sits at
            # <= cached + tail <= cached + width, and rows past the
            # deepest boundary are past-boundary garbage the tail
            # program overwrites/masks on any future hit. Bucketed
            # widths keep the eager-op shape set identical to the
            # ladder warmup() pre-compiled — no mid-traffic compile.
            width = min(tb, self.max_seq_len - cached)

            def kv_dense():
                return [(jax.lax.dynamic_update_slice(
                            ek, kt if width == tb else kt[:, :width],
                            (0, cached, 0, 0)),
                         jax.lax.dynamic_update_slice(
                            ev, vt if width == tb else vt[:, :width],
                            (0, cached, 0, 0)))
                        for (ek, ev), (kt, vt)
                        in zip(entry.kv, tail_kv)]

            with self._phase("prefix_admit"):
                shared |= self._prefix_register(req, pages, kv_dense)
        return tok, pages, shared, t_post

    def _watch(self, op):
        """Watchdog heartbeat around one dispatch (nullcontext when no
        watchdog is armed)."""
        import contextlib
        if self._watchdog is None:
            return contextlib.nullcontext()
        return self._watchdog.watch(op)

    def _phase(self, name):
        """Serving-phase marker for the continuous profiler
        (observability.contprof) — nullcontext when no profiler is
        armed, the _watch idiom. One GIL-atomic dict write per
        boundary; the sampler tags every stack it takes from this
        thread with the innermost open phase."""
        import contextlib
        if self.profiler is None:
            return contextlib.nullcontext()
        from ..observability import contprof
        return contprof.phase(name)

    def _dispatch_decode(self):
        # the phase covers the WHOLE dispatch — device call AND the
        # host-side sync + slot bookkeeping after it (which the
        # watchdog window deliberately excludes)
        with self._phase("decode"):
            self._dispatch_decode_impl()

    def _dispatch_decode_impl(self):
        emitted_before = self._emitted.copy()
        t0 = time.perf_counter()
        if self._dev_sched is None:
            self._dev_sched = tuple(
                jnp.asarray(a) for a in
                (self._page_table, self._seq_lens, self._last_tokens,
                 self._active, self._done, self._emitted,
                 self._max_new, self._eos, self._key_base))
        (pt_d, sl_d, lt_d, ac_d, dn_d, em_d, mn_d, eos_d, kb_d) = \
            self._dev_sched

        def dispatch():
            # injected transients fire BEFORE the execute, so a retry
            # re-submits a page pool that was never donated away
            faults.maybe_raise("dispatch_error", self._rounds)
            return self._decode_fn(
                self._params, self._buffers, self._pages,
                pt_d, sl_d, lt_d, ac_d, dn_d, em_d, mn_d, eos_d, kb_d)

        from ..resilience.retry import retryable_for
        with self._watch("decode"):
            # slow-step seam sits inside the watchdog window: a wedged
            # dispatch and an injected stall look identical to health()
            faults.maybe_sleep("slow_step", self._rounds)
            (toks, pages, seq_lens, last, done,
             emitted, *aux) = call_with_retries(
                dispatch, retries=self.dispatch_retries,
                retryable=retryable_for(self.donate),
                stats=self.retry_stats)
        self._pages = pages
        # decode only advances these four; the rest stay device-valid
        self._dev_sched = (pt_d, seq_lens, last, ac_d, done, emitted,
                           mn_d, eos_d, kb_d)
        toks = np.asarray(toks)                     # [steps, B]
        # np.array (copy): np.asarray of a jax array is a read-only
        # view, and eviction writes these in place
        self._seq_lens = np.array(seq_lens)
        self._last_tokens = np.array(last)
        self._done = np.array(done)
        self._emitted = np.array(emitted)
        # the np.array() conversions above force the device sync, so
        # this timestamp bounds real work, not async dispatch
        self.last_dispatch_s = time.perf_counter() - t0
        n_new = int((self._emitted - emitted_before).sum())
        live = int(sum(1 for s in self._slots if s is not None))
        # all live requests share one batched dispatch — ONE span on
        # the shared decode lane, carrying who rode it
        self.spans.add("decode", t0, t0 + self.last_dispatch_s,
                       tid="decode", cat="serve",
                       args={"round": self._rounds, "tokens": n_new,
                             "live_slots": live})
        from ..observability import flightrec
        flightrec.note("serve_dispatch", round=self._rounds,
                       tokens=n_new, live_slots=live,
                       wall_s=round(self.last_dispatch_s, 6))
        self.decode_seconds += self.last_dispatch_s
        self.decode_tokens += n_new
        self.decode_dispatches += 1
        if aux:
            self._add_aux("decode", aux[0])
        # histograms ride the sync that already happened above — one
        # count-weighted observe per dispatch, nothing per token
        self._m_dispatch.observe(self.last_dispatch_s)
        self._m_decode_dispatches.inc()
        if n_new:
            self._m_tok.observe(self.last_dispatch_s / n_new,
                                count=n_new)
            self._m_decode_tokens.inc(n_new)
        for b in range(self.max_slots):
            slot = self._slots[b]
            if slot is None:
                continue
            n = int(self._emitted[b] - emitted_before[b])
            if n:
                # live steps are the first n of the scan (done is
                # monotonic within a dispatch)
                slot.out_tokens.extend(int(t) for t in toks[:n, b])

    def _dispatch_spec(self):
        with self._phase("spec_verify"):
            self._dispatch_spec_impl()

    def _dispatch_spec_impl(self):
        """One speculative decode round: the proposer drafts spec_k
        tokens per slot, the folded verify program scores all spec_k+1
        positions in ONE dispatch, and the host commits the longest
        draft prefix the target's own sampler reproduced plus exactly
        one correction (or the bonus token after a full accept) —
        every live slot advances >= 1 token per dispatch, and every
        committed token is bit-identical to plain decode's.

        The rewind is host-side bookkeeping, the r19 tail contract:
        seq_lens advances only over the committed tokens, so KV rows
        written past the commit point are masked by the attention
        length and overwritten by the next dispatch (whose verify span
        seq_lens..seq_lens+spec_k covers them) — page contents never
        roll back on device."""
        K = self.spec_k
        emitted_before = self._emitted.copy()
        t0 = time.perf_counter()
        # proposer cost — ngram host lookup or the draft model's own
        # dispatch — counts inside the decode window: acceptance gains
        # must beat it for tok/s to move
        drafts = self._spec.propose(self)           # [B, K] np.int32
        sched = tuple(jnp.asarray(a) for a in
                      (self._page_table, self._seq_lens,
                       self._last_tokens, drafts, self._key_base,
                       self._emitted))

        def dispatch():
            faults.maybe_raise("dispatch_error", self._rounds)
            return self._spec_verify_fn(
                self._params, self._buffers, self._pages, *sched)

        from ..resilience.retry import retryable_for
        with self._watch("spec_verify"):
            faults.maybe_sleep("slow_step", self._rounds)
            true, pages = call_with_retries(
                dispatch, retries=self.dispatch_retries,
                retryable=retryable_for(self.donate),
                stats=self.retry_stats)
        self._pages = pages
        true = np.asarray(true)                     # [B, K+1]; syncs
        proposed = accepted = committed = 0
        for b in range(self.max_slots):
            slot = self._slots[b]
            if slot is None or not self._active[b] or self._done[b]:
                continue
            e = int(emitted_before[b])
            mx = int(self._max_new[b])
            eos = int(self._eos[b])
            com = acc = 0
            done = False
            for j in range(K + 1):
                # position j attends rows 0..seq_lens+j-1: the prompt
                # plus drafts 0..j-1 — valid exactly while every
                # earlier draft matched, which is when this loop is
                # still running (j == K is the bonus token, reached
                # only after a full accept)
                t = int(true[b, j])
                slot.out_tokens.append(t)
                com += 1
                hit = j < K and t == int(drafts[b, j])
                acc += int(hit)
                if (e + com >= mx) or (eos >= 0 and t == eos):
                    done = True
                    break
                if j < K and not hit:
                    break               # correction committed; rewind
            self._seq_lens[b] += com
            self._emitted[b] = e + com
            self._last_tokens[b] = slot.out_tokens[-1]
            if done:
                self._done[b] = True
            slot.spec_proposed += K
            slot.spec_accepted += acc
            proposed += K
            accepted += acc
            committed += com
        self._dev_sched = None  # host state diverged from device
        self.last_dispatch_s = time.perf_counter() - t0
        live = int(sum(1 for s in self._slots if s is not None))
        self.spans.add("spec_verify", t0, t0 + self.last_dispatch_s,
                       tid="decode", cat="serve",
                       args={"round": self._rounds, "tokens": committed,
                             "proposed": proposed, "accepted": accepted,
                             "live_slots": live})
        from ..observability import flightrec
        flightrec.note("serve_spec_dispatch", round=self._rounds,
                       tokens=committed, proposed=proposed,
                       accepted=accepted, live_slots=live,
                       wall_s=round(self.last_dispatch_s, 6))
        self.decode_seconds += self.last_dispatch_s
        self.decode_tokens += committed
        self.decode_dispatches += 1
        self._m_dispatch.observe(self.last_dispatch_s)
        self._m_decode_dispatches.inc()
        self._m_spec_dispatches.inc()
        if proposed:
            self._m_spec_proposed.inc(proposed)
        if accepted:
            self._m_spec_accepted.inc(accepted)
        if committed:
            self._m_tok.observe(self.last_dispatch_s / committed,
                                count=committed)
            self._m_decode_tokens.inc(committed)
