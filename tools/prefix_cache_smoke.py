"""prefix_cache_smoke — the campaign's CPU drill for copy-on-write
prefix caching (ISSUE 16 / round 19).

Shape (seeded, CPU-only, no chip time spent):

1. build a seeded SHARED-PREFIX wave: three base prompts (the "system
   prompt / few-shot template" stand-ins) each extended with short
   random tails — the traffic pattern the prefix cache exists for;
2. run the wave TWICE through a cache-ON engine and a cache-OFF
   control (same model, same sampling, both warmed on every prefill
   bucket AND the tail-prefill ladder before the clock starts);
3. invariants, asserted hard:
   - **token-exact**: every ON stream equals its OFF stream token for
     token across both waves (the hard invariant — a cache hit may
     change TTFT, never tokens);
   - **page hit rate ≥ floor** (default 0.5): cumulative page-level
     hit rate from the ON engine's health()["prefix_cache"] — wave 1
     hits within-wave (shared bases), wave 2 hits everything;
   - **TTFT p50 strictly better ON**: the ON engine's
     serve_ttft_seconds p50 below the OFF control's on the same wave
     (hits run a short bucketed tail prefill instead of the full
     ladder);
   - **zero new traces after warmup**: compile counts frozen across
     both waves with caching ON, zero unexpected retraces;
   - refcount conservation: after close() every page is back on the
     free list (shared pages included).
4. artifacts into $BENCH_TELEMETRY_DIR: ``metrics.json`` (the ON
   engine's registry + recompile report — the validate_stages
   contract), ``prefix_cache.json`` (both engines' health sections +
   per-wave stats).

Last stdout line is a JSON verdict; exit 0 only when every assertion
holds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

NEW_TOK = 8
BASE_LENS = (80, 110, 95)    # shared-template stand-ins — long
#                              enough that a full prefill (bucket 128)
#                              visibly outweighs a hit's tail prefill
#                              (bucket 16) even on the CPU drill
TAILS = 18                   # requests per wave
MAX_SEQ_LEN = 128            # gpt-tiny's max_position_embeddings
NUM_PAGES = 64               # pool sized so reclaim never starves the
#                              index (the default serving pool is
#                              deliberately tiny)


def build_wave(seed=0, vocab=256):
    """Seeded shared-prefix wave: each request is one of the three
    base prompts plus a short random tail — same generator as the
    engine test suite's, kept tool-local so the smoke stays runnable
    without pytest."""
    import numpy as np
    rng = np.random.default_rng(seed)
    bases = [rng.integers(1, vocab, (n,)).astype(np.int32)
             for n in BASE_LENS]
    return [np.concatenate([bases[i % len(bases)],
                            rng.integers(1, vocab,
                                         (3 + i % 7,)).astype(np.int32)])
            for i in range(TAILS)]


def run_engine(model, prompts, *, prefix_cache, waves=2):
    """One engine through ``waves`` passes of the wave; returns
    (tokens_per_wave, facts)."""
    from paddle_tpu.nlp.serving import ServingEngine
    eng = ServingEngine(model, max_slots=2, page_size=16,
                        max_seq_len=MAX_SEQ_LEN, steps_per_dispatch=4,
                        num_pages=NUM_PAGES,
                        prefix_cache=prefix_cache)
    eng.warmup(buckets=sorted({len(p) for p in prompts}), decode=True)
    frozen = eng.compile_counts()
    out = [eng.generate(prompts, max_new_tokens=NEW_TOK)
           for _ in range(int(waves))]
    h = eng.health()
    ttft = eng.registry.get("serve_ttft_seconds")
    facts = {
        "prefix_cache": h.get("prefix_cache"),
        "ttft_p50_s": ttft.quantile(0.5) if ttft.count else None,
        "ttft_p99_s": ttft.quantile(0.99) if ttft.count else None,
        "compile_frozen": eng.compile_counts() == frozen,
        "unexpected_retraces": eng.tracer.unexpected_retraces(),
        "registry": eng.registry,
    }
    usable = eng.num_pages - 1           # page 0 is the write sink
    eng.close()
    facts["pages_back_after_close"] = len(eng._free_pages) == usable
    return out, facts


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--waves", type=int, default=2)
    ap.add_argument("--hit-floor", type=float, default=0.5,
                    help="minimum cumulative page-level hit rate")
    args = ap.parse_args(argv)

    out_dir = os.environ.get("BENCH_TELEMETRY_DIR") or os.path.join(
        REPO, "campaign_out", "telemetry", "prefix_cache_smoke")
    os.makedirs(out_dir, exist_ok=True)

    import paddle_tpu as paddle
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    from paddle_tpu.observability.trace import report_all

    paddle.seed(0)
    model = GPTForCausalLM(_resolve_config("gpt-tiny"))
    model.eval()
    prompts = build_wave(args.seed)

    on_toks, on = run_engine(model, prompts, prefix_cache=True,
                             waves=args.waves)
    off_toks, off = run_engine(model, prompts, prefix_cache=False,
                               waves=args.waves)

    pc = on["prefix_cache"] or {}
    total = int(pc.get("total_pages") or 0)
    hit_rate = None if not total \
        else pc.get("hit_pages", 0) / total

    checks = {
        "token_exact_on_vs_off": on_toks == off_toks,
        "page_hit_rate_over_floor": (
            hit_rate is not None and hit_rate >= args.hit_floor),
        "ttft_p50_on_below_off": (
            on["ttft_p50_s"] is not None
            and off["ttft_p50_s"] is not None
            and on["ttft_p50_s"] < off["ttft_p50_s"]),
        "zero_new_traces_after_warmup": (
            on["compile_frozen"]
            and on["unexpected_retraces"] == 0),
        "pages_back_after_close": on["pages_back_after_close"],
        "off_control_cache_disabled": off["prefix_cache"] is None,
    }

    on["registry"].dump(os.path.join(out_dir, "metrics.json"),
                        extra={"recompile_report": report_all(),
                               "stage": "prefix_cache_smoke"})
    with open(os.path.join(out_dir, "prefix_cache.json"), "w") as f:
        json.dump({"on": pc,
                   "hit_rate": hit_rate,
                   "ttft_p50_on_s": on["ttft_p50_s"],
                   "ttft_p50_off_s": off["ttft_p50_s"],
                   "ttft_p99_on_s": on["ttft_p99_s"],
                   "ttft_p99_off_s": off["ttft_p99_s"]}, f, indent=1)

    ok = all(bool(v) for v in checks.values())
    print(json.dumps({
        "ok": ok, "checks": checks,
        "page_hit_rate": None if hit_rate is None
        else round(hit_rate, 4),
        "hit_floor": args.hit_floor,
        "hits": pc.get("hits"), "misses": pc.get("misses"),
        "cow_copies": pc.get("cow_copies"),
        "ttft_p50_on_s": on["ttft_p50_s"],
        "ttft_p50_off_s": off["ttft_p50_s"],
        "out_dir": out_dir}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
