"""spec_smoke — the campaign's CPU drill for speculative decoding
(ISSUE 20 / round 20).

Shape (seeded, CPU-only, no chip time spent):

1. build a seeded wave of short random prompts and decode LONG
   (max_new 96): a tiny greedy model collapses into short token
   cycles within a few steps, which is exactly the regime the
   zero-weight prompt-lookup (ngram) proposer feeds on — the CPU
   stand-in for the natural repetitiveness of real decode traffic;
2. run the wave through a spec-ON engine (K=8, ngram draft) and a
   spec-OFF control (same model, same sampling, same warmup), both
   at steps_per_dispatch=1 — the interactive setting speculation
   exists for, where every committed token otherwise costs one
   serial target dispatch;
3. invariants, asserted hard:
   - **token-exact**: every ON stream equals its OFF stream token
     for token (the hard invariant — speculation may change latency,
     never tokens; the verify pass applies the target model's own
     per-position sampler to every lane);
   - **acceptance ≥ floor** (default 0.5): cumulative acceptance
     rate from the ON engine's health()["spec"] — the drill is
     non-vacuous only when the flagship actually confirms drafts;
   - **decode tok/s strictly better ON**: committed decode tokens
     over decode wall-time beats the OFF control on the same wave
     (a high-acceptance dispatch commits up to K+1 tokens against
     ONE folded-batch verify where the control pays one dispatch
     per token);
   - **zero new traces after warmup**: compile counts frozen across
     the wave with speculation ON, zero unexpected retraces — the
     verify scan is pre-traced by warmup();
4. artifacts into $BENCH_TELEMETRY_DIR: ``metrics.json`` (the ON
   engine's registry + recompile report — the validate_stages
   contract), ``spec_decode.json`` (both engines' facts).

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

NEW_TOK = 96                 # long decode: the cycle tail dominates,
#                              so acceptance reflects steady-state
PROMPT_LEN = 12              # short prompts — prefill stays cheap,
#                              the drill times decode
REQUESTS = 8
SPEC_K = 8                   # an accepting dispatch commits up to 9
#                              tokens where the OFF control's
#                              single-step dispatch commits one
MAX_SEQ_LEN = 128            # gpt-tiny's max_position_embeddings
NUM_PAGES = 128


def build_wave(seed=0, vocab=256):
    """Seeded wave of short random prompts. Repetitiveness comes from
    the MODEL, not the prompts: tiny greedy decode converges to short
    cycles the prompt-lookup proposer then predicts near-perfectly."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, (PROMPT_LEN,)).astype(np.int32)
            for _ in range(REQUESTS)]


def run_engine(model, prompts, *, spec):
    """One engine through the wave; returns (tokens, facts)."""
    from paddle_tpu.nlp.serving import ServingEngine
    eng = ServingEngine(model, max_slots=4, page_size=16,
                        max_seq_len=MAX_SEQ_LEN, steps_per_dispatch=1,
                        num_pages=NUM_PAGES,
                        spec_decode=spec, spec_k=SPEC_K,
                        spec_draft="ngram")
    eng.warmup(buckets=sorted({len(p) for p in prompts}), decode=True)
    frozen = eng.compile_counts()
    out = eng.generate(prompts, max_new_tokens=NEW_TOK)
    facts = {
        "spec": eng.health().get("spec"),
        "decode_tokens": eng.decode_tokens,
        "decode_seconds": eng.decode_seconds,
        "decode_tok_s": (eng.decode_tokens / eng.decode_seconds
                         if eng.decode_seconds else None),
        "compile_frozen": eng.compile_counts() == frozen,
        "unexpected_retraces": eng.tracer.unexpected_retraces(),
        "registry": eng.registry,
    }
    eng.close()
    return out, facts


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--acceptance-floor", type=float, default=0.5,
                    help="minimum cumulative draft acceptance rate")
    args = ap.parse_args(argv)

    out_dir = os.environ.get("BENCH_TELEMETRY_DIR") or os.path.join(
        REPO, "campaign_out", "telemetry", "spec_smoke")
    os.makedirs(out_dir, exist_ok=True)

    import paddle_tpu as paddle
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    from paddle_tpu.observability.trace import report_all

    paddle.seed(0)
    model = GPTForCausalLM(_resolve_config("gpt-tiny"))
    model.eval()
    prompts = build_wave(args.seed)

    on_toks, on = run_engine(model, prompts, spec=True)
    off_toks, off = run_engine(model, prompts, spec=False)

    sp = on["spec"] or {}
    acc_rate = sp.get("acceptance_rate")

    checks = {
        "token_exact_on_vs_off": on_toks == off_toks,
        "acceptance_over_floor": (
            acc_rate is not None
            and acc_rate >= args.acceptance_floor),
        "decode_tok_s_on_above_off": (
            on["decode_tok_s"] is not None
            and off["decode_tok_s"] is not None
            and on["decode_tok_s"] > off["decode_tok_s"]),
        "zero_new_traces_after_warmup": (
            on["compile_frozen"]
            and on["unexpected_retraces"] == 0),
        "off_control_spec_disabled": off["spec"] is None,
    }

    on["registry"].dump(os.path.join(out_dir, "metrics.json"),
                        extra={"recompile_report": report_all(),
                               "stage": "spec_smoke"})
    with open(os.path.join(out_dir, "spec_decode.json"), "w") as f:
        json.dump({"on": sp,
                   "acceptance_rate": acc_rate,
                   "decode_tok_s_on": on["decode_tok_s"],
                   "decode_tok_s_off": off["decode_tok_s"],
                   "decode_tokens_on": on["decode_tokens"],
                   "decode_tokens_off": off["decode_tokens"]},
                  f, indent=1)

    ok = all(bool(v) for v in checks.values())
    print(json.dumps({
        "ok": ok, "checks": checks,
        "acceptance_rate": acc_rate,
        "acceptance_floor": args.acceptance_floor,
        "proposed": sp.get("proposed"), "accepted": sp.get("accepted"),
        "dispatches": sp.get("dispatches"),
        "decode_tok_s_on": on["decode_tok_s"],
        "decode_tok_s_off": off["decode_tok_s"],
        "out_dir": out_dir}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
