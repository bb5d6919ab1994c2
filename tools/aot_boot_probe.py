"""aot_boot_probe — artifact-boot vs traced-boot wall clock (r21).

The campaign's measured rung for the AOT serving-artifact store
(jit/serving_artifact.py): what a scale-out costs with and without the
artifact path. The two boot walls are device numbers, so the probe
needs a TPU and exits non-zero without one (the CPU correctness drill
of the same path is tests/test_serving_artifact.py). The persistent
compile cache is deliberately NOT armed here: a warm cache would make
the traced control look like the artifact boot.

1. **traced control**: build a ServingEngine and pay the full traced
   warmup (prefill buckets + decode scan) — wall-clocked;
2. **export**: lower the warmed program set into a serving artifact
   (``export_artifact`` — staged, checksummed, marker-published);
3. **artifact boot**: build a second engine over the SAME model and
   ``warm_boot`` it off the store — wall-clocked, asserted to have
   taken the AOT path (``boot_info.mode == "aot"``, zero fallbacks);
4. invariants, asserted hard: the artifact-booted engine generates
   TOKEN-EXACT vs the traced control on a seeded prompt wave, serves
   with ZERO post-boot traces (compile counts frozen across the
   wave, zero unexpected retraces), and the artifact boot wall
   strictly beats the traced wall.

Artifacts into $BENCH_TELEMETRY_DIR: ``metrics.json`` (the
validate_stages contract) and the artifact store itself. Last stdout
line is a JSON verdict; exit 0 only when every assertion holds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NEW_TOK = 8
PROMPT_LENS = (5, 12, 17, 9, 12, 5, 17, 12)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--store", default=None,
                    help="artifact store dir (default: "
                         "$BENCH_TELEMETRY_DIR/aot_store)")
    args = ap.parse_args(argv)

    out_dir = os.environ.get("BENCH_TELEMETRY_DIR") or os.path.join(
        REPO, "campaign_out", "telemetry", "aot_boot")
    os.makedirs(out_dir, exist_ok=True)
    store = args.store or os.path.join(out_dir, "aot_store")

    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.jit.serving_artifact import export_artifact, \
        warm_boot
    if jax.default_backend() != "tpu":
        sys.exit("aot_boot_probe times boots on the device and needs a "
                 f"TPU; jax.default_backend() is {jax.default_backend()!r}")
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    from paddle_tpu.nlp.serving import ServingEngine
    from paddle_tpu.observability.trace import report_all

    paddle.seed(0)
    # ONE model instance for both engines: gpt-tiny draws random
    # weights at construction, so a second build would be a different
    # model and "token-exact" would be vacuous-false
    model = GPTForCausalLM(_resolve_config("gpt-tiny"))
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 200, (int(n),)).astype(np.int32)
               for n in PROMPT_LENS]
    buckets = sorted(set(PROMPT_LENS))

    def build():
        return ServingEngine(model, max_slots=2, page_size=16,
                             max_seq_len=64, steps_per_dispatch=4)

    # traced control + export
    a = build()
    t = time.monotonic()
    a.warmup(buckets=buckets, decode=True)
    traced_s = time.monotonic() - t
    export_artifact(a, store)
    refs = a.generate(prompts, max_new_tokens=NEW_TOK)

    # artifact boot
    b = build()
    t = time.monotonic()
    info = warm_boot(b, buckets=buckets, artifact_dir=store)
    aot_s = time.monotonic() - t
    frozen = b.compile_counts()
    toks = b.generate(prompts, max_new_tokens=NEW_TOK)

    fb = [s for s in b.registry.series()
          if s.name == "serve_aot_fallback_total" and s.value]
    checks = {
        "booted_aot": info.get("mode") == "aot" and not fb,
        "token_exact": toks == refs,
        "zero_post_boot_traces": (
            b.compile_counts() == frozen
            and b.tracer.unexpected_retraces() == 0),
        "aot_beats_traced": aot_s < traced_s,
    }

    b.registry.dump(os.path.join(out_dir, "metrics.json"),
                    extra={"recompile_report": report_all(),
                           "stage": "aot_boot"})
    a.close()
    b.close()

    ok = all(bool(v) for v in checks.values())
    print(json.dumps({"ok": ok, "checks": checks,
                      "traced_boot_s": round(traced_s, 3),
                      "aot_boot_s": round(aot_s, 3),
                      "speedup": round(traced_s / max(aot_s, 1e-9), 2),
                      "artifact": info.get("artifact"),
                      "platform": str(
                          __import__("jax").devices()[0].platform),
                      "out_dir": out_dir}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
