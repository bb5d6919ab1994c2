"""Benchmark driver: flagship GPT pretrain throughput (tokens/sec/chip).

Prints ONE JSON line per completed workload, ending with the headline
GPT result:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

The LAST stdout line is always a parseable headline JSON object (with a
`workloads` field carrying every other completed measurement), so a
later hang can never erase earlier numbers.

Architecture: the orchestrator process NEVER imports jax — a chip
belongs to one process at a time, so a parent that had initialised the
backend could not start chip-owning children. Every workload — and a
tiny backend-health probe before the first one — runs in its own
killable subprocess with a hard timeout. A hung compile therefore costs
one workload + a diagnostic, not the whole artifact.

Without --smoke every worker needs a TPU and exits non-zero when
jax.default_backend() is anything else: a device metric is never
computed from a CPU run. --smoke runs toy configurations on the CPU to
exercise the plumbing; its numbers are not device numbers.

Usage:
  python bench.py                 # full TPU suite: probe, gpt, ernie, resnet50
  python bench.py --smoke         # fast CPU smoke (gpt-tiny)
  python bench.py --model resnet50 [--batch N ...]   # single workload
  python bench.py --decode        # opt-in decode bench (never default)
ref parity: tools/test_runner + benchmark/ in PaddlePaddle; the metric
matches BASELINE.json (tokens/sec/chip vs A100 share).
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

BASELINE_TOKENS_PER_SEC_PER_CHIP = 3500.0

# Peak FLOPs for MFU denominators resolve per device kind at runtime
# (env PADDLE_TPU_PEAK_FLOPS override > observability.introspect's
# per-device-kind table — the old hardcoded v5e 197e12 lives there
# now). MFU stays reported against the bf16 peak regardless of the amp
# dtype actually used, so an fp32 run shows honestly low MFU rather
# than flattering itself. Unresolvable (CPU, no override) -> both MFU
# legs are null, never computed against a made-up peak.

BASELINE_RESNET50_IMG_PER_SEC_PER_CHIP = 2900.0  # SURVEY §6: A100 fp16

# ERNIE-3.0-base (118M params): the reference's fleet-class A100 share,
# derived from the GPT-1.3B 3.5k tok/s baseline by the 6N FLOPs/token
# ratio (same training-efficiency assumption): 3.5k * 1.3e9/118e6
BASELINE_ERNIE_TOKENS_PER_SEC_PER_CHIP = 38500.0

# campaign artifacts dir; BENCH_CAMPAIGN_DIR redirects it so tests can
# exercise the null-run diagnostic against fixture summaries (and never
# write partials into the real campaign_out)
CAMPAIGN_OUT = (os.environ.get("BENCH_CAMPAIGN_DIR")
                or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "campaign_out"))

# partials live under campaign_out/ date-stamped like the summaries —
# a probe-timeout diagnostic at the repo root read like a round result
PARTIAL_PATH = os.path.join(CAMPAIGN_OUT,
                            f"bench_partial_{int(time.time())}.json")


def log(*a):
    print(*a, file=sys.stderr, flush=True)
    _Watchdog.pet()


class _Watchdog:
    """In-worker guard: if the backend stops making progress
    mid-workload (a compile that never returns), the worker fails fast
    with rc=3 instead of relying on the orchestrator's hard timeout."""

    _last = time.monotonic()
    # must exceed the longest legitimate silent stretch: a cold compile
    # of the 1.3B remat step can take many minutes with no output
    LIMIT_S = 900

    @classmethod
    def pet(cls):
        cls._last = time.monotonic()

    @classmethod
    def start(cls):
        def watch():
            while True:
                time.sleep(15)
                idle = time.monotonic() - cls._last
                if idle > cls.LIMIT_S:
                    print(
                        f"bench watchdog: no progress for {idle:.0f}s — "
                        "TPU backend unresponsive; aborting worker",
                        file=sys.stderr, flush=True)
                    os._exit(3)

        threading.Thread(target=watch, daemon=True).start()


# --------------------------------------------------------------------------
# worker-side run telemetry (docs/observability.md): every bench worker
# writes telemetry.jsonl + a final registry snapshot metrics.json into
# the stage's telemetry dir (BENCH_TELEMETRY_DIR when the campaign sets
# it per stage, else campaign_out/telemetry/<worker>), next to the
# BENCH json the orchestrator assembles. Worker-side only — these
# helpers import paddle_tpu, which the orchestrator never does.
# --------------------------------------------------------------------------

_TELEMETRY = {}


def _obs_mod(name):
    """paddle_tpu.observability.<name> WITHOUT forcing the full
    paddle_tpu package import on a caller that has not paid it (code
    driving these helpers from outside child mode): the observability
    modules are stdlib-only by contract, so when the package isn't
    already imported the module is loaded straight from its file under
    a private key. Workers — which import paddle_tpu — get the real
    module (same registry/tracer singletons the Engine publishes
    into)."""
    if "paddle_tpu" in sys.modules:
        import importlib
        return importlib.import_module(
            f"paddle_tpu.observability.{name}")
    key = f"_bench_obs_{name}"
    mod = sys.modules.get(key)
    if mod is None:
        import importlib.util
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "paddle_tpu", "observability", f"{name}.py")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return mod


def _telemetry_dir(worker):
    return (os.environ.get("BENCH_TELEMETRY_DIR")
            or os.path.join(CAMPAIGN_OUT, "telemetry", worker))


def _emit(kind, **fields):
    """One structured record into the worker's telemetry.jsonl (logger
    created lazily so the probe stays lean until it has a result)."""
    lg = _TELEMETRY.get("logger")
    if lg is None:
        worker = _TELEMETRY.get("worker")
        if worker is None:
            return None   # orchestrator process: no telemetry
        lg = _TELEMETRY["logger"] = _obs_mod(
            "telemetry").TelemetryLogger(_telemetry_dir(worker))
    return lg.emit(kind, **fields)


def _report(payload):
    """The bench output contract (one JSON line per completed workload)
    + the same record mirrored into telemetry.jsonl."""
    print(json.dumps(payload), flush=True)
    try:
        _emit("workload_result", worker=_TELEMETRY.get("worker"),
              **payload)
    except Exception as e:  # noqa: BLE001 — telemetry never kills a result
        log(f"telemetry emit failed: {e}")


def _hist_ms(h, scale=1e3):
    """Histogram rollup row (ms): the --serve ladder's latency shape,
    not just a mean."""
    if h is None or not h.count:
        return None
    return {"count": h.count,
            "mean": round(h.mean() * scale, 3),
            "p50": round(h.quantile(0.5) * scale, 3),
            "p99": round(h.quantile(0.99) * scale, 3),
            "max": round(h.max * scale, 3)}


def _finalize_worker_telemetry(worker):
    """Write the stage's metrics.json: the process-global registry
    snapshot + the recompile report, MERGED over earlier workers of the
    same stage (bench_full runs four workers into one dir). Runs in a
    finally: a failed workload still leaves its partial run facts."""
    try:
        _metrics = _obs_mod("metrics")
        MetricsRegistry = _metrics.MetricsRegistry
        get_registry = _metrics.get_registry
        report_all = _obs_mod("trace").report_all
        lg = _TELEMETRY.get("logger")
        if lg is None:
            _emit("run_end", worker=worker)   # creates the logger
            lg = _TELEMETRY.get("logger")
            if lg is None:
                return
        else:
            lg.emit("run_end", worker=worker,
                    records=lg.records)
        lg.flush()
        lg.close()
        rep = report_all()
        for t in rep["tracers"]:
            t["worker"] = worker
        workers = [worker]
        merged = MetricsRegistry()
        path = os.path.join(lg.run_dir, "metrics.json")
        # merge an earlier snapshot ONLY if it came from THIS bench
        # invocation (the orchestrator stamps one BENCH_RUN_ID and
        # multi-worker stages share a dir). Any re-invocation — direct
        # or with BENCH_TELEMETRY_DIR pointed at a persisting dir —
        # gets a fresh id and overwrites: merging across runs would
        # compound stale counters and carry a historical unexpected
        # retrace into every future report.
        run_id = os.environ.get("BENCH_RUN_ID")
        if run_id is not None and os.path.exists(path):
            try:
                with open(path) as f:
                    old = json.load(f)
                if old.get("run_id") == run_id:
                    merged.merge(old)
                    oldrep = old.get("recompile_report") or {}
                    rep["tracers"] = (oldrep.get("tracers") or []) \
                        + rep["tracers"]
                    rep["unexpected_retraces"] += oldrep.get(
                        "unexpected_retraces", 0)
                    workers = (old.get("workers") or []) + workers
            except (OSError, ValueError, KeyError,
                    json.JSONDecodeError):
                pass  # a torn earlier snapshot must not lose this one
        merged.merge(get_registry().snapshot())
        merged.dump(path, extra={"recompile_report": rep,
                                 "workers": workers,
                                 "run_id": run_id})
        log(f"telemetry: {os.path.relpath(lg.path)} + "
            f"{os.path.relpath(path)}")
    except Exception as e:  # noqa: BLE001
        log(f"telemetry finalize failed: {e}")


# --------------------------------------------------------------------------
# worker-side workloads (only these import jax; orchestrator never does)
# --------------------------------------------------------------------------

def count_params(model):
    import numpy as np
    return int(sum(np.prod(p.shape) for p in model.parameters()))


def gpt_flops_per_token(model, seq):
    """Training FLOPs/token: 6*N for the dense matmuls (fwd+bwd) plus the
    attention score/value matmuls 12*L*h*s (fwd+bwd, causal halving
    ignored to stay comparable with the usual convention)."""
    cfg = model.config
    n = count_params(model)
    return 6 * n + 12 * cfg.num_hidden_layers * cfg.hidden_size * seq


def mfu_fields(tput, units_per_call, analytic_flops_per_unit,
               sites=("train_step",)):
    """The MFU stanza every training workload reports
    (docs/observability.md "analytic vs measured"):

    - ``mfu``            analytic convention (hand-derived FLOPs/unit x
                         throughput / peak) — comparable across rounds;
    - ``mfu_measured``   what XLA actually compiled: the train-step
                         executable's cost_analysis FLOPs over the
                         measured per-call wall (units_per_call /
                         tput), same peak. Null where cost analysis is
                         unavailable (backend reports no flops key, or
                         introspection skipped/disabled);
    - ``peak_flops_used`` / ``peak_flops_source`` — the resolved
                         denominator, so both numbers are auditable.

    Drift between the two legs is the signal, not an error: the
    analytic convention ignores what XLA fused, rematerialized or
    skipped — and XLA's cost model counts a lax.scan body ONCE
    regardless of trip count, so scan-shaped sites (train_step_multi,
    scan_layers stacks) read K/L-fold low on the measured leg
    (docs/observability.md "Loop caveat")."""
    intro = _obs_mod("introspect")
    peak, src = intro.resolve_peak_flops()
    out = {"mfu": None, "mfu_measured": None,
           "peak_flops_used": peak, "peak_flops_source": src}
    if not peak or not tput:
        return out
    out["mfu"] = round(tput * analytic_flops_per_unit / peak, 4)
    seconds_per_call = units_per_call / tput
    for site in sites:
        e = intro.site_cost(site, tracer="engine")
        if e and e.get("flops"):
            out["mfu_measured"] = round(
                e["flops"] / seconds_per_call / peak, 4)
            out["measured_flops_site"] = site
            break
    return out


def build_engine(cfg_name, batch, seq, amp, use_flash=True, recompute=False,
                 moment_dtype=None, scan_layers=False, fused_qkv=False,
                 fused_ln=False, chunked_ce=0, fused_adamw=False):
    import jax.numpy as jnp
    from paddle_tpu.nlp.gpt import (GPTForCausalLM, GPT_CONFIGS,
                                    GPTPretrainingCriterion, _resolve_config)
    from paddle_tpu.hapi.engine import Engine
    from paddle_tpu.optimizer import AdamW

    max_pos = max(GPT_CONFIGS[cfg_name]["max_position_embeddings"], seq)
    model = GPTForCausalLM(_resolve_config(
        cfg_name, max_position_embeddings=max_pos,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        use_flash_attention=use_flash, recompute=recompute,
        scan_layers=scan_layers, fused_qkv=fused_qkv,
        fused_ln=fused_ln, chunked_ce=chunked_ce))
    model.train()
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01,
                parameters=model.parameters(), moment_dtype=moment_dtype,
                fused_kernel=fused_adamw)
    eng = Engine(model, loss=GPTPretrainingCriterion(), optimizer=opt,
                 amp_dtype=jnp.bfloat16 if amp else None)
    return eng


def run(eng, batch, seq, steps, warmup, scan_steps=0):
    import jax
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(0)
    vocab = eng.network.config.vocab_size
    ids = jnp.asarray(rng.integers(0, vocab, (batch, seq)), dtype=jnp.int32)
    labels = jnp.asarray(rng.integers(0, vocab, (batch, seq)),
                         dtype=jnp.int32)
    log("compiling + warmup ...")
    for i in range(warmup):
        t = time.perf_counter()
        loss, _ = eng.train_batch([ids], [labels])
        loss.block_until_ready()
        log(f"  warmup step {i}: {time.perf_counter() - t:.2f}s")
    log(f"warmup done, loss={float(loss):.4f}")
    if scan_steps:
        # K real optimizer steps per compiled call amortize the
        # per-dispatch host latency — the public Engine.train_batch_multi
        k = int(scan_steps)
        ids_k = jnp.broadcast_to(ids, (k,) + ids.shape)
        labels_k = jnp.broadcast_to(labels, (k,) + labels.shape)
        losses, _ = eng.train_batch_multi([ids_k], [labels_k])  # compile
        losses.block_until_ready()
        t0 = time.perf_counter()
        calls = max(1, steps // k)
        for _ in range(calls):
            losses, _ = eng.train_batch_multi([ids_k], [labels_k])
            _Watchdog.pet()
        losses.block_until_ready()
        dt = time.perf_counter() - t0
        return batch * seq * k * calls / dt
    t0 = time.perf_counter()
    for i in range(steps):
        loss, _ = eng.train_batch([ids], [labels])
        _Watchdog.pet()  # dispatch is async: a healthy backend returns fast
    # the param-donation chain makes the last loss depend on every step, so
    # one final sync closes the whole window
    loss.block_until_ready()
    dt = time.perf_counter() - t0
    return batch * seq * steps / dt


def build_ernie_engine(batch, seq, amp, fused_qkv=False, fused_ln=False,
                       mlm_gather=0.0):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.nlp import (ErnieForPretraining,
                                ErniePretrainingCriterion)
    from paddle_tpu.hapi.engine import Engine
    from paddle_tpu.optimizer import AdamW

    from paddle_tpu.nlp.ernie import ERNIE_CONFIGS
    from paddle_tpu.nlp.ernie import _resolve_config as _ernie_cfg
    paddle.seed(0)
    max_pos = max(ERNIE_CONFIGS["ernie-3.0-base-zh"]
                  ["max_position_embeddings"], seq)
    model = ErnieForPretraining(_ernie_cfg(
        "ernie-3.0-base-zh", max_position_embeddings=max_pos,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        fused_qkv=fused_qkv, fused_ln=fused_ln,
        mlm_gather_capacity=mlm_gather))
    model.train()
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01,
                parameters=model.parameters())
    return Engine(model, loss=ErniePretrainingCriterion(), optimizer=opt,
                  amp_dtype=jnp.bfloat16 if amp else None)


def run_ernie(eng, batch, seq, steps, warmup):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(0)
    vocab = eng.network.config.vocab_size
    ids = jnp.asarray(rng.integers(0, vocab, (batch, seq)), dtype=jnp.int32)
    # MLM labels: 15% masked positions carry the target id, rest -100
    lbl = np.where(rng.random((batch, seq)) < 0.15,
                   rng.integers(0, vocab, (batch, seq)), -100)
    labels = jnp.asarray(lbl, dtype=jnp.int32)
    nsp = jnp.asarray(rng.integers(0, 2, (batch,)), dtype=jnp.int32)
    log("compiling + warmup (ernie) ...")
    for _ in range(warmup):
        loss, _ = eng.train_batch([ids], [labels, nsp])
        loss.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss, _ = eng.train_batch([ids], [labels, nsp])
        _Watchdog.pet()
    loss.block_until_ready()
    return batch * seq * steps / (time.perf_counter() - t0)


def _resnet_layout(layout, fused_bottleneck):
    """CLI spelling -> model layout. --fused-bottleneck implies NHWC
    when the layout is left on auto (the kernel is channels-last only,
    and 'auto' resolves to NCHW off-TPU where the smoke runs live)."""
    lay = {"auto": "auto", "nhwc": "NHWC", "nchw": "NCHW"}[layout or "auto"]
    if fused_bottleneck and lay == "auto":
        lay = "NHWC"
    return lay


def build_resnet_engine(amp, s2d=False, layout="auto",
                        fused_bottleneck=False):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.hapi.engine import Engine
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50(num_classes=1000, s2d_stem=s2d, layout=layout,
                     fused_bottleneck=fused_bottleneck)
    model.train()
    opt = paddle.optimizer.Momentum(0.1, momentum=0.9,
                                    parameters=model.parameters())
    return Engine(model, loss=paddle.nn.CrossEntropyLoss(), optimizer=opt,
                  amp_dtype=jnp.bfloat16 if amp else None)


def run_resnet(eng, batch, steps, warmup, hw=224):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, 3, hw, hw)),
                    dtype=jnp.float32)
    y = jnp.asarray(rng.integers(0, 1000, (batch,)))
    log("compiling + warmup (resnet50) ...")
    for i in range(warmup):
        loss, _ = eng.train_batch([x], [y])
        loss.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss, _ = eng.train_batch([x], [y])
        _Watchdog.pet()
    loss.block_until_ready()
    return batch * steps / (time.perf_counter() - t0)


def worker_probe():
    """Backend health check: the smallest possible end-to-end compile +
    execute + device->host sync. Run in a subprocess with a timeout by
    the orchestrator; a dead backend hangs here, not in a workload.
    The graph is deliberately MINIMAL (one elementwise reduce over a
    single 8x128 tile — the smallest legal TPU tile) so time-to-first-
    signal is dominated by the backend handshake, not the compile."""
    t0 = time.perf_counter()
    import jax
    import jax.numpy as jnp
    backend = jax.default_backend()
    n = len(jax.devices())
    x = jnp.ones((8, 128), jnp.bfloat16)
    s = float((x * 2).sum())  # forces compile + transfer
    _report({
        "probe": "ok", "backend": backend, "devices": n,
        "result": s, "seconds": round(time.perf_counter() - t0, 1),
    })


def worker_decode(args):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    from paddle_tpu.nlp.generation import generate
    import numpy as np
    if args.smoke:
        cfg, batch, new_tok = "gpt-tiny", 2, 16
    else:
        cfg, batch, new_tok = "gpt2-en", 8, 128
    cfg = args.config or cfg
    batch = args.batch or batch
    use_flash = not args.smoke and not args.no_flash
    # the Pallas decode kernel additionally sits behind an env gate (see
    # ops/attention.py flash_decode) — report what actually ran
    flash_kernel = (use_flash and
                    os.environ.get("PADDLE_TPU_FLASH_DECODE") == "1")
    model = GPTForCausalLM(_resolve_config(
        cfg, max_position_embeddings=1024, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0,
        use_flash_attention=use_flash))
    model.eval()
    if args.serve_dtype:
        # the simplest rung of the serving ladder: cast every weight to
        # bf16 — halves the per-token HBM weight stream vs fp32
        model = model.to(dtype=args.serve_dtype)
        log(f"serving weights cast to {args.serve_dtype}")
    if args.weight_only:
        from paddle_tpu.nn.quant import quantize_for_serving
        n = quantize_for_serving(model, weight_dtype=args.weight_only)
        log(f"weight-only {args.weight_only}: {n} layers converted")
    rng = np.random.default_rng(0)
    vocab = model.config.vocab_size
    prompt = jnp.asarray(rng.integers(0, vocab, (batch, 64)), jnp.int32)
    log(f"bench decode: {cfg} batch={batch} new_tokens={new_tok} "
        f"flash={use_flash}")
    cache_dt = args.cache_dtype or "float32"
    def ready(out):
        jax.block_until_ready(getattr(out, "_value", out))

    ready(generate(model, prompt, max_new_tokens=new_tok,
                   cache_dtype=cache_dt))  # compile
    log("decode compiled; timing ...")
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        out = generate(model, prompt, max_new_tokens=new_tok,
                       cache_dtype=cache_dt)
        _Watchdog.pet()
    ready(out)
    dt = (time.perf_counter() - t0) / reps
    _report({
        "metric": "gpt_decode_tokens_per_sec_per_chip",
        "value": round(batch * new_tok / dt, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": None,
        "config": cfg, "batch": batch, "new_tokens": new_tok,
        "ms_per_step": round(dt / new_tok * 1e3, 2),
        "flash": use_flash, "flash_kernel": flash_kernel,
        "weight_only": args.weight_only,
        "serve_dtype": args.serve_dtype,
        "cache_dtype": cache_dt,
        "backend": jax.default_backend(),
    })


SERVE_DTYPES = ("float32", "bfloat16", "int8")


def _serve_ladder(smoke):
    """(batch, cache_dtype, flash) rungs. TPU: the full cross product
    batch 1/8/32 x fp32/bf16/int8 x flash off/on. CPU smoke: every axis
    still covered (flash rungs run the identical Pallas kernel in
    interpret mode) but the cross product is pruned to keep the dryrun
    inside the smoke timeout."""
    if not smoke:
        return [(b, d, f) for b in (1, 8, 32) for d in SERVE_DTYPES
                for f in (False, True)]
    return ([(b, d, False) for b in (1, 8) for d in SERVE_DTYPES]
            + [(8, d, True) for d in SERVE_DTYPES]
            + [(32, "float32", False)])


def _serve_model(kind, smoke):
    if kind == "llama":
        from paddle_tpu.nlp.llama import LlamaForCausalLM, LlamaConfig
        if smoke:
            # GQA (2 kv heads for 4 query heads) + head_dim 64 so the
            # paged Pallas kernel gate accepts the flash rungs
            cfg = LlamaConfig(vocab_size=256, hidden_size=256,
                              num_hidden_layers=2, num_attention_heads=4,
                              num_key_value_heads=2,
                              intermediate_size=256,
                              max_position_embeddings=512)
        else:
            from paddle_tpu.nlp.llama import _resolve_config as _llama_cfg
            cfg = _llama_cfg("llama-1b")
        return LlamaForCausalLM(cfg), "llama"
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    if smoke:
        # heads=1 -> head_dim 64: the CPU flash rungs exercise the real
        # kernel (interpret mode) instead of silently falling back
        cfg = _resolve_config("gpt-tiny", num_attention_heads=1)
    else:
        cfg = _resolve_config("gpt2-en", hidden_dropout_prob=0.0,
                              attention_probs_dropout_prob=0.0)
    return GPTForCausalLM(cfg), "gpt"


def worker_serve(args):
    """Continuous-batching serving ladder (paddle_tpu.nlp.serving):
    per rung, one warmup wave compiles the (bucket, strategy) programs,
    then a timed wave of 2x max_slots requests runs through admission /
    decode / eviction with the compile counters asserted FROZEN — a
    recompiling steady state fails the rung loudly instead of timing
    compiles."""
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.nlp.serving import ServingEngine
    from paddle_tpu.observability.metrics import (MetricsRegistry,
                                                  get_registry)

    smoke = args.smoke
    paddle.seed(0)
    model, kind = _serve_model(args.serve_model, smoke)
    vocab = model.config.vocab_size
    if smoke:
        page_size, max_seq, new_tok, spd = 16, 48, 8, 2
        prompt_lens = (10, 12, 15, 13)
    else:
        # max_seq 256 = 2 pages/slot: the b32 fp32 rung's pool stays
        # ~6GB (129 pages x 128 x H x D x 4B x k,v x L would be 2x
        # that at 512 — too close to the 16GB chip with weights)
        page_size, max_seq, new_tok, spd = 128, 256, 128, 16
        prompt_lens = (96, 120, 64, 100)
    ladder = _serve_ladder(smoke)
    if args.batch:
        ladder = [r for r in ladder if r[0] == args.batch]
    if args.cache_dtype:
        ladder = [r for r in ladder if r[1] == args.cache_dtype]
    if args.no_flash:
        ladder = [r for r in ladder if not r[2]]
    if args.flash_only:
        # the bench_serve_flashk stage: only the kernel rungs — the ref
        # rungs already rode bench_serve_gpt's window
        ladder = [r for r in ladder if r[2]]
    rng = np.random.default_rng(0)
    rows = []
    for batch, dtype, flash in ladder:
        tag = f"b{batch}/{dtype}/{'flash' if flash else 'ref'}"
        use_flash = True if flash else False
        # per-rung private registry: warmup publishes then
        # reset_counters() zeroes it, so the histograms below cover
        # exactly the timed wave; merged into the process registry
        # after the rung, which is how the stage's metrics.json holds
        # the ladder-wide latency shape
        rung_reg = MetricsRegistry()
        eng = ServingEngine(model, max_slots=batch, page_size=page_size,
                            max_seq_len=max_seq, cache_dtype=dtype,
                            use_flash=use_flash,
                            steps_per_dispatch=spd, registry=rung_reg,
                            spec_decode=bool(args.spec),
                            # per-rung HBM attribution: the ladder's
                            # peak per-segment numbers ride the same
                            # registry merge as the latency shape
                            mem_ledger=True)
        if args.spec:
            # the verify program only arms through warmup() (the
            # zero-recompile gate) — the wave-as-warmup below never
            # traces it, so an unwarmed --spec rung would silently
            # measure plain decode
            eng.warmup(buckets=sorted(set(prompt_lens)), decode=True)
        def wave(n):
            prompts = [rng.integers(0, vocab,
                                    (prompt_lens[i % len(prompt_lens)],))
                       for i in range(n)]
            return eng.generate(prompts, max_new_tokens=new_tok)
        wave(batch)  # warmup: compiles the rung's programs
        frozen = eng.compile_counts()
        eng.reset_counters()
        t0 = time.perf_counter()
        # steady state incl. admission/recycling; small-batch rungs get
        # extra requests so the timed window holds enough dispatches
        # for a stable number on a noisy host
        out = wave(max(2 * batch, 32))
        wall = time.perf_counter() - t0
        _Watchdog.pet()
        after = eng.compile_counts()
        recompiles = sum(after.values()) - sum(frozen.values())
        if recompiles:
            raise RuntimeError(
                f"serve rung {tag}: {recompiles} recompile(s) in steady "
                f"state ({frozen} -> {after}) — the single-program "
                "contract is broken")
        toks = sum(len(t) for t in out)
        # headline per rung = batched-DECODE throughput (the engine's
        # dispatch counters); wall-clock additionally pays the batch-1
        # prefill admissions, reported alongside
        dec_s = max(eng.decode_seconds, 1e-9)
        row = {"batch": batch, "cache_dtype": dtype, "flash": flash,
               "flash_kernel": eng.use_flash,
               "tok_s": round(eng.decode_tokens / dec_s, 1),
               "ms_per_tok": round(dec_s / max(eng.decode_tokens, 1)
                                   * 1e3, 3),
               "wall_tok_s": round(toks / wall, 1),
               "decode_dispatches": eng.decode_dispatches,
               "steady_recompiles": 0,
               # the latency SHAPE, not just the mean (the ladder's
               # p99 is the serving number a deployment pages on)
               "decode_tok_ms": _hist_ms(
                   rung_reg.get("serve_decode_token_seconds")),
               "ttft_ms": _hist_ms(rung_reg.get("serve_ttft_seconds")),
               "queue_wait_ms": _hist_ms(
                   rung_reg.get("serve_queue_wait_seconds"))}
        if args.spec:
            sp = eng.health().get("spec") or {}
            row["spec"] = {"k": sp.get("k"),
                           "draft": sp.get("draft"),
                           "proposed": sp.get("proposed"),
                           "accepted": sp.get("accepted"),
                           "acceptance_rate": sp.get("acceptance_rate")}
        if eng.ledger is not None:
            mdg = eng.ledger.digest()
            row["mem"] = {
                # peak (high-watermark) + per-segment attribution:
                # THE capacity-planning numbers a rung exists to
                # produce — how many bytes each batch/dtype point
                # actually costs, split by owner
                "high_watermark_bytes": mdg.get("high_watermark_bytes"),
                "attributed_bytes": mdg.get("attributed_bytes"),
                "unattributed_bytes": mdg.get("unattributed_bytes"),
                "segments": mdg.get("segments"),
                "used_ratio": mdg.get("used_ratio")}
        rows.append(row)
        try:
            _emit("serve_rung", model=kind, **row)
        except Exception as e:  # noqa: BLE001 — telemetry never kills a result
            log(f"telemetry emit failed: {e}")
        get_registry().merge(rung_reg.snapshot())
        mem = row.get("mem") or {}
        log(f"serve {tag}: {row['tok_s']} tok/s decode "
            f"({row['wall_tok_s']} wall; {toks} toks), recompiles 0, "
            f"p99 {((row['decode_tok_ms'] or {}).get('p99'))} ms/tok, "
            f"hbm peak {mem.get('high_watermark_bytes')} B "
            f"(kv {((mem.get('segments') or {}).get('kv_pages'))})")
        del eng
    by_rung = {(r["batch"], r["cache_dtype"], r["flash"]): r["tok_s"]
               for r in rows}
    b1 = by_rung.get((1, "float32", False))
    b8 = by_rung.get((8, "float32", False))
    speedup = round(b8 / b1, 2) if b1 and b8 else None
    best = max(rows, key=lambda r: r["tok_s"]) if rows else None
    _report({
        "metric": f"serve_{kind}_decode_tokens_per_sec_per_chip",
        "value": best["tok_s"] if best else None,
        "unit": "tokens/s/chip", "vs_baseline": None,
        "model": kind, "page_size": page_size, "max_seq_len": max_seq,
        "steps_per_dispatch": spd, "new_tokens": new_tok,
        "b8_vs_b1_speedup": speedup,
        "steady_recompiles": 0,
        "decode_tok_ms": best["decode_tok_ms"] if best else None,
        "ttft_ms": best["ttft_ms"] if best else None,
        "ladder": rows,
        "backend": jax.default_backend(),
    })


def worker_llama(args):
    """Llama pretrain throughput (the zoo's GQA flagship)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.nlp.llama import (LlamaForCausalLM,
                                      LlamaPretrainingCriterion,
                                      _resolve_config)
    from paddle_tpu.hapi.engine import Engine
    from paddle_tpu.optimizer import AdamW

    if args.smoke:
        cfg, batch, seq, steps, warmup, amp = ("llama-tiny", 4, 64, 3, 2,
                                               False)
    else:
        cfg, batch, seq, steps, warmup, amp = ("llama-1b", 4, 1024, 10, 2,
                                               True)
    cfg = args.config or cfg
    batch = args.batch or batch
    seq = args.seq or seq
    steps = args.steps or steps
    use_flash = not args.no_flash
    # the 1.1B flagship needs the same memory levers as gpt3-1.3B to
    # fit one 16GB chip: bf16 Adam moments + per-block remat
    big = cfg == "llama-1b" and not args.smoke
    moment_dtype = args.moment_dtype or ("bfloat16" if big else None)
    recompute = args.recompute or big
    log(f"bench: {cfg} batch={batch} seq={seq} steps={steps} "
        f"backend={jax.default_backend()} amp={amp} flash={use_flash} "
        f"recompute={recompute} moment_dtype={moment_dtype}")
    paddle.seed(0)
    model = LlamaForCausalLM(_resolve_config(
        cfg, use_flash_attention=use_flash, recompute=recompute))
    model.train()
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01,
                parameters=model.parameters(),
                moment_dtype=moment_dtype)
    eng = Engine(model, loss=LlamaPretrainingCriterion(), optimizer=opt,
                 amp_dtype=jnp.bfloat16 if amp else None)
    tput = run(eng, batch, seq, steps, warmup)
    fpt = gpt_flops_per_token(eng.network, seq)  # same 6N+12Lhs conv.
    _report({
        "metric": "llama_pretrain_tokens_per_sec_per_chip",
        "value": round(tput, 1), "unit": "tokens/s/chip",
        "vs_baseline": None,
        **mfu_fields(tput, batch * seq, fpt),
        "config": cfg, "batch": batch, "seq": seq, "flash": use_flash,
        "backend": jax.default_backend(),
    })


def worker_resnet(args):
    import jax
    if args.smoke:
        batch, steps, warmup, amp, hw = 4, 3, 2, False, 64
    else:
        batch, steps, warmup, amp, hw = 256, 20, 3, True, 224
    batch = args.batch or batch
    steps = args.steps or steps
    if args.serve:
        return _resnet_serve(args, batch, steps, hw)
    layout = _resnet_layout(args.layout, args.fused_bottleneck)
    log(f"bench: resnet50 batch={batch} hw={hw} steps={steps} "
        f"backend={jax.default_backend()} amp={amp} s2d={args.s2d} "
        f"layout={layout} fused_bottleneck={args.fused_bottleneck}")
    eng = build_resnet_engine(amp, s2d=args.s2d, layout=layout,
                              fused_bottleneck=args.fused_bottleneck)
    tput = run_resnet(eng, batch, steps, warmup, hw)
    # 4.1 GFLOP fwd inference at 224px, x3 for fwd+bwd; scaled for
    # smaller images
    flops_per_img = 3 * 4.1e9 * (hw / 224.0) ** 2
    _report({
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(tput, 1),
        "unit": "images/s/chip",
        # vs_baseline compares against an A100 number — meaningless for
        # a CPU smoke run, so only reported on TPU
        "vs_baseline": round(
            tput / BASELINE_RESNET50_IMG_PER_SEC_PER_CHIP, 4)
        if not args.smoke else None,
        **mfu_fields(tput, batch, flops_per_img),
        "batch": batch, "image": hw, "s2d_stem": args.s2d,
        "layout": eng.network._layout,
        "fused_bottleneck": bool(args.fused_bottleneck),
        "backend": jax.default_backend(),
    })


def _resnet_serve(args, batch, steps, hw):
    """Inference img/s; --fold-bn applies the conv_bn_fuse_pass
    equivalent (incubate.fuse_conv_bn) before jit — one fewer
    elementwise HBM pass per conv at serving."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.nn.layer import functional_call
    from paddle_tpu.tensor import Tensor
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    layout = _resnet_layout(args.layout, args.fused_bottleneck)
    model = resnet50(layout=layout,
                     fused_bottleneck=args.fused_bottleneck)
    model.eval()
    folded = 0
    if args.fold_bn:
        from paddle_tpu.incubate import fuse_conv_bn
        model, folded = fuse_conv_bn(model)
    dtype = jnp.float32 if args.smoke else jnp.bfloat16
    if not args.smoke:
        model.to(dtype=dtype)
    params, buffers = model.raw_state()
    log(f"bench: resnet50 SERVE batch={batch} hw={hw} steps={steps} "
        f"fold_bn={args.fold_bn} (folded {folded} pairs) "
        f"layout={model._layout}")

    @jax.jit
    def fwd(params, buffers, x):
        out = functional_call(model, params, buffers, Tensor(x))
        return out._value if isinstance(out, Tensor) else out

    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (batch, 3, hw, hw)), dtype)
    fwd(params, buffers, x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fwd(params, buffers, x)
        _Watchdog.pet()
    out.block_until_ready()
    dt = time.perf_counter() - t0
    tput = batch * steps / dt
    _report({
        "metric": "resnet50_serve_images_per_sec_per_chip",
        "value": round(tput, 1), "unit": "images/s/chip",
        "vs_baseline": None, "fold_bn": bool(args.fold_bn),
        "folded_pairs": folded, "batch": batch, "image": hw,
        "layout": model._layout,
        "fused_bottleneck": bool(args.fused_bottleneck),
        "backend": jax.default_backend(),
    })


def worker_ernie(args):
    import jax
    if args.smoke:
        batch, seq, steps, warmup, amp = 4, 64, 3, 2, False
    else:
        batch, seq, steps, warmup, amp = 32, 512, 20, 3, True
    batch = args.batch or batch
    seq = args.seq or seq
    steps = args.steps or steps
    log(f"bench: ernie-3.0-base batch={batch} seq={seq} steps={steps} "
        f"backend={jax.default_backend()} amp={amp} "
        f"fused_qkv={args.fused_qkv}")
    eng = build_ernie_engine(batch, seq, amp, fused_qkv=args.fused_qkv,
                             fused_ln=args.fused_ln,
                             mlm_gather=args.mlm_gather)
    tput = run_ernie(eng, batch, seq, steps, warmup)
    fpt = gpt_flops_per_token(eng.network, seq)  # same 6N+12Lhs conv.
    _report({
        "metric": "ernie3_base_pretrain_tokens_per_sec_per_chip",
        "value": round(tput, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(
            tput / BASELINE_ERNIE_TOKENS_PER_SEC_PER_CHIP, 4)
        if not args.smoke else None,
        **mfu_fields(tput, batch * seq, fpt),
        "batch": batch, "seq": seq, "fused_qkv": args.fused_qkv,
        "fused_ln": args.fused_ln, "mlm_gather": args.mlm_gather, "chunked_ce": args.chunked_ce,
        "fused_adamw": args.fused_adamw,
        "backend": jax.default_backend(),
    })


def worker_gpt(args, big=False):
    import jax
    if args.smoke:
        cfg, batch, seq, steps, warmup, amp = "gpt-tiny", 4, 64, 4, 2, False
    elif big:
        # BASELINE.json configs[3]: the 1.3B flagship on one 16GB chip —
        # needs bf16 Adam moments + remat to fit
        cfg, batch, seq, steps, warmup, amp = "gpt3-1.3B", 4, 1024, 10, 2, True
    else:
        cfg, batch, seq, steps, warmup, amp = "gpt3-345M", 8, 1024, 20, 3, True
    cfg = args.config or cfg
    batch = args.batch or batch
    seq = args.seq or seq
    steps = args.steps or steps

    use_flash = not args.no_flash
    recompute = args.recompute or (big and not args.smoke)
    moment_dtype = "bfloat16" if (big and not args.smoke) else None
    if args.moment_dtype:
        moment_dtype = args.moment_dtype
    log(f"bench: {cfg} batch={batch} seq={seq} steps={steps} "
        f"backend={jax.default_backend()} amp={amp} flash={use_flash} "
        f"recompute={recompute} moment_dtype={moment_dtype} "
        f"scan_layers={args.scan_layers}")
    scan_layers = args.scan_layers
    eng = build_engine(cfg, batch, seq, amp, use_flash=use_flash,
                       recompute=recompute, moment_dtype=moment_dtype,
                       scan_layers=scan_layers, fused_qkv=args.fused_qkv,
                       fused_ln=args.fused_ln, chunked_ce=args.chunked_ce,
                       fused_adamw=args.fused_adamw)
    tput = run(eng, batch, seq, steps, warmup, scan_steps=args.scan_steps)
    fpt = gpt_flops_per_token(eng.network, seq)
    # --scan-steps compiles ONE K-step program (train_step_multi): its
    # cost analysis covers K optimizer steps, so the measured leg's
    # per-call window is K steps of tokens
    k = int(args.scan_steps or 0)
    _report({
        # the 1.3B metric name only when the 1.3B config actually ran
        # (smoke mode and --config overrides fall back to the generic one)
        "metric": ("gpt3_1p3b_pretrain_tokens_per_sec_per_chip"
                   if big and cfg == "gpt3-1.3B"
                   else "gpt_pretrain_tokens_per_sec_per_chip"),
        "value": round(tput, 1),
        "unit": "tokens/s/chip",
        # vs_baseline compares against an A100 number — only meaningful on
        # the real chip
        "vs_baseline": round(tput / BASELINE_TOKENS_PER_SEC_PER_CHIP, 4)
        if not args.smoke else None,
        **mfu_fields(tput, batch * seq * (k or 1), fpt,
                     sites=(("train_step_multi",) if k
                            else ("train_step",))),
        "config": cfg, "batch": batch, "seq": seq, "flash": use_flash,
        "scan_layers": scan_layers, "fused_qkv": args.fused_qkv,
        "fused_ln": args.fused_ln, "chunked_ce": args.chunked_ce,
        "fused_adamw": args.fused_adamw,
        "backend": jax.default_backend(),
    })


def worker_input_pipeline(args):
    """Input-pipeline load test: decode/augment img/s per worker mode
    (inline / thread prefetch / N spawn processes) against a null
    consumer. ref: paddle's worker-process DataLoader exists exactly to
    beat the GIL on this workload; the 2,225 img/s ResNet consumer is
    the rate to beat. Steady-state: timing starts at the FIRST batch,
    so spawn+import cost (amortized over an epoch in real training)
    is excluded."""
    import multiprocessing
    from paddle_tpu.io import DataLoader
    from paddle_tpu.io.synthetic import SyntheticImageDataset

    n = 192 if args.smoke else 1536
    batch = args.batch or 32
    ds = SyntheticImageDataset(n)
    results = {}

    def timed(tag, **kw):
        dl = DataLoader(ds, batch_size=batch, shuffle=False,
                        drop_last=True, **kw)
        it = iter(dl)
        first = next(it)
        t0 = time.perf_counter()
        count = 0
        for b in it:
            count += int(b.shape[0])
        dt = time.perf_counter() - t0
        del first
        results[tag] = round(count / dt, 1)
        log(f"  {tag}: {results[tag]} img/s")

    timed("inline")
    timed("threads_2", num_workers=2)
    worker_counts = (1, 2) if args.smoke else (1, 2, 4)
    for w in worker_counts:
        timed(f"proc_{w}", num_workers=w, use_process_workers=True)
    best = max(results.values())
    _report({
        "metric": "input_pipeline_img_per_sec", "value": best,
        "unit": "img/s", "vs_baseline": round(best / 2225.0, 4),
        "host_cores": multiprocessing.cpu_count(),
        "batch": batch, "images": n, "modes": results,
        "note": "vs_baseline compares against the r4 ResNet-50 TPU "
                "consumer rate (2225 img/s); scaling needs host cores",
    })


WORKERS = {
    "gpt": lambda a: worker_gpt(a, big=False),
    "gpt-1.3b": lambda a: worker_gpt(a, big=True),
    "ernie": worker_ernie,
    "llama": worker_llama,
    "resnet50": worker_resnet,
    "decode": worker_decode,
    "serve": worker_serve,
    "input-pipeline": worker_input_pipeline,
}


# --------------------------------------------------------------------------
# orchestrator (jax-free)
# --------------------------------------------------------------------------

class WorkloadResult:
    def __init__(self, name, ok, data=None, error=None, seconds=0.0):
        self.name, self.ok, self.data = name, ok, data
        self.error, self.seconds = error, seconds


def _spawn(extra_args, timeout_s, tag):
    """Run `python bench.py <extra_args>` in a killable subprocess.
    stderr streams through live; stdout is captured (the JSON lines).
    Returns (rc, last_json_dict_or_None, error_string_or_None)."""
    cmd = [sys.executable, os.path.abspath(__file__)] + extra_args
    print(f"[bench] {tag}: {' '.join(extra_args)} (timeout {timeout_s}s)",
          file=sys.stderr, flush=True)
    t0 = time.monotonic()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=None,
                            text=True, start_new_session=True)
    out_lines = []

    def pump():
        for line in proc.stdout:
            out_lines.append(line)
    th = threading.Thread(target=pump, daemon=True)
    th.start()
    try:
        proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        # SIGKILL the whole process group: an XLA client stuck inside a
        # compile ignores SIGTERM
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        proc.wait()
        th.join(timeout=5)
        return (None, None,
                f"timeout after {timeout_s}s (killed)",
                time.monotonic() - t0)
    th.join(timeout=5)
    dt = time.monotonic() - t0
    parsed = None
    for line in reversed(out_lines):
        line = line.strip()
        if line.startswith("{"):
            try:
                parsed = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
    if proc.returncode != 0:
        return (proc.returncode, parsed,
                f"worker exited rc={proc.returncode}", dt)
    return (proc.returncode, parsed, None, dt)


def _proc_starttime(pid):
    """Kernel start time of `pid` (clock ticks since boot; field 22 of
    /proc/<pid>/stat, parsed after the last ')' — comm may hold spaces).
    Returns 0 if unreadable. Single owner of the 'pid starttime'
    pidfile identity format; tools/tpu_campaign.py imports this."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            stat = f.read()
        return int(stat.rsplit(")", 1)[1].split()[19])
    except (OSError, IndexError, ValueError):
        return 0


def _flush_partial(results, probe):
    """Persist everything measured so far — survives any later wedge."""
    try:
        os.makedirs(os.path.dirname(PARTIAL_PATH), exist_ok=True)
        with open(PARTIAL_PATH, "w") as f:
            json.dump({
                "probe": probe,
                "workloads": {r.name: (r.data if r.ok else
                                       {"error": r.error}) for r in results},
            }, f, indent=1)
    except OSError:
        pass


DRIVER_MARKER = os.path.join(CAMPAIGN_OUT, "driver_bench_active")


def _preempt_campaign():
    """A driver-style bench run owns the chip: kill any in-flight
    campaign stage (tools/tpu_campaign.py records its pid) and leave a
    marker that makes tpu_campaign.py hold off, so two processes never
    time the TPU simultaneously. The marker
    is removed when orchestrate() returns; its mtime bounds the hold-off
    if this process dies uncleanly."""
    pid_path = os.path.join(CAMPAIGN_OUT, "current_stage.pid")
    try:
        parts = open(pid_path).read().split()
        pid = int(parts[0])
        recorded_start = int(parts[1]) if len(parts) > 1 else 0
        # identity check: never killpg a recycled pid from a stale file.
        # The kernel starttime recorded at spawn is the strong check
        # (a recycled pid can't share it); 0 is the writer's
        # "unreadable" sentinel and legacy pid-only files omit it —
        # both fall through to the cmdline substring fallback alone.
        if recorded_start and _proc_starttime(pid) != recorded_start:
            raise ValueError("pid recycled (starttime mismatch)")
        cmdline = open(f"/proc/{pid}/cmdline", "rb").read().decode(
            "utf-8", "replace")
        if "bench.py" in cmdline or "tpu_campaign" in cmdline \
                or "roofline" in cmdline or "fusion_audit" in cmdline:
            os.killpg(pid, signal.SIGKILL)
            print(f"[bench] killed in-flight campaign stage (pgid {pid})"
                  " — driver bench takes the chip", file=sys.stderr,
                  flush=True)
    except (OSError, ValueError, IndexError, ProcessLookupError,
            PermissionError):
        pass
    try:
        os.makedirs(CAMPAIGN_OUT, exist_ok=True)
        with open(DRIVER_MARKER, "w") as f:
            f.write(str(os.getpid()))
    except OSError:
        pass


def _release_chip():
    try:
        os.remove(DRIVER_MARKER)
    except OSError:
        pass


def orchestrate(workloads, args, passthrough):
    smoke = args.smoke
    host_only = workloads == ["input-pipeline"]  # no chip involved:
    # don't preempt the campaign, don't gate on the backend probe
    if not smoke and not host_only \
            and not os.environ.get("CAMPAIGN_CHILD"):
        _preempt_campaign()
        try:
            return _orchestrate_impl(workloads, args, passthrough)
        finally:
            _release_chip()
    return _orchestrate_impl(workloads, args, passthrough,
                             skip_probe=host_only)


def _orchestrate_impl(workloads, args, passthrough, skip_probe=False):
    smoke = args.smoke
    probe_timeout = int(os.environ.get("BENCH_PROBE_TIMEOUT",
                                       240 if smoke else 600))
    work_timeout = int(os.environ.get("BENCH_WORK_TIMEOUT",
                                      600 if smoke else 1800))

    if skip_probe:
        probe, err, dt = {"probe": "ok", "backend": "host-only",
                          "seconds": 0.0}, None, 0.0
    else:
        rc, probe, err, dt = _spawn(["--worker", "probe"]
                                    + (["--smoke"] if smoke else []),
                                    probe_timeout, "probe")
    if probe is None or probe.get("probe") != "ok":
        # error text can embed a multi-KB backend traceback — bound it,
        # the final line must never outgrow the driver's capture
        err_text = f"backend probe failed: {err or probe}"
        diag = {
            "metric": "gpt_pretrain_tokens_per_sec_per_chip",
            "value": None, "unit": "tokens/s/chip", "vs_baseline": None,
            "error": err_text[:800],
            "probe_seconds": round(dt, 1),
        }
        print(json.dumps(diag), flush=True)
        return 2
    print(f"[bench] probe ok: backend={probe.get('backend')} "
          f"in {probe.get('seconds')}s", file=sys.stderr, flush=True)

    results = []
    headline = None
    for name in workloads:
        wargs = (["--worker", name] + (["--smoke"] if smoke else [])
                 + passthrough)
        rc, data, err, dt = _spawn(wargs, work_timeout, name)
        ok = data is not None and err is None
        results.append(WorkloadResult(name, ok, data, err, dt))
        if ok:
            # incremental flush: each result is printed the moment it
            # exists, so a later hang can't erase it
            print(json.dumps(data), flush=True)
            if headline is None and (name in ("gpt", "decode")
                                     or len(workloads) == 1):
                headline = data
        else:
            print(f"[bench] {name} FAILED: {err}", file=sys.stderr,
                  flush=True)
        _flush_partial(results, probe)
        if not ok and skip_probe:
            continue  # host-only workload: never touch the backend
        if not ok:
            # a failed workload may have left the backend hung — reprobe
            # before burning timeout on the next one
            rc2, p2, e2, _ = _spawn(["--worker", "probe"]
                                    + (["--smoke"] if smoke else []),
                                    probe_timeout, "reprobe")
            if p2 is None or p2.get("probe") != "ok":
                print("[bench] backend wedged after failure — stopping "
                      "with partial results", file=sys.stderr, flush=True)
                break

    # final line: the headline (gpt) result, carrying all other completed
    # workloads, ALWAYS the last JSON object on stdout
    extra = {r.name: r.data for r in results if r.ok and r.data is not headline}
    failures = {r.name: r.error for r in results if not r.ok}
    if headline is not None:
        final = dict(headline)
        if extra:
            final["workloads"] = extra
        if failures:
            final["failed_workloads"] = failures
        print(json.dumps(final), flush=True)
        return 0
    # headline failed: emit a best-available final line so the artifact
    # still parses (value null signals the miss honestly)
    first = workloads[0]
    final = {
        "metric": ("gpt_pretrain_tokens_per_sec_per_chip"
                   if first in ("gpt", "decode") else first),
        "value": None, "unit": "tokens/s/chip", "vs_baseline": None,
        "error": failures.get(first) or failures.get("gpt")
        or "headline workload did not run",
    }
    if extra:
        final["workloads"] = extra
    if failures:
        final["failed_workloads"] = failures
    print(json.dumps(final), flush=True)
    return 4


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--config", default=None)
    ap.add_argument("--model", choices=tuple(WORKERS), default=None)
    ap.add_argument("--no-flash", action="store_true",
                    help="disable the Pallas flash-attention path (fallback "
                         "number if the kernel regresses)")
    ap.add_argument("--recompute", action="store_true",
                    help="rematerialize decoder blocks (enables larger "
                         "batches)")
    ap.add_argument("--moment-dtype", default=None,
                    help="Adam moment dtype override (e.g. bfloat16)")
    ap.add_argument("--serve", action="store_true",
                    help="resnet50: inference throughput instead of "
                         "training")
    ap.add_argument("--fold-bn", action="store_true",
                    help="resnet50 --serve: fold BatchNorms into conv "
                         "weights first (conv_bn_fuse_pass parity)")
    ap.add_argument("--s2d", action="store_true",
                    help="resnet50: MLPerf space-to-depth stem (exactly "
                         "equivalent 4x4/s1 conv over 12 channels)")
    ap.add_argument("--layout", choices=("auto", "nhwc", "nchw"),
                    default=None,
                    help="resnet50: conv-stack layout A/B — nhwc is the "
                         "TPU-native channels-last pipeline (ONE boundary "
                         "transpose, HWIO kernels); auto resolves to nhwc "
                         "on TPU, nchw elsewhere")
    ap.add_argument("--fused-bottleneck", action="store_true",
                    help="resnet50: route the bottleneck 1x1-conv+BN+ReLU"
                         "(+residual) chains through the Pallas fused "
                         "kernel (the diagnosed HBM-bandwidth wall; "
                         "implies nhwc while --layout is auto)")
    ap.add_argument("--dryrun", action="store_true",
                    help="alias for --smoke")
    ap.add_argument("--weight-only", choices=("int8", "int4"), default=None,
                    help="decode: serve with weight-only-quantized linears "
                         "(HBM-bandwidth lever)")
    ap.add_argument("--serve-dtype", default=None,
                    choices=("bfloat16", "float16"),
                    help="decode: cast model weights for serving "
                         "(bf16 halves the HBM weight stream)")
    ap.add_argument("--cache-dtype", default=None,
                    help="decode/serve KV cache dtype (bfloat16 halves "
                         "decode HBM traffic; serve also takes int8)")
    ap.add_argument("--spec", action="store_true",
                    help="--serve: arm speculative decoding on every "
                         "rung (ngram draft, PADDLE_TPU_SPEC_K "
                         "tokens/dispatch); rows gain the acceptance "
                         "stats and stay token-exact vs plain rungs")
    ap.add_argument("--serve-model", choices=("gpt", "llama"),
                    default="gpt",
                    help="serve: which zoo model the ladder decodes "
                         "(llama exercises GQA + RoPE paged decode)")
    ap.add_argument("--flash-only", action="store_true",
                    help="serve: run only the flash-kernel rungs (the "
                         "bench_serve_flashk stage — ref rungs already "
                         "measured by bench_serve_gpt)")
    ap.add_argument("--mlm-gather", type=float, default=0.0,
                    help="ernie: gather at most this fraction of "
                         "positions (the masked ~15%%) before the "
                         "MLM head — head FLOPs/logits shrink "
                         "~1/c-fold (0 = full head)")
    ap.add_argument("--fused-adamw", action="store_true",
                    help="gpt: one-HBM-pass Pallas optimizer update "
                         "(the 22.8ms-vs-11.8ms-floor lever)")
    ap.add_argument("--chunked-ce", type=int, default=0,
                    help="gpt: fuse the LM head into the loss over "
                         "token chunks of this size (the [N,vocab] "
                         "logits never materialize)")
    ap.add_argument("--fused-ln", action="store_true",
                    help="gpt: fuse residual add + LayerNorm into one "
                         "Pallas pass (elementwise-HBM lever)")
    ap.add_argument("--fused-qkv", action="store_true",
                    help="gpt: one [h,3h] qkv matmul (Megatron "
                         "head-interleaved) instead of three [h,h]")
    ap.add_argument("--scan-layers", action="store_true",
                    help="gpt: stacked-params lax.scan over decoder "
                         "layers (O(1-block) compiled program)")
    ap.add_argument("--scan-steps", type=int, default=0,
                    help="run K optimizer steps per compiled call "
                         "(lax.scan) to amortize dispatch latency")
    ap.add_argument("--input-pipeline", action="store_true",
                    help="measure decode/augment img/s per DataLoader "
                         "worker mode (inline/threads/processes) "
                         "against a null consumer")
    ap.add_argument("--decode", action="store_true",
                    help="measure KV-cache generation throughput instead "
                         "of training (opt-in; never on the default path)")
    ap.add_argument("--worker", default=None,
                    help="internal: run one workload in-process")
    ap.add_argument("--all", action="store_true",
                    help="run every workload incl. smoke mode")
    args = ap.parse_args()
    if args.dryrun:
        args.smoke = True

    # one id per bench invocation, inherited by spawned workers: the
    # telemetry finalize merges an existing metrics.json only when it
    # was written under the SAME id (multi-worker stages share a dir;
    # re-invocations overwrite instead of compounding stale counters)
    os.environ.setdefault("BENCH_RUN_ID",
                          f"{int(time.time() * 1e3)}-{os.getpid()}")

    if args.worker:
        # ---- child mode: the only place jax is imported ----
        if args.smoke or args.worker == "input-pipeline":
            # input-pipeline is a host-side workload: it never needs
            # the chip, so it never takes it
            import _cpu_env  # noqa: F401  (must precede the jax import)
        _Watchdog.start()
        _TELEMETRY["worker"] = args.worker
        try:
            if args.worker == "input-pipeline":
                worker_input_pipeline(args)
                return
            from paddle_tpu.utils.compile_cache import enable_compile_cache
            log(f"compile cache: {enable_compile_cache()}")
            import jax
            if not args.smoke and jax.default_backend() != "tpu":
                # a device metric is never computed from a CPU run
                sys.exit(f"bench worker {args.worker!r} needs a TPU: "
                         f"jax.default_backend() is "
                         f"{jax.default_backend()!r}. --smoke runs the "
                         "CPU plumbing check; its numbers are not device "
                         "numbers.")
            if args.worker == "probe":
                worker_probe()
                return
            WORKERS[args.worker](args)
        finally:
            # every stage leaves telemetry.jsonl + metrics.json — on
            # failure too (the partial run facts ARE the diagnostic)
            _finalize_worker_telemetry(args.worker)
        return

    # ---- orchestrator mode: jax-free ----
    if args.input_pipeline:
        workloads = ["input-pipeline"]
    elif args.decode:
        workloads = ["decode"]
    elif args.serve and args.model is None:
        # the continuous-batching serving ladder (nlp/serving.py);
        # resnet50 inference keeps its historical `--model resnet50
        # --serve` spelling
        workloads = ["serve"]
    elif args.model:
        workloads = [args.model]
    elif args.smoke and not args.all:
        workloads = ["gpt"]
    else:
        # headline first: a later hang can't erase the number that
        # matters. 1.3B runs LAST (newest path = highest wedge risk).
        workloads = ["gpt", "ernie", "resnet50", "gpt-1.3b"]

    # flags that only one workload family reads: reject elsewhere instead
    # of silently benching the default config under a tuned-looking name
    if args.weight_only and workloads != ["decode"]:
        ap.error("--weight-only applies to decode serving only "
                 "(use --decode)")
    if args.cache_dtype and workloads not in (["decode"], ["serve"]):
        ap.error("--cache-dtype applies to decode/serve only "
                 "(use --decode or --serve)")
    if args.serve_model != "gpt" and workloads != ["serve"]:
        ap.error("--serve-model applies to the serving ladder only "
                 "(use --serve)")
    if args.flash_only and workloads != ["serve"]:
        ap.error("--flash-only applies to the serving ladder only "
                 "(use --serve)")
    if args.spec and workloads != ["serve"]:
        ap.error("--spec applies to the serving ladder only "
                 "(use --serve)")
    if args.flash_only and args.no_flash:
        ap.error("--flash-only and --no-flash select disjoint rungs")
    if args.serve_dtype and workloads != ["decode"]:
        ap.error("--serve-dtype applies to decode serving only "
                 "(use --decode)")
    if args.serve_dtype and args.weight_only:
        ap.error("--serve-dtype and --weight-only are separate rungs of "
                 "the serving ladder: quantization derives its scales "
                 "from fp32 weights, so casting first would quantize "
                 "rounded values and mislabel the result")
    if args.moment_dtype and not set(workloads) <= {"gpt", "gpt-1.3b",
                                                    "llama"}:
        ap.error("--moment-dtype applies to the gpt/llama training "
                 "workloads only")
    if args.scan_layers and not set(workloads) <= {"gpt", "gpt-1.3b"}:
        ap.error("--scan-layers applies to the gpt training "
                 "workloads only")
    if args.fused_qkv and not set(workloads) <= {"gpt", "gpt-1.3b",
                                                 "ernie"}:
        ap.error("--fused-qkv applies to the gpt/ernie training "
                 "workloads only")
    if args.fused_ln and not set(workloads) <= {"gpt", "gpt-1.3b",
                                                "ernie"}:
        ap.error("--fused-ln applies to the gpt/ernie training "
                 "workloads only")
    if args.chunked_ce and not set(workloads) <= {"gpt", "gpt-1.3b"}:
        ap.error("--chunked-ce applies to the gpt training "
                 "workloads only")
    if args.fused_adamw and not set(workloads) <= {"gpt", "gpt-1.3b"}:
        ap.error("--fused-adamw applies to the gpt training "
                 "workloads only")
    if args.mlm_gather and workloads != ["ernie"]:
        ap.error("--mlm-gather applies to the ernie workload only")
    if args.fold_bn and workloads != ["resnet50"]:
        ap.error("--fold-bn applies to resnet50 serving only "
                 "(use --model resnet50 --serve)")
    if args.serve and workloads not in (["resnet50"], ["serve"]):
        ap.error("--serve runs the serving ladder (alone) or resnet50 "
                 "inference (--model resnet50 --serve)")
    if (args.layout or args.fused_bottleneck) \
            and workloads != ["resnet50"]:
        ap.error("--layout/--fused-bottleneck apply to the resnet50 "
                 "workload only (use --model resnet50)")

    # per-workload tuning flags only make sense for a single explicit
    # workload — forwarding them to the whole suite would silently bench
    # every model at a non-standard config
    passthrough = []
    overrides = {"--steps": args.steps, "--batch": args.batch,
                 "--seq": args.seq, "--config": args.config,
                 "--moment-dtype": args.moment_dtype,
                 "--weight-only": args.weight_only,
                 "--serve-dtype": args.serve_dtype,
                 "--cache-dtype": args.cache_dtype,
                 "--serve-model": (args.serve_model
                                   if args.serve_model != "gpt"
                                   else None)}
    if len(workloads) == 1:
        for flag, val in overrides.items():
            if val is not None:
                passthrough += [flag, str(val)]
        if args.no_flash:
            passthrough.append("--no-flash")
        if args.flash_only:
            passthrough.append("--flash-only")
        if args.spec:
            passthrough.append("--spec")
        if args.recompute:
            passthrough.append("--recompute")
        if args.s2d:
            passthrough.append("--s2d")
        if args.layout:
            passthrough += ["--layout", args.layout]
        if args.fused_bottleneck:
            passthrough.append("--fused-bottleneck")
        if args.serve:
            passthrough.append("--serve")
        if args.fold_bn:
            passthrough.append("--fold-bn")
        if args.scan_steps:
            passthrough += ["--scan-steps", str(args.scan_steps)]
        if args.scan_layers:
            passthrough.append("--scan-layers")
        if args.fused_qkv:
            passthrough.append("--fused-qkv")
        if args.fused_ln:
            passthrough.append("--fused-ln")
        if args.chunked_ce:
            passthrough += ["--chunked-ce", str(args.chunked_ce)]
        if args.fused_adamw:
            passthrough.append("--fused-adamw")
        if args.mlm_gather:
            passthrough += ["--mlm-gather", str(args.mlm_gather)]
    elif any(v is not None for v in overrides.values()) or args.no_flash \
            or args.recompute or args.scan_steps or args.s2d \
            or args.scan_layers or args.fused_qkv or args.fused_ln \
            or args.chunked_ce or args.fused_adamw or args.mlm_gather \
            or args.layout or args.fused_bottleneck:
        print("[bench] ignoring per-workload flags in full-suite mode "
              "(use --model to tune one workload)", file=sys.stderr,
              flush=True)
    sys.exit(orchestrate(workloads, args, passthrough))


if __name__ == "__main__":
    main()
