"""telemetry_smoke — the campaign's CPU observability drill.

Runs the acceptance shape of docs/observability.md end to end without
spending chip time: a 5-step guarded Model.fit (with one injected
NaN step, so the guard counters are provably live), a 4-request serve
wave scraped MID-FLIGHT through the live /metrics endpoint (final
scrape must match the in-process registry byte-for-byte — the
no-torn-histogram contract), and a 3-step NaN rollback storm that must
leave a parseable flight-recorder dump carrying the storm's own step
records. Asserts the expected metric names exist (including the
compiled-cost xla_cost_flops and measured-MFU gauges — the smoke pins
PADDLE_TPU_PEAK_FLOPS so the MFU plumbing runs on CPU), the latency
histograms have non-zero counts, and the RecompileTracer saw 0
unexpected retraces — and writes telemetry.jsonl + metrics.json
exactly like a bench stage.

Output dir: $BENCH_TELEMETRY_DIR (tpu_campaign sets it per stage) or
campaign_out/telemetry/telemetry_smoke. Last stdout line is a JSON
verdict; exit 0 only when every assertion holds.
"""
from __future__ import annotations

import json
import os
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# exercise the MFU plumbing on CPU: without a resolvable peak the MFU
# gauges are (correctly) absent and this drill could not pin them
os.environ.setdefault("PADDLE_TPU_PEAK_FLOPS", "197e12")

EXPECTED_TRAIN = [
    "train_step_seconds", "train_steps_total", "train_loss",
    "train_samples_per_s", "train_skipped_steps_total",
    "train_rollbacks_total",
    # round-10 introspection layer (docs/observability.md): compiled
    # cost analysis + measured MFU against the pinned peak
    "train_peak_flops", "train_mfu_measured", "xla_cost_flops",
]
EXPECTED_SERVE = [
    "serve_ttft_seconds", "serve_decode_token_seconds",
    "serve_queue_wait_seconds", "serve_dispatch_seconds",
    "serve_requests_total", "serve_page_occupancy", "serve_free_pages",
    "serve_decode_tokens_total", "serve_deadline_misses_total",
    "serve_evictions_total",
]
EXPECTED_LOADER = ["dataloader_batch_wait_seconds",
                   "dataloader_batches_total"]
# histograms the acceptance criterion requires to hold real samples
NONZERO_HISTS = ["train_step_seconds", "serve_ttft_seconds",
                 "serve_decode_token_seconds"]


def run_guarded_fit(run_dir):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.hapi.callbacks import TelemetryCallback
    from paddle_tpu.resilience import TrainGuard, faults

    paddle.seed(0)
    net = paddle.nn.Linear(8, 4)
    model = paddle.Model(net)
    guard = TrainGuard(snapshot_every=1, rollback_after=3)
    model.prepare(paddle.optimizer.AdamW(1e-2,
                                         parameters=net.parameters()),
                  paddle.nn.CrossEntropyLoss(), guard=guard)
    rng = np.random.default_rng(0)
    X = rng.standard_normal((20, 8)).astype("float32")
    Y = rng.integers(0, 4, (20,)).astype("int64")
    cb = TelemetryCallback(run_dir=run_dir, write_metrics=False)
    faults.clear()
    faults.inject("nan_grads", step=3)   # one provably-skipped step
    model.fit(paddle.io.TensorDataset([X, Y]), epochs=1, batch_size=4,
              verbose=0, shuffle=False, callbacks=[cb])
    faults.clear()
    return {"skipped": guard.skipped_steps,
            "good_steps": guard.good_steps,
            "jsonl_records": cb.logger.records}


def run_serve_wave(n_requests=4):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    from paddle_tpu.nlp.serving import ServingEngine

    from paddle_tpu.observability.metrics import get_registry

    paddle.seed(0)
    model = GPTForCausalLM(_resolve_config("gpt-tiny",
                                           num_attention_heads=1))
    # an engine's registry is private by default; the smoke asserts the
    # whole catalogue in one process-global export, so share it
    eng = ServingEngine(model, max_slots=2, page_size=8, max_seq_len=32,
                        steps_per_dispatch=2, registry=get_registry())
    exp = eng.serve_metrics(port=0)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.config.vocab_size, (6 + i,))
               for i in range(n_requests)]
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    # drive the wave by hand so the endpoint is scraped WHILE requests
    # are in flight — the live-scrape acceptance, not a post-hoc read
    finished, mid_scrape_ok, rounds = [], False, 0
    while eng._queue or any(s is not None for s in eng._slots):
        finished.extend(eng.step())
        rounds += 1
        if rounds == 1:
            txt = urllib.request.urlopen(exp.url + "/metrics",
                                         timeout=10).read().decode()
            mid_scrape_ok = ("serve_decode_tokens_total" in txt
                             and "serve_ttft_seconds_bucket" in txt)
        if rounds > 1000:
            raise RuntimeError("serve wave did not drain")
    # quiesced: the scraped exposition must equal the in-process
    # registry's own rendering — series-for-series, value-for-value
    final_txt = urllib.request.urlopen(exp.url + "/metrics",
                                       timeout=10).read().decode()
    parity = final_txt == get_registry().to_prometheus()
    health = json.load(urllib.request.urlopen(exp.url + "/healthz",
                                              timeout=10))
    report = json.load(urllib.request.urlopen(exp.url + "/report",
                                              timeout=10))
    exp.close()
    h = eng.health()
    res = {"requests": len(finished),
           "tokens": sum(len(r["tokens"]) for r in finished),
           "unexpected_retraces": eng.tracer.unexpected_retraces(),
           "ok": h["status_counts"]["ok"],
           "scrape_mid_wave": mid_scrape_ok,
           "scrape_parity": parity,
           "healthz_ok": health.get("status") == "ok"
           and "status_counts" in health,
           "report_cost_sites": len(((report.get("cost_report") or {})
                                     .get("sites") or {}))}
    eng.close()
    return res


def run_rollback_storm(run_dir):
    """A 3-consecutive-NaN storm through a guarded fit: rollback MUST
    trip and MUST leave a parseable flight_rollback*.json carrying the
    storm's own guard_step records (the chaos acceptance shape that
    validate_stages also enforces on campaign chaos stages)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.resilience import TrainGuard, faults

    os.environ["PADDLE_TPU_FLIGHT_DIR"] = run_dir
    paddle.seed(1)
    net = paddle.nn.Linear(8, 4)
    model = paddle.Model(net)
    guard = TrainGuard(snapshot_every=1, rollback_after=3)
    model.prepare(paddle.optimizer.AdamW(
        1e-2, parameters=net.parameters()),
        paddle.nn.CrossEntropyLoss(), guard=guard)
    rng = np.random.default_rng(1)
    X = rng.standard_normal((24, 8)).astype("float32")
    Y = rng.integers(0, 4, (24,)).astype("int64")
    faults.clear()
    faults.inject("nan_grads", step=2, count=3)
    model.fit(paddle.io.TensorDataset([X, Y]), epochs=1, batch_size=4,
              verbose=0, shuffle=False)
    faults.clear()
    dumps = sorted(f for f in os.listdir(run_dir)
                   if f.startswith("flight_rollback")
                   and f.endswith(".json"))
    parsed = bad_step_records = 0
    for fn in dumps:
        with open(os.path.join(run_dir, fn)) as fh:
            doc = json.load(fh)
        if isinstance(doc.get("records"), list):
            parsed += 1
            bad_step_records += sum(
                1 for r in doc["records"]
                if r.get("kind") == "guard_step" and not r.get("ok"))
    return {"rollbacks": guard.rollbacks, "dumps": len(dumps),
            "parsed": parsed, "bad_step_records": bad_step_records}


def main():
    t0 = time.perf_counter()
    run_dir = (os.environ.get("BENCH_TELEMETRY_DIR")
               or os.path.join(REPO, "campaign_out", "telemetry",
                               "telemetry_smoke"))
    fit = run_guarded_fit(run_dir)
    serve = run_serve_wave()
    storm = run_rollback_storm(run_dir)

    from paddle_tpu.observability.metrics import get_registry
    from paddle_tpu.observability.trace import report_all
    reg = get_registry()
    names = set(reg.names())
    problems = []
    for name in EXPECTED_TRAIN + EXPECTED_SERVE + EXPECTED_LOADER:
        if name not in names:
            problems.append(f"metric missing: {name}")
    for name in NONZERO_HISTS:
        series = [m for m in reg.series() if m.name == name]
        if series and not sum(m.count for m in series):
            problems.append(f"histogram empty: {name}")
    if fit["skipped"] != 1:
        problems.append(f"guard skipped {fit['skipped']} steps, "
                        "expected exactly 1 (injected NaN)")
    if serve["ok"] != serve["requests"]:
        problems.append(f"serve wave finished {serve['ok']}/"
                        f"{serve['requests']} ok")
    if not serve["scrape_mid_wave"]:
        problems.append("mid-wave /metrics scrape missing serve series")
    if not serve["scrape_parity"]:
        problems.append("/metrics scrape != in-process registry "
                        "exposition (torn or diverged endpoint)")
    if not serve["healthz_ok"]:
        problems.append("/healthz missing engine health snapshot")
    if not serve["report_cost_sites"]:
        problems.append("/report carries no compiled-cost sites")
    if storm["rollbacks"] < 1:
        problems.append("rollback storm did not trip a rollback")
    if not storm["dumps"]:
        problems.append("rollback left no flight_rollback*.json dump")
    if storm["parsed"] != storm["dumps"]:
        problems.append(f"{storm['dumps'] - storm['parsed']} flight "
                        "dump(s) unparseable")
    if storm["bad_step_records"] < 3:
        problems.append("flight dump missing the storm's own "
                        f"guard_step records "
                        f"({storm['bad_step_records']}/3)")
    rep = report_all()
    if rep["unexpected_retraces"]:
        problems.append(f"{rep['unexpected_retraces']} unexpected "
                        "retraces — a compiled program was rebuilt")

    metrics_path = reg.dump(os.path.join(run_dir, "metrics.json"),
                            extra={"recompile_report": rep})
    verdict = {
        "telemetry_smoke": "ok" if not problems else "FAIL",
        "problems": problems,
        "fit": fit, "serve": serve, "flight": storm,
        "metric_names": len(names),
        "unexpected_retraces": rep["unexpected_retraces"],
        "metrics_json": os.path.relpath(metrics_path, REPO),
        "seconds": round(time.perf_counter() - t0, 1),
    }
    print(json.dumps(verdict), flush=True)
    return 0 if not problems else 1


if __name__ == "__main__":
    sys.exit(main())
