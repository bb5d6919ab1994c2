"""mem_smoke — the campaign's CPU drill for the device-memory ledger
plane (ISSUE 20).

Shape (seeded, CPU-only, no chip time spent):

1. build a seeded wave of short prompts — half of them REPEATED so the
   prefix cache serves real hits — and run it through a ServingEngine
   with the memory ledger ARMED (mem_ledger=True, an explicit
   capacity so headroom/used-ratio forecasting is live);
2. invariants, asserted hard:
   - **zero-recompile untouched**: compile counts frozen across the
     wave with the ledger armed, zero unexpected retraces — track/
     release are host-side dict arithmetic and must never perturb the
     trace plane;
   - **conservation**: typed segments + ``unattributed_bytes`` equal
     the ground-truth live-array byte count within 1% after the full
     wave (prefill, prefix hits, decode) — the cross-check the whole
     plane hangs off;
   - **the seams fired**: kv_pages/weights tracked, prefix_sidecar
     level non-zero after a served hit, one admission consult per
     request counted;
   - **/memory endpoint renders**: a live HTTP scrape returns the
     armed segment tree, ``engine_mem_*`` gauges are in /metrics, and
     ``exporter_scrape_seconds`` self-timed the route;
   - **the residual alarm is quiet on a clean wave** — an alarm that
     cries on healthy traffic would be muted in a week;
3. leak drill + differential gate, BOTH directions: save the clean
   ledger snapshot (A), ``mark_baseline()``, then inject a deliberate
   leak — an UNTRACKED device page block (allocated behind the
   ledger's back, never released) plus pages popped off the engine's
   free list and never returned — sweep, and prove the
   ``unattributed_bytes`` residual alarm TRIPS, and that
   ``tools/mem_diff.py --fail-on 'segment:unattributed>+50%'``
   PASSES on A-vs-A and TRIPS on A-vs-B. A gate that cannot fail
   proves nothing;
4. artifacts into $BENCH_TELEMETRY_DIR: ``metrics.json`` (registry +
   recompile report — the validate_stages contract),
   ``mem_clean.json`` / ``mem_leaked.json`` (the diffable ledger
   snapshots), a ``mem_smoke`` flight dump with the live segment tree
   attached (the anomaly-evidence path, exercised end-to-end), and
   ``mem_smoke.json`` (the drill's facts).

Last stdout line is a JSON verdict; exit 0 only when every assertion
holds.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

NEW_TOK = 24
PROMPT_LEN = 24            # 3 whole pages at page_size=8: enough
#                            boundary fingerprints for real sharing
REQUESTS = 8               # 4 distinct prompts, each submitted twice
MAX_SEQ_LEN = 64
NUM_PAGES = 128
PAGE_SIZE = 8
CAPACITY = 1 << 30         # explicit budget: CPU memory_stats has no
#                            bytes_limit, and the headroom/used-ratio
#                            forecast (and hard admission) need one
LEAK_MIN_BYTES = 8 << 20   # leak floor: far past the residual
#                            alarm's 1 MiB slack floor AND the diff
#                            gate's +50% bar at any clean baseline


def build_wave(seed=0, vocab=256):
    """REQUESTS prompts, each distinct prompt appearing twice — the
    second submission of a prompt is a guaranteed prefix-cache hit
    once the first registered its boundary pages."""
    import numpy as np
    rng = np.random.default_rng(seed)
    base = [rng.integers(1, vocab, (PROMPT_LEN,)).astype(np.int32)
            for _ in range(REQUESTS // 2)]
    return [p for p in base for _ in range(2)]


def run_engine(model, prompts):
    """One ledger-armed engine through the wave; returns the
    still-open engine + facts (caller closes — the drill scrapes the
    live /memory endpoint and runs the leak injection first)."""
    from paddle_tpu.nlp.serving import ServingEngine
    eng = ServingEngine(model, max_slots=4, page_size=PAGE_SIZE,
                        max_seq_len=MAX_SEQ_LEN,
                        num_pages=NUM_PAGES, steps_per_dispatch=1,
                        mem_ledger=True, mem_capacity_bytes=CAPACITY)
    eng.warmup(buckets=sorted({len(p) for p in prompts}), decode=True)
    frozen = eng.compile_counts()
    eng.generate(prompts, max_new_tokens=NEW_TOK)
    facts = {
        "compile_frozen": eng.compile_counts() == frozen,
        "unexpected_retraces": eng.tracer.unexpected_retraces(),
        "conservation": eng.ledger.conservation(tolerance=0.01),
        "prefix_stats": eng.prefix.stats(),
        "ledger_stats": eng.ledger.stats(),
        "segments": eng.ledger.segments(),
    }
    return eng, facts


def inject_leak(eng):
    """The deliberate leak: a device page block allocated BEHIND the
    ledger's back (never tracked, never released — the bug class the
    residual series exists to catch) plus free-list pages popped and
    never returned (the engine-side page leak, visible as a free_pages
    shortfall). Returns (held buffers, leaked page ids, leak bytes) —
    the caller must keep the buffers alive through the sweep."""
    from paddle_tpu.nlp.paged_cache import alloc_pages
    per_page = 2 * PAGE_SIZE * eng.kv_heads * eng.head_dim * 4
    n_pages = max(-(-LEAK_MIN_BYTES // per_page), 2)
    block = alloc_pages(n_pages, PAGE_SIZE, eng.kv_heads,
                        eng.head_dim, "float32")
    leak_bytes = sum(int(b.nbytes) for b in block if b is not None)
    leaked_ids = [eng._free_pages.pop() for _ in range(4)]
    return block, leaked_ids, leak_bytes


def _diff(a, b, fail_on):
    """Run the real mem_diff gate as a subprocess (what the campaign
    preflight would run); returns (exit_code, report)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mem_diff.py"),
         a, b, "--quiet", "--fail-on", fail_on],
        capture_output=True, text=True, timeout=120)
    try:
        report = json.loads(proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        report = {"unparseable": proc.stdout[-500:]}
    return proc.returncode, report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gate", default="segment:unattributed>+50%",
                    help="mem_diff --fail-on spec the injected leak "
                         "must trip")
    args = ap.parse_args(argv)

    out_dir = os.environ.get("BENCH_TELEMETRY_DIR") or os.path.join(
        REPO, "campaign_out", "telemetry", "mem_smoke")
    os.makedirs(out_dir, exist_ok=True)

    import paddle_tpu as paddle
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    from paddle_tpu.observability import flightrec, memledger
    from paddle_tpu.observability.trace import report_all

    paddle.seed(0)
    model = GPTForCausalLM(_resolve_config("gpt-tiny"))
    model.eval()
    prompts = build_wave(args.seed)

    # -- clean ledger-armed run + live endpoint scrape ---------------------
    eng, clean = run_engine(model, prompts)
    exporter = eng.serve_metrics(port=0)
    url = f"http://{exporter.host}:{exporter.port}"
    with urllib.request.urlopen(f"{url}/memory?window=60",
                                timeout=10) as r:
        live = json.loads(r.read().decode())
    with urllib.request.urlopen(f"{url}/metrics", timeout=10) as r:
        prom = r.read().decode()
    # the anomaly-evidence path, end-to-end: a flight dump carrying
    # the live segment tree (validate_stages' FLIGHT_STAGES contract)
    flightrec.note("mem_smoke",
                   attributed=clean["segments"].get("kv_pages", 0))
    flightrec.dump("mem_smoke",
                   extra={"memory": memledger.current_memory()})
    eng.registry.dump(os.path.join(out_dir, "metrics.json"),
                      extra={"recompile_report": report_all(),
                             "stage": "mem_smoke"})
    snap_a = os.path.join(out_dir, "mem_clean.json")
    eng.ledger.save(snap_a)
    alarm_clean = eng.ledger.residual_alarm

    # -- leak drill --------------------------------------------------------
    eng.ledger.mark_baseline()
    free_before = len(eng._free_pages)
    block, leaked_ids, leak_bytes = inject_leak(eng)
    eng.ledger.sweep(force=True)
    alarm_leaked = eng.ledger.residual_alarm
    snap_b = os.path.join(out_dir, "mem_leaked.json")
    eng.ledger.save(snap_b)
    free_short = len(eng._free_pages)
    del block  # buffers held alive through the sweep above
    t_health = time.perf_counter()
    h = eng.health()
    health_s = time.perf_counter() - t_health
    eng.close()

    # -- differential gate, both directions --------------------------------
    rc_clean, rep_clean = _diff(snap_a, snap_a, args.gate)
    rc_trip, rep_trip = _diff(snap_a, snap_b, args.gate)

    cons = clean["conservation"]
    stats = clean["ledger_stats"]
    checks = {
        "zero_new_traces_after_warmup": (
            clean["compile_frozen"]
            and clean["unexpected_retraces"] == 0),
        "conservation_within_1pct": cons.get("ok") is True,
        "kv_pages_tracked": clean["segments"].get("kv_pages", 0) > 0,
        "weights_tracked": clean["segments"].get("weights", 0) > 0,
        "prefix_hit_served": clean["prefix_stats"]["hits"] > 0,
        "prefix_sidecar_tracked": (
            clean["segments"].get("prefix_sidecar", 0) > 0),
        "admission_checks_counted": (
            stats["admission_checks"] >= REQUESTS),
        "memory_endpoint_renders": bool(
            live.get("armed") is True
            and (live.get("tree") or {}).get("kv_pages")),
        "mem_series_exported": (
            "engine_mem_attributed_bytes" in prom
            and "engine_mem_hbm_used_ratio" in prom),
        "exporter_scrape_self_timed": (
            "exporter_scrape_seconds" in prom),
        "residual_alarm_quiet_on_clean_wave": not alarm_clean,
        "residual_alarm_trips_on_leak": alarm_leaked,
        "leak_visible_in_health": (
            (h.get("mem") or {}).get("residual_alarm") is True),
        "pages_leaked_off_free_list": free_short == free_before - 4,
        "diff_gate_passes_clean": rc_clean == 0,
        "diff_gate_trips_leaked": rc_trip == 1,
    }

    with open(os.path.join(out_dir, "mem_smoke.json"), "w") as f:
        json.dump({"clean": clean, "gate": args.gate,
                   "leak_bytes": leak_bytes,
                   "leaked_page_ids": leaked_ids,
                   "health_s": round(health_s, 6),
                   "diff_clean": rep_clean,
                   "diff_leaked": rep_trip}, f, indent=1, default=str)

    ok = all(bool(v) for v in checks.values())
    print(json.dumps({
        "ok": ok, "checks": checks,
        "conservation": cons,
        "segments": clean["segments"],
        "gate": args.gate,
        "leak_bytes": leak_bytes,
        "out_dir": out_dir}, default=str))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
