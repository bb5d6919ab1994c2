"""profile_smoke — the campaign's CPU drill for the continuous
profiling plane (ISSUE 22).

Shape (seeded, CPU-only, no chip time spent):

1. build a seeded wave of short random prompts and run it through a
   ServingEngine with the continuous profiler ARMED (profile=True) —
   the always-on configuration the flag ships for;
2. invariants, asserted hard:
   - **zero-recompile untouched**: compile counts frozen across the
     wave with profiling ON, zero unexpected retraces — the sampler
     is host-side only and must never perturb the trace plane;
   - **phase attribution is live**: a 1 kHz watcher thread polling
     the dispatch thread's phase marker during the wave observes
     real serving phases (``decode`` and at least one
     ``prefill_<bucket>``) — the markers the engine sets around its
     dispatch path are actually raised where the sampler would see
     them (the sampler itself is then proven on the injected run,
     whose multi-second decode burn guarantees ``decode`` samples in
     the folded profile regardless of backoff state);
   - **overhead under the cap**: the profiler's self-measured duty
     cycle (EWMA of sample cost / period) sits at or under its 1%
     cap on CPU — backoffs may have fired (they are counted, not
     hidden) but the steady state must comply;
   - **/profile endpoint renders**: a live HTTP scrape of
     ``/profile?window=60`` returns the folded profile +
     self-measurement digest, and ``exporter_scrape_seconds``
     self-timed the route;
   - **flamegraph is machine-parseable**: the self-contained HTML's
     embedded JSON ``<script>`` block parses back out and its folded
     map is non-empty — the artifact a triage dir holds years later
     still yields data;
3. differential gate, BOTH directions: save the clean run's folded
   profile (A), then re-run the wave with an injected busy-loop in
   the decode dispatch path (B — a deliberate host-side regression,
   sized at half the clean run's MEASURED wall so the decode-share
   delta clears the +10pp bar on a loaded host as surely as an idle
   one) and prove ``tools/profile_diff.py --fail-on
   'phase:decode>+10%'`` PASSES on A-vs-A and TRIPS on A-vs-B. A
   gate that cannot fail proves nothing;
4. artifacts into $BENCH_TELEMETRY_DIR: ``metrics.json`` (registry +
   recompile report — the validate_stages contract),
   ``profile_clean.folded`` / ``profile_injected.folded``,
   ``flamegraph.html``, a ``profile_smoke`` flight dump with the live
   profile attached (the anomaly-evidence path, exercised
   end-to-end), and ``profile_smoke.json`` (the drill's facts).

Last stdout line is a JSON verdict; exit 0 only when every assertion
holds.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

NEW_TOK = 48
PROMPT_LEN = 12
REQUESTS = 6
MAX_SEQ_LEN = 128
NUM_PAGES = 128
PROFILE_HZ = 59.0      # prime, dense enough to catch phases on a
#                        short CPU wave while the duty cycle stays
#                        far under the 1% cap
MIN_HZ = PROFILE_HZ / 4.0   # backoff floor for the drill's engines:
#                        overhead spikes on a loaded host may halve the
#                        rate (counted, checked) but must not collapse
#                        it to 1 Hz, where a multi-second decode burn
#                        could land between samples
BURN_FRACTION = 0.5    # injected decode burn, as a fraction of the
#                        measured CLEAN run's wall: sizing the
#                        regression relative to the baseline keeps the
#                        decode-share delta (~burn/(1+burn) ≈ +33pp)
#                        comfortably past the +10pp gate on any host,
#                        loaded or idle — a fixed burn constant would
#                        dilute to nothing when warmup compiles run
#                        slow under contention
BURN_MIN_S = 2.0       # absolute burn floor (sample-count floor at
#                        the backed-off rate)


def build_wave(seed=0, vocab=256):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, (PROMPT_LEN,)).astype(np.int32)
            for _ in range(REQUESTS)]


def run_engine(model, prompts, *, burn_total=0.0):
    """One profiled engine through the wave; returns facts + the
    still-open engine (caller closes — the clean run scrapes its
    live /profile endpoint first)."""
    from paddle_tpu.nlp.serving import ServingEngine
    eng = ServingEngine(model, max_slots=4, page_size=16,
                        max_seq_len=MAX_SEQ_LEN, steps_per_dispatch=1,
                        num_pages=NUM_PAGES,
                        profile=True, profile_hz=PROFILE_HZ)
    eng.profiler.min_hz = MIN_HZ
    eng.warmup(buckets=sorted({len(p) for p in prompts}), decode=True)
    frozen = eng.compile_counts()
    if burn_total > 0.0:
        # the deliberate regression: burn host time inside the decode
        # dispatch — the phase wrapper is already open, so attribution
        # is automatic and the folded profile's decode share must grow.
        # The budget is spread across dispatches (the wave has at
        # least NEW_TOK decode rounds, so a NEW_TOK/2 divisor always
        # drains it) rather than burned in one lump, so the profile
        # shows a hot *path*, not one monster sample.
        orig = eng._dispatch_decode_impl
        remaining = [float(burn_total)]
        step_cap = max(burn_total / (NEW_TOK / 2.0), 0.01)

        def burn():
            if remaining[0] > 0.0:
                t0 = time.perf_counter()
                quota = min(step_cap, remaining[0])
                while time.perf_counter() - t0 < quota:
                    sum(i * i for i in range(200))
                remaining[0] -= time.perf_counter() - t0
            orig()
        eng._dispatch_decode_impl = burn
    # deterministic phase-wiring witness: generate() runs on THIS
    # thread, so a 1 kHz watcher polling this thread's phase marker
    # observes every phase the dispatch path raises — orders of
    # magnitude denser than the sampler, immune to its Hz backoff
    from paddle_tpu.observability import contprof
    observed = set()
    stop = threading.Event()
    me = threading.get_ident()

    def watch():
        while not stop.is_set():
            ph = contprof.current_phase(me)
            if ph:
                observed.add(ph)
            time.sleep(0.001)
    w = threading.Thread(target=watch, daemon=True)
    w.start()
    try:
        eng.generate(prompts, max_new_tokens=NEW_TOK)
    finally:
        stop.set()
        w.join(2.0)
    facts = {
        "compile_frozen": eng.compile_counts() == frozen,
        "unexpected_retraces": eng.tracer.unexpected_retraces(),
        "digest": eng.profiler.digest(),
        "observed_phases": sorted(observed),
    }
    return eng, facts


def _parse_flame(path):
    """Extract the embedded profile JSON back out of the flamegraph
    HTML — the machine-parseability contract."""
    with open(path, encoding="utf-8") as f:
        html = f.read()
    marker = '<script id="profile-data" type="application/json">'
    i = html.index(marker) + len(marker)
    j = html.index("</script>", i)
    return json.loads(html[i:j].replace("<\\/", "</"))


def _diff(a, b, fail_on):
    """Run the real profile_diff gate as a subprocess (what the
    campaign preflight would run); returns (exit_code, report)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "profile_diff.py"),
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
    ap.add_argument("--gate", default="phase:decode>+10%",
                    help="profile_diff --fail-on spec the injected "
                         "regression must trip")
    args = ap.parse_args(argv)

    out_dir = os.environ.get("BENCH_TELEMETRY_DIR") or os.path.join(
        REPO, "campaign_out", "telemetry", "profile_smoke")
    os.makedirs(out_dir, exist_ok=True)

    import paddle_tpu as paddle
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    from paddle_tpu.observability import contprof, flightrec
    from paddle_tpu.observability.trace import report_all

    paddle.seed(0)
    model = GPTForCausalLM(_resolve_config("gpt-tiny"))
    model.eval()
    prompts = build_wave(args.seed)

    # -- clean profiled run + live endpoint scrape -------------------------
    t0 = time.perf_counter()
    eng, clean = run_engine(model, prompts)
    t_clean = time.perf_counter() - t0
    folded_a = os.path.join(out_dir, "profile_clean.folded")
    eng.profiler.save(folded_a)
    flame_path = eng.profiler.flamegraph_html(
        os.path.join(out_dir, "flamegraph.html"))
    exporter = eng.serve_metrics(port=0)
    url = f"http://{exporter.host}:{exporter.port}"
    with urllib.request.urlopen(f"{url}/profile?window=60",
                                timeout=10) as r:
        live = json.loads(r.read().decode())
    with urllib.request.urlopen(f"{url}/metrics", timeout=10) as r:
        prom = r.read().decode()
    # the anomaly-evidence path, end-to-end: a flight dump carrying
    # the live profile (validate_stages' FLIGHT_STAGES contract)
    flightrec.note("profile_smoke", samples=clean["digest"]["samples"])
    flightrec.dump("profile_smoke",
                   extra={"profile": contprof.current_profile()})
    eng.registry.dump(os.path.join(out_dir, "metrics.json"),
                      extra={"recompile_report": report_all(),
                             "stage": "profile_smoke"})
    eng.close()

    # -- injected-regression run -------------------------------------------
    burn_total = max(BURN_MIN_S, BURN_FRACTION * t_clean)
    eng2, injected = run_engine(model, prompts, burn_total=burn_total)
    folded_b = os.path.join(out_dir, "profile_injected.folded")
    eng2.profiler.save(folded_b)
    eng2.close()

    # -- differential gate, both directions --------------------------------
    rc_clean, rep_clean = _diff(folded_a, folded_a, args.gate)
    rc_trip, rep_trip = _diff(folded_a, folded_b, args.gate)

    flame = _parse_flame(flame_path)
    dg = clean["digest"]
    phases = dg["phases"]
    checks = {
        "zero_new_traces_after_warmup": (
            clean["compile_frozen"]
            and clean["unexpected_retraces"] == 0),
        "decode_phase_marked": "decode" in clean["observed_phases"],
        "prefill_phase_marked": any(
            p.startswith("prefill_") for p in clean["observed_phases"]),
        "decode_phase_sampled": (
            injected["digest"]["phases"].get("decode", 0) > 0),
        "overhead_under_cap": dg["overhead_ratio"] <= 0.01,
        "profile_endpoint_renders": (
            live.get("folded") and live.get("digest") is not None),
        "exporter_scrape_self_timed": (
            "exporter_scrape_seconds" in prom),
        "flamegraph_parseable": bool(flame.get("folded")),
        "diff_gate_passes_clean": rc_clean == 0,
        "diff_gate_trips_injected": rc_trip == 1,
        "injected_run_still_frozen": (
            injected["compile_frozen"]
            and injected["unexpected_retraces"] == 0),
    }

    with open(os.path.join(out_dir, "profile_smoke.json"), "w") as f:
        json.dump({"clean_digest": dg,
                   "injected_digest": injected["digest"],
                   "observed_phases": clean["observed_phases"],
                   "gate": args.gate,
                   "diff_clean": rep_clean,
                   "diff_injected": rep_trip}, f, indent=1)

    ok = all(bool(v) for v in checks.values())
    print(json.dumps({
        "ok": ok, "checks": checks,
        "samples": dg["samples"],
        "overhead_ratio": dg["overhead_ratio"],
        "backoffs": dg["backoffs"],
        "phases": phases,
        "gate": args.gate,
        "burn_total_s": round(burn_total, 3),
        "injected_decode_delta_pp": next(
            (fl.get("delta_pp") for fl in rep_trip.get("failures", [])
             if fl.get("key") == "phase:decode"), None),
        "out_dir": out_dir}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
