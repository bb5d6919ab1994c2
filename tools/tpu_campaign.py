"""One-command TPU measurement campaign (run on the machine with the chip).

Runs, in order of scoreboard value, each piece subprocess-isolated so a
wedge costs one stage (results land incrementally in campaign_out/):

  1. backend probe (tiny matmul)                 -> probe.json
  2. bench full suite (gpt, ernie, resnet50,     -> bench_full.json
     gpt-1.3b)
  3. resnet50 --s2d A/B                          -> bench_resnet_s2d.json
  3b. resnet50 NHWC layout / fused-bottleneck    -> bench_resnet_nhwc.json
      A/B (the r6 "win ResNet" directive)           bench_resnet_nhwc_fused.json
  4. gpt moment_dtype=bfloat16 A/B               -> bench_gpt_bf16m.json
  5. decode bench (jnp path)                     -> bench_decode.json
  6. fusion audit (gpt + resnet optimized HLO)   -> fusion_audit.md

Usage: python tools/tpu_campaign.py [--skip N,M] [--only N]
Each stage prints PASS/FAIL + seconds; stop/resume freely — stages are
independent. After a FAIL the campaign reprobes the backend and stops
if the terminal is wedged (leaving earlier artifacts intact).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "campaign_out")

sys.path.insert(0, REPO)
from bench import _proc_starttime  # noqa: E402  (single owner of the
#                                     'pid starttime' pidfile format)


def run(cmd, timeout, log_name, env_extra=None):
    os.makedirs(OUT, exist_ok=True)
    log_path = os.path.join(OUT, log_name)
    env = dict(os.environ)
    # stages must not trigger bench.py's driver-preemption path (which
    # exists to kill *us* when the round-end driver bench starts)
    env["CAMPAIGN_CHILD"] = "1"
    # per-stage telemetry dir: bench workers (and telemetry_smoke)
    # write telemetry.jsonl + metrics.json here, next to <stage>.log —
    # validate_stages checks completed stages produced a parseable one.
    # Cleared first: the worker-side finalize MERGES an existing
    # metrics.json (same-run multi-worker stages), so a previous run's
    # leftovers would pollute this run's counters and keep a
    # historical unexpected-retrace in the report forever
    tele_dir = os.path.join(OUT, "telemetry",
                            os.path.splitext(log_name)[0])
    shutil.rmtree(tele_dir, ignore_errors=True)
    env["BENCH_TELEMETRY_DIR"] = tele_dir
    env.update(env_extra or {})
    pid_path = os.path.join(OUT, "current_stage.pid")
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=REPO, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True, env=env)
        try:
            # "pid starttime": the kernel starttime (field 22 of
            # /proc/<pid>/stat) lets the driver-bench preemptor prove
            # the pid was not recycled before it SIGKILLs the group
            with open(pid_path, "w") as f:
                f.write(f"{proc.pid} {_proc_starttime(proc.pid)}")
        except OSError:
            pass
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                proc.kill()
            proc.wait()
            rc = "timeout"
        finally:
            try:
                os.remove(pid_path)
            except OSError:
                pass
    dt = round(time.monotonic() - t0, 1)
    tail = open(log_path).read()[-400:]
    return rc, dt, tail


def last_json(log_name):
    try:
        for line in reversed(open(os.path.join(OUT, log_name)).readlines()):
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)
    except (OSError, json.JSONDecodeError):
        pass
    return None


PY = sys.executable

DRIVER_MARKER = os.path.join(OUT, "driver_bench_active")


def _driver_bench_active(max_age_s=45 * 60):
    """True while the round-end driver bench holds the chip (marker is
    removed on its clean exit; mtime bounds a crashed run's hold)."""
    try:
        return (time.time() - os.path.getmtime(DRIVER_MARKER)) < max_age_s
    except OSError:
        return False

STAGES = [
    ("probe", [PY, "bench.py", "--worker", "probe"], 600, {}),
    # static invariant sweep (ISSUE 13, CPU, seconds): tools/tpulint
    # over paddle_tpu/ + tools/ + bench.py — trace-safety, durability,
    # concurrency, telemetry-JSON and doc-catalogue contracts checked
    # BEFORE any chaos stage burns minutes discovering the same bug at
    # runtime. No chip time; the stage's lint_report.json lands
    # in its telemetry dir (the CLI honors BENCH_TELEMETRY_DIR) where
    # validate_stages requires non_baselined == 0.
    ("staticcheck", [PY, "-m", "tools.tpulint", "--json"], 600,
     {"JAX_PLATFORMS": "cpu"}),
    # resilience chaos drill (ISSUE 3): fault-injection suite with a
    # fixed seed, forced onto CPU — it validates the build's failure
    # handling (guard/rollback, preemption resume, serving
    # degradation) WITHOUT spending chip time, so it runs first
    ("chaos_smoke", [PY, "-m", "pytest", "tests/test_resilience.py",
                     "-q", "-m", "chaos", "-p", "no:cacheprovider",
                     "-p", "no:randomly"], 1800,
     {"JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": "0"}),
    # observability drill (ISSUE 4, CPU): 5-step guarded fit + serve
    # wave, asserts the metric catalogue + zero unexpected retraces and
    # writes the same telemetry.jsonl/metrics.json shape bench stages do
    ("telemetry_smoke", [PY, "tools/telemetry_smoke.py"], 1200,
     {"JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": "0"}),
    # fleet chaos drill (ISSUE 6 + 8 + 9, CPU): in-process serving
    # replicas under a seeded fault wave (replica crash/wedge/slow,
    # flaky transport, drain/rejoin, hedging, shed storms, router
    # crash + journal disk faults) — asserts 100% request completion
    # with token-exact failover dedup, one causally-linked trace tree
    # per request with attribution within tolerance, SLO burn-rate
    # alerting, exactly-once delivery across router crashes, and 0
    # unexpected retraces fleet-wide. The stage exports a merged fleet
    # metrics.json that the fleet canary gate below diffs against the
    # committed golden (which therefore also covers the
    # fleet_journal_* recovery counters).
    # (PADDLE_TPU_RUN_SLOW=1 unmasks the slow-marked real-subprocess
    # supervisor drills so the canary golden also covers the
    # fleet_respawns/crash_loops/boot counters.)
    ("fleet_chaos_smoke", [PY, "-m", "pytest",
                           "tests/test_fleet_serving.py",
                           "tests/test_fleet_tracing.py",
                           "tests/test_fleet_recovery.py",
                           "tests/test_fleet_proc.py",
                           "tests/test_fleet_autoscale.py",
                           "tests/test_prefix_cache.py",
                           "tests/test_spec_decode.py", "-q",
                           "-m", "chaos", "-p", "no:cacheprovider",
                           "-p", "no:randomly"], 3600,
     {"JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": "0",
      "PADDLE_TPU_RUN_SLOW": "1"}),
    # router durability drill in isolation (ISSUE 9, CPU): seeded
    # kill-router-mid-wave (crash seam, SIGTERM preemption, torn
    # journal writes, transient disk errors), recover against the
    # same live replicas, assert token-exact + exactly-once + frozen
    # compile counts + a parseable fleet_router_recovery flight dump.
    # DELIBERATELY duplicates the recovery slice inside
    # fleet_chaos_smoke (~4 CPU-minutes): the chaos stage must
    # include these tests so the canary golden covers the
    # fleet_journal_* counters, while this stage gives the durability
    # path its own pass/fail line + flight-dump validation
    # (validate_stages.FLIGHT_STAGES) for fast triage.
    ("fleet_recovery_smoke", [PY, "-m", "pytest",
                              "tests/test_fleet_recovery.py", "-q",
                              "-m", "chaos", "-p", "no:cacheprovider",
                              "-p", "no:randomly"], 1800,
     {"JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": "0"}),
    # process-supervision drill in isolation (ISSUE 10, CPU): REAL
    # subprocess replicas — kill -9 mid-decode → router failover +
    # supervisor respawn + warm-boot health-gated rejoin (token-exact,
    # zero steady-state recompiles), a persistent exit-at-boot seed
    # tripping the crash-loop breaker (quarantine + flight dump),
    # SIGTERM child drain, slow-boot gate kills. DELIBERATELY overlaps
    # the proc slice inside fleet_chaos_smoke (golden/canary coverage
    # vs fast triage — the same split fleet_recovery_smoke uses), and
    # its own pass/fail line validates flight dumps
    # (validate_stages.FLIGHT_STAGES).
    ("fleet_supervisor_smoke", [PY, "-m", "pytest",
                                "tests/test_fleet_proc.py", "-q",
                                "-m", "chaos", "-p",
                                "no:cacheprovider", "-p",
                                "no:randomly"], 2400,
     {"JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": "0",
      "PADDLE_TPU_RUN_SLOW": "1"}),
    # telemetry-history / tenancy / anomaly-sentinel drill (ISSUE 11,
    # CPU, seeded): a clean golden wave the sentinel must stay quiet
    # on (including a replay over the COMMITTED clean golden archive
    # tools/golden/history_clean_wave.json — band drift that alarms
    # on known-good history fails here), then a wave with an injected
    # mid-wave latency regression the sentinel MUST fire on (leaving
    # a parseable fleet_anomaly flight dump); per-tenant token totals
    # must sum EXACTLY to fleet counters and compile counts stay
    # frozen with accounting on. The stage's history_snapshot.json is
    # then driven through the history gate below (metrics_diff
    # --history --at/--vs): quiet span clean, regression span trips.
    ("history_smoke", [PY, "tools/history_smoke.py"], 1800,
     {"JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": "0"}),
    # traffic capture & deterministic replay drill (ISSUE 12, CPU,
    # seeded): the committed 20-request wave
    # (tools/golden/replay_wave.json) is captured live through a
    # capture-armed fleet (archive complete, zero capture<->trace
    # sampling divergences, compile counts frozen with capture on),
    # the COMMITTED archive replays golden (token-exact per rid, zero
    # new XLA traces), the live capture replays clean under the
    # default verdict gates (per-hop attribution deltas within 5%),
    # and an injected replica_slow regression MUST trip the same gate
    # spec — both gate directions proven, vacuity-guarded. Artifacts:
    # replay_verdict.json + replay_verdict_regression.json + the
    # capture archive, next to the stage's metrics.json.
    ("replay_smoke", [PY, "tools/replay_smoke.py"], 1800,
     {"JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": "0"}),
    # elastic autoscaling drill (ISSUE 15, CPU, seeded): a one-replica
    # fleet under a pinned-slow burst — multi-window TTFT burn fires →
    # scale-out through the warm-boot gate (adopted replica takes
    # traffic with zero new steady-state traces), recovery + budget
    # refill + idle hold → scale-in (hedge-safe drain → remove).
    # Asserts no lost rid (exactly-once), ok results token-exact vs
    # an uninterrupted golden, bounded SLO breach, zero flaps, frozen
    # compile counts, scale_out/scale_in journal records reconcile,
    # and parseable fleet_scale_out/in flight dumps
    # (validate_stages.FLIGHT_STAGES).
    ("autoscale_smoke", [PY, "tools/autoscale_smoke.py"], 1800,
     {"JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": "0"}),
    # copy-on-write prefix-cache drill (ISSUE 16, CPU, seeded): a
    # shared-prefix wave through a cache-ON engine vs a cache-OFF
    # control — ON streams token-exact vs OFF across two waves (the
    # hard invariant), cumulative page hit rate >= 0.5, ON TTFT p50
    # strictly below OFF (hits run the short tail-prefill ladder, not
    # the full bucket), compile counts frozen with caching ON (zero
    # unexpected retraces), and every page back on the free list
    # after close (shared-page refcounts conserve).
    ("prefix_cache_smoke", [PY, "tools/prefix_cache_smoke.py"], 1800,
     {"JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": "0"}),
    # speculative-decoding drill (ISSUE 20, CPU, seeded): a long-decode
    # wave through a spec-ON engine (K=8, ngram prompt-lookup draft)
    # vs a spec-OFF control at steps_per_dispatch=1 — ON streams
    # token-exact vs OFF (the hard invariant: speculation may change
    # latency, never tokens), cumulative draft acceptance >= 0.5,
    # ON decode tok/s strictly above OFF (an accepting dispatch
    # commits up to K+1 tokens against one folded-batch verify),
    # compile counts frozen with speculation ON (the verify scan is
    # pre-traced by warmup), zero unexpected retraces.
    ("spec_smoke", [PY, "tools/spec_smoke.py"], 1800,
     {"JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": "0"}),
    # continuous-profiling drill (ISSUE 22, CPU, seeded): a wave
    # through a profiler-ARMED engine — compile counts frozen with
    # profiling ON (the sampler is host-side only), serving-phase
    # markers observed live on the dispatch path (decode + a prefill
    # bucket), self-measured overhead at/under the 1% duty-cycle cap,
    # /profile endpoint + flamegraph HTML render from the same run,
    # and the profile_diff gate proven BOTH directions (clean-vs-clean
    # passes, an injected decode busy-loop trips phase:decode>+10%).
    ("profile_smoke", [PY, "tools/profile_smoke.py"], 1800,
     {"JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": "0"}),
    # device-memory ledger drill (HBM ledger round, CPU, seeded): a
    # prefix-hitting wave through a ledger-ARMED engine — compile
    # counts frozen with accounting ON (track/release is host-side
    # dict arithmetic), typed segments + unattributed residual
    # conserve against ground truth within 1%, /memory endpoint +
    # engine_mem_* gauges render live, the residual alarm stays QUIET
    # on the clean wave, and the leak drill (an untracked device page
    # block + pages popped off the free list, never returned) must
    # trip BOTH the residual alarm and the mem_diff gate
    # (clean-vs-clean passes, clean-vs-leaked fails
    # segment:unattributed>+50%).
    ("mem_smoke", [PY, "tools/mem_smoke.py"], 1800,
     {"JAX_PLATFORMS": "cpu", "PYTHONHASHSEED": "0"}),
    # AOT serving-artifact boot probe (ISSUE 21, seeded): traced
    # warmup control -> export_artifact -> warm_boot a second engine
    # off the store. Asserts the artifact path was taken (mode=aot,
    # zero fallbacks), token-exact generation vs the traced control,
    # zero post-boot traces, and artifact boot wall strictly below
    # traced. Needs the chip: the boot walls are device numbers.
    ("aot_boot", [PY, "tools/aot_boot_probe.py"], 1800,
     {"PYTHONHASHSEED": "0"}),
    ("bench_full", [PY, "bench.py"], 7200, {}),
    ("bench_resnet_s2d", [PY, "bench.py", "--model", "resnet50", "--s2d"],
     2400, {}),
    # NHWC-native conv stack + Pallas fused bottleneck: the round-6
    # "win ResNet" levers. NCHW baseline is
    # bench_full's resnet50; these are the two rungs on top.
    ("bench_resnet_nhwc", [PY, "bench.py", "--model", "resnet50",
                           "--layout", "nhwc"], 2400, {}),
    ("bench_resnet_nhwc_fused", [PY, "bench.py", "--model", "resnet50",
                                 "--layout", "nhwc",
                                 "--fused-bottleneck"], 2400, {}),
    # s2d stem stacked on the NHWC pipeline (the stems compose)
    ("bench_resnet_nhwc_s2d", [PY, "bench.py", "--model", "resnet50",
                               "--layout", "nhwc", "--s2d"], 2400, {}),
    ("bench_gpt_bf16m", [PY, "bench.py", "--model", "gpt",
                         "--moment-dtype", "bfloat16"], 2400, {}),
    # continuous-batching serving ladder (nlp/serving.py): batch x
    # cache-dtype cross product, zero-recompile asserted per rung.
    ("bench_serve_gpt", [PY, "bench.py", "--serve"], 3600, {}),
    ("bench_serve_llama", [PY, "bench.py", "--serve", "--serve-model",
                           "llama"], 3600, {}),
    # llama pretrain: the GQA flagship's first-ever training number
    ("bench_llama", [PY, "bench.py", "--model", "llama"], 2400, {}),
    ("bench_decode", [PY, "bench.py", "--decode"], 2400, {}),
    ("bench_decode_bf16kv", [PY, "bench.py", "--decode",
                             "--cache-dtype", "bfloat16"], 2400, {}),
    ("bench_decode_int8", [PY, "bench.py", "--decode", "--weight-only",
                           "int8", "--cache-dtype", "bfloat16"], 2400,
     {}),
    ("bench_decode_bf16w", [PY, "bench.py", "--decode", "--serve-dtype",
                            "bfloat16", "--cache-dtype", "bfloat16"],
     2400, {}),
    ("bench_decode_int4", [PY, "bench.py", "--decode", "--weight-only",
                           "int4", "--cache-dtype", "bfloat16"], 2400,
     {}),
    # dense Pallas flash-decode kernel inside generate() (env-gated)
    ("bench_decode_flashk", [PY, "bench.py", "--decode", "--cache-dtype",
                             "bfloat16"], 2400,
     {"PADDLE_TPU_FLASH_DECODE": "1"}),
    # flash rungs of the serving ladder alone (the paged Pallas kernel
    # is proven on the chip by chip_smoke.py); --flash-only skips the
    # ref rungs bench_serve_gpt already measured
    ("bench_serve_flashk", [PY, "bench.py", "--serve", "--flash-only"],
     3600, {"PADDLE_TPU_FLASH_DECODE": "1"}),
    ("fusion_audit", [PY, "tools/fusion_audit.py", "--out",
                      "campaign_out/fusion_audit.md"], 3600, {}),
    ("fusion_audit_nhwc", [PY, "tools/fusion_audit.py", "--model",
                           "resnet", "--layout", "nhwc",
                           "--fused-bottleneck", "--out",
                           "campaign_out/fusion_audit_nhwc.md"], 3600,
     {}),
    ("resnet_roofline", [PY, "tools/resnet_roofline.py"], 2400, {}),
    # serving throughput +/- conv-bn folding (conv_bn_fuse_pass parity)
    ("bench_resnet_serve", [PY, "bench.py", "--model", "resnet50",
                            "--serve"], 2400, {}),
    ("bench_resnet_serve_fold", [PY, "bench.py", "--model", "resnet50",
                                 "--serve", "--fold-bn"], 2400, {}),
    # training-throughput attempts the r4 verdict asked for
    ("bench_resnet_b512", [PY, "bench.py", "--model", "resnet50",
                           "--batch", "512"], 2400, {}),
    # retry queue: stages the one 2026-07-31 hardware window never
    # reached
    ("bench_gpt13b", [PY, "bench.py", "--model", "gpt-1.3b"], 2400, {}),
    # scan-over-layers variant: O(1-block) program, compiles in a
    # fraction of the unrolled step's time
    ("bench_gpt13b_scan", [PY, "bench.py", "--model", "gpt-1.3b",
                           "--scan-layers"], 2400, {}),
    # + fused head/loss: the [N,vocab] logits never materialize —
    # the memory headroom lever for bigger 1.3B batches
    ("bench_gpt13b_scan_cce", [PY, "bench.py", "--model", "gpt-1.3b",
                               "--scan-layers", "--chunked-ce", "2048"],
     2400, {}),
    ("bench_gpt_chunkedce", [PY, "bench.py", "--model", "gpt",
                             "--chunked-ce", "2048"], 2400, {}),
    # one-HBM-pass Pallas optimizer update A/B (step anatomy: the
    # jnp AdamW chain ran at ~2x its bandwidth floor)
    ("bench_gpt_fusedadamw", [PY, "bench.py", "--model", "gpt",
                              "--fused-adamw"], 2400, {}),
    # headline batch-scaling probe: MFU 0.40 at b8 — check whether b16
    # lifts backward-pass efficiency (fits: 345M + Adam fp32 ~4.2 GB,
    # acts at b16 s1024 with flash ~4 GB)
    ("bench_gpt_b16", [PY, "bench.py", "--model", "gpt", "--batch", "16"],
     2400, {}),
    # fused [h,3h] qkv matmul A/B on the headline config
    ("bench_gpt_fusedqkv", [PY, "bench.py", "--model", "gpt",
                            "--fused-qkv"], 2400, {}),
    # fused residual-add+LayerNorm Pallas pass A/B (elementwise-HBM
    # lever from the r4 step anatomy)
    ("bench_gpt_fusedln", [PY, "bench.py", "--model", "gpt",
                           "--fused-ln"], 2400, {}),
    ("bench_gpt_fusedboth", [PY, "bench.py", "--model", "gpt",
                             "--fused-ln", "--fused-qkv"], 2400, {}),
    ("bench_ernie_fusedqkv", [PY, "bench.py", "--model", "ernie",
                              "--fused-qkv"], 2400, {}),
    ("bench_ernie_fusedln", [PY, "bench.py", "--model", "ernie",
                             "--fused-ln"], 2400, {}),
    # masked-position gather before the MLM head: ~20%% of ERNIE's
    # step FLOPs are vocab logits for unmasked positions
    ("bench_ernie_mlmgather", [PY, "bench.py", "--model", "ernie",
                               "--mlm-gather", "0.25"], 2400, {}),
    # long-context: flash 512-blocks beat XLA fused attention 1.77x at
    # s=4096 (r2 microbench) — measure the end-to-end train step there
    ("bench_gpt_s4k", [PY, "bench.py", "--model", "gpt", "--batch", "2",
                       "--seq", "4096"], 2400, {}),
    ("step_anatomy", [PY, "tools/step_anatomy.py"], 2400, {}),
    ("step_anatomy_fused", [PY, "tools/step_anatomy.py", "--fused-qkv"],
     2400, {}),
    ("step_anatomy_fusedln", [PY, "tools/step_anatomy.py",
                              "--fused-ln"], 2400, {}),
    # single-chip schedule-overhead A/B: ms/tick of FThenB vs
    # interleaved-v2 vs sequential (bounds what pipeline_cost ignores)
    ("pipeline_overhead", [PY, "tools/pipeline_overhead.py"], 2400, {}),
]

# stages addressable via --only but excluded from the default sweep
# (bench_full's workload list already includes gpt-1.3b — running the
# standalone stage too would duplicate up to 2400s of chip time)
RETRY_ONLY = {"bench_gpt13b", "bench_gpt13b_scan", "bench_gpt_b16",
              "bench_decode_flashk", "bench_serve_flashk",
              "bench_gpt_fusedqkv",
              "bench_ernie_fusedqkv", "step_anatomy", "step_anatomy_fused",
              "bench_gpt_s4k", "pipeline_overhead", "bench_gpt_fusedln",
              "bench_gpt_fusedboth", "bench_ernie_fusedln", "bench_resnet_serve",
              "bench_resnet_serve_fold", "bench_resnet_b512",
              "bench_gpt13b_scan_cce", "bench_gpt_chunkedce",
              "step_anatomy_fusedln", "bench_gpt_fusedadamw",
              "bench_ernie_mlmgather", "bench_resnet_nhwc_s2d",
              "fusion_audit_nhwc"}


# fleet canary gate (tools/README): after fleet_chaos_smoke, its
# merged fleet metrics.json is diffed against the committed golden
# with regression thresholds on the rates a canary rollout pages on.
# Thresholds are generous (the chaos wave's exact failover count is
# timing-dependent) — the gate exists to catch a failover/shed STORM
# or a placement-latency cliff, not single-event jitter.
FLEET_CANARY_GOLDEN = os.path.join("tools", "golden",
                                   "fleet_chaos_metrics.json")
FLEET_CANARY_FAIL_ON = (
    "fleet_failovers_total>200%",
    "fleet_shed_total>200%",
    "fleet_placement_wait_seconds:p99>400%",
    # router-durability counters (ISSUE 9): a journal-error or
    # recovery STORM beyond the seeded drills' deterministic counts
    # is a durability regression, not jitter
    "fleet_journal_errors_total>200%",
    "fleet_journal_recovered_requests_total>400%",
    # process-supervision counters (ISSUE 10): respawns beyond the
    # seeded drills' deterministic count = a flapping fleet; ANY
    # crash-loop breaker trip beyond the golden's deliberate one is a
    # self-healing regression (>0% = any increase)
    "fleet_respawns_total>200%",
    "fleet_crash_loops_total>0%",
    # anomaly-sentinel counters (ISSUE 11): any sentinel excursion
    # beyond the golden's count is a live regression the offline gate
    # would otherwise only see post-mortem (series skipped until the
    # golden is regenerated with a sentinel-armed chaos suite); a
    # sampled-out-trace storm likewise means the sampling knob is
    # eating observability
    "fleet_anomaly_fired_total>0%",
    "fleet_traces_sampled_out_total>200%",
    # traffic-capture counters (ISSUE 12): ANY capture write error is
    # a loss of the replay corpus, and ANY capture<->trace sampling
    # divergence means archived requests lost their attribution —
    # both ship-stoppers, not jitter. (Series skipped by metrics_diff
    # until the golden is regenerated with a capture-armed chaos
    # suite — same bootstrap as the sentinel counters above.)
    "fleet_capture_errors_total>0%",
    "fleet_capture_trace_missing_total>0%",
    # elastic-autoscaling counter (ISSUE 15): ANY controller flap
    # (opposite-direction decisions inside flap_window_s) beyond the
    # golden is an oscillating policy — the "never flaps" contract
    # made enforceable. (Overload sheds are NOT gated separately:
    # they count into fleet_shed_total, whose storm gate above
    # already covers them, and their exact count is timing-sensitive
    # on a loaded CI box.)
    "fleet_autoscale_flaps_total>0%",
    # prefix-cache counter (ISSUE 16): the chaos suite's prefix drill
    # produces a deterministic hit count — hits falling >50% below
    # the golden means shared prompts stopped matching (fingerprint
    # or admission regression) while everything else still passes
    # token-exactness. (Series skipped by metrics_diff until the
    # golden is regenerated with the prefix drill in the suite.)
    "fleet_prefix_hits_total<50%",
    # speculative-decoding counter (ISSUE 20): the chaos suite's spec
    # drill produces a deterministic accepted-draft count — acceptance
    # falling >50% below the golden means the flagship stopped
    # confirming drafts (proposer or verify regression) while
    # token-exactness still passes (speculation never changes tokens,
    # so only the acceptance counter can reveal a dead proposer).
    "fleet_spec_accepted_total<50%",
    # continuous-profiling counters (ISSUE 22): the profiler gauges
    # its OWN cost — a duty-cycle ratio above the golden's by >100%
    # means the sampler got expensive (a stack-depth or thread-count
    # explosion), and a truncated-sample STORM means the trie bound
    # is eating the profile (both are observability regressions the
    # flamegraph would silently hide). (Series skipped by
    # metrics_diff until the golden is regenerated with a
    # profiler-armed chaos suite — same bootstrap as the sentinel
    # counters above.)
    "profile_overhead_ratio>100%",
    "profile_samples_dropped_total>200%",
    # device-memory ledger gauge (HBM ledger round): the fleet-max
    # unattributed residual growing >200% past the golden means
    # replicas are allocating device memory the segment tree cannot
    # name — the exact drift the ledger exists to catch, surfaced at
    # the fleet rollup before any single replica OOMs. (Series
    # skipped by metrics_diff until the golden is regenerated with a
    # ledger-armed chaos suite — same bootstrap as the sentinel
    # counters above.)
    "fleet_mem_unattributed_bytes>200%",
)

# history gate (ISSUE 11): ONE archive, two instants, both directions
# proven — the clean span must show no fleet_anomaly_* increase, the
# injected-regression span MUST trip the same spec (a gate that never
# fires is not a gate). Uses the stage's marks.json epoch marks.
HISTORY_GATE_FAIL_ON = ("fleet_anomaly_fired_total>0%",)


def run_history_gate(stage_name):
    """Drive tools/metrics_diff.py --history over the stage's
    archive at its clean/regression marks; leave history_verdict.json
    (required by tools/validate_stages.py on _history_gate-marked
    summaries). ok = clean span quiet AND regression span tripped."""
    tele = os.path.join(OUT, "telemetry", stage_name)
    snap = os.path.join(tele, "history_snapshot.json")
    verdict = {"gate": "history", "snapshot": snap,
               "fail_on": list(HISTORY_GATE_FAIL_ON)}
    try:
        with open(os.path.join(tele, "marks.json")) as f:
            marks = json.load(f)

        def gate(t0, t1):
            cmd = [PY, "tools/metrics_diff.py", "--history", snap,
                   "--at", repr(float(t0)), "--vs", repr(float(t1)),
                   "--quiet"]
            for spec in HISTORY_GATE_FAIL_ON:
                cmd += ["--fail-on", spec]
            proc = subprocess.run(cmd, cwd=REPO, capture_output=True,
                                  text=True, timeout=120)
            lines = [l for l in proc.stdout.strip().splitlines() if l]
            return json.loads(lines[-1]) if lines else {"ok": False}

        clean = gate(marks["t0"], marks["t_clean"])
        regression = gate(marks["t_clean"], marks["t_end"])
        # vacuity guard: the gated series must actually be present in
        # the clean-span diff — a quiet verdict over snapshots that
        # never carried fleet_anomaly_* would prove nothing
        covered = any(k.startswith("fleet_anomaly_fired_total")
                      for k in (clean.get("counters") or {}))
        verdict["clean_span"] = {"ok": clean.get("ok"),
                                 "covered": covered,
                                 "failures": clean.get("failures")}
        verdict["regression_span"] = {
            "ok": regression.get("ok"),
            "failures": regression.get("failures")}
        verdict["ok"] = bool(clean.get("ok")) and covered \
            and not regression.get("ok")
    except Exception as e:  # noqa: BLE001 — the gate must leave a
        #                     verdict either way
        verdict.update(ok=False, error=f"{type(e).__name__}: {e}")
    os.makedirs(tele, exist_ok=True)
    with open(os.path.join(tele, "history_verdict.json"), "w") as f:
        json.dump(verdict, f, indent=1)
    return verdict


def run_fleet_canary_gate(stage_name):
    """Run tools/metrics_diff.py golden-vs-stage and leave the
    verdict file tools/validate_stages.py requires
    (telemetry/<stage>/canary_verdict.json). Returns the verdict."""
    tele = os.path.join(OUT, "telemetry", stage_name)
    candidate = os.path.join(tele, "metrics.json")
    cmd = [PY, "tools/metrics_diff.py", FLEET_CANARY_GOLDEN,
           candidate, "--quiet"]
    for spec in FLEET_CANARY_FAIL_ON:
        cmd += ["--fail-on", spec]
    try:
        proc = subprocess.run(cmd, cwd=REPO, capture_output=True,
                              text=True, timeout=120)
        lines = [l for l in proc.stdout.strip().splitlines() if l]
        verdict = json.loads(lines[-1]) if lines \
            else {"ok": False, "error": "metrics_diff emitted nothing"}
    except Exception as e:  # noqa: BLE001 — the gate must leave a
        #                     verdict either way
        verdict = {"ok": False, "error": f"{type(e).__name__}: {e}"}
    verdict["gate"] = "fleet_canary"
    verdict["golden"] = FLEET_CANARY_GOLDEN
    verdict["fail_on"] = list(FLEET_CANARY_FAIL_ON)
    os.makedirs(tele, exist_ok=True)
    with open(os.path.join(tele, "canary_verdict.json"), "w") as f:
        json.dump(verdict, f, indent=1)
    return verdict


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated stage names to run")
    ap.add_argument("--skip", default="",
                    help="comma-separated stage names to skip")
    args = ap.parse_args()
    only = args.only.split(",") if args.only else None
    skip = set(args.skip.split(",")) if args.skip else set()
    scale = float(os.environ.get("CAMPAIGN_TIMEOUT_SCALE", "1"))
    # _captured_at orders archived summaries reliably (file mtimes
    # collapse after a fresh checkout; bench.py's null-run diagnostic
    # sorts on this). Dict-shaped so readers iterating stage entries
    # skip it via the missing "ok" key.
    # _telemetry marks a summary produced by a campaign that exports
    # per-stage telemetry dirs — validate_stages only enforces the
    # metrics.json check on such summaries (a pre-telemetry archive
    # must not read as an observability regression). _flightrec
    # likewise marks that chaos-family stages dump crash flight
    # records into their telemetry dir (round-10 introspection layer)
    # _fleet_canary marks a campaign whose fleet_chaos_smoke stage is
    # gated by the metrics_diff canary diff — validate_stages requires
    # the gate's verdict file on such summaries. _history_gate
    # likewise marks that history_smoke is gated by the two-instant
    # history diff (run_history_gate)
    summary = {"_captured_at": {"epoch": int(time.time())},
               "_telemetry": 1, "_flightrec": 1, "_fleet_canary": 1,
               "_history_gate": 1}
    stages = [s for s in STAGES if s[0] not in RETRY_ONLY]
    if only:  # run in the order the caller listed, not STAGES order
        by_name = {s[0]: s for s in STAGES}
        unknown = [n for n in only if n not in by_name]
        if unknown:
            sys.exit(f"unknown stage(s): {unknown}; "
                     f"known: {sorted(by_name)}")
        stages = [by_name[n] for n in only]
    for name, cmd, timeout, env in stages:
        timeout = max(10, int(timeout * scale))
        if name in skip:
            continue
        if _driver_bench_active():
            print("driver bench owns the chip — campaign yields "
                  "(remaining stages left pending)", flush=True)
            break
        print(f"=== {name} (timeout {timeout}s) ===", flush=True)
        rc, dt, tail = run(cmd, timeout, f"{name}.log", env)
        parsed = last_json(f"{name}.log")
        ok = rc == 0
        summary[name] = {"ok": ok, "rc": rc, "seconds": dt,
                         "ended_at": int(time.time()), "result": parsed}
        if name == "fleet_chaos_smoke" and ok:
            verdict = run_fleet_canary_gate(name)
            gate_ok = bool(verdict.get("ok"))
            summary[name]["canary"] = {
                "ok": gate_ok,
                "failures": verdict.get("failures", []),
                "error": verdict.get("error")}
            if not gate_ok:
                ok = summary[name]["ok"] = False
                print("=== fleet canary gate FAILED: "
                      f"{verdict.get('failures') or verdict.get('error')}"
                      " ===", flush=True)
        if name == "history_smoke" and ok:
            verdict = run_history_gate(name)
            gate_ok = bool(verdict.get("ok"))
            summary[name]["history_gate"] = {
                "ok": gate_ok,
                "clean_span": verdict.get("clean_span"),
                "regression_span": verdict.get("regression_span"),
                "error": verdict.get("error")}
            if not gate_ok:
                ok = summary[name]["ok"] = False
                print("=== history gate FAILED: "
                      f"{json.dumps(verdict)[:300]} ===", flush=True)
        print(f"=== {name}: rc={rc} {dt}s "
              f"{json.dumps(parsed) if parsed else tail[-150:]!r} ===",
              flush=True)
        with open(os.path.join(OUT, "summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
        if not ok and name != "probe":
            rc2, _, _ = run([PY, "bench.py", "--worker", "probe"],
                            max(10, int(600 * scale)), "reprobe.log")
            if rc2 != 0:
                print("backend wedged after failure — stopping campaign "
                      "(earlier artifacts kept)", flush=True)
                break
        if name == "probe" and not ok:
            print("backend unreachable — campaign aborted", flush=True)
            break
    print(json.dumps(summary))
    # nonzero exit when anything failed or was never reached, so a
    # wrapper never reads a half-done campaign as success
    stage_rows = {k: v for k, v in summary.items()
                  if not k.startswith("_")}
    ran_all = all(s["ok"] for s in stage_rows.values()) and \
        len(stage_rows) == len([s for s in stages if s[0] not in skip])
    sys.exit(0 if ran_all else 1)


if __name__ == "__main__":
    main()
