"""fleet_replay — deterministic open-loop replay of captured fleet
traffic: the scoring harness for every autotune/autoscale what-if.

The capture half (``observability/trafficrec.py``, armed via
``FleetRouter(capture=dir)``) archives every admitted request with its
arrival offset, prompt, tenant/priority/deadline and — at resolve —
its output tokens and per-hop latency attribution. This tool re-drives
a fresh fleet from such an archive (or from a seeded synthetic wave)
and emits a ``replay_verdict.json`` scoring the replay against the
original:

- **open-loop arrivals**: requests are submitted at their recorded
  offsets regardless of completions (the load generator never
  back-pressures itself — queueing behaviour is part of what is
  being measured). ``--mode scaled --time-scale 0.5`` compresses the
  schedule 2x; ``--mode rate --rate 50`` re-spaces arrivals uniformly
  at 50 req/s — the "what if this traffic came faster" drills;
- **what-if knob overrides** (``--knob k=v``, repeatable): router
  knobs (``hedge_after_ms``, ``max_queue``, ``replica_queue_limit``,
  ``placement.<weight>``, the overload/brownout controller's
  ``overload_target_ms``/``brownout_*``) and engine knobs
  (``steps_per_dispatch``, ``page_size`` — the prefill-bucket-ladder
  granularity — ``max_slots``, ``max_seq_len``, ``temperature``,
  ``top_k``, ``seed``, ``prefix_cache``, ``min_prefix_pages``) —
  score a knob setting against recorded traffic without touching
  production. ``placement.prefix_affinity`` scores prefix-affinity
  routing offline; the verdict's ``prefix_stats`` section reports
  what the replay fleet's caches did (hit rate, pages shared, TTFT
  ratios), and ``--report-prefix-stats`` scans the archive's
  recorded prompts WITHOUT replaying — the expected page-level hit
  rate per page-size/min-prefix knob, the measure-before-build
  number. ``autoscale.<param>`` knobs
  (``autoscale.max_replicas=3 autoscale.scale_out_cooldown_s=0.5``
  ...) additionally arm a FleetAutoscaler over the replay fleet, so
  an autoscaling POLICY is scorable offline against a recorded
  archive — the verdict grows an ``autoscale`` section (decision
  events, flap count, final fleet size) and spawned replicas join
  the zero-new-traces math with their adoption-time frozen counts;
- **golden mode** (``--golden``): asserts token-exact outputs per
  original rid (valid when seeds/params match — greedy decoding and
  the same weights make replay bit-deterministic) and ZERO new XLA
  traces across the replay (every wave bucket is pre-warmed, compile
  counts frozen after warmup);
- **the verdict**: side-by-side SLO quantiles (TTFT/e2e p50/p99 from
  the per-request records, cross-checked against the replay fleet's
  live history plane), per-hop attribution shares (original vs
  replay, deltas), and gates — ``hop_share_delta`` (default 5%),
  ``e2e_p99_ratio``/``ttft_p99_ratio`` (replay vs original) — whose
  failures flip ``ok`` to false. The replay fleet captures its own
  archive, so original and replay are compared in the same format.

Usage:

  python tools/fleet_replay.py --archive /path/to/capture \
      --golden --out replay_verdict.json
  python tools/fleet_replay.py --archive ... --knob hedge_after_ms=50 \
      --knob placement.queued=16
  python tools/fleet_replay.py --synth 20 --synth-seed 7 \
      --write-wave wave.json           # seeded synthetic wave drill

Importable: tools/replay_smoke.py and tests drive ``synth_wave`` /
``build_fleet`` / ``replay`` / ``make_verdict`` directly.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

DEFAULT_GATES = {
    # per-hop attribution share delta (fraction of total e2e a hop
    # explains, original vs replay) — the ISSUE-12 5% clean-wave bar
    "hop_share_delta": 0.05,
    # replay-vs-original latency regression ratios (a replay that is
    # FASTER never trips; slower than these multiples does)
    "e2e_p99_ratio": 1.5,
    "ttft_p99_ratio": 1.5,
    # absolute slack under the ratio gates: a ratio trips only when
    # the replay is ALSO slower by at least this much — a 1.5x on a
    # 7 ms p99 is scheduler noise, a 1.5x on 200 ms is a regression
    "latency_floor_s": 0.05,
}

ROUTER_KNOBS = {"hedge_after_ms", "max_queue", "replica_queue_limit",
                "wedge_timeout_s", "overload_target_ms",
                "overload_interval_s", "brownout_max_new",
                "brownout_levels", "brownout_step_s"}
ENGINE_KNOBS = {"steps_per_dispatch", "page_size", "max_slots",
                "max_seq_len", "temperature", "top_k", "seed",
                "num_pages", "prefix_cache", "min_prefix_pages"}
# --knob autoscale.<param>: arms a FleetAutoscaler over the replay
# fleet (spawn_fn builds extra warmed replicas up to max_replicas) so
# an autoscale POLICY is scorable against a recorded archive — the
# verdict grows an "autoscale" section (events, flaps, final size)
AUTOSCALE_KNOBS = {"min_replicas", "max_replicas",
                   "scale_out_cooldown_s", "scale_in_cooldown_s",
                   "recovery_hold_s", "budget_floor", "scale_in_util",
                   "boot_timeout_s", "retire_timeout_s",
                   "flap_window_s"}


# -- wave sources ----------------------------------------------------------


def synth_wave(seed, n, *, burst=4, burst_gap_s=0.05,
               prompt_lens=((4, 21, 3.0), (22, 40, 1.0)),
               tenants=("tenant-0", "tenant-1", "tenant-2"),
               priorities=(0, 0, 0, 1), max_new=8, eos=None,
               vocab=256):
    """Seeded synthetic traffic wave in the archive-entry shape.

    Bursty arrivals (``burst`` requests per pulse, pulses
    ``burst_gap_s`` apart), a weighted prompt-length mixture
    (``(lo, hi, weight)`` ranges), and tenant/priority blends — the
    scale-drill generator for fleets with no recorded traffic yet.
    Pure stdlib ``random.Random(seed)``: the same seed replays the
    same wave bit-identically on any box."""
    rng = random.Random(int(seed))
    ranges = [(int(lo), int(hi), float(w))
              for lo, hi, w in prompt_lens]
    total_w = sum(w for _, _, w in ranges) or 1.0
    entries = []
    for i in range(int(n)):
        r = rng.random() * total_w
        lo, hi = ranges[-1][:2]
        for rlo, rhi, w in ranges:
            if r < w:
                lo, hi = rlo, rhi
                break
            r -= w
        plen = rng.randint(lo, max(hi, lo))
        entries.append({
            "rid": i,
            "arrival_s": round((i // int(burst)) * float(burst_gap_s),
                               6),
            "tenant": rng.choice(list(tenants)) if tenants else None,
            "priority": int(rng.choice(list(priorities))),
            "deadline_ms": None,
            "prompt": [rng.randrange(int(vocab)) for _ in range(plen)],
            "max_new": int(max_new), "eos": eos,
            "status": None, "tokens": None, "ttft_s": None,
            "e2e_s": None, "hops": None, "failovers": 0,
            "hedged": False, "replica": None})
    return entries


def load_wave(path):
    """Entries from a capture-archive DIRECTORY (trafficrec) or a
    committed wave FILE (replay_wave.json: {"entries": [...]}) —
    returns (entries, meta, stats)."""
    if os.path.isdir(path):
        from paddle_tpu.observability.trafficrec import load_archive
        return load_archive(path)
    with open(path) as f:
        doc = json.load(f)
    return (doc.get("entries") or [], doc.get("meta") or {},
            {"segments": 0, "records": len(doc.get("entries") or []),
             "torn_drops": 0, "unresolved": 0})


# -- prefix-cache what-if scan ---------------------------------------------


def prefix_stats(entries, *, page_sizes=(8, 16, 32), min_pages=1):
    """Expected page-level prefix-cache hit rate of a recorded wave,
    per page-size knob — the measure-BEFORE-build number (r19).

    Replays the archive's prompts in arrival order against an ideal
    single-replica index: a request's leading pages hit when an
    earlier request already published the same fingerprint chain.
    This is the upper bound a real fleet approaches as affinity
    routing concentrates each fingerprint on one replica; no engine
    (or jax) is involved — pure host-side hashing."""
    from paddle_tpu.nlp.paged_cache import prefix_fingerprints
    order = sorted(range(len(entries)),
                   key=lambda i: (float(entries[i].get("arrival_s")
                                        or 0.0), i))
    mp = max(int(min_pages), 1)
    out = {}
    for ps in page_sizes:
        seen = set()
        pages = hit_pages = reqs = reqs_shareable = reqs_hit = 0
        for i in order:
            fps = prefix_fingerprints(
                entries[i].get("prompt") or [], int(ps))
            reqs += 1
            pages += len(fps)
            if len(fps) >= mp:
                reqs_shareable += 1
            matched = 0
            for fp in fps:
                if fp not in seen:
                    break
                matched += 1
            if matched >= mp:
                hit_pages += matched
                reqs_hit += 1
            seen.update(fps)
        out[str(int(ps))] = {
            "page_size": int(ps), "min_prefix_pages": mp,
            "requests": reqs, "shareable_requests": reqs_shareable,
            "expected_hit_requests": reqs_hit,
            "shareable_pages": pages,
            "expected_hit_pages": hit_pages,
            "expected_page_hit_rate": None if not pages
            else round(hit_pages / pages, 4)}
    return out


# -- speculative-decoding what-if scan -------------------------------------


def spec_stats(entries, *, k_values=(2, 4, 8), nmin=1, nmax=3):
    """Expected speculative-decoding acceptance of a recorded wave,
    per ``spec.k`` knob — the measure-BEFORE-build number (r20).

    Replays each archived request's RECORDED token stream through the
    ngram proposer's exact matching rule (prompt-lookup over prompt +
    generated-so-far): at every speculative round the proposer drafts
    K tokens and the recorded stream itself adjudicates how many land
    — the target model never runs, so this is pure host work, and
    because accepted tokens are bit-identical to plain decode the
    recorded stream IS what verify would have sampled. Reports per-K
    acceptance rate and expected committed tokens per verify dispatch
    (>= 1 + acceptance * K intuition, measured exactly)."""
    from paddle_tpu.nlp.speculative import _ngram_propose
    out = {}
    for k in k_values:
        k = int(k)
        rounds = proposed = accepted = committed = streams = 0
        for e in entries:
            toks = [int(t) for t in (e.get("tokens") or [])]
            if len(toks) < 2:
                continue
            streams += 1
            ctx = [int(t) for t in (e.get("prompt") or [])] + toks[:1]
            i = 1                     # first token rides prefill
            while i < len(toks):
                drafts = _ngram_propose(ctx, k, -1, nmin, nmax)
                rounds += 1
                proposed += k
                com = 0
                for j in range(k + 1):
                    t = toks[i]
                    ctx.append(t)
                    com += 1
                    i += 1
                    hit = j < k and drafts[j] == t
                    if hit:
                        accepted += 1
                    if i >= len(toks) or not hit:
                        break
                committed += com
        out[str(k)] = {
            "k": k, "streams": streams, "rounds": rounds,
            "proposed": proposed, "accepted": accepted,
            "acceptance_rate": None if not proposed
            else round(accepted / proposed, 4),
            "tokens_per_dispatch": None if not rounds
            else round(committed / rounds, 4)}
    return out


# -- fleet construction ----------------------------------------------------


def parse_knobs(pairs):
    """--knob k=v pairs -> (router_kw, engine_kw, placement_weights,
    autoscale_kw). Unknown knobs fail loudly — a typo'd what-if is
    not a what-if. Any ``autoscale.<param>`` knob arms an autoscaler
    over the replay fleet (autoscale_kw is None when absent)."""
    router_kw, engine_kw, weights = {}, {}, {}
    autoscale_kw = None
    for pair in pairs or ():
        if "=" not in pair:
            raise ValueError(f"--knob {pair!r}: expected k=v")
        k, v = pair.split("=", 1)
        k = k.strip()
        try:
            val = json.loads(v)
        except json.JSONDecodeError:
            val = v
        if k.startswith("placement."):
            weights[k[len("placement."):]] = float(val)
        elif k.startswith("autoscale."):
            param = k[len("autoscale."):]
            if param not in AUTOSCALE_KNOBS:
                raise ValueError(
                    f"unknown knob {k!r}; autoscale params: "
                    f"{sorted(AUTOSCALE_KNOBS)}")
            if autoscale_kw is None:
                autoscale_kw = {}
            autoscale_kw[param] = val
        elif k.startswith("spec."):
            # speculative-decoding knobs: spec.k / spec.draft imply
            # arming (a what-if on K with speculation off would be
            # vacuous); spec.decode=false is the explicit OFF lever
            param = k[len("spec."):]
            if param == "k":
                engine_kw["spec_k"] = int(val)
                engine_kw.setdefault("spec_decode", True)
            elif param == "draft":
                engine_kw["spec_draft"] = str(val)
                engine_kw.setdefault("spec_decode", True)
            elif param == "decode":
                engine_kw["spec_decode"] = bool(val)
            else:
                raise ValueError(
                    f"unknown knob {k!r}; spec params: k, draft, "
                    "decode")
        elif k in ROUTER_KNOBS:
            router_kw[k] = val
        elif k in ENGINE_KNOBS:
            engine_kw[k] = val
        else:
            raise ValueError(
                f"unknown knob {k!r}; router: {sorted(ROUTER_KNOBS)}, "
                f"engine: {sorted(ENGINE_KNOBS)}, plus placement.<w> "
                "and autoscale.<param>")
    return router_kw, engine_kw, weights, autoscale_kw


def build_fleet(entries, *, model="gpt-tiny", replicas=2,
                model_seed=0, engine_kw=None, router_kw=None,
                placement_weights=None, capture_dir=None, warm=True,
                autoscale_kw=None):
    """A fresh in-process fleet sized for a replay: engines warmed on
    every prefill bucket the wave can land in (plus the decode scan),
    compile counts frozen AFTER the warmup. Returns
    (router, engines, frozen_counts).

    autoscale_kw (a dict, possibly empty) arms a FleetAutoscaler over
    the fleet: ``spawn_fn`` builds additional warmed replicas named
    ``as<N>`` (appended to ``engines`` so callers can close them),
    the autoscaler attaches as ``router.autoscaler`` and ``replay``
    drives its ``poll()`` — the what-if path for scoring an
    autoscale policy against recorded traffic."""
    import paddle_tpu as paddle
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    from paddle_tpu.nlp.serving import ServingEngine
    from paddle_tpu.serving_fleet import FleetAutoscaler, \
        FleetRouter, InprocReplica

    paddle.seed(int(model_seed))
    mdl = GPTForCausalLM(_resolve_config(model))
    mdl.eval()
    ekw = dict(max_slots=2, page_size=16, max_seq_len=64,
               steps_per_dispatch=4)
    ekw.update(engine_kw or {})
    engines = []
    warm_lens = sorted({len(e["prompt"]) for e in entries}) if warm \
        else []

    def _engine():
        eng = ServingEngine(mdl, **ekw)
        if warm_lens:
            eng.warmup(buckets=warm_lens, decode=True)
        engines.append(eng)
        return eng

    for _ in range(int(replicas)):
        _engine()
    frozen = [e.compile_counts() for e in engines]
    reps = [InprocReplica(f"r{i}", e) for i, e in enumerate(engines)]
    rkw = dict(history=True, history_interval_s=0.05)
    rkw.update(router_kw or {})
    if placement_weights:
        rkw["placement_weights"] = placement_weights
    if capture_dir is not None:
        rkw["capture"] = capture_dir
    router = FleetRouter(reps, **rkw)
    if autoscale_kw is not None:
        # pre-build + warm the spare engines NOW, before the replay
        # clock starts: spawn_fn inside asc.poll() runs on the
        # control thread, and paying multi-second XLA warmups there
        # mid-burst would freeze router.step() and charge the scored
        # policy for the harness's own spawn stall. The pool is sized
        # from the policy's max_replicas when given (else one spare);
        # an exhausted pool falls back to a lazy build.
        mr = autoscale_kw.get("max_replicas")
        pool_n = max(int(mr) - int(replicas), 0) if mr is not None \
            else 1
        pool = [_engine() for _ in range(pool_n)]
        FleetAutoscaler(router, lambda i: InprocReplica(
            f"as{i}", pool.pop(0) if pool else _engine()),
            **autoscale_kw)
    return router, engines, frozen


# -- replay loop -----------------------------------------------------------


def schedule(entries, mode="recorded", time_scale=1.0, rate=None):
    """Per-entry submit offsets (seconds from replay start)."""
    if mode == "rate":
        if not rate or rate <= 0:
            raise ValueError("--mode rate needs --rate > 0")
        return [i / float(rate) for i in range(len(entries))]
    scale = float(time_scale) if mode == "scaled" else 1.0
    return [float(e.get("arrival_s") or 0.0) * scale for e in entries]


def replay(router, entries, *, mode="recorded", time_scale=1.0,
           rate=None, timeout_s=120.0, keep_deadlines=True):
    """Open-loop re-drive: submit each entry at its scheduled offset
    (never waiting for earlier completions), stepping the router
    throughout. Returns (results_by_orig_rid, wall_s, rid_map) where
    rid_map maps the replay router's rids back to the ORIGINAL
    entries' rids — an archive's rids are whatever the capturing
    router minted (non-zero-based after prior traffic, gappy after
    ring rotation or capture sampling), so nothing downstream may
    assume they line up with a fresh router's 0..n-1."""
    offs = schedule(entries, mode=mode, time_scale=time_scale,
                    rate=rate)
    order = sorted(range(len(entries)), key=lambda i: (offs[i], i))
    rid_map = {}
    results = {}
    # boot gate: the clock starts against a BOOTED fleet (every
    # replica heartbeating) — otherwise the first pulse's placement
    # wait measures fleet boot, not placement, and the original-vs-
    # replay hop shares diverge on a transient neither run owns
    t_boot = time.monotonic() + min(float(timeout_s), 10.0)
    while not router.booted and time.monotonic() < t_boot:
        router.step()
        time.sleep(0.001)
    t0 = time.monotonic()
    t_end = t0 + float(timeout_s)
    nxt = 0
    autoscaler = getattr(router, "autoscaler", None)
    while True:
        now = time.monotonic() - t0
        while nxt < len(order) and offs[order[nxt]] <= now:
            e = entries[order[nxt]]
            rid = router.submit(
                e["prompt"], e["max_new"], e.get("eos"),
                priority=int(e.get("priority") or 0),
                deadline_ms=e.get("deadline_ms")
                if keep_deadlines else None,
                tenant=e.get("tenant"))
            rid_map[rid] = e["rid"]
            nxt += 1
        router.step()
        if autoscaler is not None:
            autoscaler.poll()
        for r in router.results():
            results[rid_map.get(r["id"], r["id"])] = r
        if nxt >= len(order) and len(results) >= len(entries):
            break
        if time.monotonic() > t_end:
            raise RuntimeError(
                f"replay did not drain within {timeout_s}s "
                f"({len(results)}/{len(entries)} resolved)")
        time.sleep(0.001)
    return results, time.monotonic() - t0, rid_map


# -- verdict ---------------------------------------------------------------


def _quantile(values, q):
    vals = sorted(v for v in values if v is not None)
    if not vals:
        return None
    if len(vals) == 1:
        return vals[0]
    pos = q * (len(vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def latency_summary(entries):
    """TTFT/e2e p50/p99 (+ counts) from per-request records."""
    e2e = [e.get("e2e_s") for e in entries
           if e.get("status") == "ok"]
    ttft = [e.get("ttft_s") for e in entries
            if e.get("status") == "ok"]
    return {"requests": len(entries),
            "ok": sum(1 for e in entries if e.get("status") == "ok"),
            "e2e_p50_s": _quantile(e2e, 0.50),
            "e2e_p99_s": _quantile(e2e, 0.99),
            "ttft_p50_s": _quantile(ttft, 0.50),
            "ttft_p99_s": _quantile(ttft, 0.99)}


def hop_shares(entries):
    """Fraction of total ok-request e2e each hop name explains —
    the aggregate form of the r12 per-request attribution (shares,
    not absolute seconds, so two runs of different overall speed
    still compare hop-for-hop)."""
    total = 0.0
    sums = {}
    for e in entries:
        if e.get("status") != "ok" or not e.get("hops") \
                or e.get("e2e_s") is None:
            continue
        total += float(e["e2e_s"])
        for h in e["hops"]:
            if h.get("dur_s") is not None:
                sums[h["name"]] = sums.get(h["name"], 0.0) \
                    + float(h["dur_s"])
    if total <= 0:
        return {}
    return {name: s / total for name, s in sums.items()}


def history_quantiles(router, window_s=3600.0):
    """The replay fleet's live history plane read back (cross-check
    against the per-request summary: the history numbers are what a
    production scrape would have seen)."""
    h = getattr(router, "history", None)
    if h is None:
        return None
    return {
        "ttft_p99_s": h.quantile_over_time(
            "fleet_ttft_seconds", 0.99, window_s),
        "e2e_p99_s": h.quantile_over_time(
            "fleet_e2e_seconds", 0.99, window_s),
        "placement_wait_p99_s": h.quantile_over_time(
            "fleet_placement_wait_seconds", 0.99, window_s)}


def make_verdict(orig_entries, replay_entries, *, golden=False,
                 golden_facts=None, gates=None, mode="recorded",
                 knobs=None, history=None):
    """Score a replay against its original. Returns the verdict dict
    (``ok`` = every enabled gate and golden assertion held; failures
    are listed, vacuity-guarded — a gate that compared nothing is a
    failure, not a pass)."""
    gates = dict(DEFAULT_GATES, **(gates or {}))
    failures = []
    by_rid = {e["rid"]: e for e in replay_entries}

    # -- golden: token-exact per rid + frozen compiles ---------------------
    gsec = None
    if golden:
        compared, mismatched = 0, []
        for e in orig_entries:
            if e.get("status") != "ok" or e.get("tokens") is None:
                continue
            r = by_rid.get(e["rid"])
            if r is None or r.get("tokens") is None:
                mismatched.append(e["rid"])
                continue
            compared += 1
            if list(r["tokens"]) != list(e["tokens"]):
                mismatched.append(e["rid"])
        facts = golden_facts or {}
        gsec = {"enabled": True, "compared": compared,
                "mismatched_rids": mismatched[:32],
                "token_exact": compared > 0 and not mismatched,
                "compile_frozen": facts.get("compile_frozen"),
                "unexpected_retraces": facts.get(
                    "unexpected_retraces"),
                "new_traces": facts.get("new_traces")}
        if compared == 0:
            failures.append({"gate": "golden",
                             "reason": "nothing compared (vacuous)"})
        elif mismatched:
            failures.append({"gate": "golden",
                             "reason": f"{len(mismatched)} rid(s) not "
                                       "token-exact",
                             "rids": mismatched[:32]})
        if facts.get("compile_frozen") is False \
                or (facts.get("new_traces") or 0) > 0 \
                or (facts.get("unexpected_retraces") or 0) > 0:
            failures.append({"gate": "golden",
                             "reason": "replay traced new programs",
                             "new_traces": facts.get("new_traces"),
                             "unexpected_retraces": facts.get(
                                 "unexpected_retraces")})

    # -- SLO quantiles side by side ----------------------------------------
    orig_lat = latency_summary(orig_entries)
    rep_lat = latency_summary(replay_entries)
    ratios = {}
    for stat in ("e2e_p50_s", "e2e_p99_s", "ttft_p50_s",
                 "ttft_p99_s"):
        a, b = orig_lat.get(stat), rep_lat.get(stat)
        ratios[stat] = None if not a or b is None else round(b / a, 4)
    floor = float(gates.get("latency_floor_s") or 0.0)
    for gate_name, stat in (("e2e_p99_ratio", "e2e_p99_s"),
                            ("ttft_p99_ratio", "ttft_p99_s")):
        lim = gates.get(gate_name)
        r = ratios.get(stat)
        if lim is None:
            continue
        if r is None:
            if orig_lat.get(stat) is not None:
                failures.append({"gate": gate_name,
                                 "reason": "replay produced no "
                                           f"{stat} (vacuous)"})
        elif r > float(lim) and (rep_lat[stat] - orig_lat[stat]
                                 > floor):
            failures.append({"gate": gate_name, "ratio": r,
                             "limit": float(lim),
                             "floor_s": floor,
                             "original": orig_lat.get(stat),
                             "replay": rep_lat.get(stat)})

    # -- per-hop attribution deltas ----------------------------------------
    orig_sh = hop_shares(orig_entries)
    rep_sh = hop_shares(replay_entries)
    hop_rows = {}
    max_delta = 0.0
    for name in sorted(set(orig_sh) | set(rep_sh)):
        a = orig_sh.get(name, 0.0)
        b = rep_sh.get(name, 0.0)
        d = abs(b - a)
        max_delta = max(max_delta, d)
        hop_rows[name] = {"orig_share": round(a, 4),
                          "replay_share": round(b, 4),
                          "delta": round(d, 4)}
    lim = gates.get("hop_share_delta")
    if lim is not None and orig_sh:
        if not hop_rows:
            failures.append({"gate": "hop_share_delta",
                             "reason": "no hops compared (vacuous)"})
        elif max_delta > float(lim):
            worst = max(hop_rows, key=lambda n: hop_rows[n]["delta"])
            failures.append({"gate": "hop_share_delta",
                             "max_delta": round(max_delta, 4),
                             "limit": float(lim), "worst_hop": worst})

    return {"ok": not failures, "mode": mode,
            "knobs": dict(knobs or {}),
            "requests": {"original": len(orig_entries),
                         "replay": len(replay_entries)},
            "golden": gsec,
            "slo": {"original": orig_lat, "replay": rep_lat,
                    "ratios": ratios},
            "history": history,
            "attribution": {"hops": hop_rows,
                            "max_share_delta": round(max_delta, 4)},
            "gates": gates, "failures": failures}


# -- one-shot driver (CLI + replay_smoke's engine) -------------------------


def run_replay(entries, *, out_dir, mode="recorded", time_scale=1.0,
               rate=None, golden=False, gates=None, knob_pairs=None,
               replicas=2, model="gpt-tiny", model_seed=0,
               timeout_s=120.0, faults_arm=None):
    """Build a capture-armed fleet, re-drive ``entries``, and return
    (verdict, replay_entries). ``faults_arm`` is an optional callable
    run after warmup (the injected-regression drill's seam)."""
    from paddle_tpu.observability.trafficrec import load_archive
    from paddle_tpu.observability.trace import report_all

    router_kw, engine_kw, weights, autoscale_kw = \
        parse_knobs(knob_pairs)
    cap_dir = os.path.join(out_dir, "replay_archive")
    router, engines, frozen = build_fleet(
        entries, model=model, replicas=replicas,
        model_seed=model_seed, engine_kw=engine_kw,
        router_kw=router_kw, placement_weights=weights,
        capture_dir=cap_dir, autoscale_kw=autoscale_kw)
    autoscale_facts = None
    try:
        if faults_arm is not None:
            faults_arm()
        _results, wall_s, rid_map = replay(
            router, entries, mode=mode, time_scale=time_scale,
            rate=rate, timeout_s=timeout_s)
        hist = history_quantiles(router)
        asc = getattr(router, "autoscaler", None)
        base_n = len(frozen)
        compare = list(engines[:base_n])
        if asc is not None:
            # spawned replicas joined with their compile counts
            # frozen at adoption — fold them into the zero-new-traces
            # math (engines spawned but never adopted have no frozen
            # baseline and stay out of the comparison)
            spawn_frozen = {id(rep.engine): fz
                            for rep, fz in asc.spawned
                            if fz is not None
                            and hasattr(rep, "engine")}
            for e in engines[base_n:]:
                fz = spawn_frozen.get(id(e))
                if fz is not None:
                    compare.append(e)
                    frozen = frozen + [fz]
            autoscale_facts = {
                "events": asc.health()["decisions"],
                "flaps": int(router.registry.get(
                    "fleet_autoscale_flaps_total").value),
                "replicas_final": len(router.replicas),
                "state": asc.state}
        counts = [e.compile_counts() for e in compare]
        new_traces = sum(
            sum(c.values()) for c in counts) - sum(
            sum(c.values()) for c in frozen)
        golden_facts = {
            "compile_frozen": counts == frozen,
            "new_traces": new_traces,
            "unexpected_retraces":
                router.compile_report()["unexpected_retraces"]}
        # live prefix-cache facts, harvested before teardown: what
        # the replay fleet's caches actually did with this traffic
        # (vs prefix_stats' ideal scan) — the verdict's prefix_stats
        # section folds in the TTFT ratios so one JSON answers "did
        # the knob pay?"
        prefix_live = {"engines": 0, "hits": 0, "misses": 0,
                       "hit_pages": 0, "total_pages": 0,
                       "shared_pages": 0, "cow_copies": 0,
                       "evictions": 0}
        for e in engines:
            pc = e.health().get("prefix_cache")
            if not pc:
                continue
            prefix_live["engines"] += 1
            for k in ("hits", "misses", "hit_pages", "total_pages",
                      "shared_pages", "cow_copies", "evictions"):
                prefix_live[k] += int(pc.get(k) or 0)
        prefix_live["page_hit_rate"] = None \
            if not prefix_live["total_pages"] else round(
                prefix_live["hit_pages"]
                / prefix_live["total_pages"], 4)
        # live speculative-decoding facts (engines armed via --knob
        # spec.*): what the draft/verify loop actually accepted on
        # this traffic, vs spec_stats' offline scan
        spec_live = {"engines": 0, "proposed": 0, "accepted": 0,
                     "dispatches": 0}
        for e in engines:
            sp = e.health().get("spec")
            if not sp:
                continue
            spec_live["engines"] += 1
            for k in ("proposed", "accepted", "dispatches"):
                spec_live[k] += int(sp.get(k) or 0)
        spec_live["acceptance_rate"] = None \
            if not spec_live["proposed"] else round(
                spec_live["accepted"] / spec_live["proposed"], 4)
    finally:
        router.close()
        for e in engines:
            e.close()
    replay_entries, _meta, _stats = load_archive(cap_dir)
    # the replay fleet's archive carries ITS router's fresh rids —
    # translate back to the original rids before scoring, or golden
    # token-exactness would only ever match 0-based contiguous
    # archives (the rid_map is the ground truth, not arithmetic)
    for e in replay_entries:
        e["rid"] = rid_map.get(e["rid"], e["rid"])
    verdict = make_verdict(
        entries, replay_entries, golden=golden,
        golden_facts=golden_facts, gates=gates, mode=mode,
        knobs={"pairs": list(knob_pairs or ()),
               "replicas": replicas}, history=hist)
    verdict["wall_s"] = round(wall_s, 3)
    verdict["autoscale"] = autoscale_facts
    verdict["prefix_stats"] = None if not prefix_live["engines"] \
        else dict(prefix_live,
                  ttft_p50_ratio=verdict["slo"]["ratios"]
                  .get("ttft_p50_s"),
                  ttft_p99_ratio=verdict["slo"]["ratios"]
                  .get("ttft_p99_s"))
    verdict["spec_stats"] = None if not spec_live["engines"] \
        else dict(spec_live,
                  e2e_p50_ratio=verdict["slo"]["ratios"]
                  .get("e2e_p50_s"),
                  e2e_p99_ratio=verdict["slo"]["ratios"]
                  .get("e2e_p99_s"))
    report_all()  # keep the tracer rollup warm for post-hoc reads
    return verdict, replay_entries


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="open-loop replay of captured fleet traffic")
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--archive", metavar="DIR_OR_JSON",
                     help="capture archive dir (trafficrec) or a "
                          "committed wave json")
    src.add_argument("--synth", type=int, metavar="N",
                     help="generate a seeded synthetic wave of N "
                          "requests instead")
    ap.add_argument("--synth-seed", type=int, default=0)
    ap.add_argument("--synth-burst", type=int, default=4)
    ap.add_argument("--synth-gap", type=float, default=0.05,
                    help="seconds between synthetic bursts")
    ap.add_argument("--write-wave", metavar="PATH",
                    help="save the (synthetic) wave as a committed "
                         "wave json and exit")
    ap.add_argument("--mode", choices=("recorded", "scaled", "rate"),
                    default="recorded")
    ap.add_argument("--time-scale", type=float, default=1.0)
    ap.add_argument("--rate", type=float, default=None,
                    help="req/s for --mode rate")
    ap.add_argument("--knob", action="append", default=[],
                    metavar="K=V", help="what-if override (repeat)")
    ap.add_argument("--golden", action="store_true",
                    help="assert token-exact + zero new traces")
    ap.add_argument("--report-prefix-stats", action="store_true",
                    help="scan the wave's recorded prompts and "
                         "report expected page-level prefix-cache "
                         "hit rates (no replay; honors --knob "
                         "page_size/min_prefix_pages, else sweeps "
                         "page sizes 8/16/32)")
    ap.add_argument("--report-spec-stats", action="store_true",
                    help="replay the wave's recorded token streams "
                         "through the ngram proposer and report "
                         "expected speculative acceptance rate / "
                         "tokens-per-dispatch (no replay; honors "
                         "--knob spec.k, else sweeps K 2/4/8)")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--model", default="gpt-tiny")
    ap.add_argument("--model-seed", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=120.0)
    ap.add_argument("--out", default=None,
                    help="verdict path (default "
                         "<outdir>/replay_verdict.json)")
    args = ap.parse_args(argv)

    if args.synth is not None:
        entries = synth_wave(args.synth_seed, args.synth,
                             burst=args.synth_burst,
                             burst_gap_s=args.synth_gap)
        meta = {"synth_seed": args.synth_seed}
    else:
        entries, meta, stats = load_wave(args.archive)
        if not entries:
            print(json.dumps({"ok": False,
                              "error": f"no entries in "
                                       f"{args.archive}",
                              "stats": stats}))
            return 1
    if args.write_wave:
        with open(args.write_wave, "w") as f:
            json.dump({"format": 1, "meta": meta,
                       "entries": entries}, f, indent=1)
        print(json.dumps({"ok": True, "wrote_wave": args.write_wave,
                          "entries": len(entries)}))
        return 0
    if args.report_prefix_stats:
        _rkw, ekw, _w, _a = parse_knobs(args.knob)
        pss = [int(ekw["page_size"])] if "page_size" in ekw \
            else [8, 16, 32]
        mp = int(ekw.get("min_prefix_pages") or 1)
        print(json.dumps({
            "ok": True, "entries": len(entries),
            "prefix_stats": prefix_stats(entries, page_sizes=pss,
                                         min_pages=mp)}))
        return 0
    if args.report_spec_stats:
        _rkw, ekw, _w, _a = parse_knobs(args.knob)
        ks = [int(ekw["spec_k"])] if "spec_k" in ekw else [2, 4, 8]
        print(json.dumps({
            "ok": True, "entries": len(entries),
            "spec_stats": spec_stats(entries, k_values=ks)}))
        return 0

    out_dir = os.path.join(tempfile.gettempdir(),
                           "paddle_tpu_fleet_replay")
    os.makedirs(out_dir, exist_ok=True)
    verdict, _rep = run_replay(
        entries, out_dir=out_dir, mode=args.mode,
        time_scale=args.time_scale, rate=args.rate,
        golden=args.golden, knob_pairs=args.knob,
        replicas=args.replicas, model=args.model,
        model_seed=args.model_seed, timeout_s=args.timeout)
    out_path = args.out or os.path.join(out_dir,
                                        "replay_verdict.json")
    with open(out_path, "w") as f:
        json.dump(verdict, f, indent=1)
    print(json.dumps(verdict))
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
