"""Drives `ServingEngine.submit`/`step` for a window under a closed loop:
`clients` callers with `think_seconds` between a reply and the next request.
The host loop is: submit what is due, `step()`, collect. The slots are full
and `warm_finished` requests have been answered before the window opens.
Every length, count and engine argument comes from the traffic and
configuration files.

The driver times requests itself: submit on its own clock, first token at
the end of the request's `prefill_*` span in `eng.spans` (the engine closes
it with a host sync), finish when `step()` hands the result back."""
from __future__ import annotations

import gc
import time
import types

import numpy as np

from benchmarks import traffic_gen
from benchmarks.drivers.train import (adopt_seed_weights, check_sizes,
                                      seed_weights)
from benchmarks.harness import np_rng, percentile
from benchmarks.reference import gpt as reference


class Request:
    __slots__ = ("rid", "client", "prompt", "want", "t_submit", "t_first",
                 "t_finish", "tokens", "status")

    def __init__(self, rid, client, prompt, want, t_submit):
        self.rid, self.client, self.prompt, self.want = \
            rid, client, prompt, want
        self.t_submit, self.t_first, self.t_finish = t_submit, None, None
        self.tokens, self.status = None, None

    @property
    def ok(self):
        return (self.status == "ok" and self.t_first is not None
                and len(self.tokens) == self.want)


class ClosedLoop:
    def __init__(self, eng, feed, clients, think_s, host):
        self.eng, self.feed, self.host = eng, feed, host
        self.think_s = think_s
        self.requests = {}          # rid -> Request
        self.due = [(0.0, c) for c in range(clients)]   # (when, client)
        self.program_spans = []     # (name, t0, t1) on perf_counter
        self.submitting = True
        # the engine's span ring stamps epoch microseconds: one span of our
        # own at a known perf_counter reading gives the offset
        tp = time.perf_counter()
        ev = eng.spans.add("bench_sync", tp, tp, tid="bench")
        self.span_offset = ev["ts"] / 1e6 - tp
        eng.spans.clear()

    def in_flight(self):
        return sum(1 for r in self.requests.values() if r.t_finish is None)

    def _submit_due(self):
        now = time.perf_counter()
        later = []
        for when, client in self.due:
            if when > now or not self.submitting:
                later.append((when, client))
                continue
            prompt, want = next(self.feed)
            t = time.perf_counter()
            rid = self.eng.submit(prompt, max_new_tokens=want)
            self.requests[rid] = Request(rid, client, prompt, want, t)
        self.due = later

    def _read_spans(self):
        for ev in self.eng.spans.events():
            if ev.get("ph") != "X":
                continue
            t0 = ev["ts"] / 1e6 - self.span_offset
            t1 = t0 + ev["dur"] / 1e6
            name = ev["name"]
            if name.startswith(("prefill_", "tail_prefill_")):
                self.program_spans.append((name, t0, t1))
                req = self.requests.get(ev["args"].get("rid"))
                if req is not None:
                    req.t_first = t1
            elif name == "decode":
                self.program_spans.append((name, t0, t1))
        self.eng.spans.clear()

    def round(self):
        with self.host.span("submit"):
            self._submit_due()
        with self.host.span("step"):
            finished = self.eng.step()
        now = time.perf_counter()
        with self.host.span("collect"):
            self._read_spans()
            for res in finished:
                req = self.requests[res["id"]]
                req.t_finish, req.tokens = now, res["tokens"]
                req.status = res["status"]
                self.due.append((now + self.think_s, req.client))
        return len(finished)


def setup(ctx, host):
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    from paddle_tpu.nlp.serving import ServingEngine
    cfg, tr = ctx.config, ctx.traffic
    st = types.SimpleNamespace()
    model = GPTForCausalLM(_resolve_config(
        cfg["preset"], **reference.sizes(cfg),
        hidden_dropout_prob=cfg["hidden_dropout_prob"],
        attention_probs_dropout_prob=cfg["attention_probs_dropout_prob"]))
    model.eval()
    ctx.log("model built")
    check_sizes(model.config, cfg)
    st.shapes = adopt_seed_weights(model, ctx)
    ctx.log("weights made and loaded")
    st.eng = ServingEngine(model, **cfg["serve"]["engine"])
    del model
    pool = traffic_gen.length_pool(tr)
    st.eng.warmup(buckets=sorted({p for p, _ in pool}))
    st.compile_counts = dict(st.eng.compile_counts())
    ctx.log(f"engine warmed: {sorted(st.compile_counts)}")
    fill(st, ctx, host)
    ctx.log("slots full, first requests answered")
    return st


def fill(st, ctx, host):
    """Open the closed loop and answer the first `warm_finished` requests:
    the slots are full and out of step with each other when the window
    opens. Counted as set-up."""
    tr = ctx.traffic
    feed = traffic_gen.serve_requests(tr, ctx.config["vocab_size"], ctx.seed)
    st.loop = ClosedLoop(st.eng, feed, tr["clients"], tr["think_seconds"],
                         host)
    done = 0
    while done < tr["warm_finished"]:
        done += st.loop.round()
    return st


def _counters(eng):
    return {"decode_seconds": eng.decode_seconds,
            "decode_tokens": eng.decode_tokens,
            "decode_dispatches": eng.decode_dispatches}


def window(st, ctx, host):
    loop = st.loop
    before = _counters(st.eng)
    host.mark()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ctx.window_seconds:
        loop.round()
    t1 = time.perf_counter()
    host.mark()
    after = _counters(st.eng)
    return {"t0": t0, "t1": t1, "before": before, "after": after}


def settle(st, ctx, host, raw):
    """The window is closed: no new requests, but every one that was sent
    gets its answer (late is late, not wrong)."""
    loop = st.loop
    loop.submitting = False
    deadline = time.perf_counter() + 60.0
    while loop.in_flight() and time.perf_counter() < deadline:
        loop.round()
    if st.eng.compile_counts() != st.compile_counts:
        raise SystemExit(f"the engine traced after warm-up: "
                         f"{st.compile_counts} -> {st.eng.compile_counts()}")
    for span in loop.program_spans:
        host.add(*span)
    return reduce_window(loop, raw["t0"], raw["t1"], raw["before"],
                         raw["after"], st.eng.steps_per_dispatch)


def overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce_window(loop, t0, t1, before, after, steps_per_dispatch):
    reqs = list(loop.requests.values())
    sent = [r for r in reqs if t0 <= r.t_submit < t1]
    failed = [r for r in sent if not r.ok]
    ttft = [(r.t_first - r.t_submit) * 1e3 for r in sent if r.ok]
    ended = [r for r in reqs if r.ok and t0 <= r.t_finish <= t1]
    tpot = [(r.t_finish - r.t_first) / (r.want - 1) * 1e3
            for r in ended if r.want > 1]
    # tokens handed back, each request's spread evenly over its decoding
    # (first token to finish) and counted for the part inside the window
    tokens = 0.0
    for r in reqs:
        if not r.ok:
            continue
        span = max(r.t_finish - r.t_first, 1e-9)
        tokens += len(r.tokens) * overlap(r.t_first, r.t_finish, t0, t1) / span
    if not ttft or not tpot:
        raise SystemExit("the window finished no request")
    return {
        "t0": t0, "t1": t1, "attempted": len(sent), "failed": len(failed),
        "finished_in_window": len(ended),
        "end_to_end": {"serve_tokens_per_s": tokens / (t1 - t0),
                       "ttft_p90_ms": percentile(ttft, 90),
                       "tpot_p90_ms": percentile(tpot, 90)},
        "counters": {k: after[k] - before[k] for k in after},
        "steps_per_dispatch": steps_per_dispatch,
        "ttft_ms": ttft, "tpot_ms": tpot}


def release(st):
    st.eng.close()
    st.eng = None
    st.loop.eng = None
    gc.collect()


def sample_for_check(loop, seed, k):
    """k finished requests drawn from the seed, the longest among them."""
    done = sorted((r for r in loop.requests.values() if r.ok),
                  key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    pick = np_rng(seed, 3).permutation(len(rest))[:max(k - 1, 0)]
    return [longest] + [rest[i] for i in pick]


def served_gap(st, ctx, sample, control=None):
    """The widest gap, over the sample, by which a served token's logit
    lies below the float32 reference's best."""
    w = seed_weights(st.shapes, ctx)
    worst = 0.0
    for r in sample:
        g = reference.served_gaps(w, reference.sizes(ctx.config),
                                  r.prompt.tolist(), r.tokens, control)
        worst = max(worst, float(np.max(np.asarray(g))))
    return worst


def check(st, ctx, result):
    loop = st.loop
    sample = sample_for_check(loop, ctx.seed, ctx.traffic["check_requests"])
    release(st)
    wrong = sum(1 for r in loop.requests.values() if not r.ok)
    numbers = [("served_logit_gap_max", served_gap(st, ctx, sample)),
               ("requests_not_answered_in_full", float(wrong))]
    detail = {"checked_requests": len(sample),
              "checked_tokens": sum(len(r.tokens) for r in sample),
              "longest_checked": max((len(r.prompt) + len(r.tokens)
                                      for r in sample), default=0)}
    return numbers, detail


def run_data(st, ctx, result):
    loop = st.loop
    t0, t1 = result["t0"], result["t1"]
    spans = [s for s in loop.program_spans if t0 <= s[1] < t1]
    reqs = [r for r in loop.requests.values() if r.ok]
    # prompts prefilled and tokens decoded inside the window, and the mean
    # number of live K/V tokens while it ran (each request taken as growing
    # evenly from its prompt to prompt + output over its decoding)
    prefilled = [len(r.prompt) for r in reqs if t0 <= r.t_first < t1]
    ctx_sum = decoded = live_token_s = live_slot_s = 0.0
    for r in reqs:
        span = max(r.t_finish - r.t_first, 1e-9)
        part = overlap(r.t_first, r.t_finish, t0, t1)
        if not part:
            continue
        a = (max(r.t_first, t0) - r.t_first) / span
        b = (min(r.t_finish, t1) - r.t_first) / span
        n = len(r.tokens)
        mean_ctx = len(r.prompt) + n * (a + b) / 2
        decoded += n * (b - a)
        ctx_sum += n * (b - a) * mean_ctx
        live_token_s += mean_ctx * part
        live_slot_s += part
    return {"kind": "serve", "counters": result["counters"],
            "steps_per_dispatch": result["steps_per_dispatch"],
            "program_spans": spans, "prefilled_prompts": prefilled,
            "decoded_tokens": decoded, "decode_context_sum": ctx_sum,
            "mean_live_tokens": live_token_s / (t1 - t0),
            "mean_live_slots": live_slot_s / (t1 - t0),
            "ttft_ms": result["ttft_ms"],
            "engine": ctx.config["serve"]["engine"]}
