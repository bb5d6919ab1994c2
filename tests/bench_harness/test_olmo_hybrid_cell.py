"""The cell `serve-olmohybrid-closed64` on the CPU at the tiny size of
`data/olmo-hybrid-tiny.json`: its files hold what the manifest says, the
driver yields the result line, an altered token and the float8 control come
out not correct, the new yardsticks count what a hand count gives, and each
new reader says None where there is nothing to read. Nothing here describes
a TPU at import."""
import argparse
import importlib.util
import json
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "serve-olmohybrid-closed64"
NEW_READERS = ("gated_delta_ms_per_step", "gated_delta_roofline",
               "olmo_hybrid_decode_roofline", "mfu.serve.olmohybrid")
PUBLISHED_LAYERS = ["linear_attention"] * 3 + ["full_attention"]

from benchmarks import harness, traffic_gen  # noqa: E402


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _config():
    return _json("benchmarks/configs/olmo-hybrid-7b-l16.json")


def _execute(seed=11):
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmarks", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    man = dict(harness.load_manifest())
    man["configs"] = [{"name": "olmo-hybrid-tiny",
                       "file": "tests/bench_harness/data/"
                               "olmo-hybrid-tiny.json"}]
    man["workloads"] = [{"name": CELL, "config": "olmo-hybrid-tiny",
                         "traffic": "tiny-serve-olmo-hybrid", "chips": 1}]
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=1.0, trace=0)
    peak = harness.load_json("peaks.json")["TPU v5 lite"]
    return run.execute(man, args, harness.device_info(), peak,
                       traffic_dir=DATA)


def test_the_configuration_file_holds_the_published_widths_and_the_cut():
    cfg = _config()
    published = dict(
        model_type="olmo_hybrid", vocab_size=100352, hidden_size=3840,
        intermediate_size=11008, num_attention_heads=30,
        num_key_value_heads=30, hidden_act="silu",
        max_position_embeddings=65536, attention_bias=False,
        rms_norm_eps=1e-6, tie_word_embeddings=False,
        linear_num_key_heads=30, linear_num_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
        rope_parameters={"rope_theta": None})
    assert {k: cfg[k] for k in published} == published
    assert cfg["published"] == {"num_hidden_layers": 32,
                                "layer_types": PUBLISHED_LAYERS * 8}
    assert cfg["num_hidden_layers"] == 16
    assert cfg["layer_types"] == PUBLISHED_LAYERS * 4
    man = harness.load_manifest()
    entry = next(c for c in man["configs"]
                 if c["name"] == "olmo-hybrid-7b-l16")
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("olmo-hybrid-7b-l16", "closed64-chat-hybrid", 1)
    assert cfg["deployment"]["layer_chips"] == 1
    assert (cfg["deployment"]["pipeline_stages"],
            cfg["deployment"]["stage"]) == (2, 0)
    assert cfg["precision"]["control"] == "float8"
    assert cfg["serve"]["engine"] == dict(
        max_slots=64, page_size=128, max_seq_len=2048, num_pages=513,
        cache_dtype="bfloat16", prefix_cache=False, use_flash=True)
    # and the program builds exactly that
    from benchmarks.drivers.serve_olmo_hybrid import model_config
    mc = model_config(cfg)
    assert (mc.num_hidden_layers, mc.head_dim, mc.conv_channels,
            mc.dtype) == (16, 128, 11520, "bfloat16")
    assert list(mc.layer_types) == cfg["layer_types"]


def test_the_traffic_file_is_the_cell_s_mix():
    tr = _json("benchmarks/traffic/closed64-chat-hybrid.json")
    engine = _config()["serve"]["engine"]
    assert tr["driver"] == "serve_olmo_hybrid"
    assert tr["clients"] == engine["max_slots"] == tr["pool"] == 64
    assert tr["prompt_tokens"] == dict(median=256, sigma=0.7, min=32,
                                       max=1024)
    assert tr["output_tokens"] == dict(median=256, sigma=0.5, min=64,
                                       max=1024)
    assert (tr["think_seconds"], tr["max_total_tokens"], tr["warm_finished"],
            tr["check_requests"]) == (0, 2048, 64, 4)
    assert set(tr["limits"]) == {"served_logit_gap_max",
                                 "served_off_best_share",
                                 "requests_not_answered_in_full"}
    assert tr["limits"]["requests_not_answered_in_full"] == 0
    pool = traffic_gen.length_pool(tr)
    assert len(pool) == 64
    assert all(32 <= p <= 1024 and 64 <= o <= 1024 and p + o <= 2048
               for p, o in pool)
    assert sorted({max(128, 1 << (p - 1).bit_length()) for p, _ in pool}) \
        == [128, 256, 512, 1024]
    # the whole pool in flight at once fits the pages with 30% to spare
    need = sum(-(-(p + o) // engine["page_size"]) for p, o in pool)
    assert math.ceil(1.3 * need) <= engine["num_pages"] - 1


def test_the_driver_yields_the_result_line_and_an_altered_token_fails(
        monkeypatch):
    line = _execute()
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"] and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "tpot_p90_ms",
                                    "setup_s"}
    assert {r["name"] for r in line["compared"]} == {
        "served_logit_gap_max", "served_off_best_share",
        "requests_not_answered_in_full"}
    json.dumps(line, allow_nan=False)
    from paddle_tpu.nlp.serving import ServingEngine
    real = ServingEngine.step

    def altered(self):
        out = real(self)
        for res in out:
            res["tokens"][len(res["tokens"]) // 2] ^= 1
        return out

    monkeypatch.setattr(ServingEngine, "step", altered)
    assert _execute()["correct"] is False


def test_the_decays_are_drawn_as_the_configuration_assumes():
    from benchmarks.drivers.serve_olmo_hybrid import make_leaf
    a_log = np.asarray(make_leaf("model.layers.0.linear_attn.A_log", (4096,),
                                 3, 0.02, "float32"))
    dt_bias = np.asarray(make_leaf("model.layers.0.linear_attn.dt_bias",
                                   (4096,), 3, 0.02, "float32"))
    a = np.exp(a_log)
    assert 0.99 < a.min() < 1.1 and 15 < a.max() < 16.1
    dt = np.log1p(np.exp(dt_bias))                  # softplus
    assert 0.9e-3 < dt.min() < 1.2e-3 and 0.08 < dt.max() < 0.101
    # the same seed and name give the same leaf; another seed another
    again = make_leaf("model.layers.0.linear_attn.A_log", (4096,), 3, 0.02,
                      "float32")
    assert np.array_equal(a_log, np.asarray(again))
    other = make_leaf("model.layers.0.linear_attn.A_log", (4096,), 4, 0.02,
                      "float32")
    assert not np.array_equal(a_log, np.asarray(other))


def test_the_control_in_float8_is_not_correct_by_one_of_the_limits():
    """The control judges its own first choices at the served positions
    against the float32 reference."""
    from benchmarks.drivers.serve_olmo_hybrid import make_leaf
    from benchmarks.reference import olmo_hybrid as reference
    cfg = _json("tests/bench_harness/data/olmo-hybrid-tiny.json")
    limits = _json("tests/bench_harness/data/"
                   "tiny-serve-olmo-hybrid.json")["limits"]
    shapes = reference.leaf_shapes(cfg)
    for seed in (5, 6):
        def leaves(names):
            return {n: make_leaf(n, shapes[n], seed,
                                 cfg["initializer_range"], "float32")
                    for n in names}
        rng = np.random.default_rng(seed)
        prompt = rng.integers(0, cfg["vocab_size"], (40,)).tolist()
        toks = rng.integers(0, cfg["vocab_size"], (88,)).tolist()
        control = np.asarray(reference.served_gaps(
            leaves, cfg, [(prompt, toks)], cfg["precision"]["control"])[0])
        assert (control.max() > limits["served_logit_gap_max"]
                or np.mean(control > 0) > limits["served_off_best_share"]), \
            (seed, control.max(), np.mean(control > 0))


def test_the_new_kernels_count_what_a_hand_count_gives():
    from benchmarks.kernels import gated_delta, olmo_hybrid_step as k
    cfg = _config()
    gdn = 2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30 + 4 * 11520 \
        + 30 + 30 + 192
    attn = 4 * 3840 * 3840 + 2 * 3840
    mlp = 3 * 3840 * 11008
    assert k.kinds(cfg) == (12, 4)
    assert k.gated_delta_params(cfg) == gdn == 88_750_332
    assert k.layer_params(cfg, "linear_attention") == gdn + mlp + 7680 \
        == 215_570_172
    assert k.layer_params(cfg, "full_attention") == attn + mlp + 7680 \
        == 185_809_920
    assert k.total_params(cfg) == 4_100_788_944
    body = 12 * 215_570_172 + 4 * 185_809_920 + 3840
    assert k.body_params(cfg) == body
    head = 3840 * 100352
    state = 30 * 96 * 192 * 4 + 3 * 11520 * 2
    # a step of 64 slots at 30,000 live tokens, bf16 weights and cache
    assert k.decode_step_bytes(cfg, 2, 2, 30_000, 64) == \
        (body + head + 64 * 3840) * 2 + 30_000 * 2 * 30 * 128 * 4 * 2 \
        + 2 * 64 * 12 * state
    sh = gated_delta.shapes(cfg, 2, 64)
    assert gated_delta.state_bytes(sh) == state
    assert gated_delta.bytes(sh) == 12 * (
        64 * (2 * state + 4 * (11520 + 60 + 2 * 5760)) + 2 * 4 * 11520)
    assert gated_delta.ops(sh) == 12 * 64 * (7 * 30 * 96 * 192
                                             + 2 * 4 * 11520)
    assert k.decode_step_ops(cfg, 64, 30_000) == \
        2 * (body + head) * 64 + 4 * 30 * 128 * 30_000 * 4 \
        + gated_delta.ops(sh)
    scan = 30 * (64 * (5 * 96 + 3 * 192) + 6 * 96 * 192) * 12
    assert k.scan_ops_per_token(cfg) == scan
    one = gated_delta.ops(gated_delta.shapes(cfg, 2, 1))
    assert k.serve_flops(cfg, [100], (5000.0, 10.0)) == \
        (2 * body + scan) * 100 + 2 * head + 2 * 30 * 128 * 4 * 100 ** 2 \
        + (2 * body + 2 * head + one) * 10 + 4 * 30 * 128 * 5000.0 * 4
    # the cell's reckoning: 12.8 GB a step at 64 slots of about 505 tokens
    assert 12.7e9 < k.decode_step_bytes(cfg, 2, 2, 64 * 505, 64) < 13.0e9
    assert gated_delta.SCOPE == "gated_delta"


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_finds_nothing_in_an_empty_trace_or_another_run(name):
    reader = harness.load_module("layer_metrics", name + ".py")
    peak = harness.load_json("peaks.json")["TPU v5 lite"]
    empty = {"modules": {}, "ops": {}, "busy_s": 0.0, "window_s": 1.0}
    run = {"kind": "serve", "config": _config(), "peak": peak,
           "window_s": 1.0, "steps_per_dispatch": 8,
           "counters": {"decode_dispatches": 10, "decode_seconds": 1.0,
                        "decode_tokens": 100},
           "engine": {"cache_dtype": "bfloat16"}, "mean_live_tokens": 100.0,
           "mean_live_slots": 4.0, "prefilled_prompts": [10],
           "decoded_tokens": 100.0, "decode_context_sum": 1000.0}
    # another driver's run: LFM2's configuration with its routing counters
    lfm2 = dict(run, config=_json("benchmarks/configs/lfm2-8b-a1b-l16.json"),
                routing={"decode": {"moe_local_assignments": 1120,
                                    "moe_experts_hit": 4900,
                                    "moe_routed_tokens": 17920}})
    assert reader.read(lfm2, empty) is None
    assert reader.read(lfm2, None) is None
    got = reader.read(run, empty)
    if name == "mfu.serve.olmohybrid":
        assert 0 < got < 100
    else:
        assert got is None          # device time comes from a trace only
    # and the LFM2 readers take this run for none of theirs
    for lfm2_reader in ("lfm2_decode_roofline", "short_conv_ms_per_step",
                        "kv_attention_ms_per_step", "mfu.serve.lfm2"):
        mod = harness.load_module("layer_metrics", lfm2_reader + ".py")
        assert mod.read(run, empty) is None
