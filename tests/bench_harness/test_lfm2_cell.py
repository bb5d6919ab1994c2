"""The cell `serve-lfm2-closed64` on the CPU at the tiny size of
`data/lfm2-tiny.json`: its files hold what the manifest says, the driver
yields the result line, an altered token and the float8 control come out
not correct, the new yardsticks count what a hand count gives, and each new
reader says None where there is nothing to read. Nothing here describes a
TPU at import."""
import argparse
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "serve-lfm2-closed64"
NEW_READERS = ("mfu.serve.lfm2", "lfm2_decode_roofline",
               "short_conv_ms_per_step", "kv_attention_ms_per_step",
               "kv_attention_roofline")
PUBLISHED_LAYERS = ["conv", "conv", "full_attention", "conv"] * 4 + \
    ["conv", "conv", "full_attention", "conv", "conv", "full_attention",
     "conv", "conv"]

from benchmarks import harness, traffic_gen  # noqa: E402


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _config():
    return _json("benchmarks/configs/lfm2-8b-a1b-l16.json")


def _execute(seed=11):
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmarks", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    man = dict(harness.load_manifest())
    man["configs"] = [{"name": "lfm2-tiny",
                       "file": "tests/bench_harness/data/lfm2-tiny.json"}]
    man["workloads"] = [{"name": CELL, "config": "lfm2-tiny",
                         "traffic": "tiny-serve-lfm2", "chips": 1}]
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=1.0, trace=0)
    peak = harness.load_json("peaks.json")["TPU v5 lite"]
    return run.execute(man, args, harness.device_info(), peak,
                       traffic_dir=DATA)


def test_the_configuration_file_holds_the_published_widths_and_the_cut():
    cfg = _config()
    published = dict(
        hidden_size=2048, intermediate_size=7168, moe_intermediate_size=1792,
        num_attention_heads=32, num_key_value_heads=8, num_experts=32,
        num_experts_per_tok=4, num_dense_layers=2, conv_L_cache=3,
        conv_bias=False, norm_eps=1e-5, norm_topk_prob=True,
        routed_scaling_factor=1, use_expert_bias=True, rope_theta=1000000,
        vocab_size=65536, max_position_embeddings=128000,
        model_type="lfm2_moe")
    assert {k: cfg[k] for k in published} == published
    assert cfg["published"] == {"num_hidden_layers": 24,
                                "layer_types": PUBLISHED_LAYERS}
    assert cfg["num_hidden_layers"] == 16
    assert cfg["layer_types"] == PUBLISHED_LAYERS[:16]
    man = harness.load_manifest()
    entry = next(c for c in man["configs"] if c["name"] == "lfm2-8b-a1b-l16")
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("lfm2-8b-a1b-l16", "closed64-chat", 1)
    # the floors: whole periods, four layers after the dense ones, all
    # experts, the whole vocabulary
    assert cfg["layer_types"] == ["conv", "conv", "full_attention",
                                  "conv"] * 4
    assert cfg["deployment"]["layer_chips"] == 1
    assert cfg["precision"]["control"] == "float8"
    assert cfg["serve"]["engine"] == dict(
        max_slots=64, page_size=128, max_seq_len=2048, num_pages=1025,
        cache_dtype="bfloat16", prefix_cache=False, use_flash=True)
    # and the program builds exactly that
    from benchmarks.drivers.serve_lfm2 import model_config
    mc = model_config(cfg)
    assert (mc.num_hidden_layers, mc.num_experts, mc.head_dim, mc.dtype) == \
        (16, 32, 64, "bfloat16")
    assert list(mc.layer_types) == cfg["layer_types"]


def test_the_traffic_file_is_the_issue_s_mix():
    tr = _json("benchmarks/traffic/closed64-chat.json")
    engine = _config()["serve"]["engine"]
    assert tr["driver"] == "serve_lfm2"
    assert tr["clients"] == engine["max_slots"] == tr["pool"] == 64
    assert tr["prompt_tokens"] == dict(median=256, sigma=0.7, min=32,
                                       max=1024)
    assert tr["output_tokens"] == dict(median=256, sigma=0.5, min=64,
                                       max=1024)
    assert (tr["think_seconds"], tr["max_total_tokens"], tr["pool_seed"],
            tr["warm_finished"], tr["check_requests"], tr["trace_seconds"],
            tr["settle_seconds"]) == (0, 2048, 20261004, 64, 4, 12, 60)
    pool = traffic_gen.length_pool(tr)
    assert len(pool) == 64
    assert all(32 <= p <= 1024 and 64 <= o <= 1024 and p + o <= 2048
               for p, o in pool)
    # four prefill programs
    assert sorted({max(128, 1 << (p - 1).bit_length()) for p, _ in pool}) \
        == [128, 256, 512, 1024]
    # the slots' worst case fits the pool: no request ever waits for pages
    assert 64 * -(-2048 // engine["page_size"]) <= engine["num_pages"] - 1


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


def test_the_control_in_float8_is_not_correct_by_one_of_the_limits():
    """The control judges its own first choices at the served positions
    against the float32 reference; at this size the share of them off the
    reference's best is what tells it from the program."""
    from benchmarks.reference import lfm2 as reference
    from benchmarks.weights_leaf import make_leaf
    cfg = _json("tests/bench_harness/data/lfm2-tiny.json")
    limits = _json("tests/bench_harness/data/tiny-serve-lfm2.json")["limits"]
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
    from benchmarks.kernels import kv_attention, lfm2_step
    cfg = _config()
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert lfm2_step.kinds(cfg) == (12, 4)
    assert lfm2_step.conv_params(cfg) == conv == 16_783_360
    assert lfm2_step.attention_params(cfg) == attn == 10_485_760
    assert lfm2_step.expert_params(cfg) == 3 * 2048 * 1792 == 11_010_048
    assert lfm2_step.expert_layers(cfg) == 14
    dense = (12 * conv + 4 * attn + 2 * 3 * 2048 * 7168 + 14 * 2048 * 32
             + 2048 * 65536)
    assert lfm2_step.dense_params(cfg) == dense
    # all of it: 5.40 B parameters with every expert (the norms' gains and
    # the selection biases, 68,544 numbers, are not counted)
    assert dense + 14 * 32 * 11_010_048 == 5_399_060_480
    # a step of 64 slots at 30,000 live tokens, 440 experts hit over the 14
    # layers by 3,584 assignments, bf16 weights and cache
    assert lfm2_step.decode_step_bytes(cfg, 2, 2, 30_000, 64, 440) == \
        (dense + 440 * 11_010_048) * 2 \
        + (30_000 * 2 * 8 * 64 * 4 + 2 * 64 * 3 * 2048 * 12) * 2
    assert lfm2_step.decode_step_ops(cfg, 64, 30_000, 3584) == \
        2 * dense * 64 + 2 * 11_010_048 * 3584 + 4 * 32 * 64 * 30_000 * 4
    body = dense - 2048 * 65536
    assert lfm2_step.serve_flops(cfg, [100], (5000.0, 10.0), 70) == \
        2 * body * 100 + 2 * 2048 * 65536 + 2 * 32 * 64 * 4 * 100 ** 2 \
        + 2 * dense * 10 + 4 * 32 * 64 * 5000.0 * 4 + 2 * 11_010_048 * 70
    sh = kv_attention.shapes(cfg, 2, 30_000)
    assert kv_attention.bytes(sh) == 30_000 * 2 * 8 * 64 * 2 * 4
    assert kv_attention.ops(sh) == 4 * 32 * 64 * 30_000 * 4
    assert (kv_attention.SCOPE, kv_attention.KERNEL) == \
        ("paged_attention", "flash_decode")


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
    routing = {"decode": {"moe_local_assignments": 1120,
                          "moe_experts_hit": 4900,
                          "moe_routed_tokens": 17920}}
    # another driver's run: the GPT driver's holds no routing counters,
    # the A.X-K1 driver's another configuration
    axk1 = dict(run, routing=routing, expert_layers=7, config=_json(
        "benchmarks/configs/axk1-ep16.json"))
    for other in (run, axk1):
        assert reader.read(other, empty) is None
        assert reader.read(other, None) is None
    got = reader.read(dict(run, routing=routing, expert_layers=14), empty)
    if name == "mfu.serve.lfm2":
        assert 0 < got < 100
    else:
        assert got is None          # device time comes from a trace only
