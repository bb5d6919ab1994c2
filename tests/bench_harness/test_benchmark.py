"""The benchmark's own tests, on the CPU at `gpt-tiny`: the manifest resolves
to its files, the trace arithmetic and the kernels' operation counts are
right, the reference agrees with the program, each driver yields the
result's keys, and `correct` comes out false under the control and under
each fault a cell can have. Nothing here describes a TPU at import."""
import argparse
import glob
import importlib.util
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

from benchmarks import harness, trace_reduce, traffic_gen  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TRAIN, SERVE = "train-345m-b8s1024", "serve-1p3b-closed12"


def _load_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmarks", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny_manifest(kind):
    man = dict(harness.load_manifest())
    file = {"train": "gpt-tiny.json", "serve": "gpt-tiny-serve.json"}[kind]
    man["configs"] = [{"name": "gpt-tiny",
                       "file": "tests/bench_harness/data/" + file}]
    man["workloads"] = [{"name": {"train": TRAIN, "serve": SERVE}[kind],
                         "config": "gpt-tiny", "traffic": f"tiny-{kind}",
                         "chips": 1}]
    return man


def _execute(kind, seed=11, seconds=1.0):
    """The rest of a run behind the harness's look for a chip."""
    run = _load_run()
    man = _tiny_manifest(kind)
    args = argparse.Namespace(workload=man["workloads"][0]["name"], seed=seed,
                              seconds=seconds, trace=0)
    peak = harness.load_json("peaks.json")["TPU v5 lite"]
    return run.execute(man, args, harness.device_info(), peak,
                       traffic_dir=DATA)


def test_manifest_resolves_to_files_and_back():
    man = harness.load_manifest()
    cfgs = {c["name"]: c for c in man["configs"]}
    for c in man["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    traffic = set()
    for w in man["workloads"]:
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        path = os.path.join(ROOT, "benchmarks", "traffic",
                            w["traffic"] + ".json")
        with open(path) as f:
            drv = json.load(f)["driver"]
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", "drivers",
                                           drv + ".py"))
        traffic.add(w["traffic"])
    cells = {w["name"] for w in man["workloads"]}
    e2e = {m["name"] for m in man["end_to_end"]}
    for m in man["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))

    def stems(sub, ext):
        return {os.path.basename(p)[:-len(ext)] for p in glob.glob(
            os.path.join(ROOT, "benchmarks", sub, "*" + ext))}
    assert stems("configs", ".json") == set(cfgs)
    assert stems("traffic", ".json") == traffic
    assert stems("layer_metrics", ".py") == {m["name"]
                                             for m in man["per_layer"]}


def test_manifest_names_units_and_bounds():
    man = harness.load_manifest()
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= man["run_seconds"] <= 51
    for m in man["end_to_end"] + man["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in man["end_to_end"]}
    for w in man["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200
    for c in man["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("benchmarks/")
    for m in man["per_layer"]:
        assert ("mfu" in m["name"] or m["name"].endswith("_roofline")
                or m["name"].startswith("device_idle")) == (m["unit"] == "%")


def test_trace_reduce_on_synthetic_intervals():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4), (6.0, 9.0)]
    assert trace_reduce.union(iv) == [(0.0, 2.0), (3.0, 4.0), (6.0, 9.0)]
    assert trace_reduce.busy_seconds(iv, 1.0, 7.0) == pytest.approx(3.0)
    assert trace_reduce.gaps(iv, 1.0, 7.0) == [(2.0, 3.0), (4.0, 6.0)]
    spans = [("step", 0.0, 10.0), ("prefill", 2.0, 3.1), ("collect", 4.5, 5.5)]
    assert trace_reduce.owner_of(2.5, spans) == "prefill"
    assert trace_reduce.owner_of(20.0, spans) == "outside_spans"
    assert trace_reduce.gap_owners([(2.0, 3.0), (4.0, 6.0)], spans) == \
        [["collect", 2.0], ["prefill", 1.0]]
    ev = [("jit_a", 0.0, 1.0), ("jit_b", 1.0, 1.5), ("jit_a", 2.0, 3.5),
          ("jit_a", 9.0, 9.5)]
    by = trace_reduce.seconds_by_name(ev, 0.0, 5.0)
    assert by == {"jit_a": [2.5, 2], "jit_b": [0.5, 1]}
    assert trace_reduce.top_by_seconds(by) == [["jit_a", 2.5], ["jit_b", 0.5]]
    assert trace_reduce.matching_seconds(by, r"_a$") == (2.5, 2)
    assert trace_reduce.clock_offset({0: 5.0, 1: 8.0}, [105.0, 108.0]) == -100
    recorded = os.path.join(DATA, "train_window.xplane.pb")
    if os.path.isfile(recorded):
        raw = trace_reduce.read_xplane(recorded)
        assert raw["devices"] and raw["marks"]


def test_kernel_operations_and_bytes_by_hand():
    from benchmarks.kernels import decode_step, flash_attention, gpt_step
    with open(os.path.join(ROOT, "benchmarks/configs/gpt3-345M.json")) as f:
        small = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/configs/gpt3-1.3B.json")) as f:
        big = json.load(f)
    # 345M: 12 h^2 + 13 h per layer, 24 layers, 50304 + 1024 rows of 1024
    n = 24 * (12 * 1024 ** 2 + 13 * 1024) + 51328 * 1024 + 2048
    assert gpt_step.param_count(small) == n == 354_871_296
    assert gpt_step.param_count(big) == 24 * (12 * 2048 ** 2 + 13 * 2048) \
        + (50304 + 2048) * 2048 + 4096 == 1_315_819_520
    assert gpt_step.train_flops_per_token(small, 1024) == \
        6 * n + 12 * 24 * 1024 * 1024
    sh = flash_attention.shapes(small, 8, 1024)
    assert flash_attention.ops(sh) == 24 * 8 * 16 * 7 * 1024 ** 2 * 64
    assert flash_attention.bytes(sh) == 24 * 12 * 8 * 1024 * 1024 * 2
    # one decode step of 16 slots at 10,000 live tokens, float32 weights
    dsh = decode_step.shapes(big, 4, 2, 10_000, 16)
    weights = 24 * (12 * 2048 ** 2 + 13 * 2048) + 50304 * 2048 + 4096
    assert decode_step.bytes(dsh) == weights * 4 + 10_000 * 2 * 24 * 2048 * 2
    assert decode_step.ops(dsh) == 2 * (24 * 12 * 2048 ** 2 + 50304 * 2048) \
        * 16 + 4 * 24 * 2048 * 10_000
    body = 24 * 12 * 2048 ** 2
    assert gpt_step.serve_flops(big, [100], (5000.0, 10.0)) == \
        2 * body * 100 + 2 * 50304 * 2048 + 2 * 24 * 2048 * 100 ** 2 \
        + 2 * (body + 50304 * 2048) * 10 + 4 * 24 * 2048 * 5000


def test_reference_agrees_with_the_program_at_tiny():
    import jax.numpy as jnp
    from benchmarks import weights
    from benchmarks.reference import gpt as reference
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    from paddle_tpu.tensor import Tensor
    with open(os.path.join(DATA, "gpt-tiny.json")) as f:
        cfg = json.load(f)
    model = GPTForCausalLM(_resolve_config("gpt-tiny"))
    model.eval()
    w = weights.make_weights(reference.leaf_shapes(cfg), 3)
    model.load_raw_state(w)
    ids = np.random.default_rng(0).integers(0, 256, (2, 40), dtype=np.int32)
    want = np.asarray(reference.forward(w, jnp.asarray(ids),
                                        reference.sizes(cfg)))
    got = np.asarray(model(Tensor(jnp.asarray(ids)))._value)
    assert np.max(np.abs(got - want)) < 2e-4 * np.max(np.abs(want))
    rows = np.asarray(reference.next_token_logits(
        w, reference.sizes(cfg), ids[0].tolist(), 9, 5))
    assert np.max(np.abs(rows - want[0, 9:14])) < 1e-4


def test_every_seed_sends_the_same_sizes_in_another_order():
    with open(os.path.join(ROOT, "benchmarks/traffic/closed12-chat.json")) as f:
        tr = json.load(f)
    pool = traffic_gen.length_pool(tr)
    assert len(pool) == tr["pool"]
    assert all(64 <= p <= 1792 and 16 <= o <= 256 and p + o <= 2048
               for p, o in pool)
    orders = []
    for seed in (1, 2 ** 31 + 5):
        feed = traffic_gen.serve_requests(tr, 50304, seed)
        orders.append([(len(p), o) for p, o in
                       (next(feed) for _ in range(tr["pool"]))])
    assert sorted(orders[0]) == sorted(orders[1]) == sorted(pool)
    assert orders[0] != orders[1]


def test_train_driver_yields_the_result_line():
    line = _execute("train")
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"] and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert {r["name"] for r in line["compared"]} == {
        "loss_step2_rel", "loss_step3_rel",
        "grad1_worst_leaf_rel", "delta3_worst_leaf_rel"}
    json.dumps(line, allow_nan=False)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_faults_come_out_not_correct(monkeypatch, fault):
    import bench
    real = bench.build_engine

    def broken(*a, **k):
        eng = real(*a, **k)
        step = eng.train_batch

        def state_unchanged(inputs, labels):
            import jax.numpy as jnp
            params, bufs = eng.network.raw_state()
            keep = {n: jnp.copy(v) for n, v in params.items()}
            out = step(inputs, labels)
            eng.network.load_raw_state(keep, bufs)
            eng.sync_from_layer()
            return out

        def half_batch(inputs, labels):
            return step([x[:len(x) // 2] for x in inputs],
                        [y[:len(y) // 2] for y in labels])

        eng.train_batch = {"state_unchanged": state_unchanged,
                           "half_batch": half_batch}[fault]
        return eng

    monkeypatch.setattr(bench, "build_engine", broken)
    line = _execute("train")
    assert line["correct"] is False
    failed = {r["name"] for r in line["compared"] if r["value"] > r["limit"]}
    assert {"state_unchanged": "delta3_worst_leaf_rel",
            "half_batch": "grad1_worst_leaf_rel"}[fault] in failed


def test_train_control_in_float8_comes_out_not_correct():
    from benchmarks.drivers import train as drv
    with open(os.path.join(DATA, "gpt-tiny.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(DATA, "tiny-train.json")) as f:
        tr = json.load(f)
    ctx = harness.Context({}, cfg, tr, 12, 1.0, False, None, 0.0)
    st = __import__("types").SimpleNamespace()
    feed = traffic_gen.train_batches(tr, cfg["vocab_size"], ctx.seed)
    from benchmarks.reference import gpt as reference
    st.shapes = reference.leaf_shapes(cfg)
    st.first = {"batches": [next(feed) for _ in range(tr["check_steps"])]}
    ref = drv.reference_steps(ctx, st)
    control = drv.reference_steps(ctx, st, cfg["precision"]["control"])
    run = _load_run()
    numbers, _ = drv.compare(control, ref)
    _, ok = run.judge(numbers, tr["limits"])
    assert not ok
    numbers, _ = drv.compare(ref, ref)
    assert run.judge(numbers, tr["limits"])[1]


def test_serve_driver_yields_the_result_line_and_an_altered_token_fails(
        monkeypatch):
    line = _execute("serve")
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "tpot_p90_ms",
                                    "setup_s"}
    assert line["attempted"] > 20 and list(line)[-1] == "compared"
    from paddle_tpu.nlp.serving import ServingEngine
    real = ServingEngine.step

    def altered(self):
        out = real(self)
        for res in out:
            res["tokens"][len(res["tokens"]) // 2] ^= 1
        return out

    monkeypatch.setattr(ServingEngine, "step", altered)
    line = _execute("serve")
    assert line["correct"] is False


def test_serve_control_in_float8_reads_above_the_limit():
    import jax.numpy as jnp
    from benchmarks import weights
    from benchmarks.reference import gpt as reference
    with open(os.path.join(DATA, "gpt-tiny-serve.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(DATA, "tiny-serve.json")) as f:
        limit = json.load(f)["limits"]["served_logit_gap_max"]
    sizes = reference.sizes(cfg)
    for seed in (5, 6, 7):
        w = weights.make_weights(reference.leaf_shapes(cfg), seed,
                                 cfg["initializer_range"])
        rng = np.random.default_rng(seed)
        prompt = rng.integers(0, cfg["vocab_size"], (40,)).tolist()
        # the reference's own greedy continuation stands for served tokens
        toks = []
        for _ in range(32):
            lg = reference.next_token_logits(w, sizes, prompt + toks,
                                             len(prompt) + len(toks) - 1, 1)
            toks.append(int(jnp.argmax(lg[0])))
        assert float(jnp.max(reference.served_gaps(w, sizes, prompt,
                                                   toks))) == 0.0
        control = float(jnp.max(reference.served_gaps(
            w, sizes, prompt, toks, cfg["precision"]["control"])))
        assert control > limit, (seed, control)


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", TRAIN, "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip().splitlines()[-1].startswith("{\"correct\"")
