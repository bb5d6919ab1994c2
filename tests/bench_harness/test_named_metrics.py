"""The nine readers that go by name (ISSUE 25), each on small hand-made
`run` and `trace` dictionaries: the value where the names are there, None
where they are not (a program that names nothing, a site that was never
captured), never 0."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness, named  # noqa: E402
from paddle_tpu.observability import introspect  # noqa: E402


def reader(name):
    return harness.load_module("layer_metrics", name + ".py").read


def trace_of(modules, ops):
    return {"modules": modules, "ops": ops, "busy_s": 1.0, "window_s": 1.0}


# four steps of a train program: per step 2 forward and 4 backward kernel
# calls, two fusions of the vocabulary end, one of the optimizer, one op
# the map does not know
TRAIN_TRACE = trace_of(
    {"jit_train_step(7)": [0.800, 4], "jit__leaf_norms(3)": [0.001, 1]},
    {"%flash_fwd.3 [tpu_custom_call]": [0.008, 4],
     "%flash_fwd.5 [tpu_custom_call]": [0.008, 4],
     "%flash_bwd_dq.1 [tpu_custom_call]": [0.020, 8],
     "%flash_bwd_dkv.1 [tpu_custom_call]": [0.028, 8],
     "%fusion.10": [0.040, 4], "%fusion.11": [0.012, 4],
     "%fusion.12": [0.016, 4], "%fusion.13": [0.100, 4],
     "%fusion.14": [0.004, 4], "%copy.2": [0.002, 4]})
TRAIN_SCOPES = {
    "flash_fwd.3": "GPTForCausalLM/GPTModel/GPTDecoderLayer/GPTAttention",
    "flash_fwd.5": "GPTForCausalLM/GPTModel/GPTDecoderLayer/GPTAttention",
    "flash_bwd_dq.1": "GPTForCausalLM/GPTModel/GPTDecoderLayer/GPTAttention",
    "flash_bwd_dkv.1": "GPTForCausalLM/GPTModel/GPTDecoderLayer/GPTAttention",
    "fusion.10": "GPTForCausalLM/lm_head",
    "fusion.11": "loss/GPTPretrainingCriterion/ParallelCrossEntropy",
    "fusion.12": "GPTForCausalLM/GPTModel/GPTEmbeddings/"
                 "VocabParallelEmbedding",
    "fusion.13": "optimizer",
    "fusion.14": "grad_clip"}

SERVE_RUN = {"counters": {"decode_seconds": 3.36, "decode_tokens": 900,
                          "decode_dispatches": 10},
             "steps_per_dispatch": 8}
SERVE_TRACE = trace_of(
    {"jit_decode(12)": [3.28, 10], "jit_prefill_512(4)": [0.057, 3],
     "jit_prefill_2048(9)": [0.080, 1], "jit_tail_prefill_128(5)": [9.0, 9]},
    {"%while.4": [3.2, 10]})


@pytest.fixture()
def captured(monkeypatch):
    """The program's registry as a run leaves it: `train_step` and `decode`
    captured, the scope map of `train_step` built."""
    introspect.clear()
    monkeypatch.setitem(introspect._sites, ("engine", "train_step"), {
        "tracer": "engine", "site": "train_step", "ts": 1.0, "flops": 1.0,
        "bytes_accessed": 1.0, "memory": None, "captures": 1,
        "_scopes": TRAIN_SCOPES})
    monkeypatch.setitem(introspect._sites, ("serving", "decode"), {
        "tracer": "serving", "site": "decode", "ts": 2.0, "flops": 4.0e10,
        "bytes_accessed": 19.25e9, "captures": 1,
        "memory": {"temp_bytes": 6_500_000_000, "argument_bytes": 1}})
    yield
    introspect.clear()


@pytest.mark.parametrize("name,run,trace,want", [
    ("flash_fwd_ms_per_step", {}, TRAIN_TRACE, 4.0),
    ("flash_bwd_ms_per_step", {}, TRAIN_TRACE, 12.0),
    ("lm_head_loss_ms_per_step", {}, TRAIN_TRACE, 17.0),
    ("optimizer_ms_per_step", {}, TRAIN_TRACE, 26.0),
    ("unscoped_ms_per_step", {}, TRAIN_TRACE, 0.5),
    ("prefill_device_ms_per_ktoken", SERVE_RUN, SERVE_TRACE,
     0.137 / (3 * 512 + 2048) * 1e6),
    ("decode_host_ms_per_dispatch", SERVE_RUN, SERVE_TRACE, 8.0),
    ("decode_temp_gb", SERVE_RUN, SERVE_TRACE, 6.5),
    ("decode_compiled_gb_per_step", SERVE_RUN, SERVE_TRACE, 19.25),
])
def test_reader_finds_its_names(captured, name, run, trace, want):
    assert reader(name)(run, trace) == pytest.approx(want, rel=1e-9)


# what the parent commit's trace looks like: every program is `jit_traced`,
# every kernel `%jvp__.<n>`, and the reader of the decode scan still guesses
ANONYMOUS = trace_of(
    {"jit_traced(7)": [0.800, 4], "jit_traced(8)": [3.28, 10]},
    {"%jvp__.24 [tpu_custom_call]": [0.008, 4], "%fusion.10": [0.040, 4],
     "%while.4": [3.2, 10]})


@pytest.mark.parametrize("name", [
    "flash_fwd_ms_per_step", "flash_bwd_ms_per_step",
    "lm_head_loss_ms_per_step", "optimizer_ms_per_step",
    "unscoped_ms_per_step", "prefill_device_ms_per_ktoken",
    "decode_host_ms_per_dispatch"])
@pytest.mark.parametrize("trace", [ANONYMOUS, None,
                                   trace_of({}, {})],
                         ids=["anonymous", "no_trace", "empty"])
def test_reader_without_its_names_returns_none(captured, name, trace):
    assert reader(name)(SERVE_RUN, trace) is None


@pytest.mark.parametrize("name", [
    "lm_head_loss_ms_per_step", "optimizer_ms_per_step",
    "unscoped_ms_per_step", "decode_temp_gb",
    "decode_compiled_gb_per_step"])
def test_reader_of_a_site_never_captured_returns_none(name, capsys):
    introspect.clear()
    introspect._skipped[("serving", "decode")] = "compile took 130.0s"
    try:
        assert reader(name)(SERVE_RUN, TRAIN_TRACE) is None
        if name.startswith("decode_"):
            assert "compile took 130.0s" in capsys.readouterr().err
    finally:
        introspect.clear()


def test_a_program_without_site_scopes_reads_none(captured, monkeypatch):
    """The parent commit's introspection has `site_cost` and no
    `site_scopes`: the scope readers give nothing and do not raise."""
    monkeypatch.delattr(introspect, "site_scopes")
    assert named.introspect() is None
    assert reader("unscoped_ms_per_step")({}, TRAIN_TRACE) is None
    assert reader("decode_temp_gb")(SERVE_RUN, SERVE_TRACE) == 6.5


def test_programs_and_kernels_are_found_by_whole_names():
    tr = trace_of({"jit_train_step(1)": [1.0, 2],
                   "jit_train_step_multi(2)": [5.0, 1],
                   "jit_decode(3)": [2.0, 4], "jit_decode(9)": [1.0, 2]},
                  {"%flash_fwd.1 [tpu_custom_call]": [0.25, 2],
                   "%flash_decode.1 [tpu_custom_call]": [1.0, 8]})
    assert named.module(tr, "train_step") == (1.0, 2)
    assert named.module(tr, "decode") == (3.0, 6)
    assert named.module(tr, "spec_verify") is None
    assert named.kernel_seconds(tr, "flash_fwd") == 0.25
    assert named.kernel_seconds(tr, "flash_bwd_dq", "flash_bwd_dkv") is None
    assert named.instruction("%fusion.12 [tpu_custom_call]") == "fusion.12"
