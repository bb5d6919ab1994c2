"""paddle.flops vs XLA cost_analysis — the analytic-drift check.

`paddle.flops` mirrors the reference's dynamic_flops accounting:
multiply-adds counted ONCE, Linear/Conv layers only (attention score/
value matmuls, norms and activations are ignored). XLA's
`cost_analysis()` counts real FLOPs of the compiled forward (2 per
MAC, everything included). The two must track within a documented
band — if they drift apart, either the analytic mirror or the
introspection capture broke:

    ratio = xla_flops / (2 * paddle.flops MACs)

- lower bound 0.9: XLA must at least account the dense matmuls the
  analytic side counts (a ratio below ~1 means cost analysis lost
  work the convention counts — capture bug);
- upper bound 1.8: the uncounted extras (attention matmuls at small
  seq, BN/ReLU elementwise, layernorm) are bounded for the shapes
  pinned here — a blowout means the analytic mirror stopped seeing a
  layer (hook bug) or XLA started materializing something new.

Skips with a reason where this jax/backend exposes no "flops" key in
cost_analysis (the introspect layer's own null-honesty contract).
CPU-only; shapes are tiny.
"""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nn.layer import functional_call
from paddle_tpu.observability import introspect
from paddle_tpu.tensor import Tensor

RATIO_LO, RATIO_HI = 0.9, 1.8


def _xla_forward_flops(net, x_np):
    """cost_analysis FLOPs of the jitted eval forward; skips the test
    when this jax build reports no flops key."""
    net.eval()
    params, buffers = net.raw_state()

    def fwd(params, buffers, x):
        out = functional_call(net, params, buffers, Tensor(x))
        return out._value if isinstance(out, Tensor) else out

    compiled = jax.jit(fwd).lower(
        params, buffers, jax.numpy.asarray(x_np)).compile()
    cost = introspect.normalize_cost(compiled.cost_analysis())
    if not cost or not cost.get("flops"):
        pytest.skip(f"jax {jax.__version__} on "
                    f"{jax.default_backend()} exposes no 'flops' key "
                    "in cost_analysis — drift not checkable here")
    return cost["flops"]


def _assert_in_band(xla_flops, analytic_macs, what):
    assert analytic_macs > 0, f"{what}: paddle.flops counted nothing"
    ratio = xla_flops / (2.0 * analytic_macs)
    assert RATIO_LO <= ratio <= RATIO_HI, (
        f"{what}: xla={xla_flops:.3g} vs 2*analytic="
        f"{2 * analytic_macs:.3g} (ratio {ratio:.3f} outside "
        f"[{RATIO_LO}, {RATIO_HI}] — see module docstring)")
    return ratio


def test_gpt_block_analytic_tracks_compiled():
    from paddle_tpu.nlp.gpt import GPTDecoderLayer, _resolve_config
    paddle.seed(0)
    cfg = _resolve_config("gpt-tiny", hidden_dropout_prob=0.0,
                          attention_probs_dropout_prob=0.0,
                          use_flash_attention=False)
    blk = GPTDecoderLayer(cfg)
    batch, seq, hidden = 2, 16, cfg.hidden_size
    analytic = paddle.flops(blk, [batch, seq, hidden])
    x = np.random.default_rng(0).standard_normal(
        (batch, seq, hidden)).astype("float32")
    xla = _xla_forward_flops(blk, x)
    _assert_in_band(xla, analytic, "GPT block")


def test_resnet_bottleneck_analytic_tracks_compiled():
    from paddle_tpu.vision.models.resnet import BottleneckBlock
    paddle.seed(0)
    blk = BottleneckBlock(64, 16)   # 64 -> 16 -> 64, no downsample
    batch, hw = 2, 8
    analytic = paddle.flops(blk, [batch, 64, hw, hw])
    x = np.random.default_rng(0).standard_normal(
        (batch, 64, hw, hw)).astype("float32")
    xla = _xla_forward_flops(blk, x)
    _assert_in_band(xla, analytic, "ResNet bottleneck")


def test_bench_analytic_convention_tracks_compiled_train_step():
    """The 6N+12Lhs convention the benchmark computes `mfu.train` from
    (`benchmarks/kernels/gpt_step.train_flops_per_token`), against the
    cost analysis of the REAL compiled train step (fwd+bwd+opt). Wider
    band: the convention ignores the optimizer update and counts
    recompute-free backward."""
    import sys
    sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
    from bench import build_engine
    from benchmarks.kernels.gpt_step import train_flops_per_token

    paddle.seed(0)
    batch, seq = 2, 32
    eng = build_engine("gpt-tiny", batch, seq, amp=False,
                       use_flash=False)
    rng = np.random.default_rng(0)
    vocab = eng.network.config.vocab_size
    ids = rng.integers(0, vocab, (batch, seq)).astype("int32")
    labels = rng.integers(0, vocab, (batch, seq)).astype("int32")
    loss, _ = eng.train_batch([ids], [labels])
    float(np.asarray(loss))
    e = introspect.site_cost("train_step", tracer="engine")
    if e is None or not e.get("flops"):
        pytest.skip(f"jax {jax.__version__} exposes no flops for the "
                    "compiled train step")
    cfg = eng.network.config
    sizes = {k: getattr(cfg, k) for k in (
        "hidden_size", "intermediate_size", "num_hidden_layers",
        "vocab_size", "max_position_embeddings")}
    analytic = train_flops_per_token(sizes, seq) * batch * seq
    ratio = e["flops"] / analytic
    # 6N already includes the fwd+bwd factor; the loose band covers
    # the embedding/softmax/opt work the convention ignores at tiny
    # hidden sizes
    assert 0.5 <= ratio <= 3.0, (
        f"train-step drift blowout: compiled {e['flops']:.3g} vs "
        f"analytic {analytic:.3g} (ratio {ratio:.3f})")
