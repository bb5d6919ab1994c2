"""The flash kernels compile for a described TPU v5e at real widths.

No chip is attached here; the TPU's compiler is installed and compiles for
a chip that is described. Interpret mode cannot show what this shows: a
slice off the tiling, too much VMEM, or the compiler's own refusals (at
256 x 256 tiles a tile loop of static bounds around the backward's
transposed key tile tripped an internal check of the MXU pass, which is why
`_loop` unrolls every loop whose bounds are known). Nothing runs, so these
say nothing about results or times.

All in this one file and behind a fixture: only the worker that is given
the file loads the TPU's library."""
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# the package re-exports the function under the module's name
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler for it here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, b, sq, h, d, *, sk=None, causal=True, lens=False,
             dropout=0.0, grad=True, dtype=jnp.bfloat16, **kw):
    q = jax.ShapeDtypeStruct((b, sq, h, d), dtype, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, sk or sq, h, d), dtype, sharding=one_chip)
    lens_in = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)

    def f(q, k, v, kv_lens):
        o = fa.flash_attention(
            q, k, v, causal=causal, kv_lens=kv_lens if lens else None,
            dropout_p=dropout, dropout_seed=3, interpret=False, **kw)
        return o.astype(jnp.float32).sum()
    fn = jax.grad(f, argnums=(0, 1, 2)) if grad else f
    text = jax.jit(fn).lower(q, k, k, lens_in).compile().as_text()
    return text.count("tpu_custom_call")


CASES = {
    "train_cell_d64": (dict(b=8, sq=1024, h=16, d=64), 2),
    "d128": (dict(b=8, sq=1024, h=8, d=128), 2),
    "encoder_lens": (dict(b=8, sq=1024, h=12, d=64, causal=False,
                          lens=True), 2),
    "dropout": (dict(b=8, sq=1024, h=16, d=64, dropout=0.1), 2),
    "axk1_prefill_d256": (dict(b=1, sq=1024, h=64, d=256, lens=True,
                               grad=False), 1),
    "d256_gradients": (dict(b=1, sq=1024, h=8, d=256, lens=True), 3),
    "s2048_two_spans": (dict(b=2, sq=2048, h=16, d=64), 2),
    "s4096_lens_dropout": (dict(b=1, sq=4096, h=8, d=64, lens=True,
                                dropout=0.1), 2),
    "sq_lt_sk": (dict(b=2, sq=512, sk=1024, h=16, d=64), 2),
    "sq_gt_sk": (dict(b=2, sq=1024, sk=512, h=16, d=64), 2),
    "float32_d64": (dict(b=2, sq=1024, h=16, d=64, dtype=jnp.float32), 2),
    "float32_d128_lens": (dict(b=2, sq=1024, h=8, d=128, lens=True,
                               dtype=jnp.float32), 2),
    "scale_not_a_power_of_two": (dict(b=2, sq=1024, h=8, d=64,
                                      sm_scale=0.1), 2),
    "tiles_256": (dict(b=2, sq=1024, h=16, d=64, block_q=256,
                       block_k=256), 2),
    "tiles_256_d128": (dict(b=2, sq=1024, h=8, d=128, block_q=256,
                            block_k=256), 2),
    "tiles_128_not_causal": (dict(b=2, sq=1024, h=16, d=64, causal=False,
                                  block_q=128, block_k=128), 2),
    "tiles_512_256": (dict(b=2, sq=1024, h=16, d=64, block_q=512,
                           block_k=256), 2),
    "s640_five_tiles_of_128": (dict(b=2, sq=640, h=16, d=64), 2),
    "s8192_fused_backward": (dict(b=1, sq=8192, h=4, d=64), 2),
    "s16384_split_backward": (dict(b=1, sq=16384, h=2, d=64), 3),
}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_flash_attention_compiles_for_v5e(case, one_chip):
    shape, kernels = CASES[case]
    assert _compile(one_chip, **shape) == kernels


def test_flash_decode_compiles_for_v5e(one_chip):
    q = jax.ShapeDtypeStruct((8, 1, 12, 64), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((8, 1024, 12, 64), jnp.bfloat16,
                             sharding=one_chip)
    lens = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda q, k, v, l: fa.flash_decode(
        q, k, v, l, interpret=False)).lower(q, k, k, lens).compile().as_text()
    assert text.count("tpu_custom_call") == 1


def test_the_paged_decode_kernel_compiles_at_the_lfm2_cell_s_shape(one_chip):
    """`serve-lfm2-closed64`: 64 slots, 8 K/V heads serving 4 query heads
    of 64 each, 1025 pages of 128, 16 table entries a slot, bf16 pages."""
    # by name: the package re-exports a function under this module's name
    fd = importlib.import_module("paddle_tpu.ops.pallas.flash_decode")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = sds((64, 8, 4, 64), jnp.float32)
    pages = sds((8, 1025, 128, 64), jnp.bfloat16)
    text = jax.jit(lambda q, k, v, pt, lens: fd.paged_flash_decode(
        q, k, v, pt, lens, interpret=False)).lower(
            q, pages, pages, sds((64, 16), jnp.int32),
            sds((64,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1


# (kv heads, query heads per kv head, head size): serve-lfm2-closed64's
# attention layers, a GPT's heads of 128, and serve-olmohybrid-closed64's
# 30 heads of 128, every one a K/V head
DECODE_LOOPS = {"serve-lfm2-closed64": (8, 4, 64), "gpt_d128": (16, 1, 128),
                "serve-olmohybrid-closed64": (30, 1, 128)}


@pytest.mark.parametrize("cell", list(DECODE_LOOPS))
def test_the_decode_loop_copies_no_pool(cell, one_chip):
    """Four layers' token writes and paged kernel in a loop of 8 steps,
    the pools as the engine lays them out for the kernel (64 slots, 1025
    pages of 128, 16 table entries a slot, bf16). With rows of one head
    of 64 the compiler kept each pool with the page's rows on the lanes
    for the scatter and copied all eight back to row-major for the kernel
    every step (537 MB of scratch); heads side by side in rows of 128
    leave nothing to copy."""
    from paddle_tpu.nlp import paged_cache as pc
    fd = importlib.import_module("paddle_tpu.ops.pallas.flash_decode")
    hkv, g, d = DECODE_LOOPS[cell]
    b, layers = 64, 4
    pool = jax.eval_shape(lambda: pc.KVCacheSpec(hkv, d).alloc(
        1025, 128, "bfloat16", use_flash=True)[0])

    def loop(pools, pt, pos, q, k):
        def step(carry, _):
            pools, pos = carry
            new, outs = [], []
            for kp, vp in pools:
                cache = pc.PagedLayerCache(kp, vp, pt, pos)
                kp, vp, _, _ = pc.write_token_kv(cache, k, k,
                                                 jnp.ones((b,), bool))
                outs.append(fd.paged_flash_decode(
                    q, kp, vp, pt, pos + 1, interpret=False).sum())
                new.append((kp, vp))
            return (new, pos + 1), sum(outs)
        return jax.lax.scan(step, (pools, pos), None, length=8)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = sds(pool.shape, pool.dtype)
    compiled = jax.jit(loop, donate_argnums=(0,)).lower(
        [(pages, pages)] * layers, sds((b, 16), jnp.int32),
        sds((b,), jnp.int32), sds((b, hkv, g, d), jnp.float32),
        sds((b, hkv, d), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == layers
    shape = "[" + ",".join(map(str, pool.shape)) + "]"
    copies = [ln for ln in text.splitlines()
              if " copy(" in ln and shape in ln.split("copy(")[0]]
    assert copies == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# the held experts at both expert cells' real widths, a decode step's rows
# and the widest prefill bucket the rule sends to the kernel
# (moe.STREAMED_MAX_ROWS): (token rows, h, m, experts held)
EXPERT_CELLS = {
    "serve-lfm2-closed64": (64, 2048, 1792, 32),
    "serve-axk1-closed32": (32, 7168, 2048, 12),
    "lfm2_prefill_128": (128, 2048, 1792, 32),
    "axk1_prefill_128": (128, 7168, 2048, 12),
}


@pytest.mark.parametrize("cell", list(EXPERT_CELLS))
def test_grouped_experts_compiles_at_the_cell_s_widths(cell, one_chip):
    """One kernel, within the VMEM limit it sets for itself (the compiler
    refuses a kernel that needs more), with the chip's 128 MiB far off."""
    from paddle_tpu.ops.pallas import grouped_experts as ge
    t, h, m, held = EXPERT_CELLS[cell]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(lambda *a: ge.grouped_experts(
        *a, interpret=False)).lower(
            sds((t, h), jnp.float32), sds((t, held), jnp.float32),
            sds((held,), jnp.bool_), sds((held, h, 2 * m), jnp.bfloat16),
            sds((held, m, h), jnp.bfloat16)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    limit = ge._plan(t, h, m, jnp.bfloat16, None, None)[3]
    assert f'"vmem_limit_bytes":{limit}' in text.replace(" ", "") \
        or str(limit) in text
    assert limit <= 48 << 20


@pytest.mark.parametrize("weights,most_mb", [("bfloat16", 64),
                                             ("float32", None)])
def test_the_decode_loop_reads_held_weights_in_place(weights, most_mb,
                                                     one_chip):
    """Four GPT-1.3B layers' projections (q, k, v, out, fc1, fc2 at width
    2048) in a loop of 8 steps over 12 rows, float32 activations, through
    `F.linear`. With the matrices held in bfloat16 the loop reads them as
    they are: `F.linear` widens the weight to the input's float32, and
    the compiler folds that into the product, which reads the bfloat16
    matrix (3 MB of scratch). Float32 matrices it rounds outside the
    loop, into a bfloat16 copy made at every call (292 MB of scratch for
    the 403 MB the four layers hold at bfloat16)."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.tensor import Tensor
    h, m, rows, layers = 2048, 8192, 12, 4
    shapes = [(h, h)] * 4 + [(h, m), (m, h)]

    def loop(ws, x):
        def lin(a, w):
            return F.linear(Tensor(a), Tensor(w))._value

        def step(x, _):
            for q, k, v, o, fc1, fc2 in ws:
                x = x + lin(lin(x, q) * lin(x, k) + lin(x, v), o)
                x = x + lin(jax.nn.gelu(lin(x, fc1)), fc2)
            return x, x.sum()
        return jax.lax.scan(step, x, None, length=8)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(loop).lower(
        [[sds(s, weights) for s in shapes]] * layers,
        sds((rows, h), jnp.float32)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    if most_mb is not None:
        assert temp < most_mb << 20
    else:
        assert temp > 128 << 20


def _gated_delta_layer(one_chip):
    """Olmo-Hybrid's Gated DeltaNet layer at its published head widths (30
    heads, dk 96, dv 192, 4 taps over 11,520 channels) with its leaves as
    shapes of the published hidden size 3840: the layer is built at a
    hidden size of 30 (nothing of width is made) and handed the full-size
    leaves as arguments."""
    from paddle_tpu.nlp import olmo_hybrid as oh
    cfg = oh.OlmoHybridConfig(hidden_size=30, intermediate_size=8,
                              num_hidden_layers=1,
                              layer_types=("linear_attention",),
                              dtype="bfloat16")
    layer = oh.OlmoHybridGatedDeltaNet(cfg)
    full = {"q_proj": (3840, 2880), "k_proj": (3840, 2880),
            "v_proj": (3840, 5760), "z_proj": (3840, 5760),
            "a_proj": (3840, 30), "b_proj": (3840, 30), "conv": (4, 11520),
            "A_log": (30,), "dt_bias": (30,), "o_norm.weight": (192,),
            "o_proj": (5760, 3840)}
    names = {n for n, _ in layer.named_parameters()}
    assert names == set(full)
    params = {n: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
              for n, s in full.items()}
    return layer, params


def test_gated_delta_decode_compiles_at_the_cell_s_widths(one_chip):
    """`serve-olmohybrid-closed64`'s state: 64 slots of 30 heads of
    96 x 192, two heads a row of 384; one kernel, the state aliased to the
    new state and no scratch beside it."""
    from paddle_tpu.ops.pallas import gated_delta_decode as gdd

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, h = 64, 30
    compiled = jax.jit(lambda *a: gdd.gated_delta_decode(
        *a, interpret=False), donate_argnums=(5,)).lower(
            sds((b, h, 96)), sds((b, h, 96)), sds((b, h, 192)),
            sds((b, h)), sds((b, h)), sds((b, 15, 96, 384)),
            sds((b,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    kernel = [ln for ln in text.splitlines() if "%gated_delta_decode" in ln
              and "custom-call(" in ln]
    assert len(kernel) == 1 and "output_to_operand_aliasing" in kernel[0]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_the_delta_state_decode_loop_updates_the_state_in_place(
        one_chip, monkeypatch):
    """Four Gated DeltaNet layers' decode step in a loop of 8 steps over 64
    slots, each layer's float32 state (142 MB, two heads a row of 384 as
    the engine stores it) and convolution rows carried and donated as the
    engine's decode scan carries them, through the kernel as on a TPU: one
    `gated_delta_decode` a layer, under the `gated_delta` scope, reads and
    writes each state where it lies. No fusion or copy makes a whole
    state, so the scratch stays far below one layer's state."""
    from paddle_tpu.nlp import olmo_hybrid as oh
    from paddle_tpu.nlp.paged_cache import DeltaStateCache, DeltaStateSpec
    from paddle_tpu.nn.layer import functional_call
    from paddle_tpu.observability.introspect import parse_scopes
    from paddle_tpu.ops.pallas import _common
    from paddle_tpu.tensor import Tensor
    # what a TPU backend answers: the kernel, compiled natively
    monkeypatch.setattr(_common, "interpret_default", lambda: False)
    assert oh.gated_delta_path() == "kernel"
    layer, params = _gated_delta_layer(one_chip)
    b, layers = 64, 4
    state_shape = jax.eval_shape(lambda: DeltaStateSpec(
        11520, 4, 30, 96, 192).alloc(None, None, "bfloat16", b)[1]).shape
    assert state_shape == (b, 15, 96, 384)

    def loop(params, states, x, live):
        def step(states, _):
            new, outs = [], []
            for conv, state in states:
                out, kept = functional_call(
                    layer, params, {}, Tensor(x),
                    cache=DeltaStateCache(conv, state, live))
                outs.append(out._value.sum())
                new.append(kept)
            return new, sum(outs)
        return jax.lax.scan(step, states, None, length=8)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    states = [(sds((b, 3, 11520), jnp.bfloat16),
               sds(state_shape, jnp.float32))] * layers
    compiled = jax.jit(loop, donate_argnums=(1,)).lower(
        params, states, sds((b, 1, 3840), jnp.float32),
        sds((b,), jnp.bool_)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    text = compiled.as_text()
    kernels = [ln for ln in text.splitlines()
               if "custom-call(" in ln and "%gated_delta_decode" in ln]
    assert len(kernels) == layers
    scopes = parse_scopes(text)
    for ln in kernels:
        name = ln.split("=", 1)[0].split()[-1].lstrip("%")
        assert "gated_delta" in scopes[name].split("/")
    whole = "f32[" + ",".join(map(str, state_shape)) + "]"
    made = [ln for ln in text.splitlines() for op in (" fusion(", " copy(")
            if op in ln and whole in ln.split("=", 1)[1].split(op, 1)[0]]
    assert made == []


def test_the_chunked_prefill_compiles_at_the_longest_bucket(one_chip):
    """One Gated DeltaNet layer over a prompt of 1024 positions: the
    chunked scan's triangular solve and products at HIGHEST compile for
    the chip, with the scratch of a prompt's activations only."""
    layer, params = _gated_delta_layer(one_chip)
    from paddle_tpu.nn.layer import functional_call
    from paddle_tpu.tensor import Tensor

    def prefill(params, x, lens):
        out, kept = functional_call(layer, params, {}, Tensor(x),
                                    kv_lens=lens)
        return out._value, kept.conv, kept.state

    compiled = jax.jit(prefill).lower(
        params, jax.ShapeDtypeStruct((1, 1024, 3840), jnp.float32,
                                     sharding=one_chip),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 512 << 20
