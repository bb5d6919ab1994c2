"""The two paths of ops/pallas/flash_attention.py (ISSUE 32), interpret mode
on the CPU: the resident kernels (head sizes up to 128: transposed score
tiles, plain / masked / skipped tiles, one backward kernel or the split
form) against a float32 reference, the static tile counter against a brute
force count, and the rule that keeps the tiled forward for wider heads."""
import importlib
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from fleet_proc_support import jaxpr_eqns  # noqa: E402

# the package re-exports the function under the module's name
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

BLOCK = 128


def _rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32)).astype(dtype)


def _ref(q, k, v, causal=False, lens=None, dropout=0.0, seed=0,
         sm_scale=None):
    """float32 attention; dropout by the kernel's own counter hash, so the
    same elements drop (denominator from the un-dropped probabilities)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qf, kf, vf = (jnp.swapaxes(a, 1, 2).astype(jnp.float32)
                  for a in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq), s,
                      -jnp.inf)
    if lens is not None:
        s = jnp.where(jnp.arange(sk)[None, None, None, :]
                      < lens[:, None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    if dropout:
        keep = jax.vmap(lambda bh: fa._dropout_keep(
            jnp.int32(seed), bh, 0, 0, (sq, sk), sq, sk, sk, dropout))(
                jnp.arange(b * h, dtype=jnp.int32)).reshape(b, h, sq, sk)
        p = jnp.where(keep, p / (1.0 - dropout), 0.0)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vf), 1, 2)


def _value_and_grads(fn, q, k, v):
    def loss(q, k, v):
        o = fn(q, k, v).astype(jnp.float32)
        return jnp.sum(o * jnp.cos(o)), o
    (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                   has_aux=True)(q, k, v)
    return (o,) + g


def _check(case, d, dtype=jnp.float32, tol=5e-3, sq=384, sk=384, h=2,
           **blocks):
    kw = dict(case)
    lens = kw.pop("lens", None)
    b = 2
    sq, sk = kw.pop("sq", sq), kw.pop("sk", sk)
    q = _rand((b, sq, h, d), 1, dtype)
    k, v = (_rand((b, sk, h, d), i, dtype) for i in (2, 3))
    lens = None if lens is None else jnp.asarray(lens, jnp.int32)
    drop = kw.pop("dropout", 0.0)
    blocks = blocks or dict(block_q=BLOCK, block_k=BLOCK)
    got = _value_and_grads(
        lambda q, k, v: fa.flash_attention(
            q, k, v, kv_lens=lens, dropout_p=drop, dropout_seed=5,
            interpret=True, **blocks, **kw), q, k, v)
    want = _value_and_grads(
        lambda q, k, v: _ref(q, k, v, lens=lens, dropout=drop, seed=5, **kw),
        q, k, v)
    for name, a, w in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(w, np.float32),
            rtol=tol, atol=tol, err_msg=name)


# tiles of 128 over 384 x 384: every row of query tiles meets plain, masked
# and skipped key tiles
CASES = {
    "causal": dict(causal=True),
    "full": dict(causal=False),
    "lens_inside_tile": dict(lens=[200, 300]),
    "lens_on_tile_edge": dict(lens=[256, 128]),
    "lens_zero": dict(lens=[0, 384]),
    "causal_lens": dict(causal=True, lens=[200, 0]),
    "sq_lt_sk": dict(causal=True, sq=128, sk=384),
    "sq_gt_sk": dict(causal=True, sq=384, sk=128),
    "dropout": dict(causal=True, dropout=0.2),
    "dropout_lens": dict(lens=[300, 129], dropout=0.2),
    "scale_other": dict(causal=True, sm_scale=0.1),
    "scale_power_of_two": dict(causal=True, sm_scale=0.25),
}


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_resident_forward_and_gradients(case, d):
    _check(CASES[case], d)


@pytest.mark.parametrize("case", ["causal", "lens_inside_tile", "sq_gt_sk",
                                  "dropout"])
def test_resident_spans(case, monkeypatch):
    """Sequences longer than a span: the state crosses grid steps."""
    monkeypatch.setattr(fa, "_SPAN_ROWS", 256)
    kw = dict(CASES[case])
    kw.setdefault("sq", 512)
    kw.setdefault("sk", 512 if kw["sq"] == 512 else 256)
    if "lens" in kw:
        kw["lens"] = [200, 390]
    assert fa._fit_span(512, BLOCK) == 256
    _check(kw, 64, h=1)


@pytest.mark.parametrize("case", ["causal", "lens_inside_tile", "sq_gt_sk",
                                  "dropout_lens", "scale_other"])
def test_split_backward_where_dq_scratch_does_not_fit(case, monkeypatch):
    monkeypatch.setattr(fa, "_FUSED_DQ_BYTES", 64 * 1024)
    assert not fa._fused_bwd_fits(384, 64, 4)
    _check(CASES[case], 64, h=1)


@pytest.mark.parametrize("sq,d,itemsize,fits", [
    (1024, 64, 2, True), (8192, 64, 2, True), (16384, 64, 2, False),
    (4096, 128, 4, True), (8192, 128, 4, False), (1024, 256, 2, False)])
def test_fused_backward_is_chosen_from_the_shapes(sq, d, itemsize, fits):
    assert fa._fused_bwd_fits(sq, d, itemsize) is fits


def _kernel_names(fn, *args):
    return {e.params["name"]
            for e in jaxpr_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "pallas_call"}


@pytest.mark.parametrize("s,d,budget,want", [
    (256, 64, None, {"flash_fwd", "flash_bwd_dkv_dq"}),
    (256, 128, None, {"flash_fwd", "flash_bwd_dkv_dq"}),
    (256, 64, 1024, {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    (256, 256, None, {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"})])
def test_backward_kernels_by_shape(s, d, budget, want, monkeypatch):
    if budget is not None:
        monkeypatch.setattr(fa, "_FUSED_DQ_BYTES", budget)
    q = jax.ShapeDtypeStruct((1, s, 2, d), jnp.bfloat16)
    grad = jax.grad(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, interpret=True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    assert _kernel_names(grad, q, q, q) == want


@pytest.mark.parametrize("d", [64, 128])
def test_resident_bf16(d):
    _check(dict(causal=True), d, dtype=jnp.bfloat16, tol=4e-2)


@pytest.mark.parametrize("case", ["causal_lens", "dropout"])
def test_wide_heads_tiled_forward_split_backward(case):
    kw = dict(CASES[case], sq=256, sk=256)
    if "lens" in kw:
        kw["lens"] = [200, 0]
    _check(kw, 256, h=1, block_q=512, block_k=512)


def test_default_blocks_run_whole_sequences_as_one_tile():
    _check(dict(causal=True, sq=256, sk=256), 64, h=1, block_q=512,
           block_k=512)


# -- the static tile counter -------------------------------------------------

def _brute(sq, sk, bq, bk, causal, kv_len=None):
    """Per (query tile, key tile): 'plain', 'masked' or 'skipped'."""
    qp = np.arange(sq)[:, None]
    kp = np.arange(sk)[None, :]
    vis = np.ones((sq, sk), bool)
    if causal:
        vis &= qp + (sk - sq) >= kp
    if kv_len is not None:
        vis &= kp < kv_len
    kinds = {}
    for i in range(sq // bq):
        for j in range(sk // bk):
            t = vis[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
            kinds[i, j] = ("plain" if t.all() else
                           "masked" if t.any() else "skipped")
    return kinds


def test_tile_counts_at_the_train_cells_shape():
    bq = fa._fit_block(1024, fa.DEFAULT_BLOCK_Q, 64)
    bk = fa._fit_block(1024, fa.DEFAULT_BLOCK_K, 64)
    assert (bq, bk) == (512, 512)
    assert fa.tile_counts(1024, 1024, bq, bk, True) == (1, 2, 1)
    assert fa.tile_counts(1024, 1024, 256, 256, True) == (6, 4, 6)
    assert fa.tile_counts(1024, 1024, 512, 512, False) == (4, 0, 0)


SHAPES = [(1024, 1024, 256, 256, True), (1024, 1024, 512, 256, True),
          (1024, 1024, 128, 512, True), (512, 1024, 128, 256, True),
          (1024, 512, 256, 128, True), (768, 768, 128, 384, False),
          (384, 384, 384, 128, True)]


@pytest.mark.parametrize("sq,sk,bq,bk,causal", SHAPES)
def test_tile_counts_against_brute_force(sq, sk, bq, bk, causal):
    kinds = list(_brute(sq, sk, bq, bk, causal).values())
    assert fa.tile_counts(sq, sk, bq, bk, causal) == tuple(
        kinds.count(x) for x in ("plain", "masked", "skipped"))


@pytest.mark.parametrize("kv_len", [None, 0, 200, 512, 1000])
@pytest.mark.parametrize("sq,sk,bq,bk,causal", SHAPES[:5])
def test_loop_bounds_of_both_sides_against_brute_force(sq, sk, bq, bk,
                                                      causal, kv_len):
    """`_key_span` (forward: key tiles of a query tile) and `_row_span`
    (backward: query tiles of a key tile) name the same tiles, also in
    spans that start off the origin."""
    kinds = _brute(sq, sk, bq, bk, causal, kv_len)
    nq, nk = sq // bq, sk // bk
    for i0, j0 in ((0, 0), (nq // 2, nk // 2)):
        for i in range(i0, nq):
            plain, run = (int(x) for x in fa._key_span(
                i * bq, j0 * bk, nk - j0, bq, bk, sk - sq, causal, kv_len))
            for j in range(j0, nk):
                want = ("plain" if j - j0 < plain else
                        "masked" if j - j0 < run else "skipped")
                assert kinds[i, j] == want, (i, j)
        for j in range(j0, nk):
            run, plain = (int(x) for x in fa._row_span(
                i0 * bq, j * bk, nq - i0, bq, bk, sk - sq, causal, kv_len))
            for i in range(i0, nq):
                want = ("skipped" if i - i0 < run else
                        "masked" if i - i0 < plain else "plain")
                assert kinds[i, j] == want, (i, j)


# -- the separation ------------------------------------------------------------

def _forward_call(d, s=1024, decode=False, monkeypatch=None):
    """What the forward hands `pallas_call`, recorded, nothing run."""
    calls = []
    real = fa.pallas_call

    def spy(kernel, **kw):
        calls.append((kernel, kw))
        return real(kernel, **kw)
    monkeypatch.setattr(fa, "pallas_call", spy)
    q = jax.ShapeDtypeStruct((1, 1 if decode else s, 4, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, s, 4, d), jnp.bfloat16)
    lens = jax.ShapeDtypeStruct((1,), jnp.int32)
    if decode:
        jax.eval_shape(lambda q, k, v, l: fa.flash_decode(
            q, k, v, l, interpret=True), q, k, k, lens)
    else:
        jax.eval_shape(lambda q, k, v, l: fa.flash_attention(
            q, k, v, causal=True, kv_lens=l, interpret=True), q, k, k, lens)
    (kernel, kw), = calls
    return kernel, kw


@pytest.mark.parametrize("s", [128, 1024, 2048])
def test_head_size_256_keeps_the_tiled_forward(s, monkeypatch):
    kernel, kw = _forward_call(256, s, monkeypatch=monkeypatch)
    assert kernel.func is fa._fwd_kernel
    assert kernel.keywords["block_q"] == kernel.keywords["block_k"] == 128
    assert kw["name"] == "flash_fwd"
    assert kw["grid"] == (4, s // 128, s // 128)
    assert [sp.block_shape for sp in kw["in_specs"][2:]] == \
        [(1, 128, 256)] * 3
    assert [sp.block_shape for sp in kw["out_specs"]] == \
        [(1, 128, 256), (1, 128, 8)]
    assert [tuple(sc.shape) for sc in kw["scratch_shapes"]] == \
        [(128, 128), (128, 128), (128, 256)]
    assert "compiler_params" not in kw


@pytest.mark.parametrize("d", [64, 128])
def test_head_sizes_up_to_128_take_the_resident_forward(d, monkeypatch):
    kernel, kw = _forward_call(d, 2048, monkeypatch=monkeypatch)
    assert kernel.func is fa._fwd_resident_kernel
    assert kernel.keywords["block_q"] == kernel.keywords["block_k"] == 512
    assert kw["name"] == "flash_fwd"
    assert kw["grid"] == (4, 2, 2)
    assert [sp.block_shape for sp in kw["in_specs"][2:]] == \
        [(1, 1024, d)] * 3


def test_flash_decode_keeps_the_tiled_forward(monkeypatch):
    kernel, kw = _forward_call(64, 1024, decode=True,
                               monkeypatch=monkeypatch)
    assert kernel.func is fa._fwd_kernel
    assert kw["grid"] == (4, 1, 2)


def test_only_the_head_size_chooses():
    """No keyword, flag or environment variable: the public signatures are
    the parent's and the rule is a function of the head size alone."""
    import inspect
    assert list(inspect.signature(fa.flash_attention).parameters) == [
        "q", "k", "v", "causal", "sm_scale", "kv_lens", "dropout_p",
        "dropout_seed", "block_q", "block_k", "interpret"]
    assert list(inspect.signature(fa.flash_decode).parameters) == [
        "q", "k_cache", "v_cache", "kv_lens", "sm_scale", "block_k",
        "interpret"]
    assert [fa._resident(d) for d in (64, 96, 128, 192, 256)] == \
        [True, True, True, False, False]
    src = inspect.getsource(fa)
    assert "import os" not in src and "getenv" not in src


# -- sm_scale ------------------------------------------------------------------

@pytest.mark.parametrize("scale,exact", [
    (0.125, True), (0.25, True), (1.0, True), (2.0, True),
    (1 / math.sqrt(128), False), (0.1, False), (0.0721, False)])
def test_scale_moves_onto_an_operand_only_for_a_power_of_two(scale, exact):
    assert fa._scale_on_operand(scale) is exact


def _score_tile_muls(scale, grad):
    """Multiplications of a whole float32 score tile in the kernels."""
    q = jax.ShapeDtypeStruct((1, 256, 1, 64), jnp.bfloat16)

    def f(q, k, v):
        return fa.flash_attention(q, k, v, sm_scale=scale, block_q=BLOCK,
                                  block_k=BLOCK,
                                  interpret=True).astype(jnp.float32).sum()
    fn = jax.grad(f, argnums=(0, 1, 2)) if grad else f
    n = 0
    for e in jaxpr_eqns(jax.make_jaxpr(fn)(q, q, q).jaxpr):
        if e.primitive.name == "pallas_call":
            n += sum(1 for i in jaxpr_eqns(e.params["jaxpr"])
                     if i.primitive.name == "mul"
                     and i.outvars[0].aval.shape == (BLOCK, BLOCK))
    return n


def test_scale_multiplies_no_score_tile_when_a_power_of_two():
    """Not causal, no dropout: a forward tile body then holds no
    [block_k, block_q] multiply at all and a backward body one (p * (dp -
    delta)); another scale adds one to each body."""
    assert _score_tile_muls(0.125, False) == 0
    fwd = _score_tile_muls(0.1, False)
    bwd = _score_tile_muls(0.125, True)
    assert fwd > 0 and bwd > 0
    assert _score_tile_muls(0.1, True) == fwd + 2 * bwd
