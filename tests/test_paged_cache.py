"""K/V pools of heads narrower than a lane tile, stored r = 128 // D heads
to a row (`KVCacheSpec.heads_per_row`), interpret mode.

The paged Pallas kernel on such a pool against the plain reference on
the pool of one head a row and against dense attention; the token and
prompt writes against today's layout, row for row; which pools keep one
head a row; and engines that serve through the paired pools (prefix
cache hits, speculative verify) against the same engines on pools of
one head a row."""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nlp import paged_cache as pc
from paddle_tpu.nlp.serving import ServingEngine
from paddle_tpu.ops.pallas.flash_decode import paged_flash_decode


def _paired(pages, r):
    """[Hkv, P, ps, D] -> [Hkv / r, P, ps, r * D]: heads j*r .. j*r+r-1
    side by side in the rows of group j."""
    h, p, ps, d = pages.shape
    return pages.reshape(h // r, r, p, ps, d).transpose(0, 2, 3, 1, 4) \
        .reshape(h // r, p, ps, r * d)


def _dense(q, kp, vp, pt, lens):
    """Attention of each slot's query over its keys laid end to end,
    float32 throughout."""
    b, hkv, g, d = q.shape
    ps = kp.shape[2]
    k = np.asarray(kp, np.float32)[:, pt].reshape(hkv, b, -1, d)
    v = np.asarray(vp, np.float32)[:, pt].reshape(hkv, b, -1, d)
    s = np.einsum("bhgd,hbkd->bhgk", np.asarray(q, np.float32), k) \
        / np.sqrt(d)
    live = np.arange(pt.shape[1] * ps)[None, :] < np.asarray(lens)[:, None]
    s = np.where(live[:, None, None], s, -np.inf)
    m = np.max(s, axis=-1, keepdims=True)
    e = np.where(live[:, None, None], np.exp(s - np.where(
        np.isfinite(m), m, 0.0)), 0.0)
    den = e.sum(-1, keepdims=True)
    return np.einsum("bhgk,hbkd->bhgd", e, v) / np.where(den == 0, 1, den)


# lengths: an empty slot, within the first page, on a page boundary,
# across pages, the whole table
_LENS = [0, 7, 16, 33, 64]
# bf16 pages: both products take bf16 operands (the one operand rule)
_ATOL = {"float32": 3e-5, "bfloat16": 4e-3}
_DENSE_ATOL = {"float32": 3e-5, "bfloat16": 3e-2}
PARITY = [(d, dt) for d in (64, 32) for dt in ("bfloat16", "float32")]


@pytest.mark.parametrize("d,dtype", PARITY,
                         ids=[f"d{d}-{dt}" for d, dt in PARITY])
def test_the_kernel_on_paired_pools_agrees_with_one_head_a_row(d, dtype):
    r = 128 // d
    b, hkv, g, ps, p, mp = len(_LENS), 2 * r, 4, 16, 11, 4
    rng = np.random.default_rng(d)
    q = jnp.asarray(rng.standard_normal((b, hkv, g, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((hkv, p, ps, d)), dtype)
    vp = jnp.asarray(rng.standard_normal((hkv, p, ps, d)), dtype)
    pt = rng.integers(1, p, (b, mp)).astype(np.int32)
    pt[0] = pc.TRASH_PAGE               # the empty slot: an all-trash row
    pt[1, 1:] = pc.TRASH_PAGE           # past a slot's pages: the trash page
    lens = jnp.asarray(_LENS, jnp.int32)
    got = paged_flash_decode(q, _paired(kp, r), _paired(vp, r),
                             jnp.asarray(pt), lens, interpret=True)
    assert got.shape == (b, hkv, g, d)
    got = np.asarray(got, np.float32)
    ref = np.asarray(pc.paged_attention_ref(q, kp, vp, jnp.asarray(pt),
                                            lens), np.float32)
    assert np.abs(got - ref).max() < _ATOL[dtype]
    assert np.abs(got - _dense(q, kp, vp, pt, lens)).max() \
        < _DENSE_ATOL[dtype]
    assert np.all(got[0] == 0.0)
    # the same kernel on one head a row: the zero lanes add exact zeros
    one = np.asarray(paged_flash_decode(q, kp, vp, jnp.asarray(pt), lens,
                                        interpret=True), np.float32)
    assert np.abs(got - one).max() < 1e-6


def test_the_reference_refuses_a_paired_pool():
    q = jnp.zeros((1, 2, 1, 64))
    pool = jnp.zeros((1, 3, 8, 128))
    with pytest.raises(ValueError, match="2 heads of 64"):
        pc.paged_attention_ref(q, pool, pool, jnp.zeros((1, 1), jnp.int32),
                               jnp.ones((1,), jnp.int32))


@pytest.mark.parametrize("write", ["token", "prompt"])
def test_writes_to_a_paired_pool_land_where_one_head_a_row_puts_them(write):
    hkv, d, ps, p, b = 4, 64, 8, 6, 3
    rng = np.random.default_rng(1)
    one = pc.alloc_pages(p, ps, hkv, d, "float32")
    two = pc.alloc_pages(p, ps, hkv, d, "float32", heads_per_row=2)
    assert one[0].shape == (4, 6, 8, 64) and two[0].shape == (2, 6, 8, 128)
    if write == "token":
        pt = jnp.asarray([[1, 2], [3, 4], [5, 0]], jnp.int32)
        pos = jnp.asarray([3, 9, 0], jnp.int32)
        k = jnp.asarray(rng.standard_normal((b, hkv, d)), jnp.float32)
        live = jnp.asarray([True, True, False])

        def put(pools):
            cache = pc.PagedLayerCache(pools[0], pools[1], pt, pos)
            return pc.write_token_kv(cache, k, k + 1.0, live)
    else:
        k = jnp.asarray(rng.standard_normal((1, 2 * ps, hkv, d)),
                        jnp.float32)
        pages_vec = jnp.asarray([4, 2], jnp.int32)

        def put(pools):
            return pc.write_prompt_kv(*pools, k, k + 1.0, pages_vec)
    want, got = put(one), put(two)
    assert got[2] is None and got[3] is None
    for w, g in zip(want[:2], got[:2]):
        assert float(jnp.abs(w).sum()) > 0
        assert jnp.array_equal(_paired(w, 2), g)


# (kv heads, head size, cache dtype, use_flash) -> the pool's shape
LAYOUTS = {
    "d64_flash_bf16": ((8, 64, "bfloat16", True), (4, 9, 16, 128)),
    "d64_flash_f32": ((8, 64, "float32", True), (4, 9, 16, 128)),
    "d32_flash": ((4, 32, "bfloat16", True), (1, 9, 16, 128)),
    "d64_xla": ((8, 64, "bfloat16", False), (8, 9, 16, 64)),
    "d64_int8_flash": ((8, 64, "int8", True), (8, 9, 16, 64)),
    "d128_flash": ((16, 128, "bfloat16", True), (16, 9, 16, 128)),
    "d256_flash": ((2, 256, "bfloat16", True), (2, 9, 16, 256)),
    "d64_one_head": ((1, 64, "bfloat16", True), (1, 9, 16, 64)),
    "d32_heads_not_a_multiple": ((6, 32, "bfloat16", True),
                                 (6, 9, 16, 32)),
}


@pytest.mark.parametrize("case", list(LAYOUTS))
def test_which_pools_hold_heads_side_by_side(case):
    (hkv, d, dtype, flash), shape = LAYOUTS[case]
    spec = pc.KVCacheSpec(hkv, d)
    k, v, ks, vs = spec.alloc(9, 16, dtype, use_flash=flash)
    assert k.shape == v.shape == shape
    assert spec.heads_per_row(dtype, flash) == hkv // shape[0]
    assert (ks is None) == (dtype != "int8")
    if ks is not None:
        assert ks.shape == shape[:3] + (1,)


# -- engines serving through paired pools ----------------------------------

def _model(which):
    from paddle_tpu.nlp.gpt import GPTForCausalLM
    from paddle_tpu.nlp.llama import LlamaForCausalLM
    paddle.seed(0)
    if which == "gpt":      # 2 heads of 64, each its own K/V head
        m = GPTForCausalLM.from_config_name(
            "gpt-tiny", hidden_size=128, num_attention_heads=2)
    else:                   # 4 query heads over 2 K/V heads of 64
        m = LlamaForCausalLM.from_config_name(
            "llama-tiny", hidden_size=256, num_attention_heads=4,
            num_key_value_heads=2)
    m.eval()
    return m


def _wave(seed=0, n=4):
    rng = np.random.default_rng(seed)
    base = rng.integers(1, 256, (20,)).astype(np.int32)
    return [np.concatenate([base, rng.integers(1, 256, (3 + i,))
                            .astype(np.int32)]) for i in range(n)]


ENGINES = {
    "gpt_prefix_cache": ("gpt", dict(prefix_cache=True, num_pages=64)),
    "llama_spec_verify": ("llama", dict(prefix_cache=False,
                                        spec_decode=True, spec_k=3,
                                        spec_draft="ngram")),
}


def _serve(model, kw):
    eng = ServingEngine(model, max_slots=2, page_size=16, max_seq_len=64,
                        steps_per_dispatch=4, use_flash=True, **kw)
    prompts = _wave()
    eng.warmup(buckets=[len(p) for p in prompts], decode=True)
    out = [eng.generate(prompts, max_new_tokens=6) for _ in range(2)]
    h = eng.health()
    eng.close()
    return out, h, eng


@pytest.mark.parametrize("case", list(ENGINES))
def test_an_engine_on_paired_pools_serves_what_one_head_a_row_does(
        case, monkeypatch):
    which, kw = ENGINES[case]
    model = _model(which)
    paired, h, eng = _serve(model, kw)
    assert h["kv_heads_per_row"] == 2
    assert eng._pages[0][0].shape[0] == eng.kv_heads // 2
    assert eng._pages[0][0].shape[-1] == 128
    if "prefix_cache" in case:
        assert h["prefix_cache"]["hits"] > 0
    monkeypatch.setattr(pc.KVCacheSpec, "heads_per_row",
                        lambda self, dtype, flash: 1)
    one, h1, _ = _serve(model, kw)
    assert h1["kv_heads_per_row"] == 1
    assert paired == one
