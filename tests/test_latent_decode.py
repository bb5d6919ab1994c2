"""The paged Pallas kernel for absorbed-form latent decode attention
(ops/pallas/latent_decode.py, ISSUE 29) against the gathered XLA form it
replaces on a TPU, under the interpreter; then the engine with each."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from fleet_proc_support import decode_jaxpr, jaxpr_eqns  # noqa: E402
from paddle_tpu.nlp.axk1 import AXK1ForCausalLM  # noqa: E402
from paddle_tpu.nlp.paged_cache import (TRASH_PAGE,  # noqa: E402
                                        latent_paged_attention)
from paddle_tpu.nlp.serving import ServingEngine  # noqa: E402
from paddle_tpu.observability import introspect  # noqa: E402
from paddle_tpu.ops import attention  # noqa: E402
from paddle_tpu.ops.pallas.latent_decode import (  # noqa: E402
    latent_flash_decode)

MP = 4                   # table entries a slot


def _lens(ps):
    """A slot per length: inactive (an all-trash table row), one key,
    exactly a page, a page and one, two pages and a part (three visited:
    the walk ends in the buffer it began in), the full table."""
    return (0, 1, ps, ps + 1, 2 * ps + 3, MP * ps)


def _case(dtype, heads, width, pool_width, ps=8, seed=0):
    """(q, pages, table, lens): slot b owns pages 1 + b*MP ..; every row of
    the pool holds numbers, the trash page too."""
    rng = np.random.default_rng(seed)
    lens = _lens(ps)
    slots = len(lens)
    pages = rng.normal(size=(1 + slots * MP, ps, pool_width))
    pages[:, :, width:] = 0.0
    q = rng.normal(size=(slots, heads, width))
    table = 1 + np.arange(slots * MP, dtype=np.int32).reshape(slots, MP)
    table[0] = TRASH_PAGE
    return (jnp.asarray(q, jnp.float32), jnp.asarray(pages, dtype),
            jnp.asarray(table), jnp.asarray(lens, jnp.int32))


@pytest.mark.parametrize("ps", [8, 16], ids=["ps8", "ps16"])
@pytest.mark.parametrize("heads", [4, 64])
@pytest.mark.parametrize("v_width", [128, 48], ids=["v128", "v48"])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6), ("bfloat16", 4e-3)])
def test_the_kernel_equals_the_gathered_form(dtype, tol, v_width, heads, ps):
    q, pages, table, lens = _case(jnp.dtype(dtype), heads, 160, 256, ps)
    want = latent_paged_attention(q, pages, table, lens, v_width, 0.2)
    got = latent_flash_decode(q, pages, table, lens, v_width, 0.2)
    assert got.shape == want.shape == (len(lens), heads, v_width)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=tol * float(
        jnp.max(jnp.abs(want))))
    # the inactive slot: a zero row, whatever the trash page holds
    assert not np.asarray(got[0]).any()


def test_pages_past_a_slots_length_are_not_read_into_its_rows():
    """Table entries past the length name another slot's pages (a stale
    row, a page since recycled): the result is that of a table whose dead
    entries are trash, bit for bit."""
    ps = 8
    q, pages, table, lens = _case(jnp.float32, 4, 160, 256, ps, seed=1)
    stale = np.array(table)
    for b, n in enumerate(_lens(ps)):
        first_dead = -(-n // ps)
        stale[b, first_dead:] = np.asarray(table)[-1, :MP - first_dead]
    clean = np.where(np.arange(MP)[None] * ps < np.asarray(lens)[:, None],
                     np.asarray(table), TRASH_PAGE)
    got = latent_flash_decode(q, pages, jnp.asarray(stale), lens, 128, 0.2)
    want = latent_flash_decode(q, pages, jnp.asarray(clean), lens, 128, 0.2)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_a_pool_the_kernel_cannot_read_is_refused():
    with pytest.raises(ValueError, match="page_size % 8"):
        latent_flash_decode(jnp.ones((1, 4, 16)), jnp.ones((3, 8, 96)),
                            jnp.ones((1, 2), jnp.int32),
                            jnp.ones((1,), jnp.int32), 16, 1.0)


# -- the engine --------------------------------------------------------------

def _engine(use_flash, **kw):
    import paddle_tpu as paddle
    paddle.seed(5)
    model = AXK1ForCausalLM.from_config_name("axk1-tiny")
    return ServingEngine(model, max_slots=3, page_size=8, max_seq_len=64,
                         steps_per_dispatch=4, prefix_cache=False,
                         use_flash=use_flash, **kw)


def _closed_loop(eng):
    """Five requests through three slots: admissions while others decode,
    slots that finish and are taken again, a slot left idle at the end."""
    rng = np.random.default_rng(2)
    for n_prompt, n_out in ((5, 9), (17, 22), (9, 3), (30, 14), (3, 18)):
        eng.submit(rng.integers(0, eng.cfg.vocab_size, (n_prompt,),
                                dtype=np.int32), max_new_tokens=n_out)
    done = eng.run_to_completion()
    return [np.asarray(r["tokens"]).tolist()
            for r in sorted(done, key=lambda r: r["id"])]


def test_greedy_tokens_are_the_same_through_the_kernel_and_the_gathered_form():
    tokens = {}
    for use_flash in (True, False):
        eng = _engine(use_flash)
        try:
            tokens[use_flash] = _closed_loop(eng)
        finally:
            eng.close()
    assert [len(t) for t in tokens[True]] == [9, 22, 3, 14, 18]
    assert tokens[True] == tokens[False]


@pytest.mark.parametrize("use_flash,on_tpu,want", [
    (True, False, "latent_paged_kernel"),
    (False, True, "latent_gathered"),
    (None, False, "latent_gathered"),
    (None, True, "latent_paged_kernel"),
], ids=["asked", "declined", "default_off_tpu", "default_on_tpu"])
def test_health_names_the_latent_attention_the_decode_program_holds(
        monkeypatch, use_flash, on_tpu, want):
    """`health()["decode_attention"]` against what is traced: the
    `latent_decode` kernel is in the decode step exactly when it says
    "latent_paged_kernel", the `page_gather` scope exactly when it says
    "latent_gathered"; the work is under `latent_attention` either way."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: on_tpu)
    eng = _engine(use_flash)
    try:
        assert eng.health()["decode_attention"] == want
        assert eng.use_flash == (want == "latent_paged_kernel")
        kernels, scopes = set(), set()
        for eqn in jaxpr_eqns(decode_jaxpr(eng)):
            stack = str(eqn.source_info.name_stack).split("/")
            scopes |= set(stack)
            if eqn.primitive.name == "pallas_call":
                kernels.add(eqn.params["name"])
                assert "latent_attention" in stack
        assert "latent_attention" in scopes
        assert kernels == ({"latent_decode"} if eng.use_flash else set())
        assert ("page_gather" in scopes) == (not eng.use_flash)
    finally:
        eng.close()


def test_the_compiled_decode_keeps_the_scope_with_the_kernel_on():
    introspect.clear()
    eng = _engine(True)
    try:
        eng.warmup(buckets=(8,))
        scopes = introspect.site_scopes("decode", tracer=eng.tracer.name)
        under = [path.split("/") for path in scopes.values()
                 if "latent_attention" in path.split("/")]
        assert under and not any("page_gather" in p for p in under)
        assert any("latent_kv_write" in p.split("/")
                   for p in scopes.values())
    finally:
        eng.close()
        introspect.clear()
