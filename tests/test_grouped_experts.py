"""The held experts at decode widths: the `grouped_experts` Pallas kernel
(interpret mode here) against the sorted `ragged_dot` path and against a
plain loop over experts, and the rule that chooses between the paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nlp import moe
from paddle_tpu.ops.pallas import grouped_experts as ge

H, M = 256, 384          # two blocks of 128 along h, three along m
TOL = {"float32": 2e-5, "bfloat16": 2e-5}    # of the largest output


def _weights(held, dtype, seed=0, h=H, m=M):
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    w_gate_up = jax.random.normal(ka, (held, h, 2 * m), jnp.float32) * 0.05
    w_down = jax.random.normal(kb, (held, m, h), jnp.float32) * 0.05
    return w_gate_up.astype(dtype), w_down.astype(dtype)


def _tokens(t, k, router, seed=1, h=H):
    ku, ks, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    u = jax.random.normal(ku, (t, h), jnp.float32)
    _, idx = jax.lax.top_k(jax.random.uniform(ks, (t, router)), k)
    w = jax.random.uniform(kw, (t, k), jnp.float32, 0.1, 1.0)
    return u, idx.astype(jnp.int32), w


def _key(idx, offset, held, rows_live=None):
    local = idx - offset
    mine = (local >= 0) & (local < held)
    if rows_live is not None:
        mine = mine & rows_live[:, None]
    return jnp.where(mine, local, held).reshape(-1)


def _loop(u, idx, w, w_gate_up, w_down, offset, rows_live=None):
    """sum_e w_e Expert_e(u), one expert and one token at a time."""
    held, m = w_down.shape[0], w_down.shape[1]
    dt = w_gate_up.dtype
    out = np.zeros(u.shape, np.float32)
    x = u.astype(dt)
    for t in range(u.shape[0]):
        if rows_live is not None and not bool(rows_live[t]):
            continue
        for e, we in zip(np.asarray(idx[t]) - offset, np.asarray(w[t])):
            if not 0 <= e < held:
                continue
            gu = jnp.dot(x[t], w_gate_up[e],
                         preferred_element_type=jnp.float32)
            act = (jax.nn.silu(gu[:m]) * gu[m:]).astype(dt)
            out[t] += we * np.asarray(jnp.dot(
                act, w_down[e], preferred_element_type=jnp.float32))
    return out


def _streamed(u, idx, w, w_gate_up, w_down, offset, rows_live=None):
    held = w_down.shape[0]
    return moe._streamed(u, _key(idx, offset, held, rows_live), w, w_gate_up,
                         w_down, block_h=128, block_m=128, interpret=True)


def _close(got, want, dtype):
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= TOL[dtype] * np.abs(want).max()


# name: (T, k, router outputs, held, offset, rows that are padding)
CASES = {
    "every_expert_hit": (16, 4, 8, 8, 0, 0),
    "held_window_in_a_larger_router": (12, 4, 24, 6, 5, 0),
    "rows_not_a_sublane_tile": (5, 2, 8, 8, 0, 0),
    "padding_rows": (16, 2, 8, 8, 0, 6),
    "one_pick_a_token": (8, 1, 16, 16, 0, 0),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_ragged_path_and_the_plain_loop(case, dtype):
    t, k, router, held, offset, padded = CASES[case]
    u, idx, w = _tokens(t, k, router)
    w_gate_up, w_down = _weights(held, dtype)
    live = None if not padded else jnp.arange(t) < t - padded
    if padded:
        # a padding row's numbers may be anything
        u = u.at[t - padded:].set(jnp.inf)
    got, sizes, n_mine = _streamed(u, idx, w, w_gate_up, w_down, offset,
                                   live)
    want, aux = moe.held_experts(u, idx, w, w_gate_up, w_down, offset, live)
    assert moe.experts_path(t, H, M) == "ragged"       # what `want` ran
    _close(got, want, dtype)
    _close(got, _loop(u, idx, w, w_gate_up, w_down, offset, live), dtype)
    # the three counters are the present path's, number for number
    routed = t - padded
    assert [int(n_mine), int(jnp.sum(sizes > 0)), routed] == \
        [int(x) for x in aux]
    if padded:
        assert not np.asarray(got[t - padded:]).any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_an_expert_with_no_row_is_not_read(dtype):
    """Experts 1 and 3 get no row: NaN in all their weights changes
    nothing, and they are not counted as hit."""
    t, k, held = 8, 2, 5
    u, _, w = _tokens(t, k, held)
    idx = jnp.asarray([[0, 2], [2, 4], [4, 0], [0, 2]] * 2, jnp.int32)
    w_gate_up, w_down = _weights(held, dtype)
    want, sizes, _ = _streamed(u, idx, w, w_gate_up, w_down, 0)
    absent = jnp.asarray([False, True, False, True, False])[:, None, None]
    poisoned = (jnp.where(absent, jnp.nan, w_gate_up),
                jnp.where(absent, jnp.nan, w_down))
    got, _, _ = _streamed(u, idx, w, *poisoned, 0)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.isfinite(np.asarray(got)).all()
    assert [int(s > 0) for s in sizes] == [1, 0, 1, 0, 1]
    _close(got, _loop(u, idx, w, w_gate_up, w_down, 0), dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_an_expert_a_token_did_not_pick_cannot_poison_it(dtype):
    """Expert 1 is hit by token 0 alone and its output is infinite for
    every other token's row: those rows stay finite (a select, not a
    multiplication by zero)."""
    t, held = 8, 3
    u, _, w = _tokens(t, 1, held)
    idx = jnp.asarray([[1]] + [[0], [2]] * 3 + [[0]], jnp.int32)
    w_gate_up, w_down = _weights(held, dtype)
    u = u.at[1:].set(u[1:] * 1e30)        # expert 1 overflows on rows 1..
    w_gate_up = w_gate_up.at[0].set(0).at[2].set(0)
    got, _, _ = _streamed(u, idx, w, w_gate_up, w_down, 0)
    assert np.isfinite(np.asarray(got)).all()
    assert not np.asarray(got[1:]).any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_one_expert_picked_by_every_token(dtype):
    t, k, held = 16, 2, 6
    u, _, w = _tokens(t, k, held)
    idx = jnp.stack([jnp.full((t,), 3), jnp.arange(t) % 3], 1).astype(
        jnp.int32)
    w_gate_up, w_down = _weights(held, dtype)
    got, sizes, n_mine = _streamed(u, idx, w, w_gate_up, w_down, 0)
    want, aux = moe.held_experts(u, idx, w, w_gate_up, w_down, 0)
    _close(got, want, dtype)
    assert int(sizes[3]) == t and int(n_mine) == 2 * t == int(aux[0])
    assert int(aux[1]) == 4


def test_no_held_expert_is_picked():
    """A chip none of whose experts a token chose: zeros, nothing hit."""
    u, idx, w = _tokens(8, 2, 4)
    w_gate_up, w_down = _weights(4, "float32")
    got, sizes, n_mine = _streamed(u, idx, w, w_gate_up, w_down, 100)
    assert not np.asarray(got).any() and int(n_mine) == 0
    assert not np.asarray(sizes).any()


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 384),
                                    (256, 384), (None, None)])
def test_every_tiling_gives_the_same_sum(blocks):
    u, idx, w = _tokens(16, 4, 8)
    w_gate_up, w_down = _weights(8, "bfloat16")
    key = _key(idx, 0, 8)
    got = moe._streamed(u, key, w, w_gate_up, w_down, block_h=blocks[0],
                        block_m=blocks[1], interpret=True)[0]
    want = moe._ragged(u, key, w, w_gate_up, w_down)[0]
    _close(got, want, "bfloat16")


# the rule: (rows, h, m, backend) -> path
RULE = {
    "lfm2_decode": ((64, 2048, 1792, "tpu"), "streamed"),
    "axk1_decode": ((32, 7168, 2048, "tpu"), "streamed"),
    "at_the_bound": ((moe.STREAMED_MAX_ROWS, 2048, 1792, "tpu"),
                     "streamed"),
    "above_the_bound": ((moe.STREAMED_MAX_ROWS + 1, 2048, 1792, "tpu"),
                        "ragged"),
    "prefill_128": ((128, 7168, 2048, "tpu"), "streamed"),
    "prefill_256": ((256, 2048, 1792, "tpu"), "ragged"),
    "prefill_1024": ((1024, 2048, 1792, "tpu"), "ragged"),
    "h_off_128": ((64, 2000, 1792, "tpu"), "ragged"),
    "m_off_128": ((64, 2048, 1800, "tpu"), "ragged"),
    "tiny_widths": ((3, 64, 32, "tpu"), "ragged"),
    "cpu": ((64, 2048, 1792, "cpu"), "ragged"),
    "gpu": ((64, 2048, 1792, "gpu"), "ragged"),
    "this_backend": ((64, 2048, 1792, None), "ragged"),
}


@pytest.mark.parametrize("case", list(RULE))
def test_the_shape_rule(case):
    (t, h, m, backend), path = RULE[case]
    assert moe.experts_path(t, h, m, backend) == path


def test_the_rule_is_what_held_experts_follows(monkeypatch):
    """On a TPU backend `held_experts` hands decode's rows to the kernel
    and says so to whoever records; above the bound it does not."""
    calls, kernel = [], ge.grouped_experts

    def fake(x, combine, hit, w_gate_up, w_down):
        calls.append(x.shape)
        return kernel(x, combine, hit, w_gate_up, w_down, interpret=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ge, "grouped_experts", fake)
    monkeypatch.setattr(moe, "STREAMED_MAX_ROWS", 16)
    w_gate_up, w_down = _weights(8, "float32", h=128, m=128)
    with moe.recorded_paths() as seen:
        for t in (16, 24):
            u, idx, w = _tokens(t, 2, 8, h=128)
            got, aux = moe.held_experts(u, idx, w, w_gate_up, w_down, 0)
            _close(got, _loop(u, idx, w, w_gate_up, w_down, 0), "float32")
            assert int(aux[2]) == t
    assert seen == ["streamed", "ragged"] and calls == [(16, 128)]


def test_blocks_follow_the_widths():
    """About 4 MB a block at both cells' widths, whole lane tiles that
    divide the width, and a VMEM limit with room under the chip's 128 MiB
    (the compile for a described v5e is in test_flash_tpu_compile.py)."""
    for t, h, m in ((64, 2048, 1792), (32, 7168, 2048)):
        tp, th, tm, limit = ge._plan(t, h, m, jnp.bfloat16, None, None)
        assert tp == t and h % th == 0 and m % tm == 0
        assert th % 128 == 0 and tm % 128 == 0
        assert 2 << 20 <= th * 2 * m * 2 <= 4 << 20
        assert 2 << 20 <= tm * h * 2 <= 4 << 20
        assert limit < 64 << 20
    with pytest.raises(ValueError, match="multiples of 128"):
        ge._plan(8, 256, 256, jnp.float32, 96, None)
    with pytest.raises(ValueError, match="multiples of 128"):
        ge.grouped_experts(jnp.zeros((8, 64)), jnp.zeros((8, 2)),
                           jnp.ones((2,), bool), jnp.zeros((2, 64, 256)),
                           jnp.zeros((2, 128, 64)), interpret=True)


def test_the_engine_says_which_path_each_program_took():
    """On the CPU every site is "ragged"; the engine learns it when the
    program is traced."""
    import paddle_tpu as paddle
    from paddle_tpu.nlp.axk1 import AXK1ForCausalLM
    from paddle_tpu.nlp.serving import ServingEngine
    paddle.seed(0)
    eng = ServingEngine(AXK1ForCausalLM.from_config_name("axk1-tiny"),
                        max_slots=2, page_size=16, max_seq_len=64,
                        cache_dtype="bfloat16", prefix_cache=False)
    eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=3)
    eng.run_to_completion()
    said = eng.health()["moe"]["experts_path"]
    assert set(said) >= {"decode"} and set(said.values()) == {"ragged"}
    assert any(site.startswith("prefill_") for site in said)
    eng.close()
