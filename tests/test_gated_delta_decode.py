"""The Gated DeltaNet decode kernel (ops/pallas/gated_delta_decode.py), in
interpret mode, against its written specification `olmo_hybrid.
gated_delta_step` at Olmo-Hybrid's widths (30 heads, dk 96, dv 192), over a
state stored as `paged_cache.DeltaStateSpec` lays it out: two heads side by
side in rows of 384."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nlp import paged_cache
from paddle_tpu.nlp.olmo_hybrid import gated_delta_step
from paddle_tpu.ops.pallas import gated_delta_decode as gdd

H, DK, DV = 30, 96, 192
# the kernel sums over dk in another order than the spec; float32 rounding
REL = 1e-5


def _inputs(seed, b, h=H, dk=DK, dv=DV):
    """Unit k, q scaled by dk^-1/2, g = -exp(A_log) softplus(...) over the
    model's range (A in [1, 16], dt in [1e-3, 0.1] give about -1.6 to
    -1.6e-4, and a random pre-activation widens it), beta in (0, 2), a
    state of the size a long sequence leaves."""
    rng = np.random.default_rng(seed)

    def unit(shape):
        x = rng.standard_normal(shape)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit((b, h, dk)) / np.sqrt(dk)
    k = unit((b, h, dk))
    v = rng.standard_normal((b, h, dv))
    g = -np.exp(rng.uniform(np.log(1e-4), np.log(8.0), (b, h)))
    beta = rng.uniform(0.0, 2.0, (b, h))
    state = 0.5 * rng.standard_normal((b, h, dk, dv))
    return [jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta, state)]


def _both(seed, live, h=H, dk=DK, dv=DV):
    q, k, v, g, beta, state = _inputs(seed, len(live), h, dk, dv)
    live = jnp.asarray(live)
    r = paged_cache.DeltaStateSpec(1, 4, h, dk, dv).heads_per_row
    want_o, want_s = gated_delta_step(q, k, v, g, beta, state, live)
    got_o, got_s = gdd.gated_delta_decode(
        q, k, v, g, beta, paged_cache.delta_heads_paired(state, r), live,
        interpret=True)
    assert got_s.shape == paged_cache.delta_heads_paired(state, r).shape
    return (want_o, want_s), (got_o, paged_cache.delta_heads_unpaired(
        got_s, r)), state, live


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("seed,live", [
    (0, [True, False, True, True, False]),
    (1, [False, True, True, True]),
])
def test_the_kernel_is_the_step_at_the_cell_s_widths(seed, live):
    (want_o, want_s), (got_o, got_s), state, live = _both(seed, live)
    assert _rel(got_s, want_s) < REL
    assert _rel(got_o, want_o) < REL
    # a slot that is not live keeps its rows bit for bit
    dead = ~np.asarray(live)
    assert np.array_equal(np.asarray(got_s)[dead], np.asarray(state)[dead])
    assert not np.array_equal(np.asarray(got_s)[~dead],
                              np.asarray(state)[~dead])


@pytest.mark.parametrize("h,dk,dv", [(4, 16, 64), (3, 8, 32)])
def test_the_kernel_is_the_step_at_other_widths(h, dk, dv):
    """Pairs of heads of 64 (rows of 128), and one head of 32 a row."""
    (want_o, want_s), (got_o, got_s), _, _ = _both(2, [True, False, True],
                                                   h, dk, dv)
    assert _rel(got_s, want_s) < REL and _rel(got_o, want_o) < REL


def test_it_fails_if_the_kernel_reads_the_pairs_halves_swapped(monkeypatch):
    real = gdd._by_head
    monkeypatch.setattr(gdd, "_by_head",
                        lambda parts, lane, dv: real(parts[::-1], lane, dv))
    (want_o, want_s), (got_o, got_s), _, _ = _both(3, [True, True])
    assert _rel(got_s, want_s) > 1e3 * REL
    assert _rel(got_o, want_o) > 1e3 * REL


@pytest.mark.parametrize("r", [1, 2, 3])
def test_the_pair_layout_round_trips_exactly(r):
    x = jnp.asarray(np.random.default_rng(r).standard_normal(
        (2, 6, 5, 7)), jnp.float32)
    paired = paged_cache.delta_heads_paired(x, r)
    assert paired.shape == (2, 6 // r, 5, 7 * r)
    # row i of group p holds row i of heads r*p .. r*p + r - 1 in turn
    assert np.array_equal(paired[1, 1, 3, :7], x[1, r, 3])
    assert np.array_equal(paged_cache.delta_heads_unpaired(paired, r), x)


@pytest.mark.parametrize("heads,dv,r", [
    (30, 192, 2),       # Olmo-Hybrid: rows of 384, three whole lane tiles
    (30, 128, 1),       # a head is a lane tile already
    (31, 192, 1),       # an odd head would be left without a partner
    (4, 64, 2),
    (2, 32, 1),         # two heads of 32 do not fill a lane tile either
    (8, 256, 1),
])
def test_heads_per_row_follows_the_shapes(heads, dv, r):
    spec = paged_cache.DeltaStateSpec(10, 4, heads, 96, dv)
    assert spec.heads_per_row == r
    state = spec.alloc(None, None, "bfloat16", max_slots=3)[1]
    assert state.shape == (3, heads // r, 96, r * dv)
    assert state.dtype == jnp.float32


def test_a_state_in_rows_of_other_heads_is_refused():
    q, k, v, g, beta, state = _inputs(4, 2, 4, 16, 64)
    with pytest.raises(ValueError, match="rows of whole heads"):
        gdd.gated_delta_decode(q, k, v, g, beta,
                               state.reshape(2, 4, 16, 2, 32)[..., 0, :],
                               interpret=True)
