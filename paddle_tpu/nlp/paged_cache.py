"""Paged (block) KV cache — the serving-time cache contract shared by
GPT and Llama (ref: vLLM PagedAttention, arXiv:2309.06180; upstream
Paddle ships the CUDA equivalent under paddle/fluid/operators/fused/ +
FastDeploy's block-wise attention).

TPU-native shape of the idea: all shapes are STATIC so the whole decode
loop stays one compiled XLA program —

- the cache is a fixed pool of pages per layer, laid out HEAD-MAJOR
  `[Hkv, P, page_size, D]` (the layout the Pallas paged flash-decode
  kernel reads pages from HBM in, one page of a block of heads per grid
  step, and the one the plain-XLA path multiplies q with in place);
- a `[num_slots, max_pages]` int32 page table maps each serving slot's
  token positions to pages; rows are rewritten host-side at step
  boundaries only (admission/eviction — nlp/serving.py owns the free
  list), so no recompile ever;
- page 0 is RESERVED as the trash page: inactive slots point every
  table entry at it and write position 0, so masked lanes of the
  batched step have a legal destination without any dynamic shapes;
- writes go through one `scatter` (`.at[].set`) per step; per-slot
  validity is carried by `positions` ([num_slots] int32 = tokens
  already cached) and attention masks keys at index >= positions+1.

Which cache a layer has is the model's to say (`cache_specs_of`: one spec
for every layer, or one per layer): keys and values by head as above
(`KVCacheSpec`: GPT, Llama), for latent attention ONE pool `[P, ps, W]` of
the rows every head shares and no value pool (`LatentCacheSpec`,
`PagedLatentCache`: nlp/axk1.py), or NO pages at all: a state of fixed
size per serving slot, `[slots, taps, C]`, overwritten in place as the
slot's sequence grows (`ConvStateSpec`, `ConvStateCache`: the short
convolutions of nlp/lfm2.py, beside that model's K/V layers). Page table,
trash page, positions and the engine's page accounting are the same for
the paged kinds and count those layers alone.

Cache dtypes: float32 / bfloat16 store K/V directly; int8 stores
per-token-per-head symmetric-quantized rows with an f32 scale sidecar
`[Hkv, P, page_size, 1]` (the trailing singleton keeps the Mosaic lane
dim equal to the array dim, so the kernel can read scales as a legal
block — see ops/pallas/flash_decode.py).

The model integration point is `PagedLayerCache`: attention layers that
receive one as their layer cache route through
`paged_update_and_attend` instead of the dense static-cache path. It is
NOT a pytree — nlp/serving.py constructs it inside its jitted programs
from raw array arguments and unpacks the returned arrays, so it never
crosses a jit boundary.

Rewind contract (speculative decoding, round 20): rows past a slot's
committed length (`seq_lens`) are garbage by definition — attention
masks keys at index >= positions+1, and any later write at those
positions overwrites in place. So rejecting speculative KV writes
needs NO device-side cleanup: the host simply declines to advance
`seq_lens` past the accepted count (the same contract that makes the
prefix cache's private-tail pages safe to re-prefill after failover).
A spec verify dispatch writes K+1 rows per slot into already-owned
pages; committing j of them is one host-side integer add.

None of that holds for a slot state (`ConvStateSpec`): it is overwritten
in place, so a rejected write cannot be taken back and a prefix's pages
say nothing of the state at the prefix's end. The engine refuses
speculative verify, the prefix cache, an int8 state and AOT export by
name for a model with such layers (nlp/serving.py), and the decode step
updates the rows of live slots only.
"""
from __future__ import annotations

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["PagedLayerCache", "PagedLatentCache", "ConvStateCache",
           "KVCacheSpec", "LatentCacheSpec", "ConvStateSpec", "LatentRows",
           "PromptKV", "ConvStateRows", "AUX_COUNTERS", "cache_spec_of",
           "cache_specs_of", "conv_state_step", "conv_state_at",
           "write_prompt_state",
           "PrefixIndex", "alloc_pages",
           "prefix_fingerprints", "quantize_rows", "write_token_latent",
           "write_prompt_latent", "latent_paged_attention",
           "write_token_kv", "write_prompt_kv", "paged_attention_ref",
           "xla_attention_form",
           "paged_update_and_attend", "paged_layer_forward",
           "TRASH_PAGE"]

# page index 0 is never allocated to a sequence: it is the write sink
# for masked (inactive/finished) slots and for prefill bucket tail
# pages beyond a request's allocation
TRASH_PAGE = 0

# what an expert layer counts per forward, in the order of the int32
# vector it hands back through its cache (the `aux` of a layer's cache
# view in the decode step and of what its prefill forward returns) and
# the engine sums per kind of program: assignments
# that fell on experts held here, held experts that got at least one
# row, rows the router saw
AUX_COUNTERS = ("moe_local_assignments", "moe_experts_hit",
                "moe_routed_tokens")

_INT8_MAX = 127.0
# rows a matrix product needs before the TPU compiler keeps it on the MXU
_MXU_ROWS = 8
# the minor dimension of a TPU tile
_LANES = 128


class PagedLayerCache:
    """One layer's view of the paged cache plus the shared routing
    state. Plain object (deliberately not a pytree — see module doc);
    `use_flash` is trace-time-static kernel routing, everything else is
    a traced array. `aux` is what the layer hands back beside its pages
    (an expert layer's counters, AUX_COUNTERS), None where it has none."""

    __slots__ = ("k_pages", "v_pages", "k_scale", "v_scale",
                 "page_table", "positions", "use_flash", "aux")

    def __init__(self, k_pages, v_pages, page_table, positions,
                 k_scale=None, v_scale=None, use_flash=False, aux=None):
        self.k_pages = k_pages          # [Hkv, P, ps, D]
        self.v_pages = v_pages          # [Hkv, P, ps, D]
        self.k_scale = k_scale          # [Hkv, P, ps, 1] f32 | None
        self.v_scale = v_scale          # [Hkv, P, ps, 1] f32 | None
        self.page_table = page_table    # [B, MP] int32
        self.positions = positions      # [B] int32 tokens already cached
        self.use_flash = bool(use_flash)
        self.aux = aux

    def replaced(self, k_pages, v_pages, k_scale=None, v_scale=None,
                 aux=None):
        """New view with updated page arrays (same table/positions/
        routing) — what an attention layer returns as its new cache."""
        return PagedLayerCache(k_pages, v_pages, self.page_table,
                               self.positions, k_scale=k_scale,
                               v_scale=v_scale, use_flash=self.use_flash,
                               aux=aux)

    def arrays(self):
        """The pool arrays as the engine carries them between programs."""
        return self.k_pages, self.v_pages, self.k_scale, self.v_scale

    @property
    def page_size(self):
        return self.k_pages.shape[2]

    @property
    def quantized(self):
        return self.k_scale is not None


class PagedLatentCache:
    """One latent-attention layer's view of the paged cache: ONE pool
    `[P, ps, W]` whose rows are `[c_kv | k_rope]` (what every head
    shares), beside the same page table and positions a PagedLayerCache
    carries. `aux` is what the layer hands back beside its pages: a small
    int32 vector of counters (the expert layer's routing counts), None
    where the layer has none. `use_flash` is trace-time-static kernel
    routing, as PagedLayerCache's. Not a pytree, like PagedLayerCache."""

    __slots__ = ("pages", "page_table", "positions", "aux", "use_flash")

    def __init__(self, pages, page_table, positions, aux=None,
                 use_flash=False):
        self.pages = pages              # [P, ps, W]
        self.page_table = page_table    # [B, MP] int32
        self.positions = positions      # [B] int32 tokens already cached
        self.aux = aux
        self.use_flash = bool(use_flash)

    def replaced(self, pages, aux=None):
        return PagedLatentCache(pages, self.page_table, self.positions, aux,
                                use_flash=self.use_flash)

    def arrays(self):
        return (self.pages,)

    @property
    def page_size(self):
        return self.pages.shape[1]


class ConvStateCache:
    """One short-convolution layer's view of its state in the decode step:
    `state` [B, taps, C], row b the last `taps` inputs of the convolution
    that slot b's sequence fed it (the newest last, zeros before the
    sequence starts), and `live` [B] bool, the slots whose row this step
    may overwrite (None: all). No page table and no positions: the state
    has one size whatever the sequence's length. `aux` as
    PagedLayerCache's. Not a pytree."""

    __slots__ = ("state", "live", "aux")

    def __init__(self, state, live=None, aux=None):
        self.state = state              # [B, taps, C]
        self.live = live                # [B] bool | None
        self.aux = aux

    def replaced(self, state, aux=None):
        return ConvStateCache(state, self.live, aux)

    def arrays(self):
        return (self.state,)


def alloc_pages(num_pages, page_size, kv_heads, head_dim, cache_dtype):
    """Fresh page pool for ONE layer. cache_dtype: 'float32' |
    'bfloat16' | 'int8' (int8 adds the f32 scale sidecars)."""
    dt = jnp.dtype(cache_dtype) if cache_dtype != "int8" else jnp.int8
    shape = (kv_heads, num_pages, page_size, head_dim)
    k = jnp.zeros(shape, dt)
    v = jnp.zeros(shape, dt)
    if cache_dtype == "int8":
        # two distinct arrays: the engine donates the whole pool, and
        # aliased buffers trip XLA's double-donation check
        return (k, v, jnp.zeros(shape[:3] + (1,), jnp.float32),
                jnp.zeros(shape[:3] + (1,), jnp.float32))
    return k, v, None, None


class KVCacheSpec:
    """What a model with per-head keys and values tells the serving
    engine about one layer's cache: the pool is the pair
    `[Hkv, P, ps, D]` (+ the int8 scale sidecars), a cached forward
    hands back dense `(k, v)` of `[1, S, Hkv, D]` per layer."""

    kind, latent, paged = "kv", False, True

    def __init__(self, kv_heads, head_dim):
        self.kv_heads, self.head_dim = int(kv_heads), int(head_dim)

    def alloc(self, num_pages, page_size, cache_dtype, max_slots=None):
        return alloc_pages(num_pages, page_size, self.kv_heads,
                           self.head_dim, cache_dtype)

    def view(self, arrays, page_table, positions, use_flash=False,
             live=None):
        k, v, ks, vs = arrays
        return PagedLayerCache(k, v, page_table, positions, k_scale=ks,
                               v_scale=vs, use_flash=use_flash)

    def prompt_rows(self, layer):
        """The dense rows one layer's cached forward returned."""
        return layer[0], layer[1]

    def write_prompt(self, arrays, rows, pages_vec, slot=None):
        return write_prompt_kv(*arrays, *rows, pages_vec)


class LatentCacheSpec:
    """A latent-attention layer's cache: one pool `[P, ps, Wp]`, one row
    per token, no value pool and no quantized form. A row holds the W
    numbers the model caches, zero-padded to the lane width (`Wp`, the
    next multiple of 128: 576 -> 640). That is what the TPU's default
    tiled layout would occupy for a minor dimension of 576 anyway, and
    said outright it keeps the pool row-major everywhere: left at 576
    the compiler holds the pool transposed (`{1,2,0}`, no padding)
    outside the decode loop and copies all of it in and out of the loop
    every dispatch."""

    kind, latent, paged = "latent", True, True

    def __init__(self, width):
        self.width = int(width)
        self.pool_width = -(-self.width // _LANES) * _LANES

    def alloc(self, num_pages, page_size, cache_dtype, max_slots=None):
        return (jnp.zeros((num_pages, page_size, self.pool_width),
                          jnp.dtype(cache_dtype)),)

    def view(self, arrays, page_table, positions, use_flash=False,
             live=None):
        return PagedLatentCache(arrays[0], page_table, positions,
                                use_flash=use_flash)

    def prompt_rows(self, layer):
        return (layer.rows,)

    def write_prompt(self, arrays, rows, pages_vec, slot=None):
        return (write_prompt_latent(arrays[0], rows[0], pages_vec),)


class ConvStateSpec:
    """A short-convolution layer's cache: no pages, one row of state per
    serving slot, `[max_slots, taps, channels]` in the engine's cache dtype
    (no int8 form). The channels are the minor dimension: with the taps
    minor, the TPU's tiled layout pads 3 to the lane width of 128 and a
    state of 9 MB takes 400. A prefill writes the row of the slot it
    admits (`slot`, which the paged kinds do not need) with the state as
    it stands after the prompt's last token, whatever the slot's last
    request left there; the decode step shifts the rows of live slots."""

    kind, latent, paged = "conv_state", False, False

    def __init__(self, channels, taps):
        self.channels, self.taps = int(channels), int(taps)

    def alloc(self, num_pages, page_size, cache_dtype, max_slots=None):
        return (jnp.zeros((max_slots, self.taps, self.channels),
                          jnp.dtype(cache_dtype)),)

    def view(self, arrays, page_table, positions, use_flash=False,
             live=None):
        return ConvStateCache(arrays[0], live)

    def prompt_rows(self, layer):
        return (layer.state,)

    def write_prompt(self, arrays, rows, pages_vec, slot=None):
        return (write_prompt_state(arrays[0], rows[0], slot),)


def cache_spec_of(model):
    """The cache layout `model` serves with, as the model says it: its own
    `cache_spec()` where it has one (one spec for every layer, or a list
    of one per layer), else keys and values by its configuration's
    heads."""
    if hasattr(model, "cache_spec"):
        return model.cache_spec()
    cfg = model.config
    return KVCacheSpec(getattr(cfg, "num_key_value_heads", 0)
                       or cfg.num_attention_heads, cfg.head_dim)


def cache_specs_of(model):
    """(what `cache_spec_of(model)` answers, [that as one spec a layer])."""
    spec = cache_spec_of(model)
    layers = model.config.num_hidden_layers
    if not isinstance(spec, (list, tuple)):
        return spec, [spec] * layers
    if len(spec) != layers:
        raise ValueError(f"{type(model).__name__}.cache_spec() names "
                         f"{len(spec)} layers of {layers}")
    return spec, list(spec)


def quantize_rows(x):
    """Symmetric per-row int8 quantization over the trailing (D) axis.
    x [..., D] f32/bf16 -> (q int8 [..., D], scale f32 [..., 1])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = amax / _INT8_MAX
    safe = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / safe),
                 -_INT8_MAX, _INT8_MAX).astype(jnp.int8)
    return q, scale


def _dequant(pages, scale):
    """int8 rows times their float32 scale sidecar, or the pages as they
    are where there is none."""
    return pages if scale is None else pages.astype(jnp.float32) * scale


@jax.named_scope("kv_write")
def write_token_kv(cache: PagedLayerCache, k_new, v_new, live):
    """Write one token per slot into the pages. k_new/v_new
    [B, Hkv, D] (post-RoPE for Llama); live [B] bool — masked slots are
    redirected to the trash page so the scatter stays full-width.
    Returns the updated (k_pages, v_pages, k_scale, v_scale).

    Every (head, page, row) is an index of the scatter and its window
    is one row of D: a window that also spans the heads
    (`.at[:, page, row]`) makes the TPU compiler keep the whole pool
    token-major (`{3,0,2,1}`) inside the decode loop, which costs a
    copy of the pool into and out of the loop and, before each Pallas
    attention call, one more of the layer's pages back to head-major."""
    ps = cache.page_size
    pos = cache.positions
    page = jnp.take_along_axis(cache.page_table,
                               (pos // ps)[:, None], axis=1)[:, 0]
    page = jnp.where(live, page, TRASH_PAGE)[None, :]
    row = jnp.where(live, pos % ps, 0)[None, :]
    head = jnp.arange(k_new.shape[1], dtype=jnp.int32)[:, None]
    kt = jnp.swapaxes(k_new, 0, 1)      # [Hkv, B, D]
    vt = jnp.swapaxes(v_new, 0, 1)

    def put(pages, rows):
        return pages.at[head, page, row].set(rows.astype(pages.dtype))

    if cache.quantized:
        kq, ks = quantize_rows(kt)
        vq, vs = quantize_rows(vt)
        return (put(cache.k_pages, kq), put(cache.v_pages, vq),
                put(cache.k_scale, ks), put(cache.v_scale, vs))
    return put(cache.k_pages, kt), put(cache.v_pages, vt), None, None


@jax.named_scope("kv_write")
def write_prompt_kv(k_pages, v_pages, k_scale, v_scale, k_full, v_full,
                    pages_vec):
    """Prefill write: one request's whole (bucket-padded) prompt K/V
    into its pages. k_full/v_full [1, S_b, Hkv, D] with S_b a multiple
    of page_size; pages_vec [S_b // ps] int32 page ids (tail entries
    beyond the request's allocation point at TRASH_PAGE). Rows past the
    true prompt length carry garbage — they are either overwritten by
    the decode steps that reach those positions or masked by the
    attention length, never read."""
    ps = k_pages.shape[2]
    nb = k_full.shape[1] // ps

    def blocks(x):                      # [1, S_b, Hkv, D] -> [Hkv, nb, ps, D]
        x = jnp.swapaxes(x[0], 0, 1)    # [Hkv, S_b, D]
        return x.reshape(x.shape[0], nb, ps, x.shape[-1])

    kb, vb = blocks(k_full), blocks(v_full)
    if k_scale is not None:
        kq, ks = quantize_rows(kb)
        vq, vs = quantize_rows(vb)
        return (k_pages.at[:, pages_vec].set(kq),
                v_pages.at[:, pages_vec].set(vq),
                k_scale.at[:, pages_vec].set(ks),
                v_scale.at[:, pages_vec].set(vs))
    return (k_pages.at[:, pages_vec].set(kb.astype(k_pages.dtype)),
            v_pages.at[:, pages_vec].set(vb.astype(v_pages.dtype)),
            None, None)


class LatentRows:
    """What a latent layer's cached (prefill) forward hands back: the
    prompt's dense `[1, S, W]` rows and the layer's counters."""

    __slots__ = ("rows", "aux")

    def __init__(self, rows, aux=None):
        self.rows, self.aux = rows, aux


class PromptKV(tuple):
    """What a K/V layer's cached (prefill) forward hands back where the
    layer also counts: the pair `(k, v)` of `[1, S, Hkv, D]`, as a plain
    tuple would be, and the layer's counters as `aux`."""

    def __new__(cls, k, v, aux=None):
        self = super().__new__(cls, (k, v))
        self.aux = aux
        return self


class ConvStateRows:
    """What a short-convolution layer's cached (prefill) forward hands
    back: the state `[B, taps, C]` as it stands after each row's last
    true token, and the layer's counters."""

    __slots__ = ("state", "aux")

    def __init__(self, state, aux=None):
        self.state, self.aux = state, aux


def conv_state_step(cache: ConvStateCache, g):
    """One decode step of a slot state. g [B, C] float32, this token's
    input to the convolution. Returns (window [B, taps, C] float32: the
    stored inputs of the last `taps - 1` tokens, then g itself unrounded;
    the new state in the cache's dtype: the window, for the live slots
    only)."""
    old = cache.state
    window = jnp.concatenate([old[:, 1:].astype(jnp.float32), g[:, None]],
                             axis=1)
    new = window.astype(old.dtype)
    if cache.live is not None:
        new = jnp.where(cache.live[:, None, None], new, old)
    return window, new


def conv_state_at(g, lens, taps):
    """The state a prompt leaves: g [B, S, C], the convolution's inputs
    over a (right-padded) prompt; lens [B] int32 the true lengths, None
    for S. Returns [B, taps, C], rows `lens - taps .. lens - 1` of g with
    zeros where the sequence has not begun: the state after the last TRUE
    token, not after the bucket's padding."""
    b, s, _ = g.shape
    if lens is None:
        lens = jnp.full((b,), s, jnp.int32)
    at = lens[:, None] - taps + jnp.arange(taps, dtype=jnp.int32)[None, :]
    rows = jnp.take_along_axis(g, jnp.clip(at, 0, s - 1)[:, :, None], axis=1)
    return jnp.where((at >= 0)[:, :, None], rows, 0.0)


@jax.named_scope("conv_state_write")
def write_prompt_state(state, rows, slot):
    """Prefill write: rows [1, taps, C], one prompt's state, into the row
    of the slot it is admitted to. A `slot` past the last one (the
    engine's warm-up) writes nothing."""
    return state.at[slot].set(rows[0].astype(state.dtype), mode="drop")


def _to_width(x, width):
    """x zero-padded along its last axis to the pool's row width."""
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1)
                   + ((0, width - x.shape[-1]),))


@jax.named_scope("latent_kv_write")
def write_token_latent(cache: PagedLatentCache, rows):
    """One token per slot into the latent pool. rows [B, W]; inactive
    slots carry an all-trash table row and position 0 (the engine's
    contract), so the write is full-width. One index per (page, row), a
    window of one row: the pool keeps its layout (see write_token_kv)."""
    ps = cache.page_size
    pos = cache.positions
    page = jnp.take_along_axis(cache.page_table, (pos // ps)[:, None],
                               axis=1)[:, 0]
    rows = _to_width(rows, cache.pages.shape[-1])
    return cache.pages.at[page, pos % ps].set(rows.astype(cache.pages.dtype))


@jax.named_scope("latent_kv_write")
def write_prompt_latent(pages, rows, pages_vec):
    """Prefill write: rows [1, S_b, W] (S_b a multiple of the page size)
    into the pages `pages_vec` names, as write_prompt_kv does."""
    ps = pages.shape[1]
    rows = _to_width(rows, pages.shape[-1])
    blocks = rows[0].reshape(rows.shape[1] // ps, ps, rows.shape[-1])
    return pages.at[pages_vec].set(blocks.astype(pages.dtype))


@jax.named_scope("latent_attention")
def latent_paged_attention(q, pages, page_table, lens, v_width, sm_scale,
                           use_flash=False):
    """Absorbed-form latent attention over the paged pool.

    q [B, H, W] (each head's `[q_nope W_UK^T | q_rope]`); pages
    [P, ps, Wp] rows `[c_kv | k_rope | zeros]`; the values are the rows'
    first `v_width` numbers, so a row read once serves both products.
    Returns float32 [B, H, v_width] (`sum p c_kv`, before W_UV).

    use_flash: the Pallas kernel (ops/pallas/latent_decode.py), which
    walks each slot's live pages where they lie. Else plain XLA, always
    the gathered form: every head of a slot attends the same rows, so the
    in-place form (q against the whole pool) would multiply B x H query
    rows with every slot's pages, B times the work; it copies every
    slot's whole table width, dead pages included, and reads the copy
    twice. Its second product runs over the whole rows and its first
    `v_width` columns are kept: slicing the gathered rows first writes a
    second copy of them. One operand rule for both (paged_attention_ref's):
    the rows stay in the cache's dtype into both products, q and the
    unnormalised exponentials are rounded to it, scores, softmax and sums
    are float32."""
    if use_flash:
        from ..ops.pallas.latent_decode import latent_flash_decode
        return latent_flash_decode(q, pages, page_table, lens, v_width,
                                   sm_scale)
    live = _live_keys(page_table, lens, pages.shape[1])     # [B, MP, ps]
    with jax.named_scope("page_gather"):
        rows = pages[page_table]                            # [B, MP, ps, Wp]
    q = _to_width(q, rows.shape[-1])
    s = jnp.einsum("bhw,bmrw->bhmr", q.astype(rows.dtype), rows,
                   preferred_element_type=jnp.float32) * sm_scale
    return _softmax_product(s, live[:, None].astype(jnp.float32), rows,
                            "bhmr,bmrw->bhw")[..., :v_width]


def xla_attention_form(num_pages, batch, table_width):
    """Which of the two plain-XLA forms `paged_attention_ref` takes, from
    shapes alone: "in_place" contracts q against the whole pool where it
    lies and reads every page once, so it is the cheaper one as long as
    the pool holds no more pages than the tables could name
    (`num_pages <= batch * table_width + 1`, which a fully provisioned
    engine meets with equality); "gathered" copies each slot's table
    width out of the pool first and is the better one only for a pool
    much larger than the batch can reach."""
    return ("in_place" if num_pages <= batch * table_width + 1
            else "gathered")


def _live_keys(page_table, lens, page_size):
    """[B, MP, ps] bool: key `r` of the slot's `m`-th page lies below the
    slot's length."""
    mp = page_table.shape[1]
    kpos = (jnp.arange(mp, dtype=jnp.int32)[:, None] * page_size
            + jnp.arange(page_size, dtype=jnp.int32)[None, :])
    return kpos[None] < lens[:, None, None]


def _softmax_product(s, weight, v, contract):
    """The second half both forms share. s float32 scores whose last two
    axes are the keys; weight (broadcastable to s) counts how often each
    key is live for the row, 0 masks it; returns
    einsum(contract, exp(s - max) * weight, v) / sum, float32. The
    exponentials are rounded to v's dtype unnormalised, as the Pallas
    kernel's are; the sum that divides them is the float32 one."""
    s = jnp.where(weight > 0, s, -jnp.inf)
    m = jnp.max(s, axis=(-2, -1), keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)      # fully masked row -> 0
    e = jnp.exp(s - m) * weight
    denom = jnp.sum(e, axis=(-2, -1))
    out = jnp.einsum(contract, e.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out / jnp.where(denom == 0.0, 1.0, denom)[..., None]


@jax.named_scope("paged_attention")
def paged_attention_ref(q, k_pages, v_pages, page_table, lens,
                        k_scale=None, v_scale=None, sm_scale=None):
    """Paged attention in plain jnp: the serving engine's path wherever
    the Pallas kernel is not the default, and the kernel's parity pin.

    q [B, Hkv, G, D] (G = query heads per kv head); pages
    [Hkv, P, ps, D]; page_table [B, MP]; lens [B] int32 — keys at
    flat index >= lens[b] are masked. Returns [B, Hkv, G, D].

    The operand rule (shared with ops/pallas/flash_decode.py): both
    products take K and V in the cache's own dtype straight from the
    pages, accumulate in float32, and the softmax runs in float32; q and
    the exponentials are rounded to the cache's dtype (for a bf16 cache
    that is what the MXU's default precision does to float32 operands
    anyway). Nothing of the gathered size [Hkv, B, MP, ps, D] is ever
    written in a wider dtype than the cache, and this code neither
    transposes nor reshapes K or V: only the tiny q. int8 pages keep
    their numerics: dequantized to float32 with the scale sidecar first.

    Two forms, `xla_attention_form` of the shapes: "in_place"
    multiplies q with every page of the pool where it lies,
    [Hkv, B*G, D] x [Hkv, P, ps, D], and masks the scores by how often
    the slot's table names the page below the slot's length (0 for
    pages of other slots and the trash page; a page named twice counts
    twice, as gathering it twice would). "gathered" copies
    `pages[:, page_table]` (every slot's whole table width, dead pages
    included) and multiplies in that layout; q's G rows are padded to 8
    so that the compiler keeps a matrix product on the MXU and does not
    turn a one-row product into a float32 multiply-and-reduce over a
    float32 copy of the gathered keys. That copy-then-read is why the
    Pallas kernel exists at serving batch sizes."""
    b, hkv, g, d = q.shape
    p, ps = k_pages.shape[1], k_pages.shape[2]
    mp = page_table.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    live = _live_keys(page_table, lens, ps)                 # [B, MP, ps]
    qt = jnp.swapaxes(q, 0, 1)                              # [Hkv, B, G, D]

    if xla_attention_form(p, b, mp) == "in_place":
        k = _dequant(k_pages, k_scale)
        v = _dequant(v_pages, v_scale)
        owns = page_table[:, :, None] == \
            jnp.arange(p, dtype=jnp.int32)[None, None, :]   # [B, MP, P]
        count = jnp.einsum("bmp,bmr->bpr", owns.astype(jnp.float32),
                           live.astype(jnp.float32))        # [B, P, ps]
        s = jnp.einsum("hqd,hprd->hqpr",
                       qt.reshape(hkv, b * g, d).astype(k.dtype), k,
                       preferred_element_type=jnp.float32) * sm_scale
        out = _softmax_product(s, jnp.repeat(count, g, axis=0)[None], v,
                               "hqpr,hprd->hqd").reshape(hkv, b, g, d)
    else:
        @jax.named_scope("page_gather")
        def gather(pages, scale):       # -> [Hkv, B, MP, ps, D]
            return _dequant(pages[:, page_table],
                            None if scale is None else scale[:, page_table])

        k = gather(k_pages, k_scale)
        v = gather(v_pages, v_scale)
        gp = -(-g // _MXU_ROWS) * _MXU_ROWS
        qp = jnp.pad(qt, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
        s = jnp.einsum("hbgd,hbmpd->hbgmp", qp.astype(k.dtype), k,
                       preferred_element_type=jnp.float32) * sm_scale
        out = _softmax_product(
            s, live[None, :, None].astype(jnp.float32), v,
            "hbgmp,hbmpd->hbgd")[:, :, :g]
    return jnp.swapaxes(out, 0, 1).astype(q.dtype)


def _rope_rows(x, positions, theta, inv_freq=None):
    """RoPE for single-token rows: x [B, H, D], positions [B] — the
    per-slot-offset case of llama.apply_rope (ONE shared formula: a
    convention drift between prefill and paged decode would silently
    break K parity)."""
    from .llama import apply_rope
    return apply_rope(x[:, None], positions[:, None], theta,
                      inv_freq)[:, 0]


def paged_layer_forward(q, k, v, cache: PagedLayerCache, out_proj,
                        groups=1, rope_theta=None):
    """The whole per-layer serving branch both GPTAttention and
    LlamaAttention delegate to: Tensor-level dispatch (apply_op) around
    paged_update_and_attend plus the output projection. Returns
    (projected out, new PagedLayerCache)."""
    from ..autograd import apply_op

    def run(qv, kv, vv):
        out, new_pages = paged_update_and_attend(
            qv, kv, vv, cache, groups=groups, rope_theta=rope_theta)
        return (out,) + new_pages

    out, kp, vp, ks, vs = apply_op(run, q, k, v, differentiable=False)
    b, s = out.shape[0], out.shape[1]
    return (out_proj(out.reshape([b, s, -1])),
            cache.replaced(kp, vp, ks, vs))


def paged_update_and_attend(q, k, v, cache: PagedLayerCache, groups=1,
                            rope_theta=None):
    """The per-layer serving step, shared by GPT and Llama attention:
    (optionally RoPE at per-slot positions,) write the new token's K/V
    into the pages, attend the single query row against the slot's
    paged history (self included).

    q [B, 1, H, D]; k/v [B, 1, Hkv, D] raw projections. Returns
    (out [B, 1, H, D], (k_pages, v_pages, k_scale, v_scale)).
    Masked slots (positions route their table row to the trash page —
    the engine's contract) produce zero attention rows; the engine
    discards their sampled tokens."""
    b, sq, h, d = q.shape
    assert sq == 1, "paged decode is the single-token path"
    hkv = k.shape[2]
    assert h == hkv * groups, (h, hkv, groups)
    pos = cache.positions
    q1 = q[:, 0]                        # [B, H, D]
    k1 = k[:, 0]                        # [B, Hkv, D]
    v1 = v[:, 0]
    if rope_theta is not None:
        q1 = _rope_rows(q1, pos, rope_theta)
        k1 = _rope_rows(k1, pos, rope_theta)
    # live-ness is encoded upstream: inactive slots carry an all-trash
    # page table row, so the write is always safe full-width
    live = jnp.ones((b,), jnp.bool_)
    k_pages, v_pages, k_scale, v_scale = write_token_kv(cache, k1, v1,
                                                        live)
    lens = pos + 1                      # the written token attends itself
    qg = q1.reshape(b, hkv, groups, d)
    if cache.use_flash:
        from ..ops.attention import paged_flash_decode
        with jax.named_scope("paged_attention"):
            out = paged_flash_decode(qg, k_pages, v_pages,
                                     cache.page_table, lens,
                                     k_scale=k_scale, v_scale=v_scale)
    else:
        out = paged_attention_ref(qg, k_pages, v_pages, cache.page_table,
                                  lens, k_scale=k_scale, v_scale=v_scale)
    out = out.reshape(b, 1, h, d)
    return out, (k_pages, v_pages, k_scale, v_scale)


# -- COW prefix caching (host side) -----------------------------------------
#
# A request whose prompt shares a page-aligned prefix with an earlier
# prompt can reuse that prompt's already-written pages instead of
# recomputing prefill for them. The sharing unit is the FULL page:
# fingerprints are a rolling blake2b chain over page-sized token
# blocks, so a boundary fingerprint commits to the entire token prefix
# before it (two prompts with the same boundary-j fingerprint share
# tokens [0, j*page_size) with cryptographic certainty, and the chain
# is process-independent — the fleet router recomputes the same values
# from heartbeat-advertised page sizes).
#
# COW discipline is structural, not trapped: boundaries stop at
# (len-1)//page_size, so the final prompt position ALWAYS lands in the
# request's private tail (the sampled first token needs a live
# forward), and decode writes land at positions >= len — page index
# len//ps >= any shared boundary — i.e. never on a shared page. The
# "copy" in copy-on-write is the short tail prefill re-materializing
# the partial page privately.


def prefix_fingerprints(prompt, page_size):
    """Rolling per-page-boundary fingerprints of a prompt.

    Returns [fp_1, .., fp_j] hex digests where fp_j commits to tokens
    [0, j*page_size). Boundaries are capped at (len-1)//page_size so
    the final prompt position always stays in the private tail (its
    forward pass samples the first token — see module note above)."""
    arr = np.ascontiguousarray(np.asarray(prompt, np.int64))
    nb = max((arr.shape[0] - 1) // page_size, 0) if arr.shape[0] else 0
    h = hashlib.blake2b(digest_size=12)
    h.update(b"ps%d" % page_size)
    out = []
    for j in range(nb):
        h.update(arr[j * page_size:(j + 1) * page_size].tobytes())
        out.append(h.hexdigest())
    return out


class _PrefixEntry:
    __slots__ = ("fp", "pages", "kv", "hits", "last_used")

    def __init__(self, fp, pages, kv, now):
        self.fp = fp
        self.pages = tuple(pages)   # page ids, boundary order
        self.kv = kv                # [(k, v)] per layer: padded dense
        #                             [1, max_seq_len, Hkv, D] device
        #                             buffers (shared across nested
        #                             boundaries; rows past a boundary
        #                             are overwritten/masked by the
        #                             tail program)
        self.hits = 0
        self.last_used = now


class PrefixIndex:
    """Host-side refcounted index of immutable shared prefix pages.

    One entry per registered page boundary (nested boundaries of the
    same prompt are separate entries sharing page ids and K/V views).
    Two refcounts per owned page: ``owners`` (how many entries cover
    it) and ``rc`` (how many live slots map it). A page returns to the
    engine's free list only when BOTH reach zero — slots release rc on
    finish, entries release owners on LRU eviction, and eviction skips
    any entry with a page still pinned by a live slot (shared pages
    evict LRU only at refcount 0).

    Entries also pin a dense padded copy of the prefix K/V rows (per
    layer, [1, max_seq_len, Hkv, D], built once at registration): the
    tail-prefill program needs the prefix as a dense static-cache
    buffer so the tail's keys/queries attend it exactly as a full
    prefill would, and keeping it device-resident makes a hit
    admission a pure dispatch — zero per-hit transfers. The index
    itself stays engine-agnostic host bookkeeping: the buffers are
    opaque objects it never touches."""

    def __init__(self, page_size, min_pages=1, max_entries=512):
        self.page_size = int(page_size)
        self.min_pages = max(int(min_pages), 1)
        self.max_entries = int(max_entries)
        self._entries = {}      # fp -> _PrefixEntry
        self._owners = {}       # page -> entry count
        self._rc = {}           # page -> live slot count
        self._clock = 0         # monotonic LRU clock (no wall time)
        # counters (plain monotonic ints; the engine surfaces them
        # through health() and the fleet router folds them into the
        # fleet_prefix_* registry series off heartbeats)
        self.hits = 0
        self.misses = 0
        self.hit_pages = 0
        self.total_pages = 0    # shareable prompt pages seen (denom)
        self.cow_copies = 0     # private tail pages re-materialized
        self.evictions = 0
        self.adopted_pages = 0  # pages ever adopted (monotonic; the
        #                         fleet_prefix_shared_pages_total feed
        #                         — shared_pages is the level, this
        #                         the counter)

    # -- introspection ----------------------------------------------------

    @property
    def entries(self):
        return len(self._entries)

    @property
    def owned_pages(self):
        """Pages currently owned by the index (not on the free list)."""
        return set(self._owners)

    @property
    def owned_page_count(self):
        return len(self._owners)

    def pinned(self, page):
        return self._rc.get(page, 0) > 0

    def fingerprint_set(self):
        """All registered boundary fingerprints (heartbeat inventory)."""
        return set(self._entries)

    def covers(self, fps):
        """True when every boundary in the chain is already
        registered (an insert would be a no-op)."""
        return all(fp in self._entries for fp in fps)

    def top_fingerprints(self, n=5):
        """[(fp, pages, hits)] hottest entries, for health()."""
        rows = sorted(self._entries.values(),
                      key=lambda e: (-e.hits, -e.last_used))
        return [(e.fp, len(e.pages), e.hits) for e in rows[:n]]

    def stats(self):
        return {"entries": len(self._entries),
                "shared_pages": len(self._owners),
                "hits": self.hits, "misses": self.misses,
                "hit_pages": self.hit_pages,
                "total_pages": self.total_pages,
                "cow_copies": self.cow_copies,
                "evictions": self.evictions,
                "adopted_pages": self.adopted_pages}

    def sidecar_bytes(self):
        """Device bytes pinned by the dense K/V sidecars, deduplicated
        by object identity (nested boundary entries of one prompt
        share ONE sidecar — counting it per entry would overstate the
        footprint by the nesting depth). The memory ledger's
        prefix_sidecar level reads this."""
        seen, total = set(), 0
        for e in self._entries.values():
            if e.kv is None or id(e.kv) in seen:
                continue
            seen.add(id(e.kv))
            for k, v in e.kv:
                total += int(getattr(k, "nbytes", 0) or 0)
                total += int(getattr(v, "nbytes", 0) or 0)
        return total

    def audit(self, live_refs=None):
        """Cross-check the index's two refcount maps against their
        definitions — the release-on-failover leak detector the
        memory ledger runs every sweep. Returns a list of problem
        strings (empty = consistent); never raises.

        Checks: ``_owners`` must equal per-page coverage recomputed
        from the live entries; ``_rc`` pins must only exist on owned
        pages and must be positive; and, when the engine passes
        ``live_refs`` (page -> count of live slots mapping it via
        slot.shared), ``_rc`` must match it exactly — a pin with no
        live slot is a page that will never return to the free list,
        a live slot without a pin is a page eviction can free under a
        running request."""
        problems = []
        cover = {}
        for e in self._entries.values():
            for p in e.pages:
                cover[p] = cover.get(p, 0) + 1
        if cover != self._owners:
            bad = {p for p in set(cover) | set(self._owners)
                   if cover.get(p, 0) != self._owners.get(p, 0)}
            problems.append(
                f"owner counts diverge from entry coverage on pages "
                f"{sorted(bad)[:8]}")
        for p, n in self._rc.items():
            if n <= 0:
                problems.append(f"non-positive pin {n} on page {p}")
            if p not in self._owners:
                problems.append(f"pin on unowned page {p}")
        if live_refs is not None:
            live = {p: n for p, n in live_refs.items() if n > 0}
            if live != self._rc:
                bad = {p for p in set(live) | set(self._rc)
                       if live.get(p, 0) != self._rc.get(p, 0)}
                problems.append(
                    f"slot pins diverge from live page-table "
                    f"references on pages {sorted(bad)[:8]}")
        return problems

    # -- lookup / refcounting ---------------------------------------------

    def match(self, fps):
        """Longest registered boundary of a fingerprint chain:
        (entry, npages) or None. A boundary hit implies every shorter
        boundary matches too (rolling chain), so scanning from the
        longest suffices; respects min_pages."""
        for j in range(len(fps), self.min_pages - 1, -1):
            e = self._entries.get(fps[j - 1])
            if e is not None:
                return e, j
        return None

    def acquire(self, entry):
        """Pin an entry's pages for a live slot; returns the page ids
        in boundary order."""
        self._clock += 1
        entry.hits += 1
        entry.last_used = self._clock
        for p in entry.pages:
            self._rc[p] = self._rc.get(p, 0) + 1
        return list(entry.pages)

    def release(self, pages):
        """Drop a finished slot's pin on shared pages. Pages stay owned
        by their entries (reuse is the point) — only eviction frees."""
        for p in pages:
            n = self._rc.get(p, 0) - 1
            if n > 0:
                self._rc[p] = n
            else:
                self._rc.pop(p, None)

    # -- registration / eviction ------------------------------------------

    def insert(self, fps, pages, kv, *, pin=True):
        """Register boundaries [min_pages .. len(fps)] of a prompt.

        ``pages`` are the donor slot's prompt pages (>= len(fps) of
        them); ``kv`` is the padded dense K/V sidecar ([(k, v)] per
        layer, [1, max_seq_len, Hkv, D]) — one object, shared by
        every nested boundary entry (rows past a boundary are
        overwritten/masked by the tail program, so no per-boundary
        slices exist). Pages newly adopted by the index get rc pinned
        for the donor slot when ``pin`` (the slot is still running on
        them; its release drops the pin). Returns (adopted, freed):
        the set of pages the index now owns among
        ``pages[:len(fps)]``, and pages released by capacity eviction
        that the caller MUST return to its free list."""
        adopted, freed = set(), []
        self._clock += 1
        for j in range(self.min_pages, len(fps) + 1):
            fp = fps[j - 1]
            if fp in self._entries:
                self._entries[fp].last_used = self._clock
                continue
            if len(self._entries) >= self.max_entries and \
                    not self._evict_entries(1, freed):
                break               # full and nothing evictable
            self._entries[fp] = _PrefixEntry(fp, pages[:j], kv,
                                             self._clock)
            for p in pages[:j]:
                if p not in self._owners:
                    adopted.add(p)
                self._owners[p] = self._owners.get(p, 0) + 1
        self.adopted_pages += len(adopted)
        if pin:
            for p in adopted:
                self._rc[p] = self._rc.get(p, 0) + 1
        return adopted, freed

    def evict(self, need_pages):
        """Free at least ``need_pages`` pages by LRU entry eviction
        (entries whose pages are all slot-unpinned). Returns the list
        of freed page ids (may be shorter than asked)."""
        freed = []
        while len(freed) < need_pages:
            got = self._evict_entries(1, freed)
            if not got:
                break
        return freed

    def _evict_entries(self, n, freed=None):
        """Evict up to n LRU entries with no slot-pinned page; append
        fully-released pages to ``freed``. Returns entries evicted."""
        done = 0
        for e in sorted(self._entries.values(),
                        key=lambda e: e.last_used):
            if done >= n:
                break
            if any(self._rc.get(p, 0) for p in e.pages):
                continue
            del self._entries[e.fp]
            self.evictions += 1
            done += 1
            for p in e.pages:
                left = self._owners.get(p, 0) - 1
                if left > 0:
                    self._owners[p] = left
                else:
                    self._owners.pop(p, None)
                    if freed is not None:
                        freed.append(p)
        return done
