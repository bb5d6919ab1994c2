"""Paged (block) KV cache — the serving-time cache contract shared by
GPT and Llama (ref: vLLM PagedAttention, arXiv:2309.06180; upstream
Paddle ships the CUDA equivalent under paddle/fluid/operators/fused/ +
FastDeploy's block-wise attention).

TPU-native shape of the idea: all shapes are STATIC so the whole decode
loop stays one compiled XLA program —

- the cache is a fixed pool of pages per layer, laid out HEAD-MAJOR
  `[Hkv, P, page_size, D]` (the layout the Pallas paged flash-decode
  kernel reads pages from HBM in, one (head, page) block per grid step);
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
"""
from __future__ import annotations

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["PagedLayerCache", "PrefixIndex", "alloc_pages",
           "prefix_fingerprints", "quantize_rows",
           "write_token_kv", "write_prompt_kv", "paged_attention_ref",
           "paged_update_and_attend", "paged_layer_forward",
           "TRASH_PAGE"]

# page index 0 is never allocated to a sequence: it is the write sink
# for masked (inactive/finished) slots and for prefill bucket tail
# pages beyond a request's allocation
TRASH_PAGE = 0

_INT8_MAX = 127.0


class PagedLayerCache:
    """One layer's view of the paged cache plus the shared routing
    state. Plain object (deliberately not a pytree — see module doc);
    `use_flash` is trace-time-static kernel routing, everything else is
    a traced array."""

    __slots__ = ("k_pages", "v_pages", "k_scale", "v_scale",
                 "page_table", "positions", "use_flash")

    def __init__(self, k_pages, v_pages, page_table, positions,
                 k_scale=None, v_scale=None, use_flash=False):
        self.k_pages = k_pages          # [Hkv, P, ps, D]
        self.v_pages = v_pages          # [Hkv, P, ps, D]
        self.k_scale = k_scale          # [Hkv, P, ps, 1] f32 | None
        self.v_scale = v_scale          # [Hkv, P, ps, 1] f32 | None
        self.page_table = page_table    # [B, MP] int32
        self.positions = positions      # [B] int32 tokens already cached
        self.use_flash = bool(use_flash)

    def replaced(self, k_pages, v_pages, k_scale=None, v_scale=None):
        """New view with updated page arrays (same table/positions/
        routing) — what an attention layer returns as its new cache."""
        return PagedLayerCache(k_pages, v_pages, self.page_table,
                               self.positions, k_scale=k_scale,
                               v_scale=v_scale, use_flash=self.use_flash)

    @property
    def page_size(self):
        return self.k_pages.shape[2]

    @property
    def quantized(self):
        return self.k_scale is not None


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


def quantize_rows(x):
    """Symmetric per-row int8 quantization over the trailing (D) axis.
    x [..., D] f32/bf16 -> (q int8 [..., D], scale f32 [..., 1])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = amax / _INT8_MAX
    safe = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / safe),
                 -_INT8_MAX, _INT8_MAX).astype(jnp.int8)
    return q, scale


def _dequant(pages, scale, dtype):
    x = pages.astype(jnp.float32)
    if scale is not None:
        x = x * scale
    return x.astype(dtype)


@jax.named_scope("kv_write")
def write_token_kv(cache: PagedLayerCache, k_new, v_new, live):
    """Write one token per slot into the pages. k_new/v_new
    [B, Hkv, D] (post-RoPE for Llama); live [B] bool — masked slots are
    redirected to the trash page so the scatter stays full-width.
    Returns the updated (k_pages, v_pages, k_scale, v_scale)."""
    ps = cache.page_size
    pos = cache.positions
    page = jnp.take_along_axis(cache.page_table,
                               (pos // ps)[:, None], axis=1)[:, 0]
    page = jnp.where(live, page, TRASH_PAGE)
    row = jnp.where(live, pos % ps, 0)
    kt = jnp.swapaxes(k_new, 0, 1)      # [Hkv, B, D]
    vt = jnp.swapaxes(v_new, 0, 1)
    if cache.quantized:
        kq, ks = quantize_rows(kt)
        vq, vs = quantize_rows(vt)
        return (cache.k_pages.at[:, page, row].set(kq),
                cache.v_pages.at[:, page, row].set(vq),
                cache.k_scale.at[:, page, row].set(ks),
                cache.v_scale.at[:, page, row].set(vs))
    return (cache.k_pages.at[:, page, row].set(kt.astype(
                cache.k_pages.dtype)),
            cache.v_pages.at[:, page, row].set(vt.astype(
                cache.v_pages.dtype)),
            None, None)


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


@jax.named_scope("paged_attention")
def paged_attention_ref(q, k_pages, v_pages, page_table, lens,
                        k_scale=None, v_scale=None, sm_scale=None):
    """jnp reference paged attention (the XLA-fused fallback path and
    the parity pin for the Pallas kernel).

    q [B, Hkv, G, D] (G = query heads per kv head); pages
    [Hkv, P, ps, D]; page_table [B, MP]; lens [B] int32 — keys at
    flat index >= lens[b] are masked. Returns [B, Hkv, G, D].

    Gathers the slot's pages into a dense [B, S_cap, ...] view — the
    reference trades the kernel's in-place HBM reads for clarity; the
    gather is why the Pallas kernel exists at serving batch sizes."""
    b, hkv, g, d = q.shape
    ps = k_pages.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    @jax.named_scope("page_gather")
    def gather(pages, scale):
        x = pages[:, page_table]        # [Hkv, B, MP, ps, D]
        x = _dequant(x, None if scale is None else scale[:, page_table],
                     jnp.float32)
        x = jnp.moveaxis(x, 1, 0)       # [B, Hkv, MP, ps, D]
        return x.reshape(b, hkv, -1, d)  # [B, Hkv, S_cap, D]

    k = gather(k_pages, k_scale)
    v = gather(v_pages, v_scale)
    s = jnp.einsum("bhgd,bhkd->bhgk", q.astype(jnp.float32), k,
                   preferred_element_type=jnp.float32) * sm_scale
    kpos = jnp.arange(k.shape[2], dtype=jnp.int32)[None, None, None, :]
    s = jnp.where(kpos < lens[:, None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows -> 0
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _rope_rows(x, positions, theta):
    """RoPE for single-token rows: x [B, H, D], positions [B] — the
    per-slot-offset case of llama.apply_rope (ONE shared formula: a
    convention drift between prefill and paged decode would silently
    break K parity)."""
    from .llama import apply_rope
    return apply_rope(x[:, None], positions[:, None], theta)[:, 0]


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
