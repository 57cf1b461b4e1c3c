"""Paged quantized KV cache: a global pool of fixed-size token pages.

Angular quantization is random-access by construction — every token row is a
fixed number of packed bits with no calibration state — so the compressed
payload can live in non-contiguous fixed-size pages exactly like a raw
vLLM-style block cache (the property FibQuant calls out as the enabler for
paged compressed caches). This module provides the two halves:

  * device side — `PagedKVCache`: layer-stacked pool arrays
    `(L, P, n_kv, page_size, ...)` holding the *packed* payload (angle words
    + norm nibbles + per-vector min/max), a `(B, max_pages)` page table of
    physical page ids per decode slot, and per-slot `lengths`. Pages are
    shared across layers: physical page p holds the same token range in
    every layer, so the page table stays `O(B * max_pages)` instead of
    growing with depth.

  * host side — `PageAllocator`: the free-list control plane the scheduler
    drives between jit'd steps. Allocation state never enters jit; the
    device only ever sees the page table the allocator produced.

Physical page 0 is reserved as the *trash page*: inactive decode slots in a
running batch still execute the (masked) append scatter, and pointing their
writes at page 0 keeps them from stomping live pages without a branch in the
hot loop. The allocator therefore hands out ids 1..P-1.

Per-page valid counts are derived, not stored: page j of a slot holds
`clip(length - j*page_size, 0, page_size)` valid tokens (`page_valid_counts`)
— masking in the attend paths uses the slot length directly, identical math
to the contiguous cache's `_score_mask`.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import kvcache
from repro.configs.base import ModelConfig
from repro.core import packing
from repro.core.quantizer import KVQuantizer, QuantizedKV


class PagedKVCache(NamedTuple):
    """Device-side paged pool + per-slot indirection.

    k/v:        QuantizedKV pools, arrays (L, P, n_kv, page_size, ...) —
                one page of one kv head is a (page_size, X) tile; the
                Pallas kernel streams one page of a block of kv heads
                per grid step
    page_table: (B, max_pages) int32 physical page ids (0 = unused/trash;
                entries past a slot's allocation are masked via lengths)
    lengths:    (B,) int32 — valid tokens per decode slot
    """

    k: QuantizedKV
    v: QuantizedKV
    page_table: jax.Array
    lengths: jax.Array


def pages_for_tokens(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold n_tokens (ceil; 0 tokens still costs 0 pages)."""
    if n_tokens < 0:
        raise ValueError(f"n_tokens must be >= 0, got {n_tokens}")
    return -(-n_tokens // page_size)


def page_payload_bytes(qz: KVQuantizer, cfg: ModelConfig,
                       page_size: int) -> int:
    """Payload bytes ONE physical page occupies across all layers (K + V)."""
    c = qz.config
    per_tok = (
        packing.token_payload_bytes(
            c.n_pairs, c.index_width,
            c.k_norm.bits, c.resolved_storage)
        + packing.token_payload_bytes(
            c.n_pairs, c.index_width,
            c.v_norm.bits, c.resolved_storage))
    return cfg.num_attn_layers * cfg.num_kv_heads * page_size * per_tok


def init_paged_cache(cfg: ModelConfig, qz: KVQuantizer, num_pages: int,
                     page_size: int, batch: int,
                     max_pages: int) -> PagedKVCache:
    """Zero-filled pool + empty page tables.

    `batch` is the number of decode slots, `max_pages` the page-table width
    (the longest context any one slot may reach, in pages).
    """
    if cfg.sliding_window is not None:
        raise ValueError(
            "paged caches do not implement ring-buffer sliding windows; "
            "use the contiguous cache for windowed configs")
    if num_pages < 2:
        raise ValueError(f"num_pages must be >= 2 (page 0 is reserved), "
                         f"got {num_pages}")
    lead = (cfg.num_attn_layers, num_pages, cfg.num_kv_heads, page_size)
    return PagedKVCache(
        k=kvcache._quantized_zeros(qz, lead, qz.config.k_norm),
        v=kvcache._quantized_zeros(qz, lead, qz.config.v_norm),
        page_table=jnp.zeros((batch, max_pages), jnp.int32),
        lengths=jnp.zeros((batch,), jnp.int32),
    )


def cache_physical_bytes(cache: PagedKVCache) -> int:
    """Pool-resident payload bytes (page table / lengths bookkeeping
    excluded, mirroring the contiguous `cache_physical_bytes`)."""
    return kvcache.cache_physical_bytes((cache.k, cache.v))


def write_prompt_pages(pool: QuantizedKV, codes: QuantizedKV,
                       page_ids: jax.Array, page_size: int) -> QuantizedKV:
    """Scatter a prefill chunk's quantized codes into pool pages.

    pool arrays: (L, P, n_kv, page_size, X); codes arrays: (L, C, n_kv, X)
    with C == len(page_ids) * page_size (the scheduler pads prompts to a
    whole number of pages; tail slots hold encoded padding that stays masked
    until decode overwrites it — the same invariant as the dense engine).
    """
    def put(pool_a, codes_a):
        pages = tokens_to_pages(codes_a, page_size, axis=1)
        return pool_a.at[:, page_ids].set(pages.astype(pool_a.dtype))

    return jax.tree.map(put, pool, codes)


def tokens_to_pages(a: jax.Array, page_size: int, axis: int) -> jax.Array:
    """(..., T, n_kv, X) -> (..., T/page_size, n_kv, page_size, X), the
    token axis at `axis`: token-major codes cut into pool-layout pages."""
    lead, (t, nkv, x) = a.shape[:axis], a.shape[axis:]
    a = a.reshape(*lead, t // page_size, page_size, nkv, x)
    return jnp.swapaxes(a, axis + 1, axis + 2)


def pages_to_tokens(a: jax.Array, axis: int) -> jax.Array:
    """Inverse of `tokens_to_pages`: (..., n, n_kv, page_size, X) ->
    (..., n*page_size, n_kv, X), the page axis at `axis`."""
    lead, (n, nkv, ps, x) = a.shape[:axis], a.shape[axis:]
    return jnp.swapaxes(a, axis + 1, axis + 2).reshape(*lead, n * ps, nkv, x)


def append_token_pages(layer_pool: QuantizedKV, new_q: QuantizedKV,
                       page_table: jax.Array, lengths: jax.Array,
                       active: jax.Array, page_size: int) -> QuantizedKV:
    """Write one token per decode slot at (page_table[i, len//ps], len%ps).

    Operates on ONE layer's pool slice (the decode step scans layers with
    the pool as scan xs): layer_pool arrays (P, n_kv, ps, X), new_q arrays
    (B, 1, n_kv, X). Inactive slots are redirected to the reserved trash
    page 0 so the scatter stays branch-free.
    """
    b = page_table.shape[0]
    page_idx = jnp.clip(lengths // page_size, 0, page_table.shape[1] - 1)
    phys = page_table[jnp.arange(b), page_idx]  # (B,)
    phys = jnp.where(active, phys, 0)
    offset = jnp.where(active, lengths % page_size, 0)

    def put(pool_a, new_a):
        return pool_a.at[phys, :, offset].set(
            new_a[:, 0].astype(pool_a.dtype))

    return jax.tree.map(put, layer_pool, new_q)


def append_tokens_pages(layer_pool: QuantizedKV, new_q: QuantizedKV,
                        page_table: jax.Array, lengths: jax.Array,
                        valid: jax.Array, page_size: int) -> QuantizedKV:
    """Write up to `q_len` tokens per decode slot in ONE scatter.

    The speculative verify path's optimistic append: token j of slot i
    lands at (page_table[i, (lengths[i]+j)//ps], (lengths[i]+j) % ps),
    crossing page boundaries as needed. `valid` is a (B, q_len) bool mask
    — verify dispatches are padded to a static q_len (one jit variant per
    table width, never per acceptance count), and masked positions are
    redirected to the reserved trash page 0, exactly like inactive slots
    in the single-token `append_token_pages`.

    layer_pool arrays: (P, n_kv, ps, X); new_q arrays: (B, q_len, n_kv, X).
    """
    b, q_len = valid.shape
    pos = lengths[:, None] + jnp.arange(q_len, dtype=lengths.dtype)[None, :]
    page_idx = jnp.clip(pos // page_size, 0, page_table.shape[1] - 1)
    phys = jnp.take_along_axis(page_table, page_idx, axis=1)  # (B, q_len)
    phys = jnp.where(valid, phys, 0).reshape(-1)
    offset = jnp.where(valid, pos % page_size, 0).reshape(-1)

    def put(pool_a, new_a):
        flat = new_a.reshape(b * q_len, *new_a.shape[2:])
        return pool_a.at[phys, :, offset].set(flat.astype(pool_a.dtype))

    return jax.tree.map(put, layer_pool, new_q)


def pop_tokens(allocator: "PageAllocator", owner, page_table_row: np.ndarray,
               length: int, n: int, page_size: int, *,
               min_length: int = 0, free_empty: bool = False
               ) -> tuple[int, np.ndarray]:
    """Transactional rollback: drop the last `n` tokens of one slot.

    The speculative draft-verify-rollback loop appends draft tokens'
    quantized K/V optimistically; when verification rejects a suffix, this
    op pops it. Host-side control plane only (like the allocator): the
    rejected codes stay in the pool as dead bytes past the new frontier —
    masked by every attend path and overwritten by the next append — so no
    device work is needed to roll back.

    Validation (the invariants the rollback must never cross):

      * `n >= 0` and `length - n >= min_length` — a pop may never descend
        below the commit boundary the caller names (the prefill frontier,
        which also covers any shared-prefix page's coverage, since shared
        blocks are always whole prompt blocks).
      * with `free_empty=True`, pages left *wholly* past the new frontier
        (they held only popped tokens) are released back to the allocator
        and their table entries zeroed. A page in that range with
        refcount > 1 — shared with the prefix trie or another request —
        raises instead of freeing: copy-on-write sharing means co-owners
        still read it, and a shared page inside a popped suffix can only
        mean the refcount bookkeeping broke. The partially-valid frontier
        page is always kept.

    The paged scheduler pops with `free_empty=False` mid-flight (its
    admission reserved pages for the request's whole span — freeing them
    would re-introduce mid-flight OOM) and `free_empty=True` when the
    request finishes inside a verify step, so wholly-speculative tail
    pages return through this validated path before eviction releases the
    rest.

    Returns `(new_length, freed_page_ids)`; mutates `page_table_row` in
    place when pages are freed.
    """
    length, n = int(length), int(n)
    if n < 0:
        raise ValueError(f"cannot pop {n} tokens")
    new_length = length - n
    if new_length < min_length:
        raise ValueError(
            f"pop of {n} tokens from length {length} would descend below "
            f"the commit boundary {min_length} (prefill / shared-prefix "
            f"coverage)")
    freed: list[int] = []
    if free_empty and n > 0:
        lo = pages_for_tokens(new_length, page_size)
        hi = pages_for_tokens(length, page_size)
        for j in range(lo, hi):
            page = int(page_table_row[j])
            if page == 0:
                raise ValueError(
                    f"pop range covers unmapped page-table entry {j} "
                    f"(popped tokens must live in mapped pages)")
            if allocator.refcount(page) > 1:
                raise RuntimeError(
                    f"copy-on-write violation: pop would free page {page} "
                    f"(refcount {allocator.refcount(page)}) still shared "
                    f"by the prefix trie or another request")
            freed.append(page)
        if freed:
            allocator.release_pages(owner, freed)
            page_table_row[lo:hi] = 0
    return new_length, np.asarray(freed, np.int32)


def gather_pages(pool: QuantizedKV, page_table: jax.Array) -> QuantizedKV:
    """Materialize a contiguous (B, max_pages*ps, n_kv, X) view of one
    layer's (P, n_kv, ps, X) pool via the page table — the quant-xla
    fallback's indirection (the Pallas kernel gathers per-page in its
    index_map instead and never materializes this)."""
    return jax.tree.map(lambda a: pages_to_tokens(a[page_table], axis=1),
                        pool)


def per_page_valid(length: int, max_pages: int, page_size: int) -> np.ndarray:
    """(max_pages,) valid-token count per logical page of one slot."""
    j = np.arange(max_pages)
    return np.clip(int(length) - j * page_size, 0, page_size).astype(np.int64)


class PageAllocator:
    """Host-side refcounted free-list allocator over physical pages 1..P-1.

    The scheduler calls this between jit'd steps; nothing here touches
    device memory. Frees push onto the list tail and allocations pop from
    it (LIFO), so recently freed pages are reused first — the property the
    alloc-after-free tests pin (warm pages stay warm).

    Copy-on-write sharing (the prefix cache, `serving/prefix.py`) is built
    on per-page reference counts:

      * `alloc(n, owner)` hands out fresh pages at refcount 1 — `owner`
        holds the only reference and may write the page.
      * `share(pages, owner)` adds `owner` as one more reference to pages
        some other owner already holds (refcount += 1 each). A page with
        refcount > 1 is *immutable*: the scheduler's append guard redirects
        any write aimed at it to the trash page and treats the attempt as
        an invariant violation.
      * `release(owner)` drops every reference `owner` holds; a page
        returns to the free list only when its refcount hits zero.
        `free` is the same operation under its historical name.

    The conservation invariant (`check_conservation`, pinned by hypothesis
    tests) generalizes the exclusive-ownership one: free pages + distinct
    referenced pages partition 1..P-1, and every page's refcount equals
    the number of owners holding it.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (page 0 is reserved), "
                f"got {num_pages}")
        self.num_pages = num_pages
        self.reset()

    def reset(self) -> None:
        """Return to the all-free state (every refcount zero)."""
        # ascending ids at the tail so the first-ever allocation starts at
        # page 1 (pop from the end)
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._owned: dict[object, list[int]] = {}
        self._refs: dict[int, int] = {}

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_live(self) -> int:
        """Distinct pages with refcount >= 1 (shared pages count once)."""
        return len(self._refs)

    @property
    def total_refs(self) -> int:
        """Sum of refcounts == sum of per-owner holdings."""
        return sum(self._refs.values())

    def live_pages(self, owner=None) -> list[int]:
        """Pages `owner` references (or every referenced page, duplicates
        included when shared across owners, if `owner` is None)."""
        if owner is not None:
            return list(self._owned.get(owner, ()))
        return [p for pages in self._owned.values() for p in pages]

    def refcount(self, page: int) -> int:
        """Current reference count of one physical page (0 = free)."""
        return self._refs.get(int(page), 0)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int, owner) -> np.ndarray:
        """Take n fresh pages for `owner` at refcount 1; raises when the
        pool is exhausted (the scheduler checks `can_alloc` first — running
        dry mid-admission is a bug, not backpressure)."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: need {n}, have {len(self._free)} "
                f"free of {self.num_pages - 1}")
        got = [self._free.pop() for _ in range(n)]
        for p in got:
            self._refs[p] = 1
        self._owned.setdefault(owner, []).extend(got)
        return np.asarray(got, np.int32)

    def share(self, pages, owner) -> None:
        """Add `owner` as one more reference to already-live `pages`
        (refcount += 1 each). Sharing a free page, or the same page twice
        under one owner, is a caller bug and raises."""
        pages = [int(p) for p in pages]
        held = set(self._owned.get(owner, ()))
        for p in pages:
            if p not in self._refs:
                raise ValueError(f"cannot share free page {p}")
            if p in held:
                raise ValueError(
                    f"owner {owner!r} already references page {p}")
            held.add(p)  # catch duplicates within this call too
        for p in pages:
            self._refs[p] += 1
        self._owned.setdefault(owner, []).extend(pages)

    def release(self, owner) -> int:
        """Drop every reference `owner` holds; pages whose refcount hits
        zero return to the free list. Returns how many pages were actually
        freed (shared pages survive their co-owners)."""
        return self._release(self._owned.pop(owner, []))

    def release_pages(self, owner, pages) -> int:
        """Drop `owner`'s references to a subset of its pages (the prefix
        trie's LRU eviction path). Returns how many pages were freed."""
        held = self._owned.get(owner, [])
        for p in pages:
            held.remove(int(p))  # raises if owner never held it
        if not held:
            self._owned.pop(owner, None)
        return self._release([int(p) for p in pages])

    def _release(self, pages: list) -> int:
        freed = 0
        for p in pages:
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)
                freed += 1
        return freed

    # historical name: exclusive-ownership callers say "free"
    free = release

    def check_conservation(self) -> None:
        """Free + referenced pages must partition 1..P-1, and every page's
        refcount must equal the number of owners holding it."""
        live = set(self._refs)
        if live & set(self._free):
            raise AssertionError("page aliasing: a page is free AND live")
        if live | set(self._free) != set(range(1, self.num_pages)):
            raise AssertionError(
                f"page leak: {len(live) + len(self._free)} accounted of "
                f"{self.num_pages - 1}")
        by_owner: dict[int, int] = {}
        for pages in self._owned.values():
            for p in pages:
                by_owner[p] = by_owner.get(p, 0) + 1
        if by_owner != self._refs:
            raise AssertionError(
                "refcount drift: per-owner holdings disagree with refs")


class ShardedPageAllocators:
    """N mirror `PageAllocator`s kept in lockstep by construction.

    Sharded serving splits the pool's kv-head axis over N devices but keeps
    ONE logical page space: page i holds shard s's heads of the same tokens
    on device s, so every allocator decision must land identically on all
    shards. Rather than trusting call sites, this wrapper presents the full
    PageAllocator interface, mirrors every operation to all N allocators,
    and asserts the returned values (and, in `check_conservation`, the full
    free/owned/refcount state) agree across shards — divergence is a bug
    surfaced at the op that caused it, not a corrupted pool later.

    The scheduler and the prefix trie hold one of these exactly as they
    would a plain allocator; with n_shards=1 it degenerates to a checked
    pass-through."""

    def __init__(self, num_pages: int, n_shards: int):
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self.num_pages = num_pages
        self.n_shards = n_shards
        self.shards = [PageAllocator(num_pages) for _ in range(n_shards)]

    def _agree(self, name: str, results):
        r0 = results[0]
        for i, r in enumerate(results[1:], 1):
            same = (np.array_equal(r0, r) if isinstance(r0, np.ndarray)
                    else r0 == r)
            if not same:
                raise AssertionError(
                    f"shard allocator lockstep broken: {name} returned "
                    f"{r0!r} on shard 0 but {r!r} on shard {i}")
        return r0

    def _mirror(self, name: str, *args, **kw):
        return self._agree(
            name, [getattr(a, name)(*args, **kw) for a in self.shards])

    def reset(self) -> None:
        self._mirror("reset")

    @property
    def num_free(self) -> int:
        return self._agree("num_free", [a.num_free for a in self.shards])

    @property
    def num_live(self) -> int:
        return self._agree("num_live", [a.num_live for a in self.shards])

    @property
    def total_refs(self) -> int:
        return self._agree("total_refs", [a.total_refs for a in self.shards])

    def live_pages(self, owner=None) -> list:
        return self._mirror("live_pages", owner)

    def refcount(self, page: int) -> int:
        return self._mirror("refcount", page)

    def can_alloc(self, n: int) -> bool:
        return self._mirror("can_alloc", n)

    def alloc(self, n: int, owner) -> np.ndarray:
        return self._mirror("alloc", n, owner)

    def share(self, pages, owner) -> None:
        return self._mirror("share", pages, owner)

    def release(self, owner) -> int:
        return self._mirror("release", owner)

    def release_pages(self, owner, pages) -> int:
        return self._mirror("release_pages", owner, pages)

    # historical name, matching PageAllocator
    free = release

    def check_conservation(self) -> None:
        """Per-shard conservation, then full cross-shard state equality."""
        for i, a in enumerate(self.shards):
            try:
                a.check_conservation()
            except AssertionError as e:
                raise AssertionError(f"shard {i}: {e}") from e
        a0 = self.shards[0]
        for i, a in enumerate(self.shards[1:], 1):
            if (a._free != a0._free or a._refs != a0._refs
                    or a._owned != a0._owned):
                raise AssertionError(
                    f"shard allocator lockstep broken: shard {i} state "
                    f"diverged from shard 0")
