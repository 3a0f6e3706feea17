"""Paged KV cache: fixed-size pages in a preallocated pool.

The serving-side memory manager (the vLLM PagedAttention layout,
recast for TPU static shapes): the KV cache for ALL resident sequences
lives in ONE preallocated pool per layer —
``(num_layers, num_pages, kv_heads, page_size, head_dim)`` for each of
k and v (head-major pages: one (page, kv head) is a contiguous
``(page_size, head_dim)`` tile, the block the decode-attention kernel
DMAs — Mosaic needs a block's two minor dims whole or (8, 128)-aligned,
which a one-head slice of a ``(kv_heads, head_dim)`` minor pair is
not) — and every sequence owns a *page table*: a fixed-width row of
page ids mapping its logical positions ``[p * page_size, (p+1) *
page_size)`` onto pool pages.  Sequences of wildly different lengths
pack the pool densely, admission/eviction recycles pages between
decode steps, and the decode step's SHAPES never change (the pool, the
(max_batch, pages_per_seq) page-table block, the per-slot scalars), so
it compiles exactly once.

Storage dtype is configurable (bf16 default — halves the pool bytes;
the attention kernels widen the page reads at the seam, the APX306
contract).

Page id 0 is the **garbage page**: :class:`PageAllocator` never hands
it out, and every masked write (inactive slot, padded prompt tail) is
routed there instead of being predicated out — the scatter stays a
dense static-shape op and can never corrupt a live sequence's page.
Every page-table read is clamped into the pool (the APX107 contract:
a stale or corrupt table entry reads/writes garbage, never wraps).

Device-side helpers here are pure functions on the pool arrays (jit
inside the decode/prefill steps); the allocator and page tables are
host-side bookkeeping owned by the scheduler.
"""

import dataclasses
from collections import deque
from typing import Any, Dict, List, Optional

import jax.numpy as jnp

__all__ = [
    "GARBAGE_PAGE", "KVCacheConfig", "PageAllocator", "alloc_pools",
    "copy_page", "pages_needed", "write_decode_kv", "write_prompt_kv",
]

#: page id 0 — reserved, never allocated; the destination of every
#: masked (inactive / padded) cache write
GARBAGE_PAGE = 0


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    """Static shape of the pool (all fields bake into the compiled
    steps).

    ``num_pages`` includes the reserved garbage page, so the usable
    capacity is ``num_pages - 1`` pages.  ``pages_per_seq`` is the
    page-table width: the longest supportable sequence is
    ``pages_per_seq * page_size`` positions.
    """

    num_pages: int = 128
    page_size: int = 16
    pages_per_seq: int = 16
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.num_pages < 2:
            raise ValueError("num_pages must be >= 2: page 0 is the "
                             "reserved garbage page")
        if self.page_size < 1 or self.pages_per_seq < 1:
            raise ValueError("page_size and pages_per_seq must be >= 1")

    @property
    def max_len(self) -> int:
        return self.pages_per_seq * self.page_size


def pages_needed(total_positions: int, page_size: int) -> int:
    """Pages to reserve for a sequence that will cache
    ``total_positions`` tokens (admission reserves the WORST case —
    prompt + max_new_tokens — so a mid-generation allocation failure
    cannot exist and FIFO admission cannot starve)."""
    return -(-int(total_positions) // int(page_size))


def alloc_pools(num_layers: int, kv_heads: int, head_dim: int,
                cfg: KVCacheConfig) -> Dict[str, jnp.ndarray]:
    """Zero-initialized k/v pools:
    ``(L, num_pages, kv_heads, page_size, head_dim)`` each, in the
    storage dtype.  Donated through the decode/prefill jits — the pool
    is updated in place across the whole serve loop."""
    shape = (num_layers, cfg.num_pages, kv_heads, cfg.page_size, head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


class PageAllocator:
    """Host-side refcounted free list over the pool's pages (page 0
    reserved).

    FIFO recycling: freed pages go to the back of the free list, so a
    use-after-free bug surfaces as stale-but-old data (maximally
    distinguishable) rather than freshly-written lookalike values.

    Refcounts (the prefix-sharing substrate): :meth:`allocate` hands a
    page out at refcount 1, :meth:`share` takes an extra reference on a
    LIVE page (a second sequence — or the prefix trie — mapping the
    same physical page), and :meth:`free` drops one reference, only
    recycling the page when the count reaches zero.  A page with
    refcount > 1 must never be written in place — the scheduler
    copy-on-writes it (:func:`copy_page`) before the first divergent
    write.  The garbage page is outside the scheme entirely: its
    refcount is pinned 0 and it can be neither allocated, shared, nor
    freed.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 reserved)")
        self.num_pages = int(num_pages)
        self._free = deque(range(1, self.num_pages))
        self._refs: Dict[int, int] = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def live_pages(self) -> int:
        """Pages currently allocated (refcount >= 1)."""
        return len(self._refs)

    def refcount(self, page: int) -> int:
        """References held on ``page`` (0 = free; the garbage page is
        always 0 — it is never allocated)."""
        return self._refs.get(int(page), 0)

    def can_allocate(self, n: int) -> bool:
        return n <= len(self._free)

    def allocate(self, n: int) -> Optional[List[int]]:
        """``n`` pages at refcount 1 each, or None (never a partial
        grab) when the pool cannot cover the request."""
        if n > len(self._free):
            return None
        pages = [self._free.popleft() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def share(self, pages) -> None:
        """Take one extra reference on each (live) page — a sequence or
        the prefix trie mapping an already-resident physical page."""
        for p in pages:
            p = int(p)
            if p == GARBAGE_PAGE:
                raise ValueError("page 0 is reserved and never shared")
            if p not in self._refs:
                raise ValueError(f"share of free page {p} — only live "
                                 f"(allocated) pages can gain references")
            self._refs[p] += 1

    def free(self, pages) -> None:
        """Drop one reference per page; a page recycles to the free
        list only when its last reference is dropped."""
        for p in pages:
            p = int(p)
            if p == GARBAGE_PAGE:
                raise ValueError("page 0 is reserved and never allocated")
            if not (0 < p < self.num_pages):
                raise ValueError(f"page id {p} outside pool "
                                 f"[1, {self.num_pages})")
            if p not in self._refs:
                raise ValueError(f"double free of page {p}")
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)


# ----------------------------------------------------------- device writes
def copy_page(pools, src: int, dst: int):
    """Copy-on-write seam: duplicate pool page ``src`` into ``dst``
    across every layer of both pools.

    ``src``/``dst`` are HOST ints handed out by :class:`PageAllocator`
    (``dst`` freshly allocated, refcount 1) — the scheduler calls this
    once, before the first divergent write to a shared (refcount > 1)
    page, then repoints the writing sequence's page table at ``dst``.
    Neither side may be the reserved garbage page.
    """
    src, dst = int(src), int(dst)
    num_pages = pools["k"].shape[1]
    for p in (src, dst):
        if not (GARBAGE_PAGE < p < num_pages):
            raise ValueError(
                f"copy_page({src}, {dst}): page {p} outside the "
                f"allocatable pool (1, {num_pages})")
    if src == dst:
        raise ValueError(f"copy_page: src == dst == {src}")
    return {"k": pools["k"].at[:, dst].set(pools["k"][:, src]),
            "v": pools["v"].at[:, dst].set(pools["v"][:, src])}


def write_decode_kv(k_pool, v_pool, k_new, v_new, page_tables, positions,
                    active):
    """Scatter one decode step's k/v into a layer's pools.

    ``k_pool``/``v_pool``: (num_pages, H_kv, page_size, D);
    ``k_new``/``v_new``: (B, H_kv, D) the current tokens' heads;
    ``page_tables``: (B, P) int32; ``positions``: (B,) the tokens'
    0-based positions; ``active``: (B,) bool — the WRITE mask (a
    multi-position verify/chunk caller may pass a narrower mask than
    slot liveness, e.g. to leave shared prefix pages untouched).
    Inactive rows write the garbage page; all page-table reads are
    clamped (APX107).
    """
    num_pages, page_size = k_pool.shape[0], k_pool.shape[2]
    P = page_tables.shape[1]
    page_ix = jnp.clip(positions // page_size, 0, P - 1)
    rows = jnp.take_along_axis(page_tables, page_ix[:, None], axis=1)[:, 0]
    dest = jnp.where(active, jnp.clip(rows, 0, num_pages - 1), GARBAGE_PAGE)
    slot = jnp.where(active, positions % page_size, 0)
    # (dest, slot) are split by the head slice, so the indexed view is
    # (B, H_kv, D) — k_new's own layout
    k_pool = k_pool.at[dest, :, slot].set(k_new.astype(k_pool.dtype))
    v_pool = v_pool.at[dest, :, slot].set(v_new.astype(v_pool.dtype))
    return k_pool, v_pool


def write_prompt_kv(k_pool, v_pool, k_stack, v_stack, page_table_row,
                    prompt_len, start=0):
    """Scatter a prefilled prompt's k/v into ALL layers' pools at once.

    ``k_pool``/``v_pool``: (L, num_pages, H_kv, page_size, D);
    ``k_stack``/``v_stack``: (L, S, H_kv, D) the training forward's
    per-layer post-RoPE keys/values for the (padded) prompt;
    ``page_table_row``: (P,) the sequence's page table;
    ``prompt_len``: scalar int32 — positions >= it (the pad tail)
    write the garbage page.  ``start``: scalar int32 — positions < it
    ALSO write the garbage page: the prefix-sharing window (those
    positions' k/v already live in shared pool pages, which must not be
    rewritten through this sequence's table).
    """
    num_pages, page_size = k_pool.shape[1], k_pool.shape[3]
    P = page_table_row.shape[0]
    S = k_stack.shape[1]
    s = jnp.arange(S, dtype=jnp.int32)
    page_ix = jnp.clip(s // page_size, 0, P - 1)
    rows = jnp.take(page_table_row, page_ix)
    valid = (s >= start) & (s < prompt_len)
    dest = jnp.where(valid, jnp.clip(rows, 0, num_pages - 1), GARBAGE_PAGE)
    slot = jnp.where(valid, s % page_size, 0)
    # advanced indices split by slices lead the indexed view: (S, L,
    # H_kv, D)
    k_pool = k_pool.at[:, dest, :, slot].set(
        jnp.moveaxis(k_stack, 1, 0).astype(k_pool.dtype))
    v_pool = v_pool.at[:, dest, :, slot].set(
        jnp.moveaxis(v_stack, 1, 0).astype(v_pool.dtype))
    return k_pool, v_pool
