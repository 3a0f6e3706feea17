"""Paged KV cache: fixed-size pages in a preallocated pool.

The serving-side memory manager (the vLLM PagedAttention layout,
recast for TPU static shapes): the cache for ALL resident sequences
lives in preallocated pools —
``(num_layers, num_pages, heads, dim, page_size)`` each — and WHICH
pools is the served model's to say (its *cache spec*, name ->
``(layers, heads, dim)``): GPT gives ``{"k", "v"}`` of ``kv_heads x
head_dim``, a latent-attention model ONE pool of one "head" whose
``dim`` is its compressed latent plus its shared rotary key.  The
allocator, the page tables, the garbage page, :func:`copy_page` and the
in-place writers serve any such set of named pools.  Every sequence
owns a *page table*: a fixed-width row of
page ids mapping its logical positions ``[p * page_size, (p+1) *
page_size)`` onto pool pages.  Sequences of wildly different lengths
pack the pool densely, admission/eviction recycles pages between
decode steps, and the decode step's SHAPES never change (the pool, the
(max_batch, pages_per_seq) page-table block, the per-slot scalars), so
it compiles exactly once.

**The pool's order, and why.**  Pages are stored head-dim-major: one
(page, kv head) is a contiguous ``(head_dim, page_size)`` tile with
the page's positions in the minor dimension.  That is the order in
which row-major IS the TPU's own tiled layout for the array — 128
positions fill the 128 lanes, where a 64-wide head dim would leave
half of them as padding and XLA would prefer ANOTHER layout for the
array than the row-major one a Pallas kernel reads.  The pool lives in
this one order for its whole life and nothing re-lays it out:

- the decode-attention kernel reads a ``(head_dim, page_size)`` tile
  of the stacked pool at ``(layer, page, kv head)`` from its block
  index map (the layer and the page table are scalar-prefetched);
- every program that writes the pool — the decode step, the verify
  step, a prefill chunk, the prefill — writes through ONE aliased
  Pallas call (:mod:`apex_tpu.ops.kv_write_pallas`): a page tile is
  read, the written columns replaced, the tile written back;
- the pools are the decode step's layer-loop CARRY.  No XLA op inside
  a step produces a pool-sized value: an XLA scatter, slice or
  ``dynamic_update_slice`` on the pool invites layout assignment to
  copy the whole pool to the layout that op prefers and back (PERF.md,
  PR 25: 57 ms of a 106 ms decode step, and the pool held twice).
  tests/test_tpu_bringup.py compiles both programs for a v5e and pins
  that.

The plain-XLA read and write on the same order (``impl="xla"``) stay
as the CPU path and the numerics specification.

Storage dtype is configurable (bf16 default — halves the pool bytes;
the attention kernels widen the page reads at the seam, the APX306
contract).

Page id 0 is the **garbage page**: :class:`PageAllocator` never hands
it out, and every masked write (inactive slot, padded prompt tail) is
routed there instead of being predicated out — the write stays a
dense static-shape op and can never corrupt a live sequence's page.
Every page-table read is clamped into the pool (the APX107 contract:
a stale or corrupt table entry reads/writes garbage, never wraps).

**A second kind of cache entry: per-slot state.**  A recurrent layer
(a linear-attention state, a short convolution's tail) keeps a FIXED
amount a sequence, whatever its length: no pages, no page table, one
row a decode slot.  A cache spec names such an entry with a
:class:`PerSlot` in place of the paged ``(layers, heads, dim)`` tuple,
and :func:`alloc_named_pools` makes it ``(layers, slots + 1) + shape``:
row ``slots`` is the **garbage row**, the page-0 of this kind — the
destination of every masked write, never read.  The same rule holds
for it as for a pool: it is the layer loop's carry, written in place
through aliased kernels (:mod:`apex_tpu.ops.kda`), and no XLA op in a
step produces a value of its size.  A prefill hands back a slot's
final values and :func:`apex_tpu.ops.kda.install_rows` puts them into
the slot's rows; nothing of a predecessor survives, and nothing can be shared
between sequences (the scheduler refuses ``prefix_sharing`` for a model
with such an entry: pages can be shared, a recurrence cannot).

**A third kind: a pool addressed in chunks, with an exact window a
slot.**  An attention that keeps the last ``window`` positions exactly
and ONE pooled column for every ``stride`` positions before them (a
:class:`Windowed` entry) caches neither one column a token nor a fixed
amount.  Its pool has two regions of the SAME dim-major page tiles:

- pages ``[1, num_pages)`` are handed out by the allocator as ever, but
  a column stands for ``stride`` positions, so a page covers
  ``page_size * stride`` of them — exactly one window (the spec is
  refused otherwise) — and admission reserves ``ceil(positions /
  window)`` pages (:func:`page_positions`);
- pages ``num_pages + slot * window_pages + j`` are decode slot
  ``slot``'s **window buffer**: ``window_pages = window // page_size``
  lane-tile pages that the allocator never sees, column ``position mod
  window``.  It starts again from empty every ``window`` positions: a
  sequence's live length in it falls to zero and nothing is copied or
  cleared.

A query's cache is then ONE page list (:func:`windowed_view`): the
pages of the windows that have closed, every column of them, and after
them the slot's window pages up to its live column.  The walk kernel
reads it as any other table, one softmax over both, and never fetches a
dead window page; a reused slot's old columns lie past the length.  A
prefill puts the prompt's pooled columns into its pages and the columns
of its last, open window into the slot's buffer
(:func:`write_prompt_windowed`).  Nothing of a window buffer can be
shared between sequences, so the scheduler refuses ``prefix_sharing``.

Device-side helpers here are pure functions on the pool arrays (jit
inside the decode/prefill steps); the allocator and page tables are
host-side bookkeeping owned by the scheduler.
"""

import dataclasses
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax.numpy as jnp
from jax import lax

__all__ = [
    "COUNTERS", "GARBAGE_PAGE", "KVCacheConfig", "PageAllocator", "PerSlot",
    "Windowed", "alloc_named_pools", "alloc_pools", "copy_page",
    "named_pools", "open_window", "page_positions", "pages_needed", "per_slot_names",
    "windowed_entry", "windowed_view", "write_block_pools", "write_decode_kv",
    "write_decode_pools", "write_prompt_kv", "write_prompt_pools",
    "write_prompt_windowed",
]

#: page id 0 — reserved, never allocated; the destination of every
#: masked (inactive / padded) cache write
GARBAGE_PAGE = 0

#: the one key of a step's carried cache state that is no pool: a small
#: int32 vector of device-side counters that a served model accumulates
#: in its decode step (read back once, after a window, by
#: ``ContinuousBatchingScheduler.read_counters``)
COUNTERS = "counters"


class PerSlot(NamedTuple):
    """A cache spec's entry for per-slot state (module doc): ``layers``
    rows of ``shape`` a decode slot, in ``dtype`` (its own, not the
    paged pools' storage dtype: a recurrent state is float32 beside a
    bf16 cache)."""

    layers: int
    shape: Tuple[int, ...]
    dtype: Any


class Windowed(NamedTuple):
    """A cache spec's entry for a pool addressed in chunks with an exact
    window a slot (module doc): ``layers`` pools of ``heads x dim``
    pages; an allocated page's column stands for ``stride`` positions,
    and every decode slot keeps the last ``window`` positions' own
    columns in ``window // page_size`` pages of its own."""

    layers: int
    heads: int
    dim: int
    stride: int
    window: int


def windowed_entry(spec, cfg: "KVCacheConfig" = None) -> Optional[Windowed]:
    """The :class:`Windowed` entry that a cache spec's paged pools are
    (all of them one, since they share a page table), or None for a
    spec of plain pools.  With ``cfg``, checked against the page size:
    a page of columns must cover exactly one window."""
    found = {e for e in spec.values() if isinstance(e, Windowed)}
    if not found:
        return None
    plain = [n for n, e in spec.items()
             if not isinstance(e, (Windowed, PerSlot))]
    if len({(e.stride, e.window) for e in found}) > 1 or plain:
        raise ValueError(
            f"a cache spec's paged pools share one page table: they are "
            f"all windowed alike or none is ({spec})")
    e = next(iter(found))
    if cfg is not None and cfg.page_size * e.stride != e.window:
        raise ValueError(
            f"a windowed cache needs page_size x stride == window (a "
            f"page of pooled columns is one window): page_size "
            f"{cfg.page_size}, stride {e.stride}, window {e.window}")
    return e


def page_positions(spec, cfg: "KVCacheConfig") -> int:
    """Positions of a sequence that one allocated page covers: the page
    size, times the stride where a column stands for a chunk."""
    e = windowed_entry(spec, cfg)
    return cfg.page_size * (e.stride if e is not None else 1)


def per_slot_names(spec) -> List[str]:
    """The names of a cache spec's per-slot entries, sorted."""
    return sorted(n for n, e in spec.items() if isinstance(e, PerSlot))


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    """Static shape of the pool (all fields bake into the compiled
    steps).

    ``num_pages`` includes the reserved garbage page, so the usable
    capacity is ``num_pages - 1`` pages.  ``pages_per_seq`` is the
    page-table width: the longest supportable sequence is
    ``pages_per_seq * page_size`` positions.

    On the chip ``page_size`` is the pool's minor (lane) dimension: a
    multiple of 128 stores the pool with no padding; any other size is
    correct and pads every page to the next multiple of 128 lanes (a
    16-position page holds 8x its bytes).  ``head_dim`` rides the
    sublanes, where multiples of 8 (fp32) or 16 (bf16) are whole.
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


def alloc_named_pools(spec, cfg: KVCacheConfig,
                      slots: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    """Zero-initialized cache state from a served model's cache spec.
    A paged entry, name -> ``(layers, heads, dim)``, is a pool
    ``(layers, num_pages, heads, dim, page_size)`` in the storage dtype
    (dim-major pages: module doc); a :class:`Windowed` entry the same
    with ``slots * window // page_size`` more pages, the slots' window
    buffers, after the allocator's; a :class:`PerSlot` entry is
    ``(layers, slots + 1) + shape`` in its own dtype (``slots``: the
    decode slots, ``DecodeConfig.max_batch``).  Donated through the
    decode/prefill jits — updated in place across the whole serve
    loop."""
    out = {}
    for name, entry in spec.items():
        if isinstance(entry, PerSlot):
            if slots is None:
                raise ValueError(f"cache entry {name!r} is per-slot state: "
                                 f"alloc_named_pools needs slots")
            out[name] = jnp.zeros((int(entry.layers), int(slots) + 1)
                                  + tuple(entry.shape), entry.dtype)
        elif isinstance(entry, Windowed):
            if slots is None:
                raise ValueError(f"cache entry {name!r} keeps a window a "
                                 f"slot: alloc_named_pools needs slots")
            windowed_entry(spec, cfg)
            pages = cfg.num_pages + int(slots) * (entry.window
                                                  // cfg.page_size)
            out[name] = jnp.zeros((int(entry.layers), pages,
                                   int(entry.heads), int(entry.dim),
                                   cfg.page_size), cfg.dtype)
        else:
            layers, heads, dim = entry
            out[name] = jnp.zeros((int(layers), cfg.num_pages, int(heads),
                                   int(dim), cfg.page_size), cfg.dtype)
    return out


def alloc_pools(num_layers: int, kv_heads: int, head_dim: int,
                cfg: KVCacheConfig) -> Dict[str, jnp.ndarray]:
    """The k/v pools of a GPT-style cache:
    ``(L, num_pages, kv_heads, head_dim, page_size)`` each."""
    shape = (num_layers, kv_heads, head_dim)
    return alloc_named_pools({"k": shape, "v": shape}, cfg)


def named_pools(pools) -> Dict[str, jnp.ndarray]:
    """The pools of a step's carried cache state, without its
    :data:`COUNTERS` entry."""
    return {k: v for k, v in pools.items() if k != COUNTERS}


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
    across every layer of every named pool (every entry must be a paged
    pool: a model with per-slot state shares no prefix).

    ``src``/``dst`` are HOST ints handed out by :class:`PageAllocator`
    (``dst`` freshly allocated, refcount 1) — the scheduler calls this
    once, before the first divergent write to a shared (refcount > 1)
    page, then repoints the writing sequence's page table at ``dst``.
    Neither side may be the reserved garbage page.

    A program of its own, outside the steps, and plain XLA: it may copy
    the pool to do it (the steps may not — module doc).
    """
    src, dst = int(src), int(dst)
    num_pages = next(iter(named_pools(pools).values())).shape[1]
    for p in (src, dst):
        if not (GARBAGE_PAGE < p < num_pages):
            raise ValueError(
                f"copy_page({src}, {dst}): page {p} outside the "
                f"allocatable pool (1, {num_pages})")
    if src == dst:
        raise ValueError(f"copy_page: src == dst == {src}")
    return {name: (p if name == COUNTERS
                   else p.at[:, dst].set(p[:, src]))
            for name, p in pools.items()}


def _write(impl, k_new, k_pool, kernel_impl, xla_impl):
    """The one dispatch between the in-place kernel
    (``apex_kv_write``) and the plain-XLA write.  ``impl`` is the
    step's ``attn_impl``, so the write follows the attention kernel
    (same availability test, same forcing); a chosen kernel degrades
    once to ``xla_impl`` through the fallback registry ("kv_write")."""
    from apex_tpu.ops.decode_attention_pallas import dispatch_pool_kernel

    return dispatch_pool_kernel("kv_write", impl, k_new, k_pool,
                                kernel_impl, xla_impl)


def _row_targets(page_tables, positions, mask, page_size, num_pages):
    """Per written ROW: its pool page (every table read clamped,
    APX107; masked rows and positions outside the table go to the
    garbage page) and its slot in the page."""
    P = page_tables.shape[-1]
    page_ix = positions // page_size
    mask = mask & (page_ix >= 0) & (page_ix < P)
    rows = jnp.take_along_axis(
        page_tables, jnp.clip(page_ix, 0, P - 1)[:, None], axis=1)[:, 0]
    dest = jnp.where(mask, jnp.clip(rows, 0, num_pages - 1), GARBAGE_PAGE)
    slot = jnp.where(mask, positions % page_size, 0)
    return dest, slot


def _tile_targets(table_rows, page_ix, live, num_pages):
    """Per written TILE (``page_ix``: its logical page in its
    sequence's table row, ``live``: (..., page_size) the columns it
    writes): the pool page — clamped; a tile outside the table or with
    no live column goes to the garbage page — and the live columns."""
    P = table_rows.shape[-1]
    live = live & ((page_ix >= 0) & (page_ix < P))[..., None]
    rows = jnp.take_along_axis(table_rows, jnp.clip(page_ix, 0, P - 1),
                               axis=-1)
    dest = jnp.where(jnp.any(live, axis=-1),
                     jnp.clip(rows, 0, num_pages - 1), GARBAGE_PAGE)
    return dest, live


def write_decode_pools(pools, news, page_tables, positions, active,
                       layer=None, width=1, impl="auto"):
    """Write a decode step's new columns into ONE layer of the pools,
    in place.

    ``pools``: a tuple of pools of one shape (a cache's named pools in
    a fixed order): the stacked (L, num_pages, H_kv, D, page_size)
    pools with ``layer`` the (traced) layer index — what the decode
    step's layer loop carries — or one layer's 4-D pools
    (``layer=None``: the ``layer=0`` case of a leading-1 view).
    ``news``: one (B, H_kv, D) array a pool, the current tokens' heads;
    ``page_tables``: (B // width, P) int32; ``positions``: (B,) the
    tokens' 0-based positions; ``active``: (B,) bool — the WRITE mask
    (a multi-position verify/chunk caller may pass a narrower mask than
    slot liveness, e.g. to leave shared prefix pages untouched).
    Inactive rows write the garbage page or nothing; all page-table
    reads are clamped (APX107).

    ``width`` W > 1: rows come in groups of W CONSECUTIVE positions of
    one sequence sharing a table row (``positions[s * W + w] ==
    positions[s * W] + w``).  The kernel path places a group's rows at
    their columns of the (at most ``ceil((W - 1) / page_size) + 1``)
    page tiles they touch and writes each tile in ONE grid step.

    ``impl``: "auto" | "pallas" | "interpret" | "xla" (the step's
    ``attn_impl``).  "xla" is a plain scatter: correct everywhere, the
    CPU path and the registry's degrade path — and slow on the chip,
    where an XLA write of the pool makes layout assignment re-lay out
    the whole pool around it (module doc).  Returns the pools, as a
    tuple in the order given.
    """
    from apex_tpu.ops.decode_attention_pallas import stacked_pools

    one_layer = pools[0].ndim == 4
    pools, layer = stacked_pools(tuple(pools), layer)
    news = tuple(news)
    _, num_pages, h_kv, D, page_size = pools[0].shape
    B = news[0].shape[0]
    S, P = page_tables.shape
    if S * width != B:
        raise ValueError(
            f"rows ({B}) must equal page-table rows ({S}) x width "
            f"({width})")
    positions = positions.astype(jnp.int32)

    def xla_impl():
        tables = (jnp.repeat(page_tables, width, axis=0) if width > 1
                  else page_tables)
        dest, slot = _row_targets(tables, positions, active, page_size,
                                  num_pages)
        # advanced indices split by the (head, dim) slices lead the
        # indexed view: (B, H_kv, D) — the new rows' own layout
        return tuple(p.at[layer, dest, :, :, slot].set(x.astype(p.dtype))
                     for p, x in zip(pools, news))

    def kernel_impl():
        from apex_tpu.ops.kv_write_pallas import pool_write_pallas

        lane = jnp.arange(page_size, dtype=jnp.int32)
        if width == 1:
            # one tile a slot; the source is the new column, which
            # every masked lane (only ``slot``) takes
            dest, slot = _row_targets(page_tables, positions, active,
                                      page_size, num_pages)
            live = lane[None, :] == slot[:, None]

            def tiles(x):
                return x[None, :, None]
        else:
            n_t = (width + page_size - 2) // page_size + 1
            first = positions.reshape(S, width)[:, 0]
            page_ix = (first // page_size)[:, None] \
                + jnp.arange(n_t, dtype=jnp.int32)[None, :]      # (S, n_t)
            # column c of tile j holds the group's row w
            w = page_ix[:, :, None] * page_size + lane[None, None, :] \
                - first[:, None, None]                           # (S, n_t, p)
            wc = jnp.clip(w, 0, width - 1).reshape(S, n_t * page_size)
            live = ((w >= 0) & (w < width)) & jnp.take_along_axis(
                active.reshape(S, width), wc, axis=1,
                mode="clip").reshape(w.shape)
            dest, live = _tile_targets(page_tables, page_ix, live, num_pages)
            dest = dest.reshape(S * n_t)
            live = live.reshape(S * n_t, page_size)

            def tiles(x):
                x = jnp.take_along_axis(x.reshape(S, width, h_kv, D),
                                        wc[:, :, None, None], axis=1,
                                        mode="clip")
                return x.reshape(1, S * n_t, page_size, h_kv, D)

        return pool_write_pallas(
            pools, [tiles(x) for x in news], dest, live, layer,
            interpret=(impl == "interpret"))

    pools = _write(impl, news[0], pools[0], kernel_impl, xla_impl)
    if one_layer:
        return tuple(p[0] for p in pools)
    return tuple(pools)


def write_decode_kv(k_pool, v_pool, k_new, v_new, page_tables, positions,
                    active, layer=None, width=1, impl="auto"):
    """:func:`write_decode_pools` for a cache of two pools, ``k`` and
    ``v``.  Returns the two pools."""
    return write_decode_pools(
        (k_pool, v_pool), (k_new, v_new), page_tables, positions, active,
        layer=layer, width=width, impl=impl)


def write_prompt_pools(pools, stacks, page_table_row, prompt_len, start=0,
                       impl="auto"):
    """Write a prefilled prompt's columns into ALL layers' pools, in
    place.

    ``pools``: a tuple of (L, num_pages, H_kv, D, page_size) pools of
    one shape; ``stacks``: one (L, S, H_kv, D) array a pool — the
    forward's per-layer values for the (padded) prompt, as they are
    cached (a GPT's post-RoPE keys and its values; a latent model's
    normed latent with its rotary key); ``page_table_row``: (P,) the
    sequence's page table; ``prompt_len``: scalar int32 — positions >=
    it (the pad tail) are not written (a tile of nothing but pad goes
    to the garbage page).  ``start``: scalar int32 — positions < it are
    NOT written either: the prefix-sharing window (those positions
    already live in shared pool pages, which must not be rewritten
    through this sequence's table).

    The kernel path transposes the prompt's stacks into ``ceil(S /
    page_size)`` page tiles a layer in XLA (the prompt's size, not the
    pool's) and ``[start, prompt_len)`` becomes each tile's lane mask.
    ``impl`` as :func:`write_decode_pools`.  Returns the pools, as a
    tuple in the order given.
    """
    pools, stacks = tuple(pools), tuple(stacks)
    L, num_pages, h_kv, D, page_size = pools[0].shape
    S = stacks[0].shape[1]
    s = jnp.arange(S, dtype=jnp.int32)

    def xla_impl():
        dest, slot = _row_targets(
            jnp.broadcast_to(page_table_row[None],
                             (S,) + page_table_row.shape),
            s, (s >= start) & (s < prompt_len), page_size, num_pages)
        # advanced indices split by slices lead the indexed view:
        # (S, L, H_kv, D)
        return tuple(p.at[:, dest, :, :, slot].set(
                         jnp.moveaxis(x, 1, 0).astype(p.dtype))
                     for p, x in zip(pools, stacks))

    def kernel_impl():
        from apex_tpu.ops.kv_write_pallas import pool_write_pallas

        n_t = pages_needed(S, page_size)
        col = jnp.arange(n_t * page_size, dtype=jnp.int32) \
            .reshape(n_t, page_size)
        dest, live = _tile_targets(
            page_table_row, jnp.arange(n_t, dtype=jnp.int32),
            (col >= start) & (col < prompt_len) & (col < S), num_pages)

        def tiles(x):
            x = jnp.pad(x, ((0, 0), (0, n_t * page_size - S), (0, 0),
                            (0, 0)))
            return x.reshape(L, n_t, page_size, h_kv, D)

        return pool_write_pallas(
            pools, [tiles(x) for x in stacks], dest, live, 0,
            interpret=(impl == "interpret"))

    return tuple(_write(impl, stacks[0], pools[0], kernel_impl, xla_impl))


def write_prompt_kv(k_pool, v_pool, k_stack, v_stack, page_table_row,
                    prompt_len, start=0, impl="auto"):
    """:func:`write_prompt_pools` for a cache of two pools, ``k`` and
    ``v``.  Returns the two pools."""
    return write_prompt_pools(
        (k_pool, v_pool), (k_stack, v_stack), page_table_row, prompt_len,
        start=start, impl=impl)


# ------------------------------------------------------- windowed entries
def windowed_view(page_tables, positions, active, rows, entry: Windowed,
                  page_size: int, num_pages: int):
    """What a :class:`Windowed` cache is to a decode step's rows, as ONE
    page list a row (module doc).

    ``page_tables``: (B, P) the allocator's pages, entry ``w`` the
    pooled columns of window ``w``; ``positions``: (B,) the current
    tokens' positions; ``active``: (B,) bool; ``rows``: (B,) the decode
    slot of each row (its window buffer); ``num_pages``: the
    ALLOCATOR's page count (``KVCacheConfig.num_pages``), after which
    the window buffers lie.  Returns ``(tables, columns, lengths)``:
    ``tables`` (B, P + window_pages) — the pages of the closed windows
    ``0 .. w - 1``, then the slot's window pages; ``columns`` (B,) where
    the current token's own column goes in that list (``w * page_size +
    position mod window``); ``lengths`` (B,) ``columns + 1``, 0 for an
    inactive row.  All int32, a few hundred values: nothing pool-sized.
    """
    wp = entry.window // page_size
    B, P = page_tables.shape
    positions = positions.astype(jnp.int32)
    w = positions // entry.window
    j = jnp.arange(P + wp, dtype=jnp.int32)[None, :]
    closed = jnp.take_along_axis(page_tables, jnp.clip(j, 0, P - 1), axis=1)
    own = num_pages + rows.astype(jnp.int32)[:, None] * wp \
        + jnp.clip(j - w[:, None], 0, wp - 1)
    tables = jnp.where(j < w[:, None], closed,
                       jnp.where(j < w[:, None] + wp, own, GARBAGE_PAGE))
    columns = w * page_size + positions % entry.window
    return tables.astype(jnp.int32), columns, \
        jnp.where(active, columns + 1, 0).astype(jnp.int32)


def open_window(prompt_len, padded: int, entry: Windowed):
    """Of a prompt of ``prompt_len`` positions padded to ``padded``
    (whole windows): ``(first, live)``, where its last, open window
    starts (so that a slice of one window from ``first`` stays inside
    the padded prompt) and how many of that slice's columns are the
    prompt's.  A prompt that ends on a window's edge leaves the window
    empty: the slice then starts a window early and none of it is live.
    """
    start = prompt_len // entry.window * entry.window
    return jnp.minimum(start, padded - entry.window), prompt_len - start


def write_prompt_windowed(pools, pooled, own, page_table_row, prompt_len,
                          slot, entry: Windowed, num_pages: int,
                          impl="auto"):
    """A prefilled prompt into a :class:`Windowed` cache, in place.

    ``pools``: a tuple of pools of one shape; ``pooled``: one (L, S //
    stride, heads, dim) array a pool, the prompt's pooled columns, one a
    chunk; ``own``: one (L, window, heads, dim) array a pool, the own
    columns of the prompt's last, open window (the slice
    :func:`open_window` names).  The pooled columns of the ``prompt_len
    // stride`` WHOLE chunks go to the sequence's pages
    (``page_table_row``), the open window's live columns to decode slot
    ``slot``'s window buffer; a padded position writes neither.
    Returns the pools, as a tuple in the order given."""
    pools = tuple(pools)
    wp = entry.window // pools[0].shape[-1]
    pools = write_prompt_pools(pools, pooled, page_table_row,
                               prompt_len // entry.stride, impl=impl)
    row = num_pages + slot.astype(jnp.int32) * wp \
        + jnp.arange(wp, dtype=jnp.int32)
    return write_prompt_pools(pools, own, row,
                              prompt_len % entry.window, impl=impl)


def write_block_pools(pools, news, page_tables, positions, active,
                      width: int, layer=None, impl="auto"):
    """Write a BLOCK of ``width`` consecutive columns a slot, or TWO
    blocks side by side, into ONE layer of the pools, in place: what a
    block-generating step does every pass.  It REWRITES its open
    block's columns (a denoising pass's keys and values are overwritten
    by the next pass's) and, beside them, stores those of the HELD
    block, the clean block before it, which are the ones that stay
    (``inference.decode.make_block_step``).

    As :func:`write_decode_pools` with ``width``, for blocks that start
    on a multiple of ``width``, ``width`` a divisor of the page, so that
    a block lies in one page.  ``active``: (B,) bool, one block a slot
    from ``positions``, or **(B, n)**: ``n`` consecutive blocks a slot
    from ``positions`` (the block step: ``n = 2``, the held block at
    ``positions``, live iff ``active[:, 0]``, the open one at
    ``positions + width``; ``positions`` may be ``-width`` where a slot
    has no block before its first, which is then dead).  ``news``: one
    (B * n * width, H_kv, D) array a pool, a slot's rows consecutive;
    ``page_tables``: (B, P).

    The kernel path keeps ``apex_kv_write``'s rule, one grid step a
    tile and never two: a slot's blocks lie in ONE page unless the last
    of them starts a page, so the slot hands the kernel the page of its
    LAST column with the columns of every block that lies in it
    (``pool_write_block_pallas``: a source of ``n * width`` columns, a
    lane naming the column it takes), and for ``n > 1`` the page of its
    FIRST column as a second tile only where that is another page, else
    the garbage page (a dead step).  A dead block writes nothing.
    Returns the pools, as a tuple in the order given."""
    from apex_tpu.ops.decode_attention_pallas import stacked_pools

    one_layer = pools[0].ndim == 4
    pools, layer = stacked_pools(tuple(pools), layer)
    news = tuple(news)
    _, num_pages, h_kv, D, page_size = pools[0].shape
    B = page_tables.shape[0]
    blocks = active.reshape(B, -1)
    C = blocks.shape[1] * width
    if news[0].shape[0] != B * C or page_size % width \
            or C > page_size + width:
        raise ValueError(
            f"rows ({news[0].shape[0]}) must equal page-table rows ({B}) x "
            f"blocks ({blocks.shape[1]}) x width ({width}), width divide "
            f"the page ({page_size}) and a slot's blocks lie in two pages "
            f"at most")
    positions = positions.astype(jnp.int32)
    column = jnp.arange(C, dtype=jnp.int32)
    live = jnp.repeat(blocks, width, axis=1)                    # (B, C)

    def xla_impl():
        return write_decode_pools(
            pools, news, page_tables,
            (positions[:, None] + column[None]).reshape(-1),
            live.reshape(-1), layer=layer, width=C, impl="xla")

    def kernel_impl():
        from apex_tpu.ops.kv_write_pallas import pool_write_block_pallas

        # tile 0: the page of the slot's last column; tile 1 (two blocks
        # or more): the page of its first, where that is another
        pages = (positions + C - 1)[:, None] // page_size
        if C > width:
            pages = jnp.concatenate(
                [pages, positions[:, None] // page_size], axis=1)
        tiles = pages.shape[1]
        first = positions[:, None] - pages * page_size   # column 0's lane
        lane = first[:, :, None] + column[None, None]    # (B, tiles, C)
        writes = live[:, None] & (lane >= 0) & (lane < page_size)
        if tiles > 1:
            writes = writes.at[:, 1].set(
                writes[:, 1] & (pages[:, 1] != pages[:, 0])[:, None])
        dest, writes = _tile_targets(page_tables, pages, writes, num_pages)
        # every slot's tile 0, then every slot's tile 1: the dead second
        # tiles, all at the garbage page, follow one another, and a
        # block whose index does not change is neither fetched nor
        # written back
        by_tile = lambda a: jnp.swapaxes(a, 0, 1).reshape(
            (B * tiles,) + a.shape[2:])
        return pool_write_block_pallas(
            pools, [jnp.tile(x.reshape(1, B, C, h_kv, D),
                             (1, tiles, 1, 1, 1)) for x in news],
            by_tile(dest), by_tile(first), by_tile(writes), layer,
            interpret=(impl == "interpret"))

    pools = _write(impl, news[0], pools[0], kernel_impl, xla_impl)
    if one_layer:
        return tuple(p[0] for p in pools)
    return tuple(pools)
