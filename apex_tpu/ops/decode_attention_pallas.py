"""Pallas TPU paged single-query decode attention.

The decode-side sibling of :mod:`apex_tpu.ops.flash_attention_pallas`:
one generated token per sequence attends over that sequence's KV cache,
which lives as fixed-size *pages* scattered through a preallocated pool
(:mod:`apex_tpu.inference.kv_cache`).  Small-batch decode is dominated
by the softmax reductions and per-op launch overheads around a tiny
matmul (PAPERS.md: "LLM Inference Acceleration via Efficient Operation
Fusion", arxiv 2502.17728), so the whole per-head attention — page
gather, scores, online softmax, weighted sum — runs as ONE kernel:

- **the unit of work is a live page of a sequence row with ALL of its
  kv heads** (or as many as :func:`_plan` fits into VMEM).  The pool is
  ``(L, num_pages, H_kv, D, page_size)``, so a page's heads are
  contiguous and an ``(h_blk, D, page_size)`` block is one DMA — 20
  heads of GPT-2 large are 320 KB of k and 320 KB of v, where one
  ``(D, page_size)`` tile a grid step paid the grid's and the DMA's
  overheads 3,200 times a layer (PERF.md, PR 27);
- the page table, the lengths AND the layer ride as **scalar-prefetch**
  operands (``pltpu.PrefetchScalarGridSpec``): a block is addressed as
  ``(layer, page_table[b, p], head block)`` in the STACKED pool —
  neither one layer's pool nor the gathered (B, S_max, H_kv, D) key
  tensor the XLA reference materializes in HBM ever exists here;
- **the walk is bounded by the row's length**, in one of two forms that
  the page size picks (a shape, not an option):

  * a page of whole lane tiles (``page_size % 128 == 0``; the
    benchmark's cells): grid ``(rows, kv_heads // h_blk)``, the pools
    stay in HBM (``memory_space=ANY``) and :func:`_walk_kernel` copies
    the ``ceil(length / page_size)`` live pages itself, through a ring
    of ``depth`` VMEM slots (:func:`_plan`: 3 at every cell's shapes):
    the copies run ``depth - 1`` live pages AHEAD of the page that is
    attended, across rows and head blocks, because a page's arithmetic
    takes 0.45 µs where its copy lands 0.75 µs after it is asked for
    (PERF.md, PR 50).  A slot without a live position costs one grid
    step and no copy;
  * a smaller page (lanes padded; Mosaic cannot slice such a page out
    of HBM by hand): grid ``(rows, kv_heads // h_blk, pages_per_seq)``
    with a BlockSpec whose index past the last live page names that
    page again (:func:`_kv_block_index`) — an unchanged block is not
    fetched and ``pl.when`` skips the arithmetic, at about 0.2 µs a
    dead grid step.

  Either way a whole page past a length is never read; the tail of the
  last live page is masked per position;
- pages are stored head-dim-major (:mod:`apex_tpu.inference.kv_cache`):
  the page's positions sit in the lanes, which is the device's own
  layout for the pool, so no consumer re-lays it out;
- grouped-query attention reads the group-shared kv page ONCE per kv
  head and scores all ``H // H_kv`` q heads of the group against it on
  the MXU, batched over the block's heads (no ``repeat_kv_heads``
  materialization, same as the flash kernels).  One query head a kv
  head (GPT-2) makes each product a one-row pass; measured on the
  chip, the batched passes still beat the same sums on the VPU, and a
  full table of pages streams at 80% of HBM bandwidth (PERF.md, PR 27).

The XLA reference :func:`decode_attention_xla` is the numerics
specification: it mirrors the TRAINING attention expression
(scores / sqrt(D), ``-10000.0`` mask fill, fp32 softmax — the
``scaled_upper_triang_masked_softmax`` semantics) exactly, so
token-by-token decode logits can be pinned against the full-sequence
training forward bitwise in fp32 (tests/test_inference.py).  Kernel
failures degrade to it once through
:mod:`apex_tpu.resilience.fallback` ("decode_attention").
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._pallas_tiling import LANES as _LANES, VMEM_BUDGET
from apex_tpu.transformer.functional.fused_softmax import MASK_FILL_VALUE

NEG_INF = -1e30

# ---------------------------------------------------------------- reference
def stacked_pools(pools, layer):
    """The pools (a tuple of one shape) as stacked 5-D arrays, with
    their layer.

    One layer's (num_pages, H_kv, D, page_size) pools are the case
    ``layer=0`` of a leading-1 view."""
    if pools[0].ndim == 4:
        if layer is not None:
            raise ValueError("layer given with a one-layer (4-D) pool")
        return tuple(p[None] for p in pools), 0
    if layer is None:
        raise ValueError("a stacked (5-D) pool needs its layer")
    return tuple(pools), layer


def as_stacked_pools(k_pool, v_pool, layer):
    """:func:`stacked_pools` for the two pools of a k/v cache."""
    (k_pool, v_pool), layer = stacked_pools((k_pool, v_pool), layer)
    return k_pool, v_pool, layer


def _lengths_per_row(lengths, group, width) -> int:
    """How many lengths a sequence row brings: 1 (``lengths`` (B,)) or
    2 ((B, 2): a length a half of the group)."""
    if lengths.ndim == 1:
        return 1
    if lengths.ndim != 2 or lengths.shape[1] != 2 or width != 1 \
            or group % 2:
        raise ValueError(
            f"lengths {lengths.shape} must be (B,) or, for an even group "
            f"({group}) and width 1 ({width}), (B, 2)")
    return 2


def decode_attention_xla(q, k_pool, v_pool, page_table, lengths,
                         softmax_scale=None, width=1, layer=None):
    """Single-query attention over a paged KV cache, in XLA.

    Correct everywhere; on the chip it is SLOW, and not only for the
    gather: an XLA read of the pool inside a step makes layout
    assignment copy the pool into the layout the gather prefers, once
    a layer (PERF.md, PR 25) — the kernel exists so that nothing but
    an aliased Pallas call ever touches the pool.

    ``q``: (B, H, D) — one query per sequence (the current token's
    heads).  ``k_pool``/``v_pool``: the stacked pool (L, num_pages,
    H_kv, D, page_size) with ``layer`` a (traced) scalar, or one
    layer's (num_pages, H_kv, D, page_size) — head-dim-major pages:
    each (page, kv head) is one contiguous (D, page_size) tile, and a
    page's heads one contiguous block, what the kernel DMAs.
    ``page_table``: (B, P) int32 page ids,
    CLAMPED into the pool before the gather (a stale/garbage entry
    reads the reserved garbage page instead of wrapping).  ``lengths``:
    (B,) int32 valid cache positions per sequence (0 = inactive slot —
    every position masks out and the output row is 0); or (B, 2): a
    length a HALF of each kv head's group of query heads (the first
    ``H // H_kv // 2`` of them the first length: two blocks of one
    sequence folded into the group, :func:`block_decode_attention`).

    ``width`` > 1 is the verify/chunk layout: q rows come in groups of
    ``width`` CONSECUTIVE positions of one sequence (speculative
    verification, a prefill chunk), so ``q``/``lengths`` are
    (B * width, ...) while ``page_table`` stays (B, P) — the pool pages
    are gathered ONCE per sequence and scored against all of its
    ``width`` queries, each under its own length mask.

    Returns (B, H, D) in ``v_pool``'s dtype.  The expression mirrors
    the training attention row-for-row (division by sqrt(D), -1e4 mask
    fill, fp32 softmax, probs cast to v's dtype before the weighted
    sum) so decode logits can be compared bitwise against the training
    forward in fp32.
    """
    k_pool, v_pool, layer = as_stacked_pools(k_pool, v_pool, layer)
    Bq, H, D = q.shape
    _, num_pages, h_kv, _, page_size = k_pool.shape
    B, P = page_table.shape
    group = H // h_kv
    if B * width != Bq:
        raise ValueError(
            f"q rows ({Bq}) must equal page-table rows ({B}) x width "
            f"({width})")
    _lengths_per_row(lengths, group, width)
    pt = jnp.clip(page_table, 0, num_pages - 1)
    # (B, P, H_kv, D, page) -> (B, H_kv, S_max, D)
    k = k_pool[layer, pt].transpose(0, 2, 1, 4, 3) \
        .reshape(B, h_kv, P * page_size, D)
    v = v_pool[layer, pt].transpose(0, 2, 1, 4, 3) \
        .reshape(B, h_kv, P * page_size, D)
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    # the storage dtype may be narrower than the scores' f32: widen the
    # cache reads explicitly at the seam (the APX306 contract)
    kf = k.astype(jnp.float32)
    t = jnp.arange(P * page_size, dtype=jnp.int32)
    if width > 1:
        qf = q.astype(jnp.float32).reshape(B, width, H, D)
        if softmax_scale is None:
            scores = jnp.einsum("bwhd,bhtd->bwht", qf, kf) / np.sqrt(D)
        else:
            scores = jnp.einsum("bwhd,bhtd->bwht", qf, kf) * softmax_scale
        lw = lengths.reshape(B, width)
        valid = t[None, None, None, :] < lw[:, :, None, None]
        scores = jnp.where(valid, scores, MASK_FILL_VALUE)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bwht,bhtd->bwhd", probs.astype(v.dtype), v)
        ctx = jnp.where(lw[:, :, None, None] > 0, ctx,
                        jnp.zeros_like(ctx))
        return ctx.reshape(Bq, H, D)
    qf = q.astype(jnp.float32)
    if softmax_scale is None:
        scores = jnp.einsum("bhd,bhtd->bht", qf, kf) / np.sqrt(D)
    else:
        scores = jnp.einsum("bhd,bhtd->bht", qf, kf) * softmax_scale
    # a length a sequence, or one a half of a group: then one a q head
    lengths = lengths[:, None, None] if lengths.ndim == 1 else jnp.take(
        lengths, np.arange(H) % group * 2 // group, axis=1)[:, :, None]
    valid = t[None, None, :] < lengths
    scores = jnp.where(valid, scores, MASK_FILL_VALUE)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bht,bhtd->bhd", probs.astype(v.dtype), v)
    # an ALL-masked row (inactive slot, length 0) softmaxes to a
    # uniform distribution over garbage pages; pin it to the kernel's
    # semantic (zero output).  Active rows always have >= 1 valid
    # position, so the training-parity expression above is untouched.
    return jnp.where(lengths > 0, ctx, jnp.zeros_like(ctx))


# ------------------------------------------------------------------ kernel
#: VMEM the kernel plans for (half of what a ``pallas_call`` may hold,
#: as ``apex_kv_write`` plans)
_VMEM_BUDGET = VMEM_BUDGET // 2


#: the deepest ring of page slots :func:`_plan` gives the walk: the
#: knee of the sweep (``benchmarks/decode_attention_walk.py``; PERF.md,
#: PR 50): with three a page's arithmetic bounds the walk at the grouped
#: cells' shapes and its copy at the others', and a fourth reads the same
_MAX_DEPTH = 3


def _head_bytes(group, head_dim, page_size, kv_dtype, depth=2):
    """VMEM that one kv head of a grid step's block costs: its page
    tile ``2 * depth`` times over as a block (k and v, a slot of each
    for every page of the ring; lanes padded to 128) and twice in f32
    (both widened to an f32 query), and its group's f32 rows — running
    max, sum, accumulator, the page's scores and probabilities."""
    lanes = max(page_size, _LANES)
    tile = head_dim * lanes * (2 * depth * jnp.dtype(kv_dtype).itemsize
                               + 2 * 4)
    rows = 4 * max(group, 8) * (2 * _LANES + max(head_dim, _LANES)
                                + 2 * lanes)
    return tile + rows


def _plan(rows, h_kv, group, head_dim, pages_per_seq, page_size, kv_dtype):
    """``(h_blk, grid, depth)`` for these shapes: the kv heads a grid
    step holds — the largest divisor of ``h_kv`` whose block fits the
    budget with two page slots — the grid: ``(rows, h_kv // h_blk)``
    where the kernel walks a row's live pages itself (a page of whole
    lane tiles), with a third dimension ``pages_per_seq`` where the
    grid does; and the page slots of the walk's ring: two, and as many
    more as what the budget has left holds, up to ``_MAX_DEPTH`` (a
    deeper ring never shrinks the head block; the grid's form is the
    pipeline's two).  At GPT-2 large's shapes (20 heads of 64, page
    128, bf16) a block is all 20 heads, three slots deep: 20 grid steps
    a layer where one (row, head, page slot) a step made 3,200."""
    shape = (group, head_dim, page_size, kv_dtype)
    one = _head_bytes(*shape)
    h_blk = max(d for d in range(1, h_kv + 1)
                if h_kv % d == 0 and d <= max(_VMEM_BUDGET // one, 1))
    grid = (rows, h_kv // h_blk)
    if page_size % _LANES:
        return h_blk, grid + (pages_per_seq,), 2
    slot = h_blk * (_head_bytes(*shape, depth=3) - one)
    spare = max(_VMEM_BUDGET - h_blk * one, 0)
    return h_blk, grid, min(_MAX_DEPTH, 2 + spare // slot)


def _half_lengths(len_ref, row):
    """A row's two lengths, which ``len_ref`` holds side by side."""
    two = lax.mul(row, np.int32(2))
    return len_ref[two], len_ref[lax.add(two, np.int32(1))]


def _longest(len_ref, row, halves):
    """The columns a row's walk has to read: its length, or the longer
    of its two halves'."""
    if halves == 1:
        return len_ref[row]
    return lax.max(*_half_lengths(len_ref, row))


def _row_lengths(len_ref, row, halves, group):
    """``(longest, lengths)`` of a row: what the walk runs to, and what
    :func:`_attend` masks by, a scalar or with two halves (1, group, 1),
    the first half of the group's rows under the first length."""
    if halves == 1:
        length = len_ref[row]
        return length, length
    lo, hi = _half_lengths(len_ref, row)
    rows = lax.broadcasted_iota(jnp.int32, (1, group, 1), 1)
    return lax.max(lo, hi), jnp.where(rows < group // 2, lo, hi)


def _kv_block_index(b, g, p, pt_ref, len_ref, layer_ref, *, width,
                    pages_per_seq, page_size, halves=1):
    """The pool block of grid step ``(row b, head block g, page slot
    p)``.  A slot past the row's last live page names THAT page again,
    and the pipeline does not fetch a block whose index did not change:
    a whole page past a length is never read (a row without a live
    position names its table's first entry, one block at most)."""
    last = jnp.maximum(
        (_longest(len_ref, b, halves) + page_size - 1) // page_size - 1, 0)
    slot = (b // width) * pages_per_seq + jnp.minimum(p, last)
    return (layer_ref[0], pt_ref[slot], g, 0, 0)


def _attend(q, k, v, first, length, m_ref, l_ref, acc_ref, *, denom, scale):
    """One page of a block's kv heads against their queries, batched
    over the heads on the MXU: ``q`` (h_blk, group, D), ``k``/``v``
    (h_blk, D, page) whose first position is ``first``; ``length``
    a scalar, or a length a group row (1, group, 1).  One step of the
    online softmax (f32 running max/sum/accumulator in scratch)."""
    if k.dtype != q.dtype:
        # bf16 (or narrower) cache with an f32 query: widen the
        # cache read rather than rounding q down (APX306)
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    s = jax.lax.dot_general(
        q, k, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    s = s / denom if scale is None else s * scale
    pos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(pos < length, s, NEG_INF)
    m_prev = m_ref[:, :, 0:1]
    l_prev = l_ref[:, :, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    pexp = jnp.exp(s - m_new)
    pexp = jnp.where(s > NEG_INF / 2, pexp, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(pexp, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        pexp.astype(v.dtype), v, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    acc_ref[:] = acc_ref[:] * corr + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)


def _page_copies(pools, bufs, sem, where, slot):
    """The copies of one pool page's k and v blocks into a slot of the
    ring.  (To wait for one, any page will do: a wait reads the slot's
    semaphore and size.)"""
    return [pltpu.make_async_copy(hbm.at[where], buf.at[slot],
                                  sem.at[j, slot])
            for j, (hbm, buf) in enumerate(zip(pools, bufs))]


def _walk_kernel(pt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
                 k_buf, v_buf, sem, m_ref, l_ref, acc_ref, state_ref, *,
                 h_blk, n_blk, rows, page_size, pages_per_seq, width, denom,
                 scale, halves):
    """One sequence row and one block of kv heads a grid step; the step
    walks the row's LIVE pages itself.  The pools stay in HBM; a page's
    k and v blocks are copied into one of ``depth = len(k_buf)`` VMEM
    slots, round robin, and the copies run AHEAD of the arithmetic: a
    256 KB page's copy lands 0.75 µs after it is asked for, 0.32 of it
    transfer, and its arithmetic takes 0.45 (PERF.md, PR 50), so
    ``depth - 1`` pages are kept in flight beside the one attended.  The work is one list — for each row with a live
    position, for each head block, its live pages — which the grid
    consumes a (row, head block) a step and a page an iteration, and a
    producer cursor names ``depth - 1`` pages further on: every page
    step asks for the cursor's page before it waits for its own, so the
    look-ahead crosses rows and head blocks (a row of two pages has its
    successors' pages in flight while it is attended), skips rows
    without a live position, and ends with the list: every copy that is
    started is waited for.  ``state_ref`` (SMEM) carries across grid
    steps the cursor — the (row, head block, page index) whose copy goes
    out next and the slot it goes to — and the slot the arithmetic
    reads next.

    The index arithmetic binds ``lax`` primitives directly: the kernel
    is traced with every decode program, and each ``jnp`` operator on a
    traced scalar costs a nested ``jit`` trace (with them the cell's
    warm-up took 0.35 s longer than the parent's on the chip's host,
    without 0.2 s; PERF.md, PR 27)."""
    i32 = np.int32
    add, mul, lt = lax.add, lax.mul, lax.lt
    depth = k_buf.shape[0]
    b, g = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]

    def pages_of(length):
        return lax.min(lax.div(add(length, i32(page_size - 1)),
                               i32(page_size)), i32(pages_per_seq))

    def live_pages(row):
        # with two halves the walk runs to the longer length
        return pages_of(_longest(len_ref, row, halves))

    def live_row_from(row):
        """The first row at or after ``row`` with a live position
        (``rows`` if there is none)."""
        return lax.while_loop(
            lambda r: lax.bitwise_and(
                lt(r, i32(rows)),
                lax.le(_longest(len_ref, lax.min(r, i32(rows - 1)), halves),
                       i32(0))),
            lambda r: add(r, i32(1)), row)

    def copies(page, blk, slot):
        where = (layer, page) if n_blk == 1 else (
            layer, page, pl.ds(mul(blk, i32(h_blk)), h_blk))
        return _page_copies((k_hbm, v_hbm), (k_buf, v_buf), sem, where, slot)

    def next_slot(slot):
        after = add(slot, i32(1))
        return lax.select(lax.eq(after, i32(depth)), i32(0), after)

    def ask_for_next():
        """Start the copies of the page at the cursor, if the list has
        one left, and move the cursor on."""
        row, blk, i, slot = (state_ref[0], state_ref[1], state_ref[2],
                             state_ref[3])

        @pl.when(lt(row, i32(rows)))
        def _ask():
            page = pt_ref[add(mul(lax.div(row, i32(width)),
                                  i32(pages_per_seq)), i)]
            for dma in copies(page, blk, slot):
                dma.start()
            state_ref[3] = next_slot(slot)
            more = lt(add(i, i32(1)), live_pages(row))
            state_ref[2] = lax.select(more, add(i, i32(1)), i32(0))
            # after a block's last page: the row's next head block, or
            # the first block of the next row with a live position
            if n_blk > 1:
                same_row = lt(add(blk, i32(1)), i32(n_blk))
                state_ref[1] = lax.select(
                    more, blk, lax.select(same_row, add(blk, i32(1)), i32(0)))
                more = lax.bitwise_or(more, same_row)

            @pl.when(lax.bitwise_not(more))
            def _next_row():
                state_ref[0] = live_row_from(add(row, i32(1)))

    @pl.when(lax.eq(add(b, g), i32(0)))
    def _start():
        state_ref[0] = live_row_from(i32(0))
        for j in range(1, 5):
            state_ref[j] = i32(0)

        def ask(_, carry):
            ask_for_next()
            return carry

        lax.fori_loop(i32(0), i32(depth - 1), ask, i32(0))

    # each half of the group's rows masks by its own length
    length, lengths = _row_lengths(len_ref, b, halves, q_ref.shape[3])
    n = pages_of(length)

    @pl.when(lax.eq(n, i32(0)))
    def _inactive():
        # nothing is fetched for a row without a live position
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(lax.gt(n, i32(0)))
    def _active():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

        def page_step(i, slot):
            # the ring is topped up BEFORE this page's copy is waited for
            ask_for_next()
            for dma in copies(0, 0, slot):
                dma.wait()
            _attend(q_ref[0, 0], k_buf[slot], v_buf[slot],
                    mul(i, i32(page_size)), lengths, m_ref, l_ref, acc_ref,
                    denom=denom, scale=scale)
            return next_slot(slot)

        state_ref[4] = lax.fori_loop(i32(0), n, page_step, state_ref[4])
        acc, l = acc_ref[:], l_ref[:, :, 0:1]
        if halves > 1:      # a dead half beside a live one: l == 0
            l = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


def _decode_attn_kernel(pt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref,
                        o_ref, m_ref, l_ref, acc_ref, *,
                        page_size, pages_per_seq, denom, scale, halves):
    """The form for a page under 128 lanes: one sequence row and one
    block of kv heads; the sequential grid dim walks that row's page
    slots through VMEM, all of the block's heads a step.  Online
    softmax exactly as the flash forward: running max/sum/accumulator
    in f32 scratch, finalize on the last page."""
    del pt_ref, layer_ref  # consumed by the BlockSpec index maps
    b, p = pl.program_id(0), pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    length, lengths = _row_lengths(len_ref, b, halves, q_ref.shape[3])

    # a page slot at/after the length holds no valid position: its block
    # index was clamped to the last live page, so nothing was fetched
    # for it, and nothing is computed (a freshly-admitted sequence costs
    # page-1 work even when the step shape is sized for the longest
    # resident cache)
    @pl.when(p * page_size < length)
    def _compute():
        _attend(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], p * page_size, lengths,
                m_ref, l_ref, acc_ref, denom=denom, scale=scale)

    @pl.when(p == pages_per_seq - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :, 0:1], 1e-30)  # inactive rows: l == 0
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def paged_decode_attention_pallas(q, k_pool, v_pool, page_table, lengths,
                                  softmax_scale=None, width=1,
                                  interpret=False, layer=None):
    """The Pallas paged decode-attention launcher (see module doc).

    Shapes as :func:`decode_attention_xla`; with ``lengths`` (B, 2)
    the same kernels take two lengths a row: the walk runs to the longer
    and each half of a group's query rows masks by its own (a half of
    length 0 beside a live one gives zeros).  The flattened page table,
    the lengths and the layer ride as scalar-prefetch operands; the
    kernel reads one page's ``(h_blk, D, page_size)`` block of kv heads
    at a time out of the stacked pool, which is never sliced, copied or
    re-laid out, and only pages on which the row has a live position
    (:func:`_walk_kernel` for a page of whole lane tiles,
    :func:`_decode_attn_kernel` with :func:`_kv_block_index` for a
    smaller one).  With ``width`` > 1 (the verify/chunk layout: q rows
    in groups of ``width`` consecutive positions of one sequence) a row
    reads its SEQUENCE's table row — ``pt[(b // width) * P + p]`` — so
    the table is prefetched once per sequence, not once per query row;
    ``width`` is static, one compile per verify width.
    """
    k_pool, v_pool, layer = as_stacked_pools(k_pool, v_pool, layer)
    B, H, D = q.shape
    _, num_pages, h_kv, _, page_size = k_pool.shape
    n_seq, P = page_table.shape
    if H % h_kv != 0:
        raise ValueError(f"q heads ({H}) not divisible by kv heads ({h_kv})")
    if n_seq * width != B:
        raise ValueError(
            f"q rows ({B}) must equal page-table rows ({n_seq}) x width "
            f"({width})")
    group = H // h_kv
    halves = _lengths_per_row(lengths, group, width)
    h_blk, grid, depth = _plan(B, h_kv, group, D, P, page_size,
                               k_pool.dtype)
    qg = q.reshape(B, h_kv // h_blk, h_blk, group, D)
    # clamp BEFORE prefetch: the index map output becomes a DMA source
    # address, where a garbage entry must hit the reserved garbage page,
    # never wrap (APX107's contract for page-table gathers)
    pt = jnp.clip(page_table, 0, num_pages - 1) \
        .reshape(n_seq * P).astype(jnp.int32)

    q_spec = pl.BlockSpec(
        (1, 1, h_blk, group, D),
        lambda b, g, *_: (b, g, 0, 0, 0))
    scratch = [
        pltpu.VMEM((h_blk, group, _LANES), jnp.float32),
        pltpu.VMEM((h_blk, group, _LANES), jnp.float32),
        pltpu.VMEM((h_blk, group, D), jnp.float32),
    ]
    if len(grid) == 2:      # the kernel walks the live pages itself
        pool_spec = pl.BlockSpec(memory_space=pl.ANY)
        tile = (depth, h_blk, D, page_size)
        kernel = functools.partial(
            _walk_kernel, h_blk=h_blk, n_blk=grid[1], rows=B,
            page_size=page_size, pages_per_seq=P, width=width,
            denom=float(np.sqrt(D)), scale=softmax_scale, halves=halves)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[q_spec, pool_spec, pool_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM(tile, k_pool.dtype),
                pltpu.VMEM(tile, v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, depth)),
            ] + scratch + [pltpu.SMEM((5,), jnp.int32)],
        )
        semantics = ("arbitrary", "arbitrary")
    else:
        kv_spec = pl.BlockSpec(
            (1, 1, h_blk, D, page_size),
            functools.partial(_kv_block_index, width=width, pages_per_seq=P,
                              page_size=page_size, halves=halves))
        kernel = functools.partial(
            _decode_attn_kernel, page_size=page_size, pages_per_seq=P,
            denom=float(np.sqrt(D)), scale=softmax_scale, halves=halves)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=scratch,
        )
        semantics = ("parallel", "parallel", "arbitrary")
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, v_pool.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        name="apex_decode_attention",
    )(pt, lengths.astype(jnp.int32) if halves == 1
      else lengths.astype(jnp.int32).reshape(B * halves),
      jnp.asarray(layer, jnp.int32).reshape(1), qg, k_pool, v_pool)
    return out.reshape(B, H, D)


# ---------------------------------------------------------------- dispatch
def pallas_decode_attn_available(q, k_pool) -> bool:
    """Kernel path: a real TPU and a sublane-aligned head dim.

    A k/v block is whole (head_dim, page_size) tiles, so any page size
    lowers; one under 128 pads the lanes.  (No env-var override — thread
    ``attn_impl`` through :class:`apex_tpu.inference.DecodeConfig`
    instead; APX101/102.)"""
    from apex_tpu.utils.platform import on_tpu

    return (on_tpu() and q.shape[-1] % 8 == 0
            and k_pool.shape[-2] == q.shape[-1]
            and q.dtype in (jnp.float32, jnp.bfloat16))


def dispatch_kernel(name, impl, available, kernel_impl, xla_impl):
    """Run a serving kernel or its XLA twin: ``impl`` "xla" is the
    twin, "pallas"/"interpret" force the kernel (fail loudly), "auto"
    is the kernel where ``available()`` and the twin elsewhere — and a
    chosen (not forced) kernel routes through the fallback registry
    under ``name``, degrading to the twin once."""
    if impl not in ("auto", "pallas", "interpret", "xla"):
        raise ValueError(
            f"impl must be 'auto', 'pallas', 'interpret', or 'xla'; "
            f"got {impl!r}")
    if impl == "xla":
        return xla_impl()
    forced = impl in ("pallas", "interpret")
    if not forced and not available():
        return xla_impl()

    from apex_tpu.resilience.fallback import get_registry, registry_engaged

    if registry_engaged(forced=forced):
        return get_registry().call(name, kernel_impl, xla_impl)
    return kernel_impl()


def dispatch_pool_kernel(name, impl, q, k_pool, kernel_impl, xla_impl):
    """:func:`dispatch_kernel` for the kernels that touch the KV pool,
    available where :func:`pallas_decode_attn_available`.  Shared by
    the read (:func:`decode_attention`) and the write
    (:mod:`apex_tpu.inference.kv_cache`), which follow one
    ``attn_impl``."""
    return dispatch_kernel(
        name, impl, lambda: pallas_decode_attn_available(q, k_pool),
        kernel_impl, xla_impl)


def decode_attention(q, k_pool, v_pool, page_table, lengths,
                     impl="auto", softmax_scale=None, width=1, layer=None):
    """Paged single-query decode attention — the ONE dispatch between
    the Pallas kernel and the XLA reference.

    ``impl``: "auto" (kernel on TPU, reference elsewhere), "pallas"
    (force the kernel, fail loudly), "interpret" (kernel via the Pallas
    interpreter — the CPU test path), or "xla".  ``width`` > 1 scores
    groups of consecutive positions per sequence against one shared
    page-table row (speculative verification / chunked prefill — see
    :func:`decode_attention_xla`).  Chosen (non-forced) kernel use
    routes through the resilience fallback registry
    ("decode_attention"): the first Mosaic/launch failure degrades this
    process to the reference once, with one structured warning, instead
    of killing the serve loop (:mod:`apex_tpu.resilience.fallback`).

    ``k_pool``/``v_pool`` are the stacked pools with ``layer`` the
    (traced) layer index — the kernel indexes the layer in its block
    index map, so a layer loop carries the pools untouched — or one
    layer's 4-D pool (``layer=None``), the ``layer=0`` case of a
    leading-1 view.
    """
    def xla_impl():
        return decode_attention_xla(q, k_pool, v_pool, page_table, lengths,
                                    softmax_scale=softmax_scale, width=width,
                                    layer=layer)

    def kernel_impl():
        return paged_decode_attention_pallas(
            q, k_pool, v_pool, page_table, lengths,
            softmax_scale=softmax_scale, width=width,
            interpret=(impl == "interpret"), layer=layer)

    return dispatch_pool_kernel("decode_attention", impl, q, k_pool,
                                kernel_impl, xla_impl)


def block_decode_attention(q, k_pool, v_pool, page_table, lengths, width,
                           impl="auto", softmax_scale=None, layer=None):
    """Paged attention for ``width`` query positions a sequence that
    see the same columns, or two such runs side by side (generation by
    diffusion over blocks: inside a block attention is bidirectional, so
    a block's rows share one length, the block's end).

    ``q``: (B * width, H, D), a sequence's ``width`` rows consecutive;
    ``page_table``: (B, P); ``lengths``: (B,) the columns every row of
    the sequence sees (0: an inactive slot), or **(B, 2)**: the first
    ``width // 2`` rows see ``lengths[:, 0]`` columns and the rest
    ``lengths[:, 1]`` (the block step's held block, which sees the
    cache up to its own end, beside the block that opens after it,
    which sees the held block's columns too; a half of length 0 is dead
    and reads zeros).  The verify layout
    (``decode_attention(width=...)``) is right in value and walks a
    sequence's pages once a ROW; here the rows ride ONE walk: they are
    folded into the group axis, a key/value head scored against
    ``width * H / H_kv`` query rows, so the live pages are read once a
    sequence a layer, up to the LONGER length, and each half of the
    folded rows masks by its own.  Kernel and XLA twin are
    :func:`decode_attention`'s, at that wider group (:func:`_plan`
    prices the group's rows of scratch); what tells the two cases apart
    is the shape of ``lengths``, and for (B,) the kernel traced is the
    one every other family runs.  Returns (B * width, H, D)."""
    Bw, H, D = q.shape
    h_kv = k_pool.shape[-3]
    B = page_table.shape[0]
    if B * width != Bw or H % h_kv or (lengths.ndim == 2 and width % 2):
        raise ValueError(
            f"q rows ({Bw}) must equal page-table rows ({B}) x width "
            f"({width}), q heads ({H}) divide by kv heads ({h_kv}), and "
            f"two lengths a sequence {lengths.shape} halve the width")
    group = H // h_kv
    # (B, width, h_kv, group, D) -> (B, h_kv, width * group, D)
    folded = q.reshape(B, width, h_kv, group, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, h_kv * width * group, D)
    out = decode_attention(folded, k_pool, v_pool, page_table, lengths,
                           impl=impl, softmax_scale=softmax_scale,
                           layer=layer)
    return out.reshape(B, h_kv, width, group, D).transpose(0, 2, 1, 3, 4) \
        .reshape(Bw, H, D)
