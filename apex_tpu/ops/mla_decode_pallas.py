"""Pallas TPU paged decode attention over a LATENT cache (MLA, absorbed).

Multi-head latent attention caches, per token and layer, one vector
shared by every head: the normed compressed latent ``c_kv`` (``Dl``
values) followed by the one rotary key ``k_r`` (``Dr`` values).  In the
*absorbed* form the per-head key and value projections are folded into
the query and the output::

    q_lat[h] = q_nope[h] @ W_k[h]^T              (Dl)   } the caller's,
    score[h, t] = q_lat[h] . c_kv[t] + q_rope[h] . k_r[t]  } in XLA
    o_lat[h] = sum_t P[h, t] c_kv[t]             (Dl)
    o[h]     = o_lat[h] @ W_v[h]                 (the caller's)

so a cached ``(Dl + Dr, page_size)`` tile is at once the keys of EVERY
head (all of it) and their values (its first ``Dl`` rows).  This kernel
is the middle two lines: per sequence, each tile of the latent pool is
fetched ONCE and serves all ``H`` heads — ``(H, Dl + Dr) @ (Dl + Dr,
page)`` scores, online softmax, ``(H, page) @ (page, Dl)`` accumulation
— where a per-head kernel would read the same tile ``H`` times.

- the pool is :mod:`apex_tpu.inference.kv_cache`'s one-pool cache,
  ``(L, num_pages, 1, Dl + Dr, page_size)``, positions in the lanes;
  the page table, the lengths and the layer are scalar-prefetched and
  a tile is read in place at ``(layer, page_table[b, p])`` of the
  stacked pool, which is never sliced, copied or re-laid out;
- **the walk is bounded by the sequence's own pages**, in one of two
  forms that the page size picks (:func:`_plan`: a shape, not an
  option), with the same arithmetic a tile (:func:`_tile_scores`,
  :func:`_tile_update`):

  * a page of whole lane tiles (``page_size % 128 == 0``; the
    benchmark's cells): grid ``(B,)``, the pool stays in HBM
    (``memory_space=ANY``) and :func:`_mla_walk_kernel` copies the
    ``ceil(length / page_size)`` live tiles of its sequence itself,
    round robin through ``WALK_SLOTS`` VMEM slots, the next copy asked
    for before the current one is waited for and a sequence's last
    tiles asking for the first of the next sequence with a live
    position, so the copies run back to back through the whole layer;
    a slot without a live position costs one grid step and no copy;
  * a smaller page (the lanes padded; Mosaic cannot slice such a page
    out of HBM by hand): grid ``(B, pages_per_seq / G)`` with ``G``
    BlockSpec tiles a step (the pool is passed ``G`` times), where a
    tile index past the sequence's last page is CLAMPED to that page:
    consecutive steps then name the same block, the pipeline fetches
    nothing, and ``pl.when`` skips the arithmetic.

  Until PR 33 the second form was the only one.  At the cells' shapes
  it fetched 8 tiles for every 5 live ones at each change of sequence,
  and let a sequence's copies and its arithmetic take turns: a layer's
  call took 689.9 µs where the walk takes 343.0 (128 slots, 64 heads,
  contexts of 644 on average: 16.8 and 33.8% of the HBM roofline) and
  1,454.1 where it takes 597.0 (32 heads, contexts of 1,618: 20.0 and
  48.8%), the walk bound by a tile's arithmetic and no longer by its
  copy (``benchmarks/mla_decode_walk.py`` on one TPU v5 lite; PERF.md,
  PR 33).

The XLA twin :func:`mla_decode_attention_xla` is the numerics
specification; kernel failures degrade to it once through
:mod:`apex_tpu.resilience.fallback` ("mla_decode_attention").
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._pallas_tiling import LANES as _LANES

__all__ = ["mla_decode_attention", "mla_decode_attention_xla",
           "mla_decode_pallas"]

NEG_INF = -1e30
#: tiles a grid step of the small-page form may hold (each
#: double-buffered in VMEM)
MAX_TILES_PER_STEP = 8
#: VMEM slots of the walk: two hold the tiles the arithmetic is on,
#: the others copies in flight
WALK_SLOTS = 5


def _stacked(pool, layer):
    from apex_tpu.ops.decode_attention_pallas import stacked_pools

    (pool,), layer = stacked_pools((pool,), layer)
    return pool, layer


# ---------------------------------------------------------------- reference
def mla_decode_attention_xla(q, pool, page_table, lengths, latent_dim,
                             softmax_scale, layer=None):
    """Absorbed single-query latent attention over the paged pool, in
    XLA (correct everywhere; on the chip the gather re-lays out the
    pool, as every XLA read of it does).

    ``q``: (B, H, Dl + Dr) — per head ``[q_lat, q_rope]``, in the order
    of a cached column.  ``pool``: (L, num_pages, 1, Dl + Dr,
    page_size) with ``layer`` a (traced) scalar, or one layer's 4-D
    pool.  ``page_table``: (B, P) int32, clamped into the pool before
    the gather.  ``lengths``: (B,) int32 valid positions (0 = inactive
    slot: the output row is 0).  ``latent_dim``: ``Dl``.  Returns
    (B, H, Dl) in ``q``'s dtype: each head's probability-weighted sum
    of the cached latents.
    """
    pool, layer = _stacked(pool, layer)
    B, H, Dc = q.shape
    _, num_pages, _, _, page_size = pool.shape
    P = page_table.shape[1]
    pt = jnp.clip(page_table, 0, num_pages - 1)
    # (B, P, Dc, page) -> (B, S_max, Dc)
    kv = pool[layer, pt, 0].transpose(0, 1, 3, 2) \
        .reshape(B, P * page_size, Dc)
    kf = kv.astype(jnp.float32)
    scores = jnp.einsum("bhd,btd->bht", q.astype(jnp.float32), kf) \
        * softmax_scale
    t = jnp.arange(P * page_size, dtype=jnp.int32)
    valid = t[None, None, :] < lengths[:, None, None]
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bht,btd->bhd", probs.astype(kv.dtype),
                     kv[..., :latent_dim],
                     preferred_element_type=jnp.float32)
    out = jnp.where(lengths[:, None, None] > 0, out, 0.0)
    return out.astype(q.dtype)


# ------------------------------------------------------------------ kernel
def _tile_scores(q, kv, first, length, scale):
    """Every head's scores ``(H, page)`` over one cached tile ``kv``
    (Dc, page) whose first position is ``first``, in f32 and masked by
    position."""
    if kv.dtype != q.dtype:
        kv = kv.astype(q.dtype)          # widen the cache read
    s = jax.lax.dot_general(
        q, kv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    pos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(pos < length, s, NEG_INF)


def _tile_update(s, kv, m_ref, l_ref, acc_ref, *, latent_dim, dtype):
    """One step of the online softmax, as in the flash forward (f32
    running max, sum and accumulator in scratch), with a tile's scores
    ``s`` and the tile itself, whose first ``latent_dim`` rows are every
    head's values; ``dtype`` is the query's."""
    if kv.dtype != dtype:
        kv = kv.astype(dtype)
    m_prev = m_ref[:, 0:1]
    l_prev = l_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    pexp = jnp.exp(s - m_new)
    pexp = jnp.where(s > NEG_INF / 2, pexp, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(pexp, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        pexp.astype(kv.dtype), kv[:latent_dim],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[:] = acc_ref[:] * corr + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)


def _tile_copy(pool_hbm, buf, sem, layer, page, slot):
    """The copy of one pool page's tile into a VMEM slot.  (To wait for
    one any page will do: a wait reads the slot's semaphore and size.)"""
    return pltpu.make_async_copy(pool_hbm.at[layer, page, 0], buf.at[slot],
                                 sem.at[slot])


def _mla_walk_kernel(pt_ref, len_ref, layer_ref, q_ref, pool_hbm, o_ref,
                     buf, sem, m_ref, l_ref, acc_ref, cur_ref, *,
                     rows, page_size, pages_per_seq, latent_dim, scale):
    """One sequence a grid step; the step walks the sequence's LIVE
    tiles itself.  The pool stays in HBM; a tile is copied into one of
    ``len(buf)`` VMEM slots, round robin, and the copies run AHEAD of
    the arithmetic: before a tile is waited for, the next tile not yet
    asked for is — this sequence's, or after its last the first of the
    next sequence with a live position — so the copies run back to back
    through the whole layer.  The arithmetic is on two tiles at a time:
    tile ``i + 1``'s scores are taken in the block that runs tile
    ``i``'s softmax and second product, which wait on nothing of each
    other (the walk is bound by a tile's arithmetic, not by its copy:
    PERF.md, PR 33), and every sum is made in the order of the tiles.
    ``cur_ref`` (SMEM) carries across grid steps the cursor of the
    stream: the (sequence, page slot) whose copy goes out next and the
    VMEM slot it goes to, and the VMEM slot the arithmetic reads next.

    The index arithmetic binds ``lax`` primitives directly, as
    ``decode_attention_pallas._walk_kernel`` does and for its reason
    (a ``jnp`` operator on a traced scalar is a nested ``jit`` trace in
    every decode program's warm-up; PERF.md, PR 27)."""
    i32 = np.int32
    add, mul, lt = lax.add, lax.mul, lax.lt
    slots = buf.shape[0]
    b = pl.program_id(0)
    layer = layer_ref[0]
    length = len_ref[b]

    def live_pages(row):
        return lax.min(lax.div(add(len_ref[row], i32(page_size - 1)),
                               i32(page_size)), i32(pages_per_seq))

    def live_row_from(row):
        """The first sequence at or after ``row`` with a live position
        (``rows`` if there is none)."""
        return lax.while_loop(
            lambda r: lax.bitwise_and(
                lt(r, i32(rows)),
                lax.le(len_ref[lax.min(r, i32(rows - 1))], i32(0))),
            lambda r: add(r, i32(1)), row)

    def tile_copy(page, slot):
        return _tile_copy(pool_hbm, buf, sem, layer, page, slot)

    def next_slot(slot):
        return lax.rem(add(slot, i32(1)), i32(slots))

    def ask_for_next():
        """Start the copy of the tile at the cursor, if the layer has
        one left, and move the cursor on."""
        row, i, slot = cur_ref[0], cur_ref[1], cur_ref[2]

        @pl.when(lt(row, i32(rows)))
        def _ask():
            tile_copy(pt_ref[add(mul(row, i32(pages_per_seq)), i)],
                      slot).start()
            cur_ref[2] = next_slot(slot)
            more = lt(add(i, i32(1)), live_pages(row))
            cur_ref[1] = lax.select(more, add(i, i32(1)), i32(0))

            @pl.when(lax.bitwise_not(more))
            def _next_row():
                cur_ref[0] = live_row_from(add(row, i32(1)))

    @pl.when(lax.eq(b, i32(0)))
    def _start():
        cur_ref[0] = live_row_from(i32(0))
        cur_ref[1] = i32(0)
        cur_ref[2] = i32(0)
        cur_ref[3] = i32(0)
        for _ in range(slots - 2):
            ask_for_next()

    n = live_pages(b)

    @pl.when(lax.eq(n, i32(0)))
    def _inactive():
        # nothing is fetched for a sequence without a live position
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(lax.gt(n, i32(0)))
    def _active():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        q = q_ref[0]                 # (H, Dc)

        def scores(i, slot):
            """Tile ``i`` of the sequence, once it has arrived in
            ``slot``."""
            tile_copy(0, slot).wait()
            return _tile_scores(q, buf[slot], mul(i, i32(page_size)),
                                length, scale)

        def update(s, slot):
            _tile_update(s, buf[slot], m_ref, l_ref, acc_ref,
                         latent_dim=latent_dim, dtype=q.dtype)

        def tile_step(i, carry):
            # tile i's softmax and second product beside tile i + 1's
            # scores: two chains that share nothing, in one block
            slot, s = carry
            ask_for_next()
            after = next_slot(slot)
            s_next = scores(add(i, i32(1)), after)
            update(s, slot)
            return after, s_next

        first = cur_ref[3]
        ask_for_next()
        last, s = lax.fori_loop(i32(0), lax.sub(n, i32(1)), tile_step,
                                (first, scores(i32(0), first)))
        update(s, last)
        cur_ref[3] = next_slot(last)
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _mla_decode_kernel(pt_ref, len_ref, layer_ref, q_ref, *refs,
                       tiles, page_size, steps, latent_dim, scale):
    """The form for a page under 128 lanes: one sequence a row of the
    grid; the second grid dimension walks its page slots ``tiles`` at
    a time.  Running max, sum and accumulator in f32 scratch, finalized
    on the last step."""
    del pt_ref, layer_ref  # consumed by the BlockSpec index maps
    kv_refs = refs[:tiles]
    o_ref, m_ref, l_ref, acc_ref = refs[tiles:]
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    q = q_ref[0]                 # (H, Dc)

    for i in range(tiles):
        first = (j * tiles + i) * page_size

        # tiles at/after the length hold no valid position (their block
        # index was clamped, so nothing was fetched for them either)
        @pl.when(first < length)
        def _tile(i=i, first=first):
            kv = kv_refs[i][0, 0, 0]
            _tile_update(_tile_scores(q, kv, first, length, scale), kv,
                         m_ref, l_ref, acc_ref, latent_dim=latent_dim,
                         dtype=q.dtype)

    @pl.when(j == steps - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)    # inactive rows: l == 0
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _tiles_per_step(pages_per_seq: int) -> int:
    return max(g for g in range(1, MAX_TILES_PER_STEP + 1)
               if pages_per_seq % g == 0)


def _plan(rows, pages_per_seq, page_size):
    """``(grid, tiles)`` for these shapes.  A page of whole lane tiles:
    grid ``(rows,)``, the kernel walks a sequence's live tiles itself
    through ``tiles`` VMEM slots.  A smaller page (Mosaic cannot slice
    it out of HBM by hand): grid ``(rows, pages_per_seq / tiles)``,
    ``tiles`` BlockSpecs a step."""
    if page_size % _LANES == 0:
        return (rows,), WALK_SLOTS
    tiles = _tiles_per_step(pages_per_seq)
    return (rows, pages_per_seq // tiles), tiles


def mla_decode_pallas(q, pool, page_table, lengths, latent_dim,
                      softmax_scale, interpret=False, layer=None):
    """The Pallas launcher (module doc).  Shapes as
    :func:`mla_decode_attention_xla`."""
    pool, layer = _stacked(pool, layer)
    B, H, Dc = q.shape
    _, num_pages, heads, d_pool, page_size = pool.shape
    P = page_table.shape[1]
    if heads != 1 or d_pool != Dc or not 0 < latent_dim <= Dc:
        raise ValueError(
            f"q {q.shape} (latent {latent_dim}) does not fit the latent "
            f"pool {pool.shape}: one head of {Dc} values a position")
    grid, G = _plan(B, P, page_size)
    # clamp BEFORE prefetch: an entry becomes a DMA source address
    # (APX107's contract for page-table gathers)
    pt = jnp.clip(page_table, 0, num_pages - 1).reshape(B * P) \
        .astype(jnp.int32)
    q_spec = pl.BlockSpec((1, H, Dc), lambda b, *_: (b, 0, 0))
    out_spec = pl.BlockSpec((1, H, latent_dim), lambda b, *_: (b, 0, 0))
    scratch = [
        pltpu.VMEM((H, _LANES), jnp.float32),
        pltpu.VMEM((H, _LANES), jnp.float32),
        pltpu.VMEM((H, latent_dim), jnp.float32),
    ]
    if len(grid) == 1:      # the kernel walks the live tiles itself
        kernel = functools.partial(
            _mla_walk_kernel, rows=B, page_size=page_size, pages_per_seq=P,
            latent_dim=latent_dim, scale=float(softmax_scale))
        pools = [pool]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=out_spec,
            scratch_shapes=[
                pltpu.VMEM((G, Dc, page_size), pool.dtype),
                pltpu.SemaphoreType.DMA((G,)),
            ] + scratch + [pltpu.SMEM((4,), jnp.int32)],
        )
        # the cursor is carried from one sequence to the next
        semantics = ("arbitrary",)
    else:
        def kv_spec(i):
            def index(b, j, pt_ref, len_ref, layer_ref):
                # the sequence's last page holding a valid position; a
                # tile past it names THAT page again and is not fetched
                last = jnp.maximum(
                    (len_ref[b] + page_size - 1) // page_size - 1, 0)
                p = jnp.minimum(j * G + i, last)
                return (layer_ref[0], pt_ref[b * P + p], 0, 0, 0)

            return pl.BlockSpec((1, 1, 1, Dc, page_size), index)

        kernel = functools.partial(
            _mla_decode_kernel, tiles=G, page_size=page_size, steps=grid[1],
            latent_dim=latent_dim, scale=float(softmax_scale))
        pools = [pool] * G
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[q_spec] + [kv_spec(i) for i in range(G)],
            out_specs=out_spec,
            scratch_shapes=scratch,
        )
        semantics = ("parallel", "arbitrary")
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, latent_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        name="apex_mla_decode_attention",
    )(pt, lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, *pools)


# ---------------------------------------------------------------- dispatch
def mla_decode_attention(q, pool, page_table, lengths, latent_dim,
                         softmax_scale, impl="auto", layer=None):
    """Absorbed latent decode attention — the ONE dispatch between the
    Pallas kernel and its XLA twin.  ``impl`` as in
    :func:`apex_tpu.ops.decode_attention_pallas.decode_attention`
    (the step's ``attn_impl``); a chosen kernel degrades once through
    the fallback registry ("mla_decode_attention")."""
    from apex_tpu.ops.decode_attention_pallas import dispatch_pool_kernel

    def xla_impl():
        return mla_decode_attention_xla(q, pool, page_table, lengths,
                                        latent_dim, softmax_scale,
                                        layer=layer)

    def kernel_impl():
        return mla_decode_pallas(q, pool, page_table, lengths, latent_dim,
                                 softmax_scale,
                                 interpret=(impl == "interpret"),
                                 layer=layer)

    return dispatch_pool_kernel("mla_decode_attention", impl, q, pool,
                                kernel_impl, xla_impl)
