"""Pallas TPU in-place write into the paged KV pool.

Every program that writes the pool (:mod:`apex_tpu.inference.kv_cache`)
does so through this ONE kernel, ``apex_kv_write``, with the pools
aliased input-to-output.  A cache is one pool or several of one shape
(GPT: ``k`` and ``v``; a latent cache: one): :func:`pool_write_pallas`
writes them all in one call, :func:`kv_write_pallas` is its two-pool
spelling.  The reason is layout, not speed of the write
itself: an XLA scatter or ``dynamic_update_slice`` on the pool inside a
step makes XLA's layout assignment re-lay out the WHOLE pool to the
layout the write prefers and back again (PERF.md, PR 25: more than
half of a decode step at GPT-2 large, and a step that stops fitting
the chip once the pool is the layer loop's carry).  An aliased custom
call has no preferred layout, so the pool stays where it is.

The unit of a write is a **page tile**: one pool page across all kv
heads, ``(kv_heads, head_dim, page_size)``, positions in the lanes
(split over a block of heads a step where all of them would not fit
VMEM).  One grid step reads the tile, replaces the masked columns from a
source and writes it back::

    tile[:, :, c] = src[:, :, c]  where mask[c]  else  tile[:, :, c]

The three writers differ only in what XLA prepares around it (all of
it small — never pool-sized):

- the decode token: the source is the new column, one value a (head,
  head-dim element), broadcast along the lanes; the mask is ``lane ==
  slot``; one tile a slot;
- ``width`` consecutive rows of a sequence (speculative verify, a
  prefill chunk): XLA places the rows at their columns of up to
  ``ceil((width - 1) / page_size) + 1`` source tiles a sequence;
- the prompt: its k/v transposed into ``ceil(S / page_size)`` source
  tiles a layer, the grid running over the layers too.

**One grid step a block of a tile, never two.**  The pipeline reads
step ``t + 1``'s block while step ``t`` computes, before ``t``'s
write-back: two steps on one tile would lose the first's columns.  Callers hand every
live tile to exactly one step; steps with nothing to write go to the
garbage page, whose content nobody reads.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._pallas_tiling import LANES, VMEM_BUDGET

__all__ = ["kv_write_pallas", "pool_write_block_pallas", "pool_write_pallas"]


def _heads_per_block(h_kv, head_dim, page_size, dtype, n_pools=2) -> int:
    """The most kv heads a block can hold: three blocks a pool a step
    (the pool tile in, its source, the tile out), each double-buffered,
    within half the VMEM budget.  A page under 128 pads the lanes."""
    per_head = head_dim * max(page_size, LANES) * jnp.dtype(dtype).itemsize
    fit = max(1, (VMEM_BUDGET // 2) // (6 * n_pools * per_head))
    return max(d for d in range(1, h_kv + 1) if h_kv % d == 0 and d <= fit)


def _source_blocks(x, hb, dtype):
    """Sources as the kernel reads them: (Ls, T, C, H_kv, D) -> (Ls, T,
    H_kv // hb, D, hb * C) — head dim on the sublanes as in the pool,
    and a block's ``hb`` heads side by side in the lanes, so that 20
    heads of one column are 20 lanes, not 20 lane-padded vectors (a
    (H_kv, D, 1) source is 128x its bytes on the chip and took longer
    to make than the write itself, PERF.md PR 25)."""
    Ls, T, C, h_kv, D = x.shape
    x = x.astype(dtype).reshape(Ls, T, C, h_kv // hb, hb, D)
    return x.transpose(0, 1, 3, 5, 4, 2).reshape(
        Ls, T, h_kv // hb, D, hb * C)


def _kv_write_kernel(dest_ref, layer_ref, mask_ref, *refs, heads, cols):
    del dest_ref, layer_ref  # consumed by the BlockSpec index maps
    n = len(refs) // 3       # sources, pool tiles in, pool tiles out
    keep_new = mask_ref[0] != 0          # (1, page): broadcasts over D
    for src_ref, pool_ref, out_ref in zip(refs[:n], refs[n:2 * n],
                                          refs[2 * n:]):
        src = src_ref[0, 0, 0]           # (D, heads * cols)
        for h in range(heads):
            # (D, page) columns of head h, or its one column (D, 1)
            new = src[:, h * cols:(h + 1) * cols]
            out_ref[0, 0, h] = jnp.where(keep_new, new, pool_ref[0, 0, h])


def pool_write_pallas(pools, srcs, dest, mask, layer, interpret=False):
    """Write source tiles into pool pages, in place.

    ``pools``: a tuple of pools of ONE shape and dtype, each (L,
    num_pages, H_kv, D, page_size), donated to the result.  ``srcs``:
    one (Ls, T, C, H_kv, D) source a pool — per tile its ``C`` columns,
    each a token's heads as the model computes them; ``C`` is
    ``page_size`` (a whole tile) or 1 (the one column that every masked
    lane takes).  ``dest``: (T,) int32 page ids, ALREADY clamped into
    the pool and garbage-routed by the caller, live ones pairwise
    distinct (module doc).  ``mask``: (T, page_size) bool, the columns
    to take from the source.  ``layer``: scalar int32; source layer
    ``l`` lands in pool layer ``layer + l`` (the decode step passes
    ``Ls = 1`` and its loop index, the prefill ``Ls = L`` and 0).
    Returns the pools, as a tuple.
    """
    pools, srcs = tuple(pools), tuple(srcs)
    n = len(pools)
    _, _, h_kv, D, page_size = pools[0].shape
    Ls, T, C = srcs[0].shape[:3]
    if n < 1 or len(srcs) != n or C not in (1, page_size) \
            or any(x.shape != (Ls, T, C, h_kv, D) for x in srcs) \
            or any(p.shape != pools[0].shape or p.dtype != pools[0].dtype
                   for p in pools):
        raise ValueError(
            f"sources {[x.shape for x in srcs]} do not fit pools "
            f"{[p.shape for p in pools]}")
    if mask.shape != (T, page_size) or dest.shape != (T,):
        raise ValueError(
            f"dest {dest.shape} / mask {mask.shape} do not match {T} "
            f"tiles of {page_size} columns")
    return _launch(_kv_write_kernel, pools, srcs, dest, mask, layer,
                   interpret)


def _launch(kernel, pools, srcs, dest, mask, layer, interpret):
    """The one ``apex_kv_write`` call: ``kernel(..., heads=, cols=)``
    over the grid (source layers, tiles, blocks of heads), the pools
    aliased input-to-output.  ``mask``: (T, page_size), what the kernel
    makes of a lane."""
    n = len(pools)
    _, _, h_kv, D, page_size = pools[0].shape
    Ls, T, C = srcs[0].shape[:3]
    dtype = pools[0].dtype
    hb = _heads_per_block(h_kv, D, page_size, dtype, n)
    src_spec = pl.BlockSpec(
        (1, 1, 1, D, hb * C),
        lambda l, t, h, dest_ref, layer_ref: (l, t, h, 0, 0))
    pool_spec = pl.BlockSpec(
        (1, 1, hb, D, page_size),
        lambda l, t, h, dest_ref, layer_ref: (
            layer_ref[0] + l, dest_ref[t], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Ls, T, h_kv // hb),
        in_specs=[
            pl.BlockSpec((1, 1, page_size),
                         lambda l, t, h, dest_ref, layer_ref: (t, 0, 0)),
        ] + [src_spec] * n + [pool_spec] * n,
        out_specs=[pool_spec] * n,
    )
    pool_t = jax.ShapeDtypeStruct(pools[0].shape, dtype)
    # operand numbering counts the two prefetched scalars and the mask
    out = pl.pallas_call(
        functools.partial(kernel, heads=hb, cols=C),
        grid_spec=grid_spec,
        out_shape=[pool_t] * n,
        input_output_aliases={3 + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="apex_kv_write",
    )(dest.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      mask.astype(jnp.int32).reshape(T, 1, page_size),
      *[_source_blocks(x, hb, dtype) for x in srcs], *pools)
    return tuple(out)


def kv_write_pallas(k_pool, v_pool, k_src, v_src, dest, mask, layer,
                    interpret=False):
    """:func:`pool_write_pallas` for a cache of two pools, ``k`` and
    ``v``.  Returns the two pools."""
    return pool_write_pallas((k_pool, v_pool), (k_src, v_src), dest, mask,
                             layer, interpret=interpret)


def _kv_block_write_kernel(dest_ref, layer_ref, mask_ref, *refs, heads, cols):
    """The tile write for a source of ``cols`` columns: ``mask`` names,
    a lane, the source column it takes plus one (0: the lane keeps the
    pool's).  The columns are PLACED by the MXU, a head's ``(D, cols)``
    source times the one-hot ``(cols, page)`` of the lanes' choices
    (exact: one term a lane), and one select a head merges them into the
    tile: a select a column over the whole tile, as this kernel ran
    until PR 45, bound it by the VPU at 29 ns a select a tile (1.2 us a
    tile at 4 columns, 2.2 at 8, where its bytes take 0.64)."""
    del dest_ref, layer_ref  # consumed by the BlockSpec index maps
    n = len(refs) // 3       # sources, pool tiles in, pool tiles out
    which = mask_ref[0]                  # (1, page): broadcasts over D
    column = jax.lax.broadcasted_iota(jnp.int32, (cols, which.shape[-1]), 0)
    takes = which > 0
    for src_ref, pool_ref, out_ref in zip(refs[:n], refs[n:2 * n],
                                          refs[2 * n:]):
        src = src_ref[0, 0, 0]           # (D, heads * cols)
        onehot = (which == column + 1).astype(src.dtype)
        # a float32 cache: every bit of the value through the MXU
        exact = jax.lax.Precision.HIGHEST if src.dtype == jnp.float32 \
            else None
        for h in range(heads):
            placed = jax.lax.dot_general(
                src[:, h * cols:(h + 1) * cols], onehot,
                (((1,), (0,)), ((), ())), precision=exact,
                preferred_element_type=jnp.float32)
            out_ref[0, 0, h] = jnp.where(
                takes, placed.astype(src.dtype), pool_ref[0, 0, h])


def pool_write_block_pallas(pools, srcs, dest, first, live, layer,
                            interpret=False):
    """Write a few consecutive columns a tile into pool pages, in
    place: :func:`pool_write_pallas` for sources of ``C`` columns, ``C``
    small (a block of ``W`` positions, or two blocks side by side), so
    that a tile is read and written once for all of them (the ``width``
    layout of :func:`pool_write_pallas` would hand the kernel two
    page-wide source tiles a block, the second to the garbage page).

    ``pools`` as there.  ``srcs``: one (1, T, C, H_kv, D) source a pool;
    ``dest``: (T,) int32 page ids, clamped and garbage-routed by the
    caller, live ones pairwise distinct; ``first``: (T,) int32 the lane
    the source's column 0 takes, column ``c`` then ``first + c``: a
    column whose lane falls outside the tile (``first`` may be negative)
    is not written, which is how a run of columns that crosses a page
    is handed over as two tiles with one source; ``live``: (T,) or
    (T, C) bool, the columns to write (a dead tile keeps the pool's).
    ``layer``: scalar int32.  Returns the pools, as a tuple."""
    pools, srcs = tuple(pools), tuple(srcs)
    n = len(pools)
    _, _, h_kv, D, page_size = pools[0].shape
    Ls, T, C = srcs[0].shape[:3]
    if Ls != 1 or len(srcs) != n \
            or any(x.shape != (1, T, C, h_kv, D) for x in srcs) \
            or any(p.shape != pools[0].shape or p.dtype != pools[0].dtype
                   for p in pools):
        raise ValueError(
            f"sources {[x.shape for x in srcs]} do not fit pools "
            f"{[p.shape for p in pools]} as columns of one layer")
    lane = jnp.arange(page_size, dtype=jnp.int32)[None, :]
    offset = lane - first.astype(jnp.int32)[:, None]
    inside = (offset >= 0) & (offset < C)
    live = jnp.broadcast_to(live.reshape(T, -1), (T, C))
    taken = jnp.take_along_axis(live, jnp.clip(offset, 0, C - 1), axis=1)
    which = jnp.where(inside & taken, offset + 1, 0)
    return _launch(_kv_block_write_kernel, pools, srcs, dest, which, layer,
                   interpret)
