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
  the tile's block index map dereferences ``(layer, page_table[b, p])``
  in the stacked pool, which is never sliced, copied or re-laid out;
- **the walk is bounded by the sequence's own pages.**  The grid is
  static, ``(B, pages_per_seq / G)`` with ``G`` tiles a step (the pool
  is passed ``G`` times, one BlockSpec a tile), but a tile index past
  the sequence's last page is CLAMPED to that last page: consecutive
  steps then name the same block, the pipeline fetches nothing, and
  ``pl.when`` skips the arithmetic.  A sequence of 3 pages in a
  16-page table costs 3 fetches and 2 grid steps, not 16 of each
  (PERF.md, PR 25, records the fault this avoids in
  ``apex_decode_attention``).

The XLA twin :func:`mla_decode_attention_xla` is the numerics
specification; kernel failures degrade to it once through
:mod:`apex_tpu.resilience.fallback` ("mla_decode_attention").
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._pallas_tiling import LANES as _LANES

__all__ = ["mla_decode_attention", "mla_decode_attention_xla",
           "mla_decode_pallas"]

NEG_INF = -1e30
#: tiles a grid step may hold (each double-buffered in VMEM)
MAX_TILES_PER_STEP = 8


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
def _mla_decode_kernel(pt_ref, len_ref, layer_ref, q_ref, *refs,
                       tiles, page_size, steps, latent_dim, scale):
    """One sequence a row of the grid; the second grid dimension walks
    its pages ``tiles`` at a time.  Online softmax as in the flash
    forward: running max, sum and accumulator in f32 scratch, finalized
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
            kv = kv_refs[i][0, 0, 0]             # (Dc, page)
            if kv.dtype != q.dtype:
                kv = kv.astype(q.dtype)          # widen the cache read
            s = jax.lax.dot_general(
                q, kv, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            pos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, NEG_INF)
            m_prev = m_ref[:, 0:1]
            l_prev = l_ref[:, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            pexp = jnp.exp(s - m_new)
            pexp = jnp.where(s > NEG_INF / 2, pexp, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(pexp, axis=-1, keepdims=True)
            # the tile's first Dl rows are every head's values
            pv = jax.lax.dot_general(
                pexp.astype(kv.dtype), kv[:latent_dim],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[:] = acc_ref[:] * corr + pv
            m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == steps - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)    # inactive rows: l == 0
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _tiles_per_step(pages_per_seq: int) -> int:
    return max(g for g in range(1, MAX_TILES_PER_STEP + 1)
               if pages_per_seq % g == 0)


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
    G = _tiles_per_step(P)
    steps = P // G
    # clamp BEFORE prefetch: the index map's output becomes a DMA source
    # address (APX107's contract for page-table gathers)
    pt = jnp.clip(page_table, 0, num_pages - 1).reshape(B * P) \
        .astype(jnp.int32)

    def kv_spec(i):
        def index(b, j, pt_ref, len_ref, layer_ref):
            # the sequence's last page holding a valid position; a tile
            # past it names THAT page again and is not fetched
            last = jnp.maximum((len_ref[b] + page_size - 1) // page_size
                               - 1, 0)
            p = jnp.minimum(j * G + i, last)
            return (layer_ref[0], pt_ref[b * P + p], 0, 0, 0)

        return pl.BlockSpec((1, 1, 1, Dc, page_size), index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, steps),
        in_specs=[pl.BlockSpec(
            (1, H, Dc), lambda b, j, pt_ref, len_ref, layer_ref: (b, 0, 0))]
        + [kv_spec(i) for i in range(G)],
        out_specs=pl.BlockSpec(
            (1, H, latent_dim),
            lambda b, j, pt_ref, len_ref, layer_ref: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.VMEM((H, latent_dim), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _mla_decode_kernel, tiles=G, page_size=page_size, steps=steps,
            latent_dim=latent_dim, scale=float(softmax_scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, latent_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="apex_mla_decode_attention",
    )(pt, lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, *([pool] * G))


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
