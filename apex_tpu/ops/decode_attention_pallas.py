"""Pallas TPU paged single-query decode attention.

The decode-side sibling of :mod:`apex_tpu.ops.flash_attention_pallas`:
one generated token per sequence attends over that sequence's KV cache,
which lives as fixed-size *pages* scattered through a preallocated pool
(:mod:`apex_tpu.inference.kv_cache`).  Small-batch decode is dominated
by the softmax reductions and per-op launch overheads around a tiny
matmul (PAPERS.md: "LLM Inference Acceleration via Efficient Operation
Fusion", arxiv 2502.17728), so the whole per-head attention — page
gather, scores, online softmax, weighted sum — runs as ONE kernel:

- grid ``(batch, kv_heads, pages_per_seq)``, pages sequential;
- the page table AND the layer ride as **scalar-prefetch** operands
  (``pltpu.PrefetchScalarGridSpec``), so each k/v BlockSpec index map
  dereferences ``(layer, page_table[b, p])`` and the DMA fetches
  exactly that ``(head_dim, page_size)`` tile out of the STACKED pool
  — neither one layer's pool nor the gathered (B, S_max, H_kv, D) key
  tensor the XLA reference materializes in HBM ever exists here;
- pages are stored head-dim-major (:mod:`apex_tpu.inference.kv_cache`):
  the page's positions sit in the lanes, which is the device's own
  layout for the pool, so no consumer re-lays it out.  ``q·k``
  contracts ``(1),(0)`` and ``p·v`` contracts ``(1),(1)``;
- grouped-query attention reads the group-shared kv page ONCE per kv
  head and scores all ``H // H_kv`` q heads of the group against it
  (no ``repeat_kv_heads`` materialization, same as the flash kernels);
- the per-sequence length masks both granularities: whole pages past
  the length are skipped via ``pl.when`` (no wasted MXU work on a
  fresh sequence in a long-cache-shaped step), and the tail page is
  masked per position.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._pallas_tiling import LANES as _LANES
from apex_tpu.transformer.functional.fused_softmax import MASK_FILL_VALUE

NEG_INF = -1e30

_DIM_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


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
    each (page, kv head) is one contiguous (D, page_size) tile, the
    block the kernel DMAs.
    ``page_table``: (B, P) int32 page ids,
    CLAMPED into the pool before the gather (a stale/garbage entry
    reads the reserved garbage page instead of wrapping).  ``lengths``:
    (B,) int32 valid cache positions per sequence (0 = inactive slot —
    every position masks out and the output row is 0).

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
    valid = t[None, None, :] < lengths[:, None, None]
    scores = jnp.where(valid, scores, MASK_FILL_VALUE)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bht,bhtd->bhd", probs.astype(v.dtype), v)
    # an ALL-masked row (inactive slot, length 0) softmaxes to a
    # uniform distribution over garbage pages; pin it to the kernel's
    # semantic (zero output).  Active rows always have >= 1 valid
    # position, so the training-parity expression above is untouched.
    return jnp.where(lengths[:, None, None] > 0, ctx,
                     jnp.zeros_like(ctx))


# ------------------------------------------------------------------ kernel
def _decode_attn_kernel(pt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref,
                        o_ref, m_ref, l_ref, acc_ref, *,
                        page_size, pages_per_seq, denom, scale):
    """One (sequence, kv-head) pair; the sequential grid dim walks that
    sequence's pages through VMEM.  Online softmax exactly as the flash
    forward: running max/sum/accumulator in f32 scratch, finalize on
    the last page."""
    del pt_ref, layer_ref  # consumed by the BlockSpec index maps
    b, p = pl.program_id(0), pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    # whole pages at/after the length hold no valid position: skip the
    # dots entirely (a freshly-admitted sequence costs page-1 work even
    # when the step shape is sized for the longest resident cache)
    @pl.when(p * page_size < length)
    def _compute():
        q = q_ref[0, 0]          # (group, D)
        k = k_ref[0, 0, 0]       # (D, page) — group-shared GQA page
        v = v_ref[0, 0, 0]
        if k.dtype != q.dtype:
            # bf16 (or narrower) cache with an f32 query: widen the
            # cache read rather than rounding q down (APX306)
            k = k.astype(q.dtype)
            v = v.astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = s / denom if scale is None else s * scale
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        pexp = jnp.where(s > NEG_INF / 2, pexp, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(pexp, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            pexp.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * corr + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(p == pages_per_seq - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)  # inactive rows: l == 0
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def paged_decode_attention_pallas(q, k_pool, v_pool, page_table, lengths,
                                  softmax_scale=None, width=1,
                                  interpret=False, layer=None):
    """The Pallas paged decode-attention launcher (see module doc).

    Shapes as :func:`decode_attention_xla`.  The flattened page table,
    the lengths and the layer ride as scalar-prefetch operands so the
    k/v BlockSpec index maps can dereference them — each grid step DMAs
    exactly one (D, page_size) tile of the group-shared kv head out of
    the stacked pool, which is never sliced, copied or re-laid out.  With ``width`` > 1 (the verify/chunk layout: q rows in
    groups of ``width`` consecutive positions of one sequence) the
    index maps fold the row back onto its sequence's table row —
    ``pt[(b // width) * P + p]`` — so the table is prefetched once per
    SEQUENCE, not once per query row; ``width`` is static, one compile
    per verify width.
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
    qg = q.reshape(B, h_kv, group, D)
    # clamp BEFORE prefetch: the index map output becomes a DMA source
    # address, where a garbage entry must hit the reserved garbage page,
    # never wrap (APX107's contract for page-table gathers)
    pt = jnp.clip(page_table, 0, num_pages - 1) \
        .reshape(n_seq * P).astype(jnp.int32)

    kv_spec = pl.BlockSpec(
        (1, 1, 1, D, page_size),
        lambda b, g, p, pt_ref, len_ref, layer_ref: (
            layer_ref[0], pt_ref[(b // width) * P + p], g, 0, 0),
    )
    q_spec = pl.BlockSpec(
        (1, 1, group, D),
        lambda b, g, p, pt_ref, len_ref, layer_ref: (b, g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, h_kv, P),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((group, _LANES), jnp.float32),
            pltpu.VMEM((group, _LANES), jnp.float32),
            pltpu.VMEM((group, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_attn_kernel, page_size=page_size, pages_per_seq=P,
            denom=float(np.sqrt(D)), scale=softmax_scale,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, h_kv, group, D), v_pool.dtype),
        compiler_params=_DIM_SEMANTICS,
        interpret=interpret,
        name="apex_decode_attention",
    )(pt, lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qg, k_pool, v_pool)
    return out.reshape(B, H, D)


# ---------------------------------------------------------------- dispatch
def pallas_decode_attn_available(q, k_pool) -> bool:
    """Kernel path: a real TPU and a sublane-aligned head dim.

    A k/v block is a whole (head_dim, page_size) tile, so any page size
    lowers; one under 128 pads the lanes.  (No env-var override — thread
    ``attn_impl`` through :class:`apex_tpu.inference.DecodeConfig`
    instead; APX101/102.)"""
    from apex_tpu.utils.platform import on_tpu

    return (on_tpu() and q.shape[-1] % 8 == 0
            and k_pool.shape[-2] == q.shape[-1]
            and q.dtype in (jnp.float32, jnp.bfloat16))


def dispatch_pool_kernel(name, impl, q, k_pool, kernel_impl, xla_impl):
    """Run one of the two kernels that touch the KV pool, or its XLA
    twin: ``impl`` "xla" is the twin, "pallas"/"interpret" force the
    kernel (fail loudly), "auto" is the kernel where
    :func:`pallas_decode_attn_available` and the twin elsewhere — and a
    chosen (not forced) kernel routes through the fallback registry
    under ``name``, degrading to the twin once.  Shared by the read
    (:func:`decode_attention`) and the write
    (:mod:`apex_tpu.inference.kv_cache`), which follow one ``attn_impl``.
    """
    if impl not in ("auto", "pallas", "interpret", "xla"):
        raise ValueError(
            f"impl must be 'auto', 'pallas', 'interpret', or 'xla'; "
            f"got {impl!r}")
    if impl == "xla":
        return xla_impl()
    forced = impl in ("pallas", "interpret")
    if not forced and not pallas_decode_attn_available(q, k_pool):
        return xla_impl()

    from apex_tpu.resilience.fallback import get_registry, registry_engaged

    if registry_engaged(forced=forced):
        return get_registry().call(name, kernel_impl, xla_impl)
    return kernel_impl()


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
