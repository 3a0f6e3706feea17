"""Pallas TPU kernels for the fused LM-head + cross-entropy.

Why a kernel when ``ops/fused_ce.py`` already chunks: XLA materializes
each chunk's fp32 logits in HBM between the head matmul and the
reductions that consume them — chunking bounds the PEAK but not the
TRAFFIC (still ~write+read of the full (N, V) fp32 logits each way).
These kernels keep every logits tile in VMEM, flash-attention-style:

- **forward** (grid rows × vocab-tiles, vocab sequential): per tile,
  ``s = x_blk @ e_blkᵀ`` on the MXU, online max/sum-exp update in f32
  scratch, target logit picked up by an in-tile one-hot reduction.
  The (N, V) logits never exist; HBM traffic is one read of x, O(N)
  outputs and ``ceil(N / bn)`` reads of the table — the whole table
  once a row block.
- **backward**: two kernels, mirroring the flash dq/dkv split (one
  output dim must own the sequential revisit, so dx and dembed cannot
  share a grid): each recomputes its tiles' logits, forms
  ``(softmax − onehot)·g`` in-register, and contracts immediately —
  ``dx`` accumulating over vocab tiles in scratch (``ceil(N / bn)``
  reads of the table again), ``dembed`` over row tiles (the table
  once, x ``ceil(V / bv)`` times).

:func:`plan_blocks` picks each kernel's ``(bn, bv)`` from the shapes
and dtypes under a VMEM price, by what each kernel pays for (measured
alone on a v5e, ``benchmarks/fused_ce_sweep.py``): the forward pays a
row's softmax bookkeeping once a VOCABULARY block whatever its width,
so it takes the widest; dx pays for the table's re-reads, so it takes
the tallest row block (GPT-2 medium's 8,192 × 1,024 against 50,304:
256 × 2,048 forward, 25 vocabulary blocks where 512 columns made 99;
512 × 512 in dx and dembed).  The table should arrive in the dot's
dtype (:func:`table_dtype`; ``ops/fused_ce.py`` casts a float32 master
once a call): the tiles' ``.astype(dot_dtype)`` then costs nothing and
the reads are half as wide.

MXU dots run with inputs cast to ``dot_dtype`` (bf16 by default) and
f32 accumulation — the same arithmetic XLA's default-precision f32
matmul performs on TPU, so numerics track the unfused head.

Layout: rows are flattened (S·B); the public wrapper in
``ops/fused_ce.py`` handles (S, B, ·) reshapes, tp psum composition,
and the scan fallback off-TPU.  Reference for the semantics being
accelerated: ``apex/transformer/tensor_parallel/cross_entropy.py``
(whose CUDA kernel also never gathers the full vocab row).
"""

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._pallas_tiling import LANES as _LANES
from apex_tpu.ops._pallas_tiling import VMEM_BUDGET as _VMEM_BUDGET
from apex_tpu.ops._pallas_tiling import sublane as _sublane

NEG_INF = -1e30


def _default_dot_dtype():
    """bf16 MXU dots with f32 accumulation — the same arithmetic XLA's
    default-precision f32 matmul uses on TPU, so the kernel tracks the
    unfused head.  APEX_TPU_FUSED_CE_DOT=float32 forces exact f32
    (CPU interpret parity tests; ~4x slower on the MXU)."""
    return jnp.dtype(os.environ.get("APEX_TPU_FUSED_CE_DOT", "bfloat16"))

_DIMSEM_FWD = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))
_DIMSEM_DX = _DIMSEM_FWD
_DIMSEM_DE = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _ceil_block(n, target, align):
    """Aligned block for a ceil-grid: ``target`` when n is big enough,
    else n rounded up to ``align`` (the dtype's sublane tile from
    ``_sublane`` for row blocks, the 128-lane unit for vocab blocks).
    Unlike the flash kernels' divisor search, blocks here need NOT
    divide the array — realistic tp vocab shards (e.g. 50304/8 = 6288 =
    2^4·3·131) have no lane-aligned divisor at all, and a 393-wide tile
    would fail Mosaic's sublane tiling.  Edge tiles overrun the array
    and the kernels mask them (out-of-bounds reads are garbage by the
    Pallas contract)."""
    if n >= target:
        return target
    return -(-n // align) * align


def _grid(n, block):
    return -(-n // block)


class CEPlan(NamedTuple):
    """One kernel's blocks, and what they make of the table's traffic."""
    bn: int
    bv: int
    grid: tuple         #: (row blocks, vocabulary blocks)
    table_dtype: jnp.dtype
    table_bytes: int    #: the table's bytes pulled from HBM a call
    vmem_bytes: int     #: the blocks' VMEM, by :func:`_vmem_bytes`


def table_dtype(embed_dtype, dot_dtype=None):
    """The dtype the kernels want their table in: the dot's where the
    table is wider (the float32 master against bf16 dots: the caller
    casts it ONCE a call, and the per-tile ``.astype(dot_dtype)`` then
    costs nothing), else the table's own."""
    dot_dtype = jnp.dtype(dot_dtype or _default_dot_dtype())
    embed_dtype = jnp.dtype(embed_dtype)
    return dot_dtype if dot_dtype.itemsize < embed_dtype.itemsize \
        else embed_dtype


def _vmem_bytes(kernel, bn, bv, H, x_dtype, embed_dtype):
    """A kernel's VMEM at ``(bn, bv)``: the double-buffered x and table
    tiles, the ``(bn, 1)`` columns (a lane-padded tile each: the
    targets and, forward, the three statistics; backward, lse and g)
    and what the kernel keeps besides.  Held against what Mosaic
    allocates for a described v5e (the least ``vmem_limit_bytes`` a
    call compiles under at hidden 1,024 and 2,048, PR 41):

    - forward: the statistics' scratch, ONE score-sized float32
      temporary (the scores are streamed) and half an x tile: 0 to
      1.5 MiB over what 20 blocks needed;
    - dembed: a float32 accumulator and a double-buffered float32
      output of the table tile's shape, the columns twice, a masked
      copy of the x tile and no score temporary: within 0.5 MiB of 12
      blocks;
    - dx: a float32 accumulator and a double-buffered output of x's
      shape and three score temporaries, the older, rounder guess: 0.4
      under to 1.4 over at hidden 1,024, up to 1.4 under at 2,048.

    :func:`plan_blocks` leaves 0.2 of the budget for that."""
    xb, eb = jnp.dtype(x_dtype).itemsize, jnp.dtype(embed_dtype).itemsize
    column, scores = bn * _LANES * 4, bn * bv * 4
    tiles = 2 * bn * H * xb + 2 * bv * H * eb
    if kernel == "fwd":
        return tiles + 7 * column + scores + bn * H * xb // 2
    if kernel == "dx":
        return tiles + 3 * column + 3 * scores + bn * H * (4 + 2 * xb)
    return tiles + 6 * column + 3 * bv * H * 4 + bn * H * xb


def plan_blocks(kernel, N, H, V, x_dtype, embed_dtype,
                block_n=None, block_v=None) -> CEPlan:
    """``(bn, bv)`` for ``kernel`` ("fwd", "dx" or "dembed") from the
    shapes and dtypes alone: of the row blocks 1,024, 512, 256 and the
    vocabulary blocks 2,048 down to 256 that :func:`_vmem_bytes` prices
    under 0.8 of the VMEM budget, the pair that costs the kernel least
    by what the chip showed (``benchmarks/fused_ce_sweep.py``, PR 41):

    - the forward pays each row's softmax bookkeeping (three lane
      reductions, the rescale) once a VOCABULARY block, 3.3 ns a row a
      block on a v5e whatever the block's width, as much as 300 columns
      of the product: the fewest vocabulary blocks first, then the
      fewest row blocks (the table is streamed once a row block);
    - dx has no such cost and streams the table as often: the fewest
      row blocks first, then the fewest grid steps;
    - dembed walks rows innermost, reads the table once and x once a
      vocabulary block: the fewest vocabulary blocks, then row blocks.

    Ties go to the smaller block (less padding).  ``block_n`` /
    ``block_v`` override the candidates (the tests'); ``bv`` is still
    halved to fit VMEM.  Where nothing fits (a 2,048-wide head against
    a float32 table in dembed): 256 rows by one lane tile."""
    align, embed_dtype = _sublane(x_dtype), jnp.dtype(embed_dtype)
    heights = [_ceil_block(N, b, align)
               for b in ((block_n,) if block_n else (1024, 512, 256))]
    widths = [_ceil_block(V, block_v or 2048, _LANES)]
    while widths[-1] > (_LANES if block_v else 256):
        widths.append(max(_LANES, widths[-1] // 2 // _LANES * _LANES))

    def cost(pair):
        nn, nv = _grid(N, pair[0]), _grid(V, pair[1])
        return ((nn, nv) if kernel == "dx" else (nv, nn)) + pair

    fit = [(bn, bv) for bn in heights for bv in widths
           if _vmem_bytes(kernel, bn, bv, H, x_dtype,
                          embed_dtype) <= 0.8 * _VMEM_BUDGET]
    bn, bv = min(fit or [(heights[-1], _LANES)], key=cost)
    grid = (_grid(N, bn), _grid(V, bv))
    reads = 1 if kernel == "dembed" else grid[0]
    return CEPlan(bn, bv, grid, embed_dtype,
                  reads * V * H * embed_dtype.itemsize,
                  _vmem_bytes(kernel, bn, bv, H, x_dtype, embed_dtype))


# ------------------------------------------------------------------ forward
def _masked_rows(vals, tile_idx, block, limit):
    """Zero an edge tile's overrun rows.  Selecting AFTER a contraction
    is not enough when the garbage is an operand: 0 × NaN = NaN inside
    the dot, so any tensor that feeds the MXU with possibly-OOB rows
    must be cleaned first."""
    rows = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0)
    return jnp.where(tile_idx * block + rows < limit, vals, 0)


def _masked_scores(x_ref, e_ref, j, bv, V, dot_dtype):
    """This tile's logits with edge-tile overrun columns at NEG_INF
    (Pallas fills out-of-bounds block reads with garbage — every kernel
    must neutralize them before any cross-column reduction)."""
    e = _masked_rows(e_ref[:].astype(dot_dtype), j, bv, V)
    s = jax.lax.dot_general(
        x_ref[:].astype(dot_dtype), e,
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )  # (bn, bv)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = j * bv + cols < V
    s = jnp.where(valid, s, NEG_INF)
    return s, cols, valid, e


def _fwd_kernel(x_ref, e_ref, t_ref, m_out, l_out, tgt_out,
                m_ref, l_ref, tgt_ref, *, bv, nv, V, dot_dtype):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        tgt_ref[:] = jnp.zeros_like(tgt_ref)

    s, cols, valid, _ = _masked_scores(x_ref, e_ref, j, bv, V, dot_dtype)
    m_prev = m_ref[:, 0:1]
    l_prev = l_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(jnp.exp(s - m_new), axis=-1, keepdims=True)
    # raw target logit via in-tile one-hot, gated on column VALIDITY:
    # with ceil tiles an out-of-shard local id (tp rows whose target
    # lives on another shard) can land in the padded region where s is
    # the NEG_INF mask — an ungated hit there would accumulate -1e30
    # instead of the 0 the psum contract upstream expects
    local = t_ref[:, 0:1] - j * bv
    hit = (cols == local) & valid
    tgt_new = tgt_ref[:, 0:1] + jnp.sum(
        jnp.where(hit, s, 0.0), axis=-1, keepdims=True)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
    tgt_ref[:] = jnp.broadcast_to(tgt_new, tgt_ref.shape)

    @pl.when(j == nv - 1)
    def _finalize():
        m_out[:] = m_ref[:, 0:1]
        l_out[:] = l_ref[:, 0:1]
        tgt_out[:] = tgt_ref[:, 0:1]


def fused_ce_fwd_pallas(x2, embed, t, dot_dtype=None,
                        block_n=None, block_v=None, interpret=False):
    """x2 (N, H), embed (V, H), t (N,) int32 (shard-LOCAL ids in tp).

    Returns (m, l, tgt) each (N,): running max, sum-exp at that max,
    and the raw target logit (0 where t lands outside [0, V)).  The
    caller combines — ``lse = m + log l`` dense, or pmax/psum first
    under tp."""
    dot_dtype = dot_dtype or _default_dot_dtype()
    N, H = x2.shape
    V = embed.shape[0]
    bn, bv, (nn, nv), *_ = plan_blocks("fwd", N, H, V, x2.dtype, embed.dtype,
                                       block_n, block_v)

    kernel = functools.partial(_fwd_kernel, bv=bv, nv=nv, V=V,
                               dot_dtype=dot_dtype)
    m, l, tgt = pl.pallas_call(
        kernel,
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((bn, H), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bv, H), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct((N, 1), jnp.float32)] * 3,
        scratch_shapes=[pltpu.VMEM((bn, _LANES), jnp.float32)] * 3,
        compiler_params=_DIMSEM_FWD,
        interpret=interpret,
        name="apex_fused_ce_fwd",
    )(x2, embed, t.reshape(N, 1).astype(jnp.int32))
    return m[:, 0], l[:, 0], tgt[:, 0]


# ------------------------------------------------------------- backward: dx
def _dx_kernel(x_ref, e_ref, t_ref, lse_ref, g_ref, dx_out,
               acc_ref, *, bv, nv, V, dot_dtype):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # masked s -> p = 0 at overrun columns, and the cleaned (zeroed)
    # embed rows keep 0 x garbage out of the second contraction
    s, cols, valid, e_clean = _masked_scores(x_ref, e_ref, j, bv, V, dot_dtype)
    p = jnp.exp(s - lse_ref[:, 0:1])
    local = t_ref[:, 0:1] - j * bv
    d = (p - ((cols == local) & valid).astype(jnp.float32)) * g_ref[:, 0:1]
    acc_ref[:] += jax.lax.dot_general(
        d.astype(dot_dtype), e_clean,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )  # (bn, H)

    @pl.when(j == nv - 1)
    def _finalize():
        dx_out[:] = acc_ref[:].astype(dx_out.dtype)


# --------------------------------------------------------- backward: dembed
def _dembed_kernel(x_ref, e_ref, t_ref, lse_ref, g_ref, de_out,
                   acc_ref, *, bn, bv, nn, N, V, dot_dtype):
    # grid is (v-tiles, row-tiles): i owns the output tile, j sweeps rows
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    s, cols, valid, _ = _masked_scores(x_ref, e_ref, i, bv, V, dot_dtype)
    p = jnp.exp(s - lse_ref[:, 0:1])
    local = t_ref[:, 0:1] - i * bv
    d = (p - ((cols == local) & valid).astype(jnp.float32)) * g_ref[:, 0:1]
    # rows mix here (dᵀ @ x) — unlike the row-local fwd/dx kernels an
    # overrun ROW's garbage (possibly NaN: 0 x NaN = NaN in the dot)
    # would contaminate every vocab row: mask d's rows by select AND
    # zero x's overrun rows before they touch the MXU
    d = _masked_rows(d, j, bn, N)
    x_clean = _masked_rows(x_ref[:].astype(dot_dtype), j, bn, N)
    acc_ref[:] += jax.lax.dot_general(
        d.astype(dot_dtype), x_clean,
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )  # (bv, H)

    @pl.when(j == nn - 1)
    def _finalize():
        de_out[:] = acc_ref[:]


def fused_ce_bwd_pallas(x2, embed, t, lse, g, dot_dtype=None,
                        block_n=None, block_v=None, interpret=False):
    """Gradients of ``sum(g * (lse - tgt))`` wrt x2 and embed.

    ``lse`` must be the GLOBAL logsumexp (already pmax/psum-combined in
    tp) so ``exp(s - lse)`` is the global softmax; dx comes back
    shard-local (the caller's copy-to-region psums it) and dembed is
    this shard's slice — the same contract as the scan path."""
    dot_dtype = dot_dtype or _default_dot_dtype()
    N, H = x2.shape
    V = embed.shape[0]
    bn, bv, (nn, nv), *_ = plan_blocks("dx", N, H, V, x2.dtype, embed.dtype,
                                       block_n, block_v)
    t2 = t.reshape(N, 1).astype(jnp.int32)
    lse2 = lse.reshape(N, 1).astype(jnp.float32)
    g2 = g.reshape(N, 1).astype(jnp.float32)

    row_spec = pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                            memory_space=pltpu.VMEM)
    dx = pl.pallas_call(
        functools.partial(_dx_kernel, bv=bv, nv=nv, V=V,
                          dot_dtype=dot_dtype),
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((bn, H), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bv, H), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
            row_spec, row_spec, row_spec,
        ],
        out_specs=pl.BlockSpec((bn, H), lambda i, j: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((N, H), x2.dtype),
        scratch_shapes=[pltpu.VMEM((bn, H), jnp.float32)],
        compiler_params=_DIMSEM_DX,
        interpret=interpret,
        name="apex_fused_ce_dx",
    )(x2, embed, t2, lse2, g2)

    # dembed has its own blocks: rows inside, the table read once
    bn, bv, (nn, nv), *_ = plan_blocks("dembed", N, H, V, x2.dtype,
                                       embed.dtype, block_n, block_v)
    vrow_spec = pl.BlockSpec((bn, 1), lambda i, j: (j, 0),
                             memory_space=pltpu.VMEM)
    dembed = pl.pallas_call(
        functools.partial(_dembed_kernel, bn=bn, bv=bv, nn=nn, N=N, V=V,
                          dot_dtype=dot_dtype),
        grid=(nv, nn),
        in_specs=[
            pl.BlockSpec((bn, H), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bv, H), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            vrow_spec, vrow_spec, vrow_spec,
        ],
        out_specs=pl.BlockSpec((bv, H), lambda i, j: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((V, H), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bv, H), jnp.float32)],
        compiler_params=_DIMSEM_DE,
        interpret=interpret,
        name="apex_fused_ce_dembed",
    )(x2, embed, t2, lse2, g2)
    return dx, dembed
