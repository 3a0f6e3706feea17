"""Pallas TPU combine of a no-drop expert layer's chunk: every token's
sum over the rows it holds in the chunk, added into the carry in place.

The rows of a chunk are sorted by expert (what the grouped matmuls
need); a token holds 0 to ``top_k`` of them, anywhere.  Summing them by
``top_k`` gathers of ``(T, H)`` costs ``tokens x top_k`` rows whatever
is held (PERF.md, PR 42: 7.98 ms a call at 16,384 tokens of 2,048,
``top_k`` 8, an eighth held: seven eighths of what it moved was fill;
this form 1.07 alone, ``benchmarks/moe_combine_sweep.py``, and 0.7 in
the step).  Here the cost follows the HELD rows:

- XLA sorts the chunk's rows by token (one small sort of the chunk's
  keys, dead rows last) and gathers them ONCE, in the dtype they have;
  a token block's rows are then one contiguous span of that array;
- the kernel, ``apex_moe_combine``, walks the pairs (token block, piece
  of ``PIECE`` sorted rows) that can hold a match, a monotone staircase
  of at most ``pieces + blocks - 1`` steps whose block and piece the
  index maps read from scalar-prefetched tables (as megablox's ``gmm``
  reads its tiles): a piece is fetched once while its index stands, a
  block of the carry is read once, accumulated while its index stands,
  and written once, into the carry itself (``input_output_aliases``);
- a step's sum is one product on the MXU: the ``(block, PIECE)`` matrix
  that holds a row's routing weight where the row is the token's and 0
  elsewhere, against the piece.  Float32 times bfloat16 stays exact by
  splitting the MATRIX into its three bfloat16 parts (a few vregs; the
  piece goes to the MXU as it came); an unweighted 0/1 matrix (the
  backward: the weight was applied before the ``vjp``) is one part.
  Float32 rows take a float32 product at ``highest``.

Same terms as the plain form, ``float32(row) * weight`` summed in
float32 per token, in another order (by expert, inside the MXU's
accumulator): equal to float32 rounding of a ``top_k``-term sum, not
bit for bit.  What is where a key is out of range contributes nothing:
dead rows are gathered as zeros, never read.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._pallas_tiling import LANES

__all__ = ["moe_combine_pallas", "token_block"]

#: sorted rows a step: one lane tile of keys, the contraction of one
#: MXU pass
PIECE = LANES
#: columns of a piece a product takes at once: bounds the float32
#: result of the stacked parts (3 x block x COLUMNS)
COLUMNS = 512


def token_block(tokens: int):
    """Tokens a grid block sums (a block of the carry): the largest
    sublane multiple up to ``PIECE`` that divides ``tokens``, or None
    where there is none (the caller sums the plain way)."""
    return next((b for b in (128, 64, 32, 16, 8) if tokens % b == 0), None)


def _staircase(keys, tokens, block, n_pieces):
    """The (block, piece) pairs to visit, in order, from the sorted
    ``keys`` (dead: ``>= tokens``): block ``b``'s rows are the span
    ``[start[b], start[b + 1])``, so its pieces run from the one that
    holds its first row to the one that holds its last (an empty block
    visits one piece and finds no match there).  Consecutive blocks
    share at most one piece: at most ``n_pieces + blocks - 1`` pairs.
    Returns ``(blk, pc)`` of the static length ``n_pieces + blocks``,
    the pairs past the live ones repeating the last, and their count."""
    n_blocks = tokens // block
    bounds = jnp.arange(n_blocks + 1, dtype=jnp.int32) * block
    start = jnp.sum(keys[None, :] < bounds[:, None], axis=1,
                    dtype=jnp.int32)
    first = jnp.minimum(start[:-1] // PIECE, n_pieces - 1)
    last = jnp.maximum((start[1:] - 1) // PIECE, first)
    n = last - first + 1
    step0 = jnp.cumsum(n) - n
    steps = jnp.arange(n_pieces + n_blocks, dtype=jnp.int32)
    blk = jnp.sum(step0[None, :] <= steps[:, None], axis=1,
                  dtype=jnp.int32) - 1
    pc = jnp.minimum(jnp.take(first, blk) + steps - jnp.take(step0, blk),
                     jnp.take(last, blk))
    return blk, pc, jnp.sum(n, dtype=jnp.int32).reshape(1)


def _parts(m, rows_dtype, weighted):
    """``m`` (float32) as the operands whose products with rows of
    ``rows_dtype`` are exact: itself for float32 rows, else its
    bfloat16 parts stacked along the rows, one where it is 0/1."""
    if rows_dtype == jnp.float32:
        return m
    hi = m.astype(jnp.bfloat16)
    if not weighted:
        return hi
    rest = m - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, low], axis=0)


def _combine_kernel(blk_ref, pc_ref, n_ref, meta_ref, rows_ref, carry_ref,
                    out_ref):
    del pc_ref              # consumed by the index maps
    block = out_ref.shape[0]
    weighted = meta_ref.shape[0] > 1    # the keys, then the weights' bits
    s = pl.program_id(0)
    b = blk_ref[s]

    @pl.when((s == 0) | (blk_ref[jnp.maximum(s - 1, 0)] != b))
    def _():                # the block's first step: the carry comes in
        out_ref[...] = carry_ref[...]

    @pl.when(s < n_ref[0])
    def _():
        token = b * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, PIECE), 0)
        mine = meta_ref[0:1, :] == token
        if weighted:
            w = jax.lax.bitcast_convert_type(meta_ref[1:2, :], jnp.float32)
            m = jnp.where(mine, w, 0.0)
        else:
            m = mine.astype(jnp.float32)
        m = _parts(m, rows_ref.dtype, weighted)
        precision = (jax.lax.Precision.HIGHEST
                     if rows_ref.dtype == jnp.float32 else None)
        H = rows_ref.shape[1]
        for c in range(0, H, COLUMNS):
            cols = slice(c, min(c + COLUMNS, H))
            acc = jnp.dot(m, rows_ref[:, cols], precision=precision,
                          preferred_element_type=jnp.float32)
            total = acc[:block]
            for p in range(1, acc.shape[0] // block):
                total = total + acc[p * block:(p + 1) * block]
            out_ref[:, cols] += total


def _token_order(rows, weight, key, tokens, block):
    """What the kernel reads, made by XLA: the rows gathered into token
    order in whole pieces (``(n_pieces, PIECE, H)``, dead rows and the
    padding zeros), each piece's keys and weight bits ``(n_pieces, 1 or
    2, PIECE)`` int32, and the staircase over them."""
    R, H = rows.shape
    n_pieces = -(-R // PIECE)
    padded = n_pieces * PIECE
    key = jnp.where((key >= 0) & (key < tokens), key,
                    tokens).astype(jnp.int32)
    # the weights ride the sort: a gather of R scalars costs more
    key, perm, *w = jax.lax.sort(
        [key, jnp.arange(R, dtype=jnp.int32)]
        + ([] if weight is None else [weight.astype(jnp.float32)]),
        num_keys=1, is_stable=True)
    to_pieces = lambda a, fill: jnp.pad(
        a, (0, padded - R), constant_values=fill).reshape(n_pieces, 1, PIECE)
    meta = jnp.concatenate(
        [to_pieces(key, tokens)] + [to_pieces(jax.lax.bitcast_convert_type(
            a, jnp.int32), 0) for a in w], axis=1)
    # a dead row, and the padding to whole pieces, is gathered from
    # past the end: zeros
    source = jnp.pad(jnp.where(key < tokens, perm, R), (0, padded - R),
                     constant_values=R)
    sorted_rows = jnp.take(rows, source, axis=0, mode="fill",
                           fill_value=0).reshape(n_pieces, PIECE, H)
    return (*_staircase(key, tokens, block, n_pieces), meta, sorted_rows)


def moe_combine_pallas(out, rows, weight, key, interpret=False):
    """``out[t] += sum(float32(rows[r]) * weight[r] for r with key[r]
    == t)``, in place.

    ``out``: (T, H) float32, donated to the result; ``T`` a multiple of
    :func:`token_block`.  ``rows``: (R, H), bfloat16 or float32.
    ``weight``: (R,) float32 or None (every weight 1).  ``key``: (R,)
    int32, the token of each row; a row whose key is outside ``[0, T)``
    is dead: it is never read, whatever it holds.
    """
    T, H = out.shape
    R = rows.shape[0]
    block = token_block(T)
    if block is None or out.dtype != jnp.float32 or rows.shape != (R, H) \
            or key.shape != (R,) or rows.dtype not in (jnp.bfloat16,
                                                       jnp.float32):
        raise ValueError(
            f"rows {rows.shape} {rows.dtype} / key {key.shape} do not "
            f"fit a float32 carry {out.shape} of whole token blocks")
    blk, pc, n_steps, meta, sorted_rows = _token_order(rows, weight, key, T,
                                                       block)
    at_piece = lambda s, blk, pc, n: (pc[s], 0, 0)
    at_block = lambda s, blk, pc, n: (blk[s], 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(blk.shape[0],),
        in_specs=[pl.BlockSpec((None, meta.shape[1], PIECE), at_piece),
                  pl.BlockSpec((None, PIECE, H), at_piece),
                  pl.BlockSpec((block, H), at_block)],
        out_specs=pl.BlockSpec((block, H), at_block),
    )
    # operand numbering counts the three prefetched tables
    return pl.pallas_call(
        _combine_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out.shape, out.dtype),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="apex_moe_combine",
    )(blk, pc, n_steps, meta, sorted_rows, out)
