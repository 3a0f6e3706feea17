"""Pallas TPU fused sampling head: hidden → sampled token, no HBM logits.

The decode-side sibling of :mod:`apex_tpu.ops.fused_ce_pallas`, and the
second fusion the operation-fusion paper calls out for small-batch
decode (arxiv 2502.17728): the LM head matmul, temperature, top-k
restriction, and the categorical draw collapse into ONE kernel over
vocab tiles — the (B, V) fp32 logits (200 KB/row at 50k vocab) are
never written to HBM, let alone the softmax over them.

Sampling is the **Gumbel-max trick**: ``argmax_v(logits_v / T + g_v)``
with ``g_v`` i.i.d. standard Gumbel draws a token from exactly
``softmax(logits / T)`` — an online argmax reduction, which streams
over vocab tiles the way the fused-CE forward streams its logsumexp.
The Gumbel noise comes from a **counter-based hash** of (per-row seed,
vocab column) — pure uint32 vector math, identical in the kernel and
the XLA reference, so the two implementations draw the SAME token for
the same seed (bitwise parity is testable, unlike a kernel-side PRNG).

Top-k runs as a first sweep over the same tiles: a per-row running
top-K scratch (K <= 128, one lane row) is merged with each tile by a
K-step select-extract loop; the k-th largest (the min of the scratch)
then thresholds the sampling sweep.  The grid is
``(row_tiles, sweeps * vocab_tiles)`` with the vocab dimension
sequential, so the whole head is still one kernel launch.

The XLA reference :func:`fused_sample_xla` materializes the logits and
is the numerics specification; kernel failures degrade to it once via
:mod:`apex_tpu.resilience.fallback` ("decode_sampling").
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._pallas_tiling import LANES as _LANES
from apex_tpu.ops._pallas_tiling import sublane as _sublane
from apex_tpu.ops.fused_ce_pallas import (
    NEG_INF, _ceil_block, _grid, _masked_scores,
)

#: the kernel's running top-K scratch is one (sublane, lane) tile row
#: per sequence row — K beyond the 128-lane tile would need a second
#: lane row and a cross-lane merge; the dispatch falls back to XLA
MAX_KERNEL_TOP_K = 128

#: what the two double-buffered operand blocks (hidden rows, vocab
#: tile) may take of the 16 MiB a kernel is given
_OPERAND_VMEM = 12 * 2 ** 20


# ------------------------------------------------------------ shared noise
def _hash_u32(z):
    """Counter-based uint32 mix (splitmix-style avalanche).  Pure
    vector integer ops so the kernel and the XLA reference compute the
    IDENTICAL stream — the property the sampling parity tests pin."""
    z = z * jnp.uint32(2654435761)
    z = z ^ (z >> 16)
    z = z * jnp.uint32(0x45D9F3B)
    z = z ^ (z >> 16)
    z = z * jnp.uint32(0x45D9F3B)
    z = z ^ (z >> 16)
    return z


def gumbel_from_seed(seeds, cols):
    """Standard Gumbel noise for (row seed, vocab column) pairs.

    ``seeds`` uint32 broadcastable against int32 ``cols``; the uniform
    is built from the hash's top 24 bits at odd half-steps
    (``(bits + 0.5) / 2^24``), so it lives in the OPEN interval (0, 1)
    and the double log never hits an infinity."""
    z = _hash_u32(seeds.astype(jnp.uint32)
                  ^ (cols.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)))
    # the 24 kept bits fit int32, and Mosaic has no uint32 -> float32
    # convert: go through int32 (same value, same stream in the kernel
    # and in the XLA reference)
    bits = (z >> 8).astype(jnp.int32)
    u = (bits.astype(jnp.float32) + 0.5) * jnp.float32(1.0 / (1 << 24))
    return -jnp.log(-jnp.log(u))


# ---------------------------------------------------------------- reference
def fused_sample_xla(x2, embed, seeds, temperature=1.0, top_k=0):
    """Sample one token per row from the tied LM head, in XLA.

    ``x2`` (N, H) pre-head activations; ``embed`` (V, H); ``seeds``
    (N,) uint32.  ``temperature == 0`` is greedy argmax; ``top_k > 0``
    restricts the draw to the k largest logits (ties at the k-th value
    are INCLUDED — the same ``>=`` semantics as the kernel's threshold).
    Returns (N,) int32 token ids.  Materializes the (N, V) fp32 logits
    — this is the specification and the degrade target, not the fast
    path."""
    logits = jnp.matmul(x2.astype(jnp.float32),
                        embed.T.astype(jnp.float32))
    N, V = logits.shape
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    cand = logits / jnp.float32(temperature)
    cols = jnp.arange(V, dtype=jnp.int32)
    cand = cand + gumbel_from_seed(seeds[:, None], cols[None, :])
    if top_k and top_k < V:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        cand = jnp.where(logits >= kth, cand, NEG_INF)
    return jnp.argmax(cand, axis=-1).astype(jnp.int32)


# ------------------------------------------------------------------ kernel
def _merge_top_k(running, s, k):
    """Merge one tile's scores into the running per-row top-K values:
    K iterations of (argmax, extract, mask-one) over the concatenated
    candidates — no sort primitive, so Mosaic only needs max/argmax.
    ``running``/result: (bn, LANES) f32 with columns >= k at -inf."""
    cur = jnp.concatenate([running, s], axis=1)
    out0 = jnp.full_like(running, NEG_INF)

    def body(i, carry):
        cur, out = carry
        m = jnp.max(cur, axis=1, keepdims=True)
        am = jnp.argmax(cur, axis=1)
        oh = (jax.lax.broadcasted_iota(jnp.int32, cur.shape, 1)
              == am[:, None])
        out = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, out.shape, 1) == i, m, out)
        return jnp.where(oh, NEG_INF, cur), out

    _, out = jax.lax.fori_loop(0, k, body, (cur, out0))
    return out


def _sample_kernel(x_ref, e_ref, seed_ref, tok_out,
                   topk_ref, best_v, best_i, *,
                   bv, nv, V, dot_dtype, temperature, top_k, sweeps):
    j = pl.program_id(1)
    jj = j % nv

    @pl.when(j == 0)
    def _init():
        topk_ref[:] = jnp.full_like(topk_ref, NEG_INF)
        best_v[:] = jnp.full_like(best_v, NEG_INF)
        best_i[:] = jnp.zeros_like(best_i)

    s, cols, valid, _ = _masked_scores(x_ref, e_ref, jj, bv, V, dot_dtype)

    if sweeps == 2:
        @pl.when(j < nv)
        def _threshold_sweep():
            topk_ref[:] = _merge_top_k(topk_ref[:], s, top_k)

    @pl.when(j >= (nv if sweeps == 2 else 0))
    def _sample_sweep():
        elig = valid
        if sweeps == 2:
            lane = jax.lax.broadcasted_iota(jnp.int32, topk_ref.shape, 1)
            tau = jnp.min(jnp.where(lane < top_k, topk_ref[:], jnp.inf),
                          axis=1, keepdims=True)
            elig = elig & (s >= tau)
        if temperature > 0.0:
            gcols = jj * bv + cols
            g = gumbel_from_seed(seed_ref[:, 0:1].astype(jnp.uint32), gcols)
            cand = s / jnp.float32(temperature) + g
        else:
            cand = s
        cand = jnp.where(elig, cand, NEG_INF)
        m = jnp.max(cand, axis=1, keepdims=True)
        idx = (jnp.argmax(cand, axis=1).astype(jnp.int32)
               + jj * bv)[:, None]
        # strict > : on an exact cross-tile tie the EARLIER tile wins,
        # matching jnp.argmax's first-hit semantics in the reference
        better = m > best_v[:, 0:1]
        best_i[:] = jnp.broadcast_to(
            jnp.where(better, idx, best_i[:, 0:1]), best_i.shape)
        best_v[:] = jnp.broadcast_to(
            jnp.where(better, m, best_v[:, 0:1]), best_v.shape)

    @pl.when(j == sweeps * nv - 1)
    def _finalize():
        tok_out[:] = best_i[:, 0:1]


def _operand_blocks(x2, embed, block_n, block_v):
    """``(bn, bv)``: the row and vocabulary blocks of a head's launch.
    Both operand blocks are double-buffered: a wide model (H = 7168)
    halves the vocab tile until they fit the scoped VMEM."""
    (N, H), V = x2.shape, embed.shape[0]
    bn = _ceil_block(N, block_n, align=_sublane(x2.dtype))
    bv = _ceil_block(V, block_v, align=_LANES)
    while bv > _LANES and 2 * H * (
            bn * x2.dtype.itemsize + bv * embed.dtype.itemsize) \
            > _OPERAND_VMEM:
        bv //= 2
    return bn, bv


def fused_sample_pallas(x2, embed, seeds, temperature=1.0, top_k=0,
                        dot_dtype=None, block_n=256, block_v=512,
                        interpret=False):
    """The fused sampling-head launcher (see module doc).  Shapes and
    semantics as :func:`fused_sample_xla`; ``dot_dtype`` as in the
    fused-CE kernels (bf16 MXU dots with f32 accumulation by default,
    f32 for exact-parity tests)."""
    from apex_tpu.ops.fused_ce_pallas import _default_dot_dtype

    dot_dtype = dot_dtype or _default_dot_dtype()
    N, H = x2.shape
    V = embed.shape[0]
    greedy = temperature <= 0.0
    sweeps = 2 if (top_k and top_k < V and not greedy) else 1
    if sweeps == 2 and top_k > MAX_KERNEL_TOP_K:
        raise ValueError(
            f"the kernel's running top-k scratch holds one lane tile "
            f"({MAX_KERNEL_TOP_K}); top_k={top_k} must take the XLA path")
    bn, bv = _operand_blocks(x2, embed, block_n, block_v)
    nn, nv = _grid(N, bn), _grid(V, bv)

    tok = pl.pallas_call(
        functools.partial(
            _sample_kernel, bv=bv, nv=nv, V=V, dot_dtype=dot_dtype,
            temperature=float(temperature),
            top_k=int(top_k) if sweeps == 2 else 0, sweeps=sweeps,
        ),
        grid=(nn, sweeps * nv),
        in_specs=[
            pl.BlockSpec((bn, H), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            # the sampling sweep revisits the vocab tiles: j % nv maps
            # both sweeps onto the same embed block sequence
            pl.BlockSpec((bv, H), lambda i, j: (j % nv, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((bn, _LANES), jnp.float32),
            pltpu.VMEM((bn, _LANES), jnp.float32),
            pltpu.VMEM((bn, _LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="apex_fused_sample",
    )(x2, embed, seeds.reshape(N, 1).astype(jnp.uint32))
    return tok[:, 0]


# ---------------------------------------------------------------- dispatch
def pallas_sample_available(x2, embed, top_k) -> bool:
    from apex_tpu.utils.platform import on_tpu

    return (on_tpu() and (not top_k or top_k <= MAX_KERNEL_TOP_K)
            and x2.dtype in (jnp.float32, jnp.bfloat16))


def fused_sample(x2, embed, seeds, temperature=1.0, top_k=0,
                 impl="auto", dot_dtype=None):
    """hidden (N, H) → sampled token ids (N,): the ONE dispatch between
    the fused Pallas sampling head and the materialize-then-sample XLA
    reference.  ``impl`` as in
    :func:`apex_tpu.ops.decode_attention_pallas.decode_attention`;
    chosen kernel use degrades once through the fallback registry
    ("decode_sampling")."""
    if impl not in ("auto", "pallas", "interpret", "xla"):
        raise ValueError(
            f"impl must be 'auto', 'pallas', 'interpret', or 'xla'; "
            f"got {impl!r}")

    def xla_impl():
        return fused_sample_xla(x2, embed, seeds, temperature=temperature,
                                top_k=top_k)

    if impl == "xla":
        return xla_impl()
    forced = impl in ("pallas", "interpret")
    if not forced and not pallas_sample_available(x2, embed, top_k):
        return xla_impl()

    def kernel_impl():
        return fused_sample_pallas(
            x2, embed, seeds, temperature=temperature, top_k=top_k,
            dot_dtype=dot_dtype, interpret=(impl == "interpret"))

    from apex_tpu.resilience.fallback import get_registry, registry_engaged

    if registry_engaged(forced=forced):
        return get_registry().call("decode_sampling", kernel_impl, xla_impl)
    return kernel_impl()


# ------------------------------------------- the head with its confidence
def fused_sample_confidence_xla(x2, embed, seeds, temperature=1.0,
                                exclude=None):
    """The token of :func:`fused_sample_xla` (no top-k) AND its
    confidence: the value at that token of ``softmax(logits)`` (greedy)
    or of ``softmax(logits / temperature)``, the distribution it was
    drawn from, in float32.  ``exclude``: a static row of the table
    that is left out of the draw and of the softmax (a block-generating
    model's mask id: a position is never unmasked INTO a mask).
    Returns ``((N,) int32, (N,) float32)``.  What a block-generating
    step ranks its masked positions by."""
    logits = jnp.matmul(x2.astype(jnp.float32),
                        embed.T.astype(jnp.float32))
    if exclude is not None:
        logits = logits.at[:, exclude].set(NEG_INF)
    z = logits if temperature <= 0.0 else logits / jnp.float32(temperature)
    if temperature <= 0.0:
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        cols = jnp.arange(logits.shape[1], dtype=jnp.int32)
        tok = jnp.argmax(z + gumbel_from_seed(seeds[:, None], cols[None, :]),
                         axis=-1).astype(jnp.int32)
    conf = jnp.take_along_axis(jax.nn.softmax(z, axis=-1), tok[:, None],
                               axis=-1, mode="clip")[:, 0]
    return tok, conf


def _sample_conf_kernel(x_ref, e_ref, seed_ref, tok_out, conf_out,
                        best_v, best_i, best_z, run_m, run_l, *,
                        bv, nv, V, dot_dtype, temperature, exclude):
    """:func:`_sample_kernel`'s sampling sweep with two more running
    rows a sequence row: the online logsumexp of the scaled logits and
    the scaled logit of the candidate that leads so far; the confidence
    is ``exp(z[token] - logsumexp(z))``."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_v[:] = jnp.full_like(best_v, NEG_INF)
        best_i[:] = jnp.zeros_like(best_i)
        best_z[:] = jnp.full_like(best_z, NEG_INF)
        run_m[:] = jnp.full_like(run_m, NEG_INF)
        run_l[:] = jnp.zeros_like(run_l)

    s, cols, valid, _ = _masked_scores(x_ref, e_ref, j, bv, V, dot_dtype)
    if exclude is not None:
        valid = valid & (j * bv + cols != exclude)
    z = s / jnp.float32(temperature) if temperature > 0.0 else s
    z = jnp.where(valid, z, NEG_INF)
    if temperature > 0.0:
        g = gumbel_from_seed(seed_ref[:, 0:1].astype(jnp.uint32),
                             j * bv + cols)
        cand = jnp.where(valid, z + g, NEG_INF)
    else:
        cand = z
    m = jnp.max(cand, axis=1, keepdims=True)
    am = jnp.argmax(cand, axis=1).astype(jnp.int32)[:, None]
    lane = jax.lax.broadcasted_iota(jnp.int32, cand.shape, 1)
    z_at = jnp.max(jnp.where(lane == am, z, NEG_INF), axis=1, keepdims=True)
    better = m > best_v[:, 0:1]      # strict: the earlier tile wins a tie
    best_i[:] = jnp.broadcast_to(
        jnp.where(better, am + j * bv, best_i[:, 0:1]), best_i.shape)
    best_z[:] = jnp.broadcast_to(
        jnp.where(better, z_at, best_z[:, 0:1]), best_z.shape)
    best_v[:] = jnp.broadcast_to(
        jnp.where(better, m, best_v[:, 0:1]), best_v.shape)
    m_prev = run_m[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(z, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(z - m_new), 0.0)
    run_l[:] = jnp.broadcast_to(
        run_l[:, 0:1] * jnp.exp(m_prev - m_new)
        + jnp.sum(p, axis=1, keepdims=True), run_l.shape)
    run_m[:] = jnp.broadcast_to(m_new, run_m.shape)

    @pl.when(j == nv - 1)
    def _finalize():
        tok_out[:] = best_i[:, 0:1]
        conf_out[:] = jnp.exp(best_z[:, 0:1] - run_m[:, 0:1]) \
            / run_l[:, 0:1]


def fused_sample_confidence_pallas(x2, embed, seeds, temperature=1.0,
                                   exclude=None, dot_dtype=None,
                                   block_n=256, block_v=512,
                                   interpret=False):
    """The launcher of :func:`_sample_conf_kernel`: shapes, blocks and
    ``dot_dtype`` as :func:`fused_sample_pallas`; no ``(N, V)`` logits
    reach HBM."""
    from apex_tpu.ops.fused_ce_pallas import _default_dot_dtype

    dot_dtype = dot_dtype or _default_dot_dtype()
    N, H = x2.shape
    V = embed.shape[0]
    bn, bv = _operand_blocks(x2, embed, block_n, block_v)
    nn, nv = _grid(N, bn), _grid(V, bv)
    row = pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                       memory_space=pltpu.VMEM)
    tok, conf = pl.pallas_call(
        functools.partial(
            _sample_conf_kernel, bv=bv, nv=nv, V=V, dot_dtype=dot_dtype,
            temperature=float(temperature),
            exclude=None if exclude is None else int(exclude)),
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((bn, H), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bv, H), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
            row,
        ],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((N, 1), jnp.int32),
                   jax.ShapeDtypeStruct((N, 1), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((bn, _LANES), jnp.float32),
            pltpu.VMEM((bn, _LANES), jnp.int32),
            pltpu.VMEM((bn, _LANES), jnp.float32),
            pltpu.VMEM((bn, _LANES), jnp.float32),
            pltpu.VMEM((bn, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="apex_fused_sample",
    )(x2, embed, seeds.reshape(N, 1).astype(jnp.uint32))
    return tok[:, 0], conf[:, 0]


def fused_sample_confidence(x2, embed, seeds, temperature=1.0,
                            exclude=None, impl="auto", dot_dtype=None):
    """hidden (N, H) → ``(token ids (N,), confidences (N,) float32)``:
    :func:`fused_sample` (without top-k) with the softmax value of each
    sampled token beside it (:func:`fused_sample_confidence_xla` is the
    specification).  ``impl`` and the degrade path as there."""
    from apex_tpu.ops.decode_attention_pallas import dispatch_kernel

    return dispatch_kernel(
        "decode_sampling", impl,
        lambda: pallas_sample_available(x2, embed, 0),
        lambda: fused_sample_confidence_pallas(
            x2, embed, seeds, temperature=temperature, exclude=exclude,
            dot_dtype=dot_dtype, interpret=(impl == "interpret")),
        lambda: fused_sample_confidence_xla(
            x2, embed, seeds, temperature=temperature, exclude=exclude))
