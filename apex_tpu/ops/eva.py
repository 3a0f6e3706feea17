"""EVA attention's own pieces: the chunk summary and the windowed
prefill.

EVA (Zheng et al., "Efficient Attention via Control Variates",
arXiv:2302.04542), in the deterministic form EvaByte serves: a query at
position ``t`` attends EXACTLY over the tokens of its own window
``floor(t / window)`` up to itself, and over ONE pooled key and value
for every ``chunk`` tokens of every EARLIER window, all in one softmax.
Chunk ``c``'s pooled pair is a softmax pooling of its (post-rotary)
keys by a learned direction a head::

    alpha_j = softmax_j(phi . k_j)            j in chunk c
    ktilde_c = sum_j alpha_j k_j + mu         vtilde_c = sum_j alpha_j v_j

``alpha`` is float32 over the keys as they are cached (bfloat16), and
stays float32 in the two sums: on the chip a matmul would round it to
bfloat16, so the sums run on the VPU.

What is here:

- :func:`summarise_chunks`: every whole chunk of a prompt, plain XLA (a
  prompt's size);
- :func:`eva_summarise` (``apex_eva_summarise`` and its twin): in the
  decode step, the chunk that a slot's current token closes, pooled out
  of the slot's window page IN the pool (an aliased read: no XLA op
  touches the pool); slots that close none name the garbage page, which
  is fetched once, and compute nothing;
- :func:`eva_window_attention`: one window of a prompt's attention, ONE
  flash forward (``apex_flash_fwd``): the keys are the prompt's pooled
  pairs and then the window's own, causal from there on, and a key bias
  hides the pooled pairs that the window does not see.  A prompt is a
  loop over its windows (a layer's temporaries are then a window's,
  not a prompt's).

The decode step's attention itself needs no kernel of its own: the
cache hands the walk kernel ONE page list a slot, the closed windows'
pooled pages and then the slot's window pages
(:func:`apex_tpu.inference.kv_cache.windowed_view`), and
``apex_decode_attention`` walks it as any other.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["eva_summarise", "eva_summarise_xla", "eva_window_attention",
           "eva_window_attention_xla", "pooled_capacity", "summarise_chunks"]

NEG_INF = -1e30
#: heads a grid step of ``apex_eva_summarise`` holds: 8 x (128 x 128)
#: bfloat16 is 256 KB a block of k and of v
SUMMARISE_HEADS = 8


def summarise_chunks(k, v, phi, mu, chunk: int):
    """Every whole chunk of a sequence, pooled.  ``k``, ``v``: (S,
    heads, D), S a multiple of ``chunk``, the keys post-rotary, as they
    are cached; ``phi``, ``mu``: (heads, D).  Returns ``(ktilde,
    vtilde)``, (S // chunk, heads, D) each in ``k``'s dtype."""
    S, H, D = k.shape
    kc = k.reshape(S // chunk, chunk, H, D).astype(jnp.float32)
    vc = v.reshape(S // chunk, chunk, H, D).astype(jnp.float32)
    kt, vt = _pool(kc, vc, phi, mu)
    return kt.astype(k.dtype), vt.astype(v.dtype)


def _pool(kc, vc, phi, mu):
    """Chunks' float32 keys and values ``(chunks, chunk, heads, D)``,
    pooled over a chunk's positions.  Elementwise products and sums,
    not matmuls: at its default precision the chip's matmul rounds a
    float32 operand, ``alpha``, to bfloat16."""
    scores = jnp.sum(kc * phi.astype(jnp.float32), axis=-1, keepdims=True)
    alpha = jax.nn.softmax(scores, axis=1)
    return (jnp.sum(alpha * kc, axis=1) + mu.astype(jnp.float32),
            jnp.sum(alpha * vc, axis=1))


# ------------------------------------------------- the decode step's chunk
def eva_summarise_xla(k_pool, v_pool, phi, mu, pages, first, closing, layer,
                      chunk: int):
    """The twin of ``apex_eva_summarise`` (shapes at
    :func:`eva_summarise`): a gather of the tiles, plain XLA."""
    lanes = first[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None, :]

    def cols(pool):                       # (B, heads, D, chunk)
        tiles = pool[layer, pages]
        # a slot that closes no chunk may name columns before its page
        return jnp.take_along_axis(tiles, lanes[:, None, None, :], axis=-1,
                                   mode="clip")

    # (B, chunk, heads, D): a slot's chunk
    kt, vt = _pool(cols(k_pool).astype(jnp.float32).transpose(0, 3, 1, 2),
                   cols(v_pool).astype(jnp.float32).transpose(0, 3, 1, 2),
                   phi, mu)
    keep = closing[:, None, None]
    return (jnp.where(keep, kt, 0.0).astype(k_pool.dtype),
            jnp.where(keep, vt, 0.0).astype(v_pool.dtype))


def _summarise_kernel(page_ref, first_ref, closing_ref, layer_ref, phi_ref,
                      k_ref, v_ref, kt_ref, vt_ref, *, heads, chunk):
    """One (slot, block of heads) a grid step, a head at a time on 2-D
    tiles: the head's ``(D, page)`` keys against its direction (a
    column), a softmax over the chunk's lanes, and the two float32
    weighted sums, each a column of the output block."""
    del page_ref, layer_ref     # consumed by the index maps
    b = pl.program_id(0)

    @pl.when(closing_ref[b] == 0)
    def _nothing():
        kt_ref[...] = jnp.zeros_like(kt_ref)
        vt_ref[...] = jnp.zeros_like(vt_ref)

    @pl.when(closing_ref[b] != 0)
    def _pool():
        first = first_ref[b]
        phi = phi_ref[0]                                  # (D, heads)
        for h in range(heads):
            k = k_ref[0, 0, h].astype(jnp.float32)        # (D, page)
            v = v_ref[0, 0, h].astype(jnp.float32)
            s = jnp.sum(k * phi[:, h:h + 1], axis=0, keepdims=True)
            lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            inside = (lane >= first) & (lane < first + chunk)
            s = jnp.where(inside, s, NEG_INF)
            e = jnp.where(inside,
                          jnp.exp(s - jnp.max(s, axis=1, keepdims=True)), 0.0)
            alpha = e / jnp.sum(e, axis=1, keepdims=True)  # (1, page)
            kt_ref[0, 0, :, h:h + 1] = jnp.sum(k * alpha, axis=1,
                                               keepdims=True)
            vt_ref[0, 0, :, h:h + 1] = jnp.sum(v * alpha, axis=1,
                                               keepdims=True)


def _summarise_pallas(k_pool, v_pool, phi, mu, pages, first, closing, layer,
                      chunk, interpret=False):
    _, _, H, D, page_size = k_pool.shape
    B = pages.shape[0]
    hb = next(d for d in range(min(SUMMARISE_HEADS, H), 0, -1) if H % d == 0)
    closing = closing.astype(jnp.int32)
    # a slot that closes no chunk names ONE block, page 0's first: the
    # pipeline fetches a block whose index did not change only once
    pool_spec = pl.BlockSpec(
        (1, 1, hb, D, page_size),
        lambda b, g, page_ref, first_ref, closing_ref, layer_ref: (
            layer_ref[0], page_ref[b], g * closing_ref[b], 0, 0))
    out_spec = pl.BlockSpec((1, 1, D, hb), lambda b, g, *_: (b, g, 0, 0))
    out_t = jax.ShapeDtypeStruct((B, H // hb, D, hb), jnp.float32)
    kt, vt = pl.pallas_call(
        functools.partial(_summarise_kernel, heads=hb, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, H // hb),
            in_specs=[pl.BlockSpec((1, D, hb), lambda b, g, *_: (g, 0, 0)),
                      pool_spec, pool_spec],
            out_specs=[out_spec, out_spec]),
        out_shape=[out_t, out_t],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="apex_eva_summarise",
    )(jnp.where(closing != 0, pages, 0).astype(jnp.int32),
      first.astype(jnp.int32), closing,
      jnp.asarray(layer, jnp.int32).reshape(1),
      phi.astype(jnp.float32).reshape(H // hb, hb, D).transpose(0, 2, 1),
      k_pool, v_pool)

    def rows(x):                # (B, H // hb, D, hb) -> (B, H, D)
        return x.transpose(0, 1, 3, 2).reshape(B, H, D)

    keep = (closing != 0)[:, None, None]
    kt = jnp.where(keep, rows(kt) + mu.astype(jnp.float32), 0.0)
    return kt.astype(k_pool.dtype), rows(vt).astype(v_pool.dtype)


def eva_summarise(k_pool, v_pool, phi, mu, pages, first, closing, layer,
                  chunk: int, impl="auto"):
    """Pool the chunk that each slot's current token closes, out of the
    pool.  ``k_pool``/``v_pool``: the stacked pools (L, pages, heads, D,
    page_size) with ``layer`` the (traced) layer; ``phi``, ``mu``:
    (heads, D) of that layer; ``pages``: (B,) the pool page that holds
    the slot's chunk (a page of its window buffer: a chunk never
    straddles two, ``page_size % chunk == 0``); ``first``: (B,) the
    chunk's first column in it; ``closing``: (B,) bool, the slots whose
    chunk closes in this step.  Returns ``(ktilde, vtilde)``, (B, heads,
    D) each in the pools' dtype, zero for a slot that closes none.

    ``impl`` as :func:`apex_tpu.ops.decode_attention_pallas
    .decode_attention`'s; a chosen kernel degrades once through the
    fallback registry ("eva_summarise")."""
    from apex_tpu.ops.decode_attention_pallas import dispatch_pool_kernel

    if k_pool.shape[-1] % chunk:
        raise ValueError(f"a page of {k_pool.shape[-1]} columns does not "
                         f"hold whole chunks of {chunk}")
    args = (k_pool, v_pool, phi, mu, pages, first, closing, layer, chunk)
    return dispatch_pool_kernel(
        "eva_summarise", impl, phi, k_pool,
        lambda: _summarise_pallas(*args, interpret=(impl == "interpret")),
        lambda: eva_summarise_xla(*args))


# --------------------------------------------------------------- the prompt
#: a prompt's pooled pairs are held in a buffer of whole blocks of this
#: many rows (:func:`pooled_capacity`): the flash forward's sub-tile at
#: the window's shape, so that buffer and window are whole sub-tiles of
#: the ONE key block :func:`eva_window_attention` asks for
PREFILL_BLOCK_K = 512


def pooled_capacity(windows: int, per_window: int, window: int) -> int:
    """Rows of the buffer that holds the pooled pairs a prompt of
    ``windows`` windows can see (those of all windows but the last),
    padded to whole key blocks; 0 for a prompt of one window."""
    block = min(PREFILL_BLOCK_K, window)
    return -(-(windows - 1) * per_window // block) * block


def eva_window_attention_xla(q, k, v, kt, vt, seen):
    """The twin and the specification of :func:`eva_window_attention`:
    one float32 softmax over the first ``seen`` pooled pairs and the
    window's own tokens up to the query."""
    W, H, D = q.shape
    scale = 1.0 / np.sqrt(D)
    qf = q.astype(jnp.float32)
    a = jnp.einsum("qhd,khd->hqk", qf, k.astype(jnp.float32)) * scale
    a = jnp.where(jnp.tril(jnp.ones((W, W), bool))[None], a, NEG_INF)
    b = jnp.einsum("qhd,khd->hqk", qf, kt.astype(jnp.float32)) * scale
    b = jnp.where(jnp.arange(kt.shape[0])[None, None, :] < seen, b, NEG_INF)
    p = jax.nn.softmax(jnp.concatenate([b, a], axis=-1), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", p.astype(v.dtype),
                     jnp.concatenate([vt, v], axis=0))
    return out.astype(q.dtype)


def _window_pallas(q, k, v, kt, vt, seen, interpret=False):
    """ONE flash forward, the heads as batch rows: the keys are the
    pooled buffer and then the window's own, the causal diagonal starts
    after the buffer (``k_offset``), and a key bias hides the buffer's
    rows from ``seen`` on."""
    from apex_tpu.ops.flash_attention_pallas import (
        NEG_INF as MASKED, flash_fwd_pallas,
    )

    W, H, D = q.shape
    pooled = kt.shape[0]
    heads_first = lambda x: x.transpose(1, 0, 2)
    bias = None
    if pooled:
        k = jnp.concatenate([kt, k], axis=0)
        v = jnp.concatenate([vt, v], axis=0)
        col = jnp.arange(pooled + W, dtype=jnp.int32)
        bias = jnp.where((col < seen) | (col >= pooled), 0.0,
                         MASKED).astype(jnp.float32)[None, None, :]
    out, _ = flash_fwd_pallas(
        heads_first(q), heads_first(k), heads_first(v), 1.0 / np.sqrt(D),
        True, 0, -pooled,
        # ONE key block over buffer and window: no softmax state carried
        # between grid steps (key blocks of 512 took 2.8 times as long)
        block_k=pooled + W if pooled else None,
        interpret=interpret, kv_bias=bias, heads=H)
    return heads_first(out)


def eva_window_attention(q, k, v, kt, vt, seen, impl="auto"):
    """One window of a prompt's attention: ``q``, ``k``, ``v`` (window,
    heads, D), post-rotary; ``kt``, ``vt`` (:func:`pooled_capacity`,
    heads, D) the prompt's pooled pairs so far, of which the first
    ``seen`` (a traced scalar: those of the windows before this one)
    are visible.  A query sees them and its window's tokens up to
    itself, in one softmax.  Returns (window, heads, D).

    The kernel path is one call of the flash forward (``apex_flash_fwd``,
    with a key bias); ``impl`` and the fallback registry
    ("eva_prefill_attention") as everywhere."""
    from apex_tpu.ops.decode_attention_pallas import dispatch_kernel
    from apex_tpu.utils.platform import on_tpu

    W = q.shape[0]
    return dispatch_kernel(
        "eva_prefill_attention", impl,
        lambda: on_tpu() and W % 128 == 0 and q.shape[-1] % 8 == 0,
        lambda: _window_pallas(q, k, v, kt, vt, seen,
                               interpret=(impl == "interpret")),
        lambda: eva_window_attention_xla(q, k, v, kt, vt, seen))
