"""What can be proven about the chip path without a chip.

- Every Pallas kernel lowers for a TPU at the shapes ``chip_smoke.py``
  runs (``jax.export(platforms=["tpu"])``: the Pallas→Mosaic lowering,
  where ``kv_heads > 1`` and ``temperature > 0`` used to fail), and,
  where this installation's libtpu can build a compile-only v5e
  client, compiles through the Mosaic backend with its kernel name in
  the executable.
- The compile-cache rule, the one platform probe, and ``chip_smoke.py``'s
  refusal to run without a TPU.

Whether the compiled kernels compute the right thing is ``chip_smoke.py``'s
job, on the chip.
"""

import functools
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax import export as jexport

from apex_tpu.inference.kv_cache import (
    write_decode_kv, write_decode_pools, write_prompt_kv,
    write_prompt_pools,
)
from apex_tpu.ops.decode_attention_pallas import paged_decode_attention_pallas
from apex_tpu.ops.decode_sampling_pallas import fused_sample_pallas
from apex_tpu.ops.flash_attention_pallas import flash_attention_pallas
from apex_tpu.ops.fused_ce_pallas import (
    fused_ce_bwd_pallas, fused_ce_fwd_pallas,
)
from apex_tpu.ops import eva, kda, ssd
from apex_tpu.ops.mla_decode_pallas import mla_decode_pallas
from apex_tpu.ops.layer_norm_pallas import (
    layer_norm_bwd_pallas, layer_norm_fwd_pallas,
)

REPO = Path(__file__).resolve().parents[1]
BF16, F32, I32, U32 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.uint32
VOCAB = 50304


def _decode_attn(heads, kv_heads, width, page=16, B=8, D=64, P=12,
                 pages=97, layers=None):
    """One layer's pool, or with ``layers`` the stacked pool and a
    traced layer."""
    pool = ((pages, kv_heads, D, page), BF16)
    avals = [((B * width, heads, D), BF16), pool, pool, ((B, P), I32),
             ((B * width,), I32)]
    if layers is None:
        return (lambda q, k, v, pt, n: paged_decode_attention_pallas(
            q, k, v, pt, n, width=width), avals)
    avals[1] = avals[2] = ((layers, *pool[0]), BF16)
    return (lambda q, k, v, pt, n, layer: paged_decode_attention_pallas(
        q, k, v, pt, n, width=width, layer=layer), avals + [((), I32)])


#: GPT-2 large as the benchmark's serve cells run the kernel: 20 slots,
#: 20 kv heads of 64, page 128, 8 pages a sequence, the 36-layer
#: stacked pool (a garbage page and every slot's pages) at a traced layer
_CELL = dict(B=20, D=64, page=128, P=8, pages=161, layers=36)
#: the kernel as two more serve cells run it, from their committed
#: configurations: LFM2 (``agentgen-over``: 256 slots, 32 query heads
#: over 8 of 64, 24 pages a sequence, the 3 attention layers' pool) and
#: Falcon-H1 (96 slots, 20 over 4 of 128, 20 pages, 8 layers)
_LFM2_CELL = dict(B=256, D=64, page=128, P=24, pages=3840, layers=3)
_H1_CELL = dict(B=96, D=128, page=128, P=20, pages=1024, layers=8)


def _kv_write(kv_heads, width, page=16, dtype=BF16, D=64):
    """One layer's decode/verify write into a 4-layer stacked pool."""
    B, P, pages = 8, 12, 97
    pool = ((4, pages, kv_heads, D, page), dtype)
    new = ((B * width, kv_heads, D), BF16)
    return (lambda k, v, kn, vn, pt, pos, act, layer: write_decode_kv(
        k, v, kn, vn, pt, pos, act, layer=layer, width=width,
        impl="pallas"),
        [pool, pool, new, new, ((B, P), I32), ((B * width,), I32),
         ((B * width,), jnp.bool_), ((), I32)])


def _prompt_write(kv_heads, page=16):
    L, S, P, pages, D = 4, 128, 12, 97, 64
    pool = ((L, pages, kv_heads, D, page), BF16)
    stack = ((L, S, kv_heads, D), BF16)
    return (lambda k, v, ks, vs, row, n, start: write_prompt_kv(
        k, v, ks, vs, row, n, start=start, impl="pallas"),
        [pool, pool, stack, stack, ((P,), I32), ((), I32), ((), I32)])


def _sample(temperature, top_k, rows=8, hidden=768, vocab=VOCAB,
            embed=F32, x_dtype=BF16, dot_dtype=None):
    return (lambda x, e, s: fused_sample_pallas(
        x, e, s, temperature=temperature, top_k=top_k, dot_dtype=dot_dtype),
        [((rows, hidden), x_dtype), ((vocab, hidden), embed),
         ((rows,), U32)])


# the latent-attention family at its published widths: 64 heads over a
# 512 + 64 latent column, 128 slots, page 128, a 6-layer stacked pool
_LATENT_POOL = ((6, 257, 1, 576, 128), BF16)


# the KDA/MLA cell's latent pool: 3 MLA layers, 128 slots x 48 pages
_LATENT_POOL_P48 = ((3, 6145, 1, 576, 128), BF16)


def _mla_decode(slots=128, P=16, heads=64, pool=_LATENT_POOL):
    return (lambda q, pool, pt, n, layer: mla_decode_pallas(
        q, pool, pt, n, 512, 0.1447, layer=layer),
        [((slots, heads, 576), BF16), pool, ((slots, P), I32),
         ((slots,), I32), ((), I32)])


def _latent_write(slots=128, P=16):
    return (lambda pool, new, pt, pos, act, layer: write_decode_pools(
        (pool,), (new,), pt, pos, act, layer=layer, impl="pallas"),
        [_LATENT_POOL, ((slots, 1, 576), BF16), ((slots, P), I32),
         ((slots,), I32), ((slots,), jnp.bool_), ((), I32)])


def _latent_prompt_write(S=512, P=16):
    return (lambda pool, stack, row, n, start: write_prompt_pools(
        (pool,), (stack,), row, n, start=start, impl="pallas"),
        [_LATENT_POOL, ((6, S, 1, 576), BF16), ((P,), I32), ((), I32),
         ((), I32)])


# the KDA ops at the cell's shapes: 128 slots, 32 heads of 128, 10 KDA
# layers; float32 state, bfloat16 convolution tails of 3 x 12,288
_KDA_STATE = ((10, 129, 32, 128, 128), F32)
_KDA_TAILS = ((10, 129, 3 * 12288), BF16)


def _kda_decode(slots=128):
    vec = ((slots, 32, 128), F32)
    return (lambda q, k, v, g, beta, state, active, layer: kda.kda_decode(
        q, k, v, g, beta, state, active, layer, impl="pallas"),
        [vec, vec, vec, vec, ((slots, 32), F32), _KDA_STATE,
         ((slots,), jnp.bool_), ((), I32)])


def _kda_conv_step(slots=128):
    return (lambda x, w, tails, active, layer: kda.conv_step(
        x, w, tails, active, layer, impl="pallas"),
        [((slots, 12288), BF16), ((4, 12288), BF16), _KDA_TAILS,
         ((slots,), jnp.bool_), ((), I32)])


def _kda_chunked(S):
    vec = ((S, 32, 128), F32)
    return (lambda q, k, v, g, beta, state: kda.kda_chunked(
        q, k, v, g, beta, state, impl="pallas"),
        [vec, vec, vec, vec, ((S, 32), F32), ((32, 128, 128), F32)])


_SSM_STATE = ((8, 97, 32, 128, 256), F32)
_SSM_TAILS = ((8, 97, 3 * 5120), BF16)


def _ssd_decode(slots=96):
    return (lambda x, dt, A, B, C, D, state, active, layer: ssd.ssd_decode(
        x, dt, A, B, C, D, state, active, layer, impl="pallas"),
        [((slots, 32, 128), F32), ((slots, 32), F32), ((32,), F32),
         ((slots, 2, 256), F32), ((slots, 2, 256), F32), ((32,), F32),
         _SSM_STATE, ((slots,), jnp.bool_), ((), I32)])


def _ssm_conv_step(slots=96):
    return (lambda x, w, tails, active, layer: kda.conv_step(
        x, w, tails, active, layer, impl="pallas"),
        [((slots, 5120), BF16), ((4, 5120), F32), _SSM_TAILS,
         ((slots,), jnp.bool_), ((), I32)])


def _slot_install(rows, dtype):
    return (lambda rows_, new, slot: kda.install_rows(
        rows_, new, slot, impl="pallas"),
        [rows, ((rows[0][0],) + rows[0][2:], dtype), ((), I32)])


def _flash_qk192_v128(S=1024):
    """The MLA prefill's flash forward: keys 192 wide, values 128 wide
    padded to them (``models/mla_moe._attend_full``)."""
    q = ((1, 32, S, 192), BF16)
    return (lambda q_, k_, v_: flash_attention_pallas(q_, k_, v_), [q, q, q])


def _flash(heads, kv_heads):
    q, kv = ((8, heads, 1024, 64), BF16), ((8, kv_heads, 1024, 64), BF16)
    return (jax.grad(lambda q, k, v: flash_attention_pallas(q, k, v)
                     .astype(F32).sum(), argnums=(0, 1, 2)), [q, kv, kv])


def _flash_8k(window):
    """The ``afmoe`` train cell's attention: GQA 32:4, head 128, 8,192
    positions; a sliding window of 2,048 keys, or the causal triangle."""
    q, kv = ((1, 32, 8192, 128), BF16), ((1, 4, 8192, 128), BF16)
    return (jax.grad(lambda q, k, v: flash_attention_pallas(
        q, k, v, window=window).astype(F32).sum(), argnums=(0, 1, 2)),
        [q, kv, kv])


def _grouped_matmul_train(rows=20480, hidden=2048, width=1024, held=16):
    """The held experts' gated FFN over one static chunk of the train
    cell, forward and backward (megablox gmm / tgmm under their
    custom_vjp)."""
    from apex_tpu.transformer.expert_parallel import _chunk_ffn

    def loss(x, wg, wu, wd, sizes):
        live = jnp.arange(rows) < jnp.sum(sizes)
        return _chunk_ffn(x, live, sizes, wg, wu, wd,
                          "pallas").astype(F32).sum()

    return (jax.grad(loss, argnums=(0, 1, 2, 3)),
            [((rows, hidden), BF16), ((held, hidden, width), BF16),
             ((held, hidden, width), BF16), ((held, width, hidden), BF16),
             ((held,), I32)])


def _grouped_matmul_serve(rows, groups=96, hidden=2048, width=1792):
    """A serving cell's expert layer over one static buffer, the
    stacked layers' experts side by side as groups, forward only; by
    default the LFM2 cell's (a decode step's 256 slots x top-4 or a
    prompt bucket x top-4 over three repeats' 32 experts)."""
    from apex_tpu.transformer.expert_parallel import grouped_gated_ffn

    return (lambda x, wg, wu, wd, sizes: grouped_gated_ffn(
                x, wg, wu, wd, sizes, impl="pallas"),
            [((rows, hidden), BF16), ((groups, hidden, width), BF16),
             ((groups, hidden, width), BF16), ((groups, width, hidden), BF16),
             ((groups,), I32)])


def _moe_combine(weighted, rows=20480, hidden=2048, tokens=16384, top_k=8):
    """The combine of one chunk of the train cell's expert layer as
    ``_held_chunks`` calls it: the grouped matmul's bf16 rows and their
    float32 routing weights in the forward, the bare bf16 cotangent in
    the backward, summed by token into the float32 carry in place.  No
    VMEM limit is asked for: the kernel compiles under the default
    scoped one or not at all."""
    from apex_tpu.transformer.expert_parallel import _sum_own

    def combine(out, y, w, token, valid, slot):
        return _sum_own(out, y, w if weighted else None, token, valid, slot,
                        0, "pallas")

    return (combine,
            [((tokens, hidden), F32), ((rows, hidden), BF16), ((rows,), F32),
             ((rows,), I32), ((rows,), jnp.bool_), ((tokens, top_k), I32)])


def _layer_norm(rows, hidden):
    def fwd_bwd(x, w, b, dy):
        y, mean, rstd = layer_norm_fwd_pallas(x, w, b, 1e-5)
        return y, layer_norm_bwd_pallas(x, w, dy, mean, rstd)

    return (fwd_bwd, [((rows, hidden), BF16), ((hidden,), F32),
                      ((hidden,), F32), ((rows, hidden), BF16)])


def _fused_ce(vocab, rows=8192, hidden=1024, embed=F32):
    def fwd_bwd(x, e, t, g):
        m, l, tgt = fused_ce_fwd_pallas(x, e, t)
        return tgt, fused_ce_bwd_pallas(x, e, t, m + jnp.log(l), g)

    return (fwd_bwd, [((rows, hidden), BF16), ((vocab, hidden), embed),
                      ((rows,), I32), ((rows,), F32)])


def _rms_norm(rows, hidden):
    def fwd_bwd(x, w, dy):
        y, mean, rstd = layer_norm_fwd_pallas(x, w, None, 1e-5, rms=True)
        return y, layer_norm_bwd_pallas(x, w, dy, mean, rstd, rms=True,
                                        with_bias=False)

    return (fwd_bwd, [((rows, hidden), BF16), ((hidden,), F32),
                      ((rows, hidden), BF16)])


#: EvaByte as its cell serves it: 20 slots, 32 heads of 128, page 128,
#: ONE page list a slot of 9 pages of pooled columns and 16 of its
#: window buffer, the 8-layer pool of 1 + 20 * 9 + 20 * 16 pages
_EVA_CELL = dict(B=20, D=128, page=128, P=25, pages=501, layers=8)
_EVA_POOL = ((8, 501, 32, 128, 128), BF16)


def _eva_summarise():
    return (lambda k, v, phi, mu, pages, first, closing, layer:
            eva.eva_summarise(k, v, phi, mu, pages, first, closing, layer,
                              16, impl="pallas"),
            [_EVA_POOL, _EVA_POOL, ((32, 128), BF16), ((32, 128), BF16),
             ((20,), I32), ((20,), I32), ((20,), jnp.bool_), ((), I32)])


def _eva_window_attention(pooled):
    row = ((2048, 32, 128), BF16)
    buf = ((pooled, 32, 128), BF16)
    return (lambda q, k, v, kt, vt, seen: eva.eva_window_attention(
        q, k, v, kt, vt, seen, impl="pallas"),
        [row, row, row, buf, buf, ((), I32)])


def _eva_write(width_tiles=None):
    """The decode step's column into the cell's pool, or (16 tiles) a
    prompt's open window into a slot's buffer."""
    if width_tiles is None:
        new = ((20, 32, 128), BF16)
        return (lambda k, v, kn, vn, pt, pos, act, layer: write_decode_kv(
            k, v, kn, vn, pt, pos, act, layer=layer, impl="pallas"),
            [_EVA_POOL, _EVA_POOL, new, new, ((20, 25), I32), ((20,), I32),
             ((20,), jnp.bool_), ((), I32)])
    new = ((8, 128 * width_tiles, 32, 128), BF16)
    return (lambda k, v, kn, vn, row, n: write_prompt_kv(
        k, v, kn, vn, row, n, impl="pallas"),
        [_EVA_POOL, _EVA_POOL, new, new, ((width_tiles,), I32), ((), I32)])


#: the block-generating cell (``sdar-30b-a3b.serve-blockgen-over``): 64
#: slots x a block of 4 rows, 32 query heads over 4 key/value heads of
#: 128, page 128, 10 pages a sequence, the 48-layer stacked pool of 320
#: pages at a traced layer
_BLOCK_CELL = dict(B=64, W=4, heads=32, kv=4, D=128, page=128, P=10,
                   pages=320, layers=48)
_BLOCK_POOL = ((48, 320, 4, 128, 128), BF16)


def _block_attn():
    from apex_tpu.ops.decode_attention_pallas import block_decode_attention

    c = _BLOCK_CELL
    return (lambda q, k, v, pt, n, layer: block_decode_attention(
        q, k, v, pt, n, c["W"], impl="pallas", layer=layer),
        [((c["B"] * c["W"], c["heads"], c["D"]), BF16), _BLOCK_POOL,
         _BLOCK_POOL, ((c["B"], c["P"]), I32), ((c["B"],), I32), ((), I32)])


def _block_attn_step():
    """The block STEP's call: the held block and the open one, 2W rows a
    slot as one group of 64 a key/value head, a length a half."""
    from apex_tpu.ops.decode_attention_pallas import block_decode_attention

    c = _BLOCK_CELL
    return (lambda q, k, v, pt, n, layer: block_decode_attention(
        q, k, v, pt, n, 2 * c["W"], impl="pallas", layer=layer),
        [((c["B"] * 2 * c["W"], c["heads"], c["D"]), BF16), _BLOCK_POOL,
         _BLOCK_POOL, ((c["B"], c["P"]), I32), ((c["B"], 2), I32), ((), I32)])


def _block_write(blocks=None, dtype=BF16):
    """One block a slot (``active`` (B,)), or with ``blocks`` the block
    step's call: that many side by side, ``active`` (B, blocks)."""
    from apex_tpu.inference.kv_cache import write_block_pools

    c = _BLOCK_CELL
    new = ((c["B"] * (blocks or 1) * c["W"], c["kv"], c["D"]), dtype)
    act = (c["B"], blocks) if blocks else (c["B"],)
    pool = (_BLOCK_POOL[0], dtype)
    return (lambda k, v, kn, vn, pt, pos, act, layer: write_block_pools(
        (k, v), (kn, vn), pt, pos, act, c["W"], layer=layer, impl="pallas"),
        [pool, pool, new, new, ((c["B"], c["P"]), I32),
         ((c["B"],), I32), (act, jnp.bool_), ((), I32)])


def _sample_confidence(temperature, rows=256, hidden=2048, vocab=18992):
    from apex_tpu.ops.decode_sampling_pallas import (
        fused_sample_confidence_pallas,
    )

    return (lambda x, e, s: fused_sample_confidence_pallas(
        x, e, s, temperature=temperature, exclude=vocab - 1),
        [((rows, hidden), BF16), ((vocab, hidden), BF16), ((rows,), U32)])


def _flash_block_causal(S=768):
    from apex_tpu.ops.attention import block_causal_attention

    return (lambda q, k, v: block_causal_attention(q, k, v, 4, impl="pallas"),
            [((1, 32, S, 128), BF16), ((1, 4, S, 128), BF16),
             ((1, 4, S, 128), BF16)])


#: name -> (fn, [(shape, dtype)...], kernel names the executable must hold)
CASES = {
    # generation by blocks, at its cell's shapes: a slot's 4 rows x 8
    # query heads ride one walk; the block's 4 columns into one tile;
    # the head with its confidence, greedy and drawn; the prefill's
    # block-causal flash forward at its longest bucket
    "block_attn_cell": (*_block_attn(), {"apex_decode_attention"}),
    "kv_write_block_cell": (*_block_write(), {"apex_kv_write"}),
    # the block STEP's calls: the held block beside the open one
    "block_attn_two_lengths_cell": (*_block_attn_step(),
                                    {"apex_decode_attention"}),
    "kv_write_two_blocks_cell": (*_block_write(2), {"apex_kv_write"}),
    # a float32 cache: the columns are placed by a matmul at HIGHEST
    "kv_write_two_blocks_fp32": (*_block_write(2, F32), {"apex_kv_write"}),
    "sample_confidence_greedy": (*_sample_confidence(0.0),
                                 {"apex_fused_sample"}),
    "sample_confidence_drawn": (*_sample_confidence(0.8),
                                {"apex_fused_sample"}),
    "flash_fwd_block4_s768": (*_flash_block_causal(), {"apex_flash_fwd"}),
    # serving, GPT-124M heads; 345M heads; GQA; MQA; the verify width
    "decode_attn_mha12": (*_decode_attn(12, 12, 1), {"apex_decode_attention"}),
    "decode_attn_mha16": (*_decode_attn(16, 16, 1), {"apex_decode_attention"}),
    "decode_attn_gqa16_4": (*_decode_attn(16, 4, 1), {"apex_decode_attention"}),
    "decode_attn_gqa32_8_lfm2_cell": (*_decode_attn(32, 8, 1, **_LFM2_CELL),
                                      {"apex_decode_attention"}),
    "decode_attn_gqa20_4_h1_cell": (*_decode_attn(20, 4, 1, **_H1_CELL),
                                    {"apex_decode_attention"}),
    "decode_attn_mqa": (*_decode_attn(12, 1, 1), {"apex_decode_attention"}),
    "decode_attn_verify5": (*_decode_attn(12, 12, 5),
                            {"apex_decode_attention"}),
    "decode_attn_page128": (*_decode_attn(20, 20, 1, page=128),
                            {"apex_decode_attention"}),
    "decode_attn_cell": (*_decode_attn(20, 20, 1, **_CELL),
                         {"apex_decode_attention"}),
    "decode_attn_gqa16_4_page128": (*_decode_attn(16, 4, 1, page=128),
                                    {"apex_decode_attention"}),
    "decode_attn_verify5_page128": (*_decode_attn(12, 12, 5, page=128),
                                    {"apex_decode_attention"}),
    # the in-place pool writes: decode token, verify rows, prompt;
    # page 16 (chip_smoke) and 128 (the benchmark's cells); an fp32
    # cache at head dim 128 splits a tile over blocks of heads
    "kv_write_decode": (*_kv_write(12, 1), {"apex_kv_write"}),
    "kv_write_gqa4": (*_kv_write(4, 1), {"apex_kv_write"}),
    "kv_write_verify5": (*_kv_write(12, 5), {"apex_kv_write"}),
    "kv_write_page128": (*_kv_write(20, 1, page=128), {"apex_kv_write"}),
    "kv_write_verify3_page128": (*_kv_write(20, 3, page=128),
                                 {"apex_kv_write"}),
    "kv_write_fp32_d128": (*_kv_write(20, 1, page=128, dtype=F32, D=128),
                           {"apex_kv_write"}),
    "kv_write_prompt": (*_prompt_write(12), {"apex_kv_write"}),
    "kv_write_prompt_page128": (*_prompt_write(20, page=128),
                                {"apex_kv_write"}),
    "sample_greedy": (*_sample(0.0, 0), {"apex_fused_sample"}),
    "sample_t1": (*_sample(1.0, 0), {"apex_fused_sample"}),
    "sample_t1_top40": (*_sample(1.0, 40), {"apex_fused_sample"}),
    "sample_t1_top40_verify5": (*_sample(1.0, 40, rows=40),
                                {"apex_fused_sample"}),
    # 128 rows of a 7,168-wide model over a 16,032-row bf16 head: the
    # vocabulary tile has to shrink to fit VMEM
    "sample_greedy_wide128": (*_sample(0.0, 0, rows=128, hidden=7168,
                                       vocab=16032, embed=BF16),
                              {"apex_fused_sample"}),
    "mla_decode_attn": (*_mla_decode(), {"apex_mla_decode_attention"}),
    # the second latent cell: 32 heads, 48 page slots a sequence
    "mla_decode_attn_h32_p48": (*_mla_decode(P=48, heads=32,
                                             pool=_LATENT_POOL_P48),
                                {"apex_mla_decode_attention"}),
    # a page under 128 lanes (chip_smoke's): the grid of page slots
    "mla_decode_attn_page8": (*_mla_decode(slots=8, P=12, heads=4, pool=(
        (2, 97, 1, 576, 8), BF16)), {"apex_mla_decode_attention"}),
    "latent_write_decode": (*_latent_write(), {"apex_kv_write"}),
    "latent_write_prompt": (*_latent_prompt_write(), {"apex_kv_write"}),
    # the recurrent state beside the pages: one token a slot in place,
    # the short convolution's step, the chunked prompt, the install of a
    # prefill's final state (a block of heads) and tails (a row of 16)
    "kda_decode": (*_kda_decode(), {"apex_kda_decode"}),
    "kda_conv_step": (*_kda_conv_step(), {"apex_kda_conv_step"}),
    "kda_chunked_1024": (*_kda_chunked(1024), {"apex_kda_chunk_scan"}),
    "slot_install_state": (*_slot_install(_KDA_STATE, F32),
                           {"apex_slot_install"}),
    "slot_install_tails": (*_slot_install(_KDA_TAILS, BF16),
                           {"apex_slot_install"}),
    # the second recurrence at the Falcon-H1 cell's shapes: 96 slots of
    # 32 heads x (128 x 256), the convolution's step over 5,120 channels
    # with a float32 filter, the installs of a 4 MB state and its tail
    "ssd_decode": (*_ssd_decode(), {"apex_ssd_decode"}),
    "ssm_conv_step": (*_ssm_conv_step(), {"apex_kda_conv_step"}),
    "slot_install_ssm_state": (*_slot_install(_SSM_STATE, F32),
                               {"apex_slot_install"}),
    "slot_install_ssm_tails": (*_slot_install(_SSM_TAILS, BF16),
                               {"apex_slot_install"}),
    # the windowed cache's kernels at the EvaByte cell's shapes: the
    # walk over one page list of pooled and own pages, the chunk
    # summary, a window of the prompt (with and without a pooled
    # buffer), the column and the open window's writes, a head of 320
    "eva_decode_attn_cell": (*_decode_attn(32, 32, 1, **_EVA_CELL),
                             {"apex_decode_attention"}),
    "eva_summarise": (*_eva_summarise(), {"apex_eva_summarise"}),
    "eva_window_attention_first": (*_eva_window_attention(0),
                                   {"apex_flash_fwd"}),
    "eva_window_attention_pooled": (*_eva_window_attention(1024),
                                    {"apex_flash_fwd"}),
    "eva_window_attention_pooled512": (*_eva_window_attention(512),
                                       {"apex_flash_fwd"}),
    "eva_write_decode": (*_eva_write(), {"apex_kv_write"}),
    "eva_write_open_window": (*_eva_write(16), {"apex_kv_write"}),
    "sample_greedy_v320": (*_sample(0.0, 0, rows=20, hidden=4096, vocab=320,
                                    embed=BF16, x_dtype=F32, dot_dtype=F32),
                           {"apex_fused_sample"}),
    "flash_fwd_qk192": (*_flash_qk192_v128(), {"apex_flash_fwd"}),
    # the latent family's prefill buckets' ends: one block, and a grid
    # of blocks whose static variants the grid indices choose among
    "flash_fwd_qk192_s512": (*_flash_qk192_v128(512), {"apex_flash_fwd"}),
    "flash_fwd_qk192_s4096": (*_flash_qk192_v128(4096), {"apex_flash_fwd"}),
    # training, GPT-345M and GPT-124M shapes
    "flash_345m": (*_flash(16, 16),
                   {"apex_flash_fwd", "apex_flash_dq", "apex_flash_dkv"}),
    "flash_124m": (*_flash(12, 12),
                   {"apex_flash_fwd", "apex_flash_dq", "apex_flash_dkv"}),
    "flash_gqa16_4": (*_flash(16, 4),
                      {"apex_flash_fwd", "apex_flash_dq", "apex_flash_dkv"}),
    "layer_norm_train": (*_layer_norm(8192, 1024),
                         {"apex_ln_fwd", "apex_ln_bwd"}),
    "layer_norm_decode": (*_layer_norm(8, 768), {"apex_ln_fwd", "apex_ln_bwd"}),
    "fused_ce": (*_fused_ce(VOCAB), {"apex_fused_ce_fwd", "apex_fused_ce_dx",
                                     "apex_fused_ce_dembed"}),
    "fused_ce_tp2_shard": (*_fused_ce(VOCAB // 2),
                           {"apex_fused_ce_fwd", "apex_fused_ce_dx",
                            "apex_fused_ce_dembed"}),
    # the table as the wrapper hands it over since PR 41, cast once to
    # the dot's dtype: the planner's blocks (256 x 2,048 forward, 512 x
    # 512 in dx and dembed), and a tp8 shard with no lane-aligned divisor
    "fused_ce_narrow_table": (*_fused_ce(VOCAB, embed=BF16),
                              {"apex_fused_ce_fwd", "apex_fused_ce_dx",
                               "apex_fused_ce_dembed"}),
    "fused_ce_narrow_tp8_shard": (*_fused_ce(VOCAB // 8, embed=BF16),
                                  {"apex_fused_ce_fwd", "apex_fused_ce_dx",
                                   "apex_fused_ce_dembed"}),
    # the afmoe train cell: a band of 2,048 keys and the causal triangle
    # at 8,192 positions under GQA 32:4; the fused cross entropy over an
    # eighth of a 200,192-row vocabulary at hidden 2,048 (a bf16 head:
    # its vocabulary block is clamped to VMEM); RMSNorm over 16,384
    # rows; one chunk of the held experts, forward and backward
    "flash_window_8k": (*_flash_8k(2048), {"apex_flash_fwd", "apex_flash_dq",
                                           "apex_flash_dkv"}),
    "flash_full_8k": (*_flash_8k(None), {"apex_flash_fwd", "apex_flash_dq",
                                         "apex_flash_dkv"}),
    "fused_ce_h2048_v25024": (*_fused_ce(25024, 16384, 2048, BF16),
                              {"apex_fused_ce_fwd", "apex_fused_ce_dx",
                               "apex_fused_ce_dembed"}),
    "rms_norm_8k": (*_rms_norm(16384, 2048), {"apex_ln_fwd", "apex_ln_bwd"}),
    "grouped_matmul_train": (*_grouped_matmul_train(), {"gmm", "tgmm"}),
    # the LFM2 serve cell's expert layer: the decode step's buffer and
    # the 1,024-token bucket's
    "grouped_matmul_serve_step": (*_grouped_matmul_serve(1024), {"gmm"}),
    "grouped_matmul_serve_1024": (*_grouped_matmul_serve(4096), {"gmm"}),
    # ... and the latent cell's: a contraction of 7,168, too deep for
    # one tile, over five layers' 16 held experts
    "grouped_matmul_serve_deep": (*_grouped_matmul_serve(
        1024, groups=80, hidden=7168, width=2048), {"gmm"}),
    # ... and the combine of its rows: 20,480 rows of 2,048 into 16,384
    # tokens, weighted (forward) and bare (backward)
    "moe_combine_weighted": (*_moe_combine(True), {"apex_moe_combine"}),
    "moe_combine_bare": (*_moe_combine(False), {"apex_moe_combine"}),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_lowers_for_tpu(name):
    fn, avals, _ = CASES[name]
    exp = jexport.export(jax.jit(fn), platforms=["tpu"])(
        *[jax.ShapeDtypeStruct(s, d) for s, d in avals])
    assert "tpu_custom_call" in exp.mlir_module()


def _pallas_calls(jaxpr, found=None):
    """Every ``pallas_call`` equation of a jaxpr, sub-jaxprs included."""
    found = [] if found is None else found
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            found.append(e)
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "jaxpr"):
                    _pallas_calls(sub.jaxpr, found)
                elif hasattr(sub, "eqns"):
                    _pallas_calls(sub, found)
    return found


def _lowered_grid_mapping(name):
    """The grid mapping of the one ``pallas_call`` a case lowers to."""
    fn, avals, _ = CASES[name]
    jaxpr = jax.make_jaxpr(fn)(
        *[jax.ShapeDtypeStruct(s, d) for s, d in avals])
    call, = _pallas_calls(jaxpr.jaxpr)
    return call.params["grid_mapping"]


def test_decode_attention_grid_at_the_cells_shapes():
    """A grid step of ``apex_decode_attention`` is a sequence row with
    ALL of its kv heads, and the kernel walks the row's live pages
    itself: at the serve cells' shapes the launcher plans 20 steps a
    layer (3,200 before PR 27), and the lowered kernel has that grid.
    A page under 128 lanes keeps the page slots in the grid."""
    from apex_tpu.ops.decode_attention_pallas import _plan

    def lowered_grid(name):
        return tuple(_lowered_grid_mapping(name).grid)

    B, P, page, heads = _CELL["B"], _CELL["P"], _CELL["page"], 20
    h_blk, grid, _ = _plan(B, heads, 1, _CELL["D"], P, page, BF16)
    assert h_blk == heads
    assert grid == (B, 1) == lowered_grid("decode_attn_cell")
    assert grid[0] * grid[1] <= B * P == 160
    # chip_smoke's shapes: page 16, 12 heads, 12 page slots a sequence
    assert _plan(8, 12, 1, 64, 12, 16, BF16) == (12, (8, 1, 12), 2)
    assert lowered_grid("decode_attn_mha12") == (8, 1, 12)
    # an fp32 cache at head dim 256 does not fit 20 heads a block: the
    # plan splits them, it does not overrun VMEM
    h_blk, grid, _ = _plan(B, heads, 1, 256, P, page, F32)
    assert 1 <= h_blk < heads and heads % h_blk == 0
    assert grid == (B, heads // h_blk)
    # a block step: a slot's 4 rows share their length, so they are one
    # grid step (32 query rows a key/value head, priced by the plan) and
    # the slot's pages are walked ONCE, not once a row
    c = _BLOCK_CELL
    h_blk, grid, _ = _plan(c["B"], c["kv"], c["W"] * c["heads"] // c["kv"],
                           c["D"], c["P"], c["page"], BF16)
    assert (h_blk, grid) == (c["kv"], (c["B"], 1))
    assert lowered_grid("block_attn_cell") == (c["B"], 1)


#: name: ((rows, kv heads, group, head dim, pages a sequence, dtype),
#: the head block and the ring's depth ``_plan`` gives): the five cells
#: that call the walk (the block cell with two blocks a slot folded into
#: the group), and shapes whose head block leaves the ring no third slot
_RING_PLANS = {
    "gpt2-large": ((20, 20, 1, 64, 8, BF16), (20, 3)),
    "lfm2-agentgen": ((256, 8, 4, 64, 24, BF16), (8, 3)),
    "falcon-h1": ((96, 4, 5, 128, 20, BF16), (4, 3)),
    "sdar-blockgen": ((64, 4, 64, 128, 10, BF16), (4, 3)),
    # 16 of 32 heads a block use 4.5 MiB of 8: three more slots of 1 MiB
    # would fit, three in all is the deepest ring
    "evabyte": ((20, 32, 1, 128, 25, BF16), (16, 3)),
    # 20 fp32 heads of 128 fill the budget (20.3 fit): nothing beside them
    "no-room-20-fp32-heads": ((8, 20, 1, 128, 8, F32), (20, 2)),
    # 15 of 30 such heads a block: room for the third slot
    "one-more-slot-15-of-30": ((8, 30, 1, 128, 8, F32), (15, 3)),
}


@pytest.mark.parametrize("name", sorted(_RING_PLANS))
def test_decode_attention_ring_depth_at_the_cells_shapes(name):
    """The walk's ring is as deep as the VMEM budget allows beside the
    head block that was planned without it, three slots at most (the
    sweep's knee) and never under two: 3 at every cell's shapes
    (PERF.md, PR 50)."""
    from apex_tpu.ops.decode_attention_pallas import (
        _VMEM_BUDGET, _head_bytes, _plan)

    (rows, kv, group, D, P, dtype), want = _RING_PLANS[name]
    h_blk, grid, depth = _plan(rows, kv, group, D, P, 128, dtype)
    assert (h_blk, depth) == want and grid == (rows, kv // h_blk)
    assert h_blk * _head_bytes(group, D, 128, dtype, depth) <= _VMEM_BUDGET
    if depth < 3:
        assert h_blk * _head_bytes(group, D, 128, dtype, depth + 1) \
            > _VMEM_BUDGET


def _dispatched(Sq, Sk, D, phase, block_q=None, block_k=None):
    """(bq, bk, sub) the dispatcher gives a bf16 call of this shape."""
    from apex_tpu.ops import flash_attention_pallas as fap

    bq, bk, (side, _, _) = fap.dispatched(Sq, Sk, D, BF16, phase,
                                          block_q=block_q, block_k=block_k)
    return bq, bk, side if side < max(bq, bk) else None


def _eva_blocks(pooled):
    """The blocks of the EVA window's forward over ``pooled`` buffer
    rows: ``ops/eva._window_pallas`` asks for ONE key block over buffer
    and window."""
    return _dispatched(2048, 2048 + pooled, 128, "fwd",
                       block_k=2048 + pooled if pooled else None)


def test_flash_attention_walk_at_the_cells_shapes():
    """The flash kernels walk a grid block in sub-tiles and visit the
    live triangle only.  At the train cell's shape (S 1024, D 64, bf16)
    the forward, dq and dkv each visit at most 5/8 of the square's
    sub-tiles, mask only those on the diagonal, and take no more grid
    steps a head than before PR 36 (1 forward, 4 each backward): the
    shape's row of the tuned table, as the lowered kernels have it.
    The latent prefill and the EVA window's first-window call visit no
    sub-tile wholly above the diagonal either; the EVA window's call
    over a pooled buffer takes ONE key block over buffer and window, as
    its caller asks (no state carried between grid steps), and walks
    the buffer's sub-tiles unmasked beside the window's triangle."""
    from apex_tpu.ops import flash_attention_pallas as fap

    S, D = 1024, 64
    fn, avals, _ = CASES["flash_345m"]
    grids = {e.params["name"]: tuple(e.params["grid_mapping"].grid)
             for e in _pallas_calls(jax.make_jaxpr(fn)(
                 *[jax.ShapeDtypeStruct(s, d) for s, d in avals]).jaxpr)}
    for phase, kernels, steps_before in (
            ("fwd", ("fwd",), 1), ("bwd", ("dq", "dkv"), 4)):
        row = fap.tuned_blocks(S, D, BF16, phase=phase)
        assert row is not None, "the train cell's shape has a measured row"
        bq, bk, sub = _dispatched(S, S, D, phase)
        assert (bq, bk) == row and sub and S % sub == 0
        steps = (S // bq) * (S // bk)
        assert steps <= steps_before
        for kernel in kernels:
            assert grids["apex_flash_" + kernel] == (
                (128, S // bq, S // bk) if kernel != "dkv"
                else (128, S // bk, S // bq))
            visited, masked, skipped, _ = fap.live_subtiles(
                kernel, S, S, 0, 0, bq, bk, sub)
            assert 8 * visited <= 5 * (visited + skipped)
            assert masked == S // sub          # the diagonal's, no other
            n = S // sub
            assert visited == n * (n + 1) // 2  # the triangle exactly

    # the EVA window (Sq 2048, D 128): pooled buffer, then the window
    bq, bk, sub = _dispatched(2048, 2048, 128, "fwd")
    assert (bq, bk) == (2048, 2048)
    n = 2048 // sub
    assert fap.live_subtiles("fwd", 2048, 2048, 0, 0, bq, bk, sub)[:2] == (
        n * (n + 1) // 2, n)
    for pooled in (512, 1024):
        Sk = 2048 + pooled
        bq, bk, sub = _eva_blocks(pooled)
        assert (bq, bk, sub) == (1024, Sk, 512)
        visited, masked, skipped, _ = fap.live_subtiles(
            "fwd", 2048, Sk, 0, -pooled, bq, bk, sub)
        n = 2048 // sub
        # the buffer whole, the window's triangle, its diagonal masked
        assert (visited, masked) == (
            n * (pooled // sub) + n * (n + 1) // 2, n)
        assert visited + skipped == n * (Sk // sub)
        assert tuple(_lowered_grid_mapping(
            "eva_window_attention_pooled" + "512" * (pooled == 512)
        ).grid) == (32, 2, 1)
    # the latent family's prefill (D 192), every bucket
    for S in (256, 512, 1024, 2048, 4096):
        bq, bk, sub = _dispatched(S, S, 192, "fwd")
        visited, masked, skipped, _ = fap.live_subtiles(
            "fwd", S, S, 0, 0, bq, bk, sub)
        n = S // (sub or bq)
        assert (visited, masked) == (n * (n + 1) // 2, n)


#: bodies (copies of the tile arithmetic in a kernel's code) a kernel of
#: a cell may hold: what PR 37 reads (train 7 each kernel; the EVA
#: window 8 / 10 / 12 over 0 / 512 / 1,024 pooled rows: a strip is the
#: buffer and the window's plain sub-tiles in runs of two, then its
#: diagonal's; the latent prefill 1, 3, 7, 18, 14 over its buckets).
#: And the bytes of the EVA window call's serialized module, 1.5 x what
#: PR 37 reads (13,058 / 17,374 / 18,359; the grid of blocks before it
#: 10,542 / 12,718 / 12,718).  PR 36's walk, a body a sub-tile, held 10
#: a train kernel and 36-68 an EVA window in 26,382 / 39,790 / 47,654
#: bytes, and its eight windowed prefill programs cost 32% of warm
#: set-up (ledger, PR 36: refused)
BODIES_CEILING = {"train": 7, "eva": 12, "latent": 18}
EVA_MODULE_BYTES_CEILING = {0: 19_600, 512: 26_000, 1024: 27_500}


def test_flash_code_size_at_the_cells_shapes():
    """What a flash kernel costs a program BEFORE it runs (trace, lower,
    compile, load; once a compiled program, eight in the windowed cell)
    grows with the code it emits, and the straight-line walk emits a
    body a run of sub-tiles: deterministic figures, held here on the
    CPU.  The counter's fourth figure at the cells' shapes, and the
    serialized module of the EVA window call as ``jax.export`` lowers
    it for the TPU."""
    from jax import export

    from apex_tpu.ops import flash_attention_pallas as fap

    bodies = lambda kernel, Sq, Sk, k_offset, blocks: fap.live_subtiles(
        kernel, Sq, Sk, 0, k_offset, *blocks)[3]
    for kernel in ("fwd", "dq", "dkv"):
        phase = "fwd" if kernel == "fwd" else "bwd"
        assert bodies(kernel, 1024, 1024, 0, _dispatched(
            1024, 1024, 64, phase)) <= BODIES_CEILING["train"], kernel
    for S in (256, 512, 1024, 2048, 4096):
        assert bodies("fwd", S, S, 0, _dispatched(S, S, 192, "fwd")) \
            <= BODIES_CEILING["latent"], S
    for pooled in (0, 512, 1024):
        Sk = 2048 + pooled
        blocks = _eva_blocks(pooled)
        assert fap.live_subtiles("fwd", 2048, Sk, 0, -pooled, *blocks)[3] \
            <= BODIES_CEILING["eva"], pooled
        fn, avals = _eva_window_attention(pooled)
        module = export.export(jax.jit(fn), platforms=["tpu"])(
            *[jax.ShapeDtypeStruct(s, d) for s, d in avals])
        size = len(module.mlir_module_serialized)
        assert size <= EVA_MODULE_BYTES_CEILING[pooled], (pooled, size)


def test_flash_window_walk_at_the_train_cells_shape():
    """A band of 2,048 keys at 8,192 positions: each kernel visits the
    sub-tiles the band touches and no other (by the same plans its code
    is built from), masks the two edges' only, and its grid walks the
    band's blocks, not the square."""
    from apex_tpu.ops import flash_attention_pallas as fap

    S, W, D = 8192, 2048, 128
    for kernel in ("fwd", "dq", "dkv"):
        phase = "fwd" if kernel == "fwd" else "bwd"
        bq, bk, sub = _dispatched(S, S, D, phase)
        side = sub or bq
        n, w = S // side, W // side
        visited, masked, skipped, bodies = fap.live_subtiles(
            kernel, S, S, 0, 0, bq, bk, sub, window=W)
        # per strip: the diagonal's tile, w - 1 whole ones under it and
        # the lower edge's (W is a multiple of the tile)
        want = sum(min(r, w) + 1 for r in range(n))
        assert visited == want and visited + skipped == n * n, kernel
        assert masked == n + (n - w), kernel
        causal = fap.live_subtiles(kernel, S, S, 0, 0, bq, bk, sub)
        assert visited < 0.55 * causal[0], kernel
        assert bodies <= 24, (kernel, bodies)
        band = fap._band(kernel, W, 0, 0, bq, bk, S // bq, S // bk)
        assert band.n_live == W // (bq if kernel == "dkv" else bk) + 1
    # the lowered grid of the forward: 32 heads x query blocks x the
    # band's key blocks
    _, avals, _ = CASES["flash_window_8k"]
    call, = _pallas_calls(jax.make_jaxpr(
        lambda q, k, v: flash_attention_pallas(q, k, v, window=W))(
            *[jax.ShapeDtypeStruct(s, d) for s, d in avals]).jaxpr)
    grid = tuple(call.params["grid_mapping"].grid)
    bq, bk, _ = _dispatched(S, S, D, "fwd")
    assert grid == (32, S // bq, W // bk + 1), grid


def test_mla_decode_grid_at_the_cells_shapes():
    """A grid step of ``apex_mla_decode_attention`` is a SEQUENCE, and
    the kernel walks its live tiles itself out of a pool left in HBM:
    128 steps a layer at both latent cells' shapes (256 and 768 before
    PR 33, eight BlockSpec tiles a step, dead ones fetched at every
    change of sequence).  A page under 128 lanes keeps the page slots
    in the grid and its tiles in BlockSpecs."""
    from apex_tpu.ops.mla_decode_pallas import _plan

    def lowered(name):
        mapping = _lowered_grid_mapping(name)
        pools = [str(getattr(m.transformed_block_aval, "memory_space", None))
                 for m in mapping.block_mappings
                 if m.array_aval.shape == CASES[name][1][1][0]]
        return tuple(mapping.grid), pools

    for name, P in (("mla_decode_attn", 16), ("mla_decode_attn_h32_p48", 48)):
        grid, pools = lowered(name)
        assert grid == (128,) == _plan(128, P, 128)[0]
        assert pools == ["any"]          # one operand, never a VMEM block
    grid, pools = lowered("mla_decode_attn_page8")
    assert grid == (8, 2) == _plan(8, 12, 8)[0]
    assert pools == ["None"] * 6         # six BlockSpec tiles a step


#: a compile-only v5e device, or one JSON line {"skip": why} and exit 0
_DESCRIBED_V5E = """
import json, os, sys
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
import jax
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    dev = topologies.get_topology_desc(
        topology_name="v5e:2x2", platform="tpu").devices[0]
except Exception as e:  # no compile-only TPU client on this installation
    print(json.dumps({"skip": f"{type(e).__name__}: {e}"[:300]}))
    sys.exit(0)
"""

_AOT_CHILD = _DESCRIBED_V5E + """
sys.path.insert(0, "tests")
from test_tpu_bringup import CASES
from apex_tpu.analysis.lowered import pallas_kernels
out = {}
for name, (fn, avals, want) in sorted(CASES.items()):
    args = [jax.ShapeDtypeStruct(s, d, sharding=SingleDeviceSharding(dev))
            for s, d in avals]
    try:
        found = set(pallas_kernels(jax.jit(fn).lower(*args).compile()))
        out[name] = sorted(want - found)
    except Exception as e:
        out[name] = f"{type(e).__name__}: {e}"[:600]
print(json.dumps({"device_kind": dev.device_kind, "missing": out}))
"""


def test_kernels_compile_for_v5e_without_a_chip():
    """The full XLA:TPU + Mosaic backend compile of every case, on a
    compile-only v5e client (libtpu builds one from a topology name; no
    device is opened).  In a child: libtpu stays out of this process."""
    r = subprocess.run([sys.executable, "-c", _AOT_CHILD], cwd=str(REPO),
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    if "skip" in out:
        pytest.skip(f"no compile-only TPU client: {out['skip']}")
    bad = {k: v for k, v in out["missing"].items() if v}
    assert not bad, (f"on {out['device_kind']}: kernels that failed to "
                     f"compile, or compiled without their name: {bad}")


_TRAIN_CHUNK_CHILD = _DESCRIBED_V5E + """
sys.path.insert(0, "tests")
from test_tpu_bringup import CASES
from apex_tpu.analysis.lowered import pallas_kernels
fn, avals, _ = CASES["grouped_matmul_train"]
args = [jax.ShapeDtypeStruct(s, d, sharding=SingleDeviceSharding(dev))
        for s, d in avals]
try:
    out = {"kernels": pallas_kernels(jax.jit(fn).lower(*args).compile())}
except Exception as e:
    out = {"error": f"{type(e).__name__}: {e}"[-1500:]}
print(json.dumps(out))
"""


def _lowered_blocks(fn, avals):
    """The blocks of both operands and of the output of every
    ``pallas_call`` ``fn`` lowers to, sorted."""
    calls = _pallas_calls(jax.make_jaxpr(fn)(
        *[jax.ShapeDtypeStruct(s, d) for s, d in avals]).jaxpr)
    return sorted(tuple(
        tuple(d.block_size for d in m.block_shape if hasattr(d, "block_size"))
        for m in call.params["grid_mapping"].block_mappings)
        for call in calls)


def test_the_train_chunks_grouped_matmuls_compile_at_their_own_tiles():
    """One chunk of the train cell's experts, forward and backward: nine
    megablox kernels, each at the tiles ``grouped_tiling`` plans for ITS
    product (an expert's whole 2,048 x 1,024 block resident under 256
    rows in the gate, the up, the down and both cotangents; ``tgmm`` at
    the tiles every call had), and Mosaic takes every one under the
    default scoped VMEM limit on a described v5e."""
    from apex_tpu.transformer.expert_parallel import grouped_tiling

    fn, avals, _ = CASES["grouped_matmul_train"]
    (M, H), (G, _, F) = avals[0][0], avals[1][0]
    tiles = _lowered_blocks(fn, avals)
    want = []
    for product, k, n, times in (("gmm", H, F, 2), ("gmm", F, H, 1),
                                 ("gmm_t", H, F, 1), ("gmm_t", F, H, 2),
                                 ("tgmm", H, F, 2), ("tgmm", F, H, 1)):
        tm, tk, tn = grouped_tiling(product, M, G, k, n, BF16)
        want += [{"gmm": ((tm, tk), (tk, tn), (tm, tn)),
                  "gmm_t": ((tm, tk), (tn, tk), (tm, tn)),
                  "tgmm": ((tm, tk), (tm, tn), (tk, tn))}[product]] * times
    assert tiles == sorted(want)
    assert ((256, 2048), (2048, 1024), (256, 1024)) in tiles
    assert ((256, 1024), (1024, 2048), (256, 2048)) in tiles
    r = subprocess.run([sys.executable, "-c", _TRAIN_CHUNK_CHILD],
                       cwd=str(REPO), capture_output=True, text=True,
                       timeout=600,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    if "skip" in out:
        pytest.skip(f"no compile-only TPU client: {out['skip']}")
    assert "error" not in out, out
    # the down projection's forward feeds nothing a sum's gradient needs
    assert sorted(out["kernels"]) == ["gmm"] * 5 + ["tgmm"] * 3


@pytest.mark.parametrize("name,gate", [
    ("grouped_matmul_serve_step", (128, 2048, 896)),
    ("grouped_matmul_serve_1024", (128, 2048, 896)),
    ("grouped_matmul_serve_deep", (128, 1024, 2048))])
def test_a_serving_buffers_grouped_matmuls_lower_at_the_plans_tiles(name,
                                                                    gate):
    """A serving cell's three projections as lowered: each kernel's
    blocks are what ``grouped_tiling`` plans for the call, the whole
    contraction in one tile unless it is 7,168 deep
    (``test_kernels_compile_for_v5e_without_a_chip`` hands the same
    cases to Mosaic, which refuses a block set past the scoped VMEM
    limit)."""
    from apex_tpu.transformer.expert_parallel import grouped_tiling

    fn, avals, _ = CASES[name]
    (M, H), (G, _, F) = avals[0][0], avals[1][0]
    tiles = _lowered_blocks(fn, avals)
    assert grouped_tiling("gmm", M, G, H, F, BF16) == gate
    want = []
    for k, n, times in ((H, F, 2), (F, H, 1)):
        tm, tk, tn = grouped_tiling("gmm", M, G, k, n, BF16)
        want += [((tm, tk), (tk, tn), (tm, tn))] * times
    assert tiles == sorted(want)


# --------------------------------------------- the pool stays where it is
_POOL_CHILD = _DESCRIBED_V5E + """
import jax.numpy as jnp
from apex_tpu.analysis.lowered import large_result_instructions
from apex_tpu.inference import DecodeConfig, KVCacheConfig, alloc_pools
from apex_tpu.inference.decode import make_decode_step, make_prefill
from apex_tpu.models.gpt import GPTConfig, init_params

# GPT-2 large as the benchmark's serve cells run it: 20 slots x 1,024
# tokens at page 128, prompts padded to 768, kernels forced
B, PAGE, PPS, S = 20, 128, 8, 768
cfg = GPTConfig(vocab_size=50304, hidden_size=1280, num_layers=36,
                num_attention_heads=20, max_seq_len=1024,
                position_embedding_type="learned",
                compute_dtype=jnp.bfloat16, checkpoint_layers=False)
dcfg = DecodeConfig(
    cache=KVCacheConfig(num_pages=1 + B * PPS, page_size=PAGE,
                        pages_per_seq=PPS, dtype=jnp.bfloat16),
    max_batch=B, max_prompt_len=S, temperature=0.0,
    attn_impl="pallas", sample_impl="pallas")
sh = SingleDeviceSharding(dev)
put = lambda tree: jax.tree.map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)
arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
# the tree the scheduler holds: given fp32, matrices cast once
params = put(jax.eval_shape(lambda: cfg.served_model().serving_params(
    init_params(cfg, jax.random.PRNGKey(0)))))
pools = put(jax.eval_shape(lambda: alloc_pools(
    cfg.num_layers, cfg.kv_heads, cfg.head_dim, dcfg.cache)))
I, U = jnp.int32, jnp.uint32
programs = {
    "decode_step": (make_decode_step(cfg, dcfg), (
        params, pools, arg((B,), I), arg((B,), I), arg((B,), jnp.bool_),
        arg((B, PPS), I), arg((B,), U))),
    "prefill": (make_prefill(cfg, dcfg), (
        params, pools, arg((1, S), I), arg((), I), arg((), I),
        arg((PPS,), I), arg((), U))),
}
layer_pool = pools["k"].size // cfg.num_layers
matrix = params["layers"]["wq"].size     # the smallest stacked matrix
out = {"pool_bytes": pools["k"].size * 2}
for name, (fn, args) in programs.items():
    try:
        c = fn.lower(*args).compile()
    except Exception as e:
        out[name] = {"error": f"{type(e).__name__}: {e}"[:1500]}
        continue
    found = large_result_instructions(
        c, layer_pool, containing=(dcfg.cache.num_pages, cfg.kv_heads))
    show = lambda found: [
        [i["name"], i["opcode"],
         "tpu_custom_call" in i["line"]
         and "output_to_operand_aliasing" in i["line"]]
        for i in found]
    out[name] = {
        "temp_bytes": c.memory_analysis().temp_size_in_bytes,
        "instructions": show(found),
        "matrix_sized": show(large_result_instructions(c, matrix))}
print(json.dumps(out))
"""

# the latent-attention, sparse-expert family as its benchmark cell runs
# it: published widths, 1 dense + 5 expert layers, 16 of 256 experts
# held, 128 slots x 2,048 positions at page 128, one pool of 576 values
_LATENT_POOL_CHILD = _DESCRIBED_V5E + """
import jax.numpy as jnp
from apex_tpu.analysis.lowered import large_result_instructions
from apex_tpu.inference import DecodeConfig, KVCacheConfig
from apex_tpu.inference.decode import make_decode_step, make_prefill
from apex_tpu.inference.kv_cache import COUNTERS, alloc_named_pools
from apex_tpu.models.mla_moe import MLAMoEConfig, init_params

B, PAGE, PPS, S = 128, 128, 16, 512
cfg = MLAMoEConfig(vocab_size=16032, num_dense_layers=1, num_moe_layers=5,
                   held_start=0, held_count=16)
dcfg = DecodeConfig(
    cache=KVCacheConfig(num_pages=1 + B * PPS, page_size=PAGE,
                        pages_per_seq=PPS, dtype=jnp.bfloat16),
    max_batch=B, max_prompt_len=1024, prefill_buckets=(256, 512),
    temperature=0.0, attn_impl="pallas", sample_impl="pallas")
sh = SingleDeviceSharding(dev)
put = lambda tree: jax.tree.map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)
arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
model = cfg.served_model()
params = put(jax.eval_shape(lambda: model.serving_params(
    init_params(cfg, jax.random.PRNGKey(0)))))
pools = put(jax.eval_shape(lambda: dict(
    alloc_named_pools(model.cache_spec(), dcfg.cache),
    **{COUNTERS: jnp.zeros((len(model.counter_names),), jnp.int32)})))
I, U = jnp.int32, jnp.uint32
programs = {
    "decode_step": (make_decode_step(cfg, dcfg), (
        params, pools, arg((B,), I), arg((B,), I), arg((B,), jnp.bool_),
        arg((B, PPS), I), arg((B,), U))),
    "prefill": (make_prefill(cfg, dcfg), (
        params, pools, arg((1, S), I), arg((), I), arg((), I),
        arg((PPS,), I), arg((), U))),
}
layer_pool = pools["latent"].size // cfg.num_layers
# more than ONE LAYER of the largest leaf (a layer's 16 held experts):
# here one layer of a leaf is as large as the smaller leaves' stacks, and
# the layer loop may well slice it; only a whole stack is larger
matrix = 1 + params["moe"]["we_gate"].size // cfg.num_moe_layers
out = {"pool_bytes": pools["latent"].size * 2}
for name, (fn, args) in programs.items():
    try:
        c = fn.lower(*args).compile()
    except Exception as e:
        out[name] = {"error": f"{type(e).__name__}: {e}"[:1500]}
        continue
    found = large_result_instructions(
        c, layer_pool, containing=(dcfg.cache.num_pages, 1, 576))
    show = lambda found: [
        [i["name"], i["opcode"],
         "tpu_custom_call" in i["line"]
         and "output_to_operand_aliasing" in i["line"]]
        for i in found]
    out[name] = {
        "temp_bytes": c.memory_analysis().temp_size_in_bytes,
        "instructions": show(found),
        "matrix_sized": show(large_result_instructions(c, matrix))}
print(json.dumps(out))
"""

# the KDA/MLA family as its benchmark cell runs it: published widths,
# 13 layers (10 KDA, 3 MLA), 32 of 256 experts held, 128 slots x 6,144
# positions at page 128: a latent pool for 3 layers beside 129 rows of
# float32 state and of convolution tails for 10
_KDA_POOL_CHILD = _DESCRIBED_V5E + """
import jax.numpy as jnp
from pathlib import Path
import apex_tpu.utils.platform as platform
platform.on_tpu = lambda: True      # 'auto' impls: as on the chip
from apex_tpu.analysis.lowered import large_result_instructions
from apex_tpu.inference import DecodeConfig, KVCacheConfig
from apex_tpu.inference.decode import make_decode_step, make_prefill
from apex_tpu.inference.kv_cache import COUNTERS, alloc_named_pools
from apex_tpu.models.mla_moe import init_params
from cellbench.adapters.serve_kda_mla_moe import model_config

B, PAGE, PPS, S = 128, 128, 48, 1024
cfg = model_config(json.loads(Path(
    "cellbench/configs/kimi-linear-48b-a3b-serve-ep8.json").read_text()))
dcfg = DecodeConfig(
    cache=KVCacheConfig(num_pages=1 + B * PPS, page_size=PAGE,
                        pages_per_seq=PPS, dtype=jnp.bfloat16),
    max_batch=B, max_prompt_len=4096, prefill_buckets=(512, 1024, 2048),
    temperature=0.0, attn_impl="pallas", sample_impl="pallas")
sh = SingleDeviceSharding(dev)
put = lambda tree: jax.tree.map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)
arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
model = cfg.served_model()
params = put(jax.eval_shape(lambda: model.serving_params(
    init_params(cfg, jax.random.PRNGKey(0)))))
pools = put(jax.eval_shape(lambda: dict(
    alloc_named_pools(model.cache_spec(), dcfg.cache, slots=B),
    **{COUNTERS: jnp.zeros((len(model.counter_names),), jnp.int32)})))
I, U = jnp.int32, jnp.uint32
programs = {
    "decode_step": (make_decode_step(cfg, dcfg), (
        params, pools, arg((B,), I), arg((B,), I), arg((B,), jnp.bool_),
        arg((B, PPS), I), arg((B,), U))),
    "prefill": (make_prefill(cfg, dcfg), (
        params, pools, arg((1, S), I), arg((), I), arg((), I),
        arg((PPS,), I), arg((), U), arg((), I))),
}
per_layer = lambda name, kind: pools[name].size // cfg.count(kind)
# more than one layer of the largest leaf (a layer's 32 held experts)
matrix = 1 + params["kda_moe"]["we_gate"].size // 9
nbytes = lambda a: a.size * a.dtype.itemsize
out = {"pool_bytes": nbytes(pools["latent"]),
       "step_bytes": None, "chip_bytes": 16 * 2 ** 30}
for name, (fn, args) in programs.items():
    try:
        c = fn.lower(*args).compile()
    except Exception as e:
        out[name] = {"error": f"{type(e).__name__}: {e}"[:1500]}
        continue
    show = lambda found: [
        [i["name"], i["opcode"],
         "tpu_custom_call" in i["line"]
         and "output_to_operand_aliasing" in i["line"]]
        for i in found]
    mem = c.memory_analysis()
    out[name] = {
        "temp_bytes": mem.temp_size_in_bytes,
        "program_bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes,
        "instructions": show(large_result_instructions(
            c, per_layer("latent", "mla"),
            containing=(dcfg.cache.num_pages, 1, 576))),
        "state_instructions": show(large_result_instructions(
            c, per_layer("kda_state", "kda"),
            containing=(B + 1, 32, 128, 128))),
        "tails_instructions": show(large_result_instructions(
            c, per_layer("kda_conv", "kda"), containing=(B + 1, 36864))),
        "matrix_sized": show(large_result_instructions(c, matrix))}
print(json.dumps(out))
"""

# Falcon-H1 as its benchmark cell runs it: published widths, layers 1-8,
# a quarter of the vocabulary, 96 slots, 1,024 pages of 128, the longest
# prefill (1,536 tokens): every layer writes K/V pages AND a slot's rows
_H1_POOL_CHILD = _DESCRIBED_V5E + """
import jax.numpy as jnp
from pathlib import Path
import apex_tpu.utils.platform as platform
platform.on_tpu = lambda: True      # 'auto' impls: as on the chip
from apex_tpu.analysis.lowered import (
    large_result_instructions, pallas_kernels,
)
from apex_tpu.inference.decode import make_decode_step, make_prefill
from apex_tpu.inference.kv_cache import COUNTERS, alloc_named_pools
from apex_tpu.models.falcon_h1 import init_params
from cellbench.adapters.serve_falcon_h1 import decode_config, model_config

conf = json.loads(Path(
    "cellbench/configs/falcon-h1-34b-serve-pp9.json").read_text())
cfg, dcfg = model_config(conf), decode_config(conf, 0)
B, PPS, S = dcfg.max_batch, dcfg.cache.pages_per_seq, dcfg.max_prompt_len
sh = SingleDeviceSharding(dev)
put = lambda tree: jax.tree.map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)
arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
model = cfg.served_model()
params = put(jax.eval_shape(lambda: model.serving_params(
    init_params(cfg, jax.random.PRNGKey(0)))))
pools = put(jax.eval_shape(lambda: dict(
    alloc_named_pools(model.cache_spec(), dcfg.cache, slots=B),
    **{COUNTERS: jnp.zeros((len(model.counter_names),), jnp.int32)})))
I, U = jnp.int32, jnp.uint32
programs = {
    "decode_step": (make_decode_step(cfg, dcfg), (
        params, pools, arg((B,), I), arg((B,), I), arg((B,), jnp.bool_),
        arg((B, PPS), I), arg((B,), U))),
    "prefill": (make_prefill(cfg, dcfg), (
        params, pools, arg((1, S), I), arg((), I), arg((), I),
        arg((PPS,), I), arg((), U), arg((), I))),
}
L = cfg.num_hidden_layers
# more than one layer of the largest leaf: only a whole stack is larger
matrix = 1 + params["layers"]["w_gate"].size // L
nbytes = lambda a: a.size * a.dtype.itemsize
out = {"pool_bytes": nbytes(pools["k"]), "chip_bytes": 16 * 2 ** 30,
       "weight_bytes": sum(nbytes(a) for a in jax.tree.leaves(params)),
       "state_bytes": nbytes(pools["ssm_state"])}
for name, (fn, args) in programs.items():
    try:
        c = fn.lower(*args).compile()
    except Exception as e:
        out[name] = {"error": f"{type(e).__name__}: {e}"[:1500]}
        continue
    show = lambda found: [
        [i["name"], i["opcode"],
         "tpu_custom_call" in i["line"]
         and "output_to_operand_aliasing" in i["line"]]
        for i in found]
    mem = c.memory_analysis()
    out[name] = {
        "kernels": sorted(set(pallas_kernels(c))),
        "temp_bytes": mem.temp_size_in_bytes,
        "program_bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes,
        "instructions": show(large_result_instructions(
            c, pools["k"].size // L,
            containing=(dcfg.cache.num_pages, 4, 128))),
        "state_instructions": show(large_result_instructions(
            c, pools["ssm_state"].size // L,
            containing=(B + 1, 32, 128, 256))),
        "tails_instructions": show(large_result_instructions(
            c, pools["ssm_conv"].size // L, containing=(B + 1, 15360))),
        "matrix_sized": show(large_result_instructions(c, matrix))}
print(json.dumps(out))
"""

_BLOCK_POOL_CHILD = _DESCRIBED_V5E + """
import jax.numpy as jnp
from pathlib import Path
import apex_tpu.utils.platform as platform
platform.on_tpu = lambda: True      # 'auto' impls: as on the chip
from apex_tpu.analysis.lowered import (
    large_result_instructions, pallas_kernels,
)
from apex_tpu.inference.decode import (
    init_block_state, make_block_prefill, make_block_step,
)
from apex_tpu.inference.kv_cache import COUNTERS, alloc_named_pools
from apex_tpu.models.sdar_moe import init_params
from cellbench.adapters.serve_sdar_moe import decode_config, model_config

conf = json.loads(Path(
    "cellbench/configs/sdar-30b-a3b-serve-ep8.json").read_text())
cfg, dcfg = model_config(conf), decode_config(conf, 0)
B, PPS, S = dcfg.max_batch, dcfg.cache.pages_per_seq, dcfg.max_prompt_len
sh = SingleDeviceSharding(dev)
put = lambda tree: jax.tree.map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)
arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
model = cfg.served_model()
params = put(jax.eval_shape(lambda: model.serving_params(
    init_params(cfg, jax.random.PRNGKey(0)))))
pools = put(jax.eval_shape(lambda: dict(
    alloc_named_pools(model.cache_spec(), dcfg.cache, slots=B),
    **{COUNTERS: jnp.zeros((len(model.counter_names),), jnp.int32)})))
blocks = put(jax.eval_shape(
    lambda: init_block_state(B, cfg.block_length, cfg.mask_id)))
I, U = jnp.int32, jnp.uint32
programs = {
    "decode_step": (make_block_step(cfg, dcfg), (
        params, pools, blocks, arg((B,), I), arg((B,), I),
        arg((B,), jnp.bool_), arg((B, PPS), I), arg((B,), U))),
    "prefill": (make_block_prefill(cfg, dcfg), (
        params, pools, arg((1, S), I), arg((), I), arg((PPS,), I))),
}
L = cfg.num_hidden_layers
# more than one layer of the largest leaf: only a whole stack is larger
matrix = 1 + params["layers"]["we_gate"].size // L
nbytes = lambda a: a.size * a.dtype.itemsize
out = {"pool_bytes": nbytes(pools["k"]), "chip_bytes": 16 * 2 ** 30,
       "weight_bytes": sum(nbytes(a) for a in jax.tree.leaves(params))}
for name, (fn, args) in programs.items():
    try:
        c = fn.lower(*args).compile()
    except Exception as e:
        out[name] = {"error": f"{type(e).__name__}: {e}"[:1500]}
        continue
    show = lambda found: [
        [i["name"], i["opcode"],
         "tpu_custom_call" in i["line"]
         and "output_to_operand_aliasing" in i["line"]]
        for i in found]
    mem = c.memory_analysis()
    out[name] = {
        "kernels": sorted(set(pallas_kernels(c))),
        "temp_bytes": mem.temp_size_in_bytes,
        "program_bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes,
        "instructions": show(large_result_instructions(
            c, pools["k"].size // L,
            containing=(dcfg.cache.num_pages, 4, 128))),
        "matrix_sized": show(large_result_instructions(c, matrix))}
print(json.dumps(out))
"""

_AFMOE_STEP_CHILD = _DESCRIBED_V5E + """
import re
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import apex_tpu.utils.platform as platform
platform.on_tpu = lambda: True      # the impls that ask choose the kernels
from apex_tpu.analysis.lowered import pallas_kernels
from apex_tpu.models.gpt import make_train_step
from apex_tpu.optimizers import FusedAdam
from apex_tpu.transformer import parallel_state as ps
from cellbench.adapters.train_afmoe import program_config

# the train cell as cellbench/adapters/train_afmoe.py builds it: the
# committed configuration, 2 x 8,192 tokens a step
conf = json.load(open("cellbench/configs/trinity-mini-26b-a3b-train-ep8.json"))
args = conf["cellbench"]["args"]
config = program_config(conf, args)
family = config.train_family()
mesh = ps.initialize_model_parallel(
    tensor_model_parallel_size_=1, pipeline_model_parallel_size_=1,
    devices=[dev])
optimizer = FusedAdam(lr=3e-4, betas=tuple(args["betas"]), eps=args["eps"],
                      weight_decay=args["weight_decay"],
                      param_group_fn=family.weight_decay_group,
                      group_hypers={"gain": {"weight_decay": 0.0}})
sh = NamedSharding(mesh, P())
put = lambda tree: jax.tree.map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)
params = jax.eval_shape(lambda: family.init_params(jax.random.PRNGKey(0)))
state = jax.eval_shape(lambda p: optimizer.init(family.split(p)[0]), params)
B, S = 2, int(args["seq"])
tokens = jax.ShapeDtypeStruct((B, S), jnp.int32,
                              sharding=NamedSharding(mesh, P("dp", None)))
step = make_train_step(config, optimizer, mesh, donate_state=True)
try:
    compiled = step.lower(put(params), put(state), tokens, tokens).compile()
    mem = compiled.memory_analysis()
    assignments = B * S * config.num_experts_per_tok
    # every array with T * top_k rows and a second dimension
    wide = sorted(set(re.findall(r"[a-z0-9]+\\[%d,[0-9,]+\\]" % assignments,
                                 compiled.as_text())))
    out = {"bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes
           + mem.output_size_in_bytes - mem.alias_size_in_bytes,
           "arguments": mem.argument_size_in_bytes,
           "kernels": sorted(set(pallas_kernels(compiled))),
           "aliased": len(re.findall(r"(?:may|must)-alias",
                                     compiled.as_text().splitlines()[0])),
           "donated": len(jax.tree.leaves((params, state))),
           "assignment_rows": assignments, "wide": wide,
           "buffer_rows": config.buffer_rows(B * S),
           "parameters": sum(x.size for x in jax.tree.leaves(
               family.split(params)[0]))}
except Exception as e:
    out = {"error": f"{type(e).__name__}: {e}"[:1500]}
print(json.dumps(out))
"""


def test_the_afmoe_train_step_fits_a_v5e_and_holds_no_assignment_wide_buffer():
    """The family's train step at the cell's sizes (705.5M parameters in
    float32 with Adam's moments, 2 x 8,192 tokens, full remat, every
    kernel), compiled for a v5e without a chip: it fits the chip's
    15.75 GB with the state filling over 70% of it, every kernel of the
    path is in it, and no array of it has ``T x top_k`` rows and a
    second dimension (the expert layer walks a static chunk of the held
    share)."""
    r = subprocess.run([sys.executable, "-c", _AFMOE_STEP_CHILD],
                       cwd=str(REPO), capture_output=True, text=True,
                       timeout=900,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    if "skip" in out:
        pytest.skip(f"no compile-only TPU client: {out['skip']}")
    assert "error" not in out, out
    assert 705.4e6 < out["parameters"] < 705.6e6
    assert 11.3e9 < out["bytes"] < 15.75e9, out["bytes"]
    assert out["arguments"] >= 12 * out["parameters"]
    # in place: every leaf of the parameters, of both moments and of the
    # family's state comes out in the buffer it came in, but the one the
    # step writes without reading (``last_load``: jit drops the argument)
    assert out["aliased"] == out["donated"] - 1 > 3 * 70, out
    assert set(out["kernels"]) >= {
        "apex_flash_fwd", "apex_flash_dq", "apex_flash_dkv", "apex_ln_fwd",
        "apex_ln_bwd", "apex_fused_ce_fwd", "apex_fused_ce_dx",
        "apex_fused_ce_dembed", "gmm", "tgmm", "apex_moe_combine"}
    assert out["assignment_rows"] == 131072 and out["wide"] == [], out["wide"]
    assert out["buffer_rows"] == 20480


_GPT_STEP_CHILD = _DESCRIBED_V5E + """
import re
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import apex_tpu.utils.platform as platform
platform.on_tpu = lambda: True      # the impls that ask choose the kernels
from apex_tpu.analysis.lowered import hlo_text, pallas_kernels
from apex_tpu.models.gpt import GPTConfig, init_params, make_train_step
from apex_tpu.optimizers import FusedAdam
from apex_tpu.transformer import parallel_state as ps

# the train cell as cellbench/adapters/train.py builds it: the committed
# configuration, 8 x 1,024 tokens a step
conf = json.load(open("cellbench/configs/gpt2-medium-train.json"))
args = conf["cellbench"]["args"]
seq = int(args["seq"])
config = GPTConfig(
    vocab_size=conf["vocab_size"], hidden_size=conf["n_embd"],
    num_layers=conf["n_layer"], num_attention_heads=conf["n_head"],
    max_seq_len=seq, layernorm_eps=conf["layer_norm_epsilon"],
    compute_dtype=jnp.dtype(args["compute_dtype"]), checkpoint_layers=True,
    remat_policy=args["remat_policy"], position_embedding_type="learned",
    use_flash_attention=True, fused_ce=True, fused_ce_chunk=128)
mesh = ps.initialize_model_parallel(
    tensor_model_parallel_size_=1, pipeline_model_parallel_size_=1,
    devices=[dev])
optimizer = FusedAdam(lr=3e-4, betas=tuple(args["betas"]), eps=args["eps"],
                      weight_decay=args["weight_decay"])
sh = NamedSharding(mesh, P())
put = lambda tree: jax.tree.map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)
params = jax.eval_shape(lambda: init_params(config, jax.random.PRNGKey(0)))
state = jax.eval_shape(optimizer.init, params)
tokens = jax.ShapeDtypeStruct((8, seq), jnp.int32,
                              sharding=NamedSharding(mesh, P("dp", None)))
step = make_train_step(config, optimizer, mesh, donate_state=True)
try:
    compiled = step.lower(put(params), put(state), tokens, tokens).compile()
    mem = compiled.memory_analysis()
    txt = hlo_text(compiled)
    # each fused-CE kernel's custom call: the types of what it is handed
    calls = {}
    for line in txt.splitlines():
        name = re.search(r"\\((apex_fused_ce_[a-z]+)\\)", line)
        if name and "operand_layout_constraints=" in line:
            calls[name.group(1)] = re.findall(
                r"(?:bf16|f32|s32)\\[[0-9,]+\\]",
                line.split("operand_layout_constraints=")[1]
                .split("frontend_attributes")[0])
    V, H = conf["vocab_size"], conf["n_embd"]
    out = {"bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes
           + mem.output_size_in_bytes - mem.alias_size_in_bytes,
           "kernels": sorted(set(pallas_kernels(compiled))),
           "calls": calls,
           "casts": len(re.findall(r"= bf16\\[%d,%d\\]\\S* (?:convert|fusion)\\("
                                   % (V, H), txt))}
except Exception as e:
    out = {"error": f"{type(e).__name__}: {e}"[:1500]}
print(json.dumps(out))
"""


def test_the_gpt_train_step_hands_the_ce_kernels_a_bf16_table():
    """The GPT-2 medium step at the train cell's sizes, compiled for a
    v5e without a chip: all three fused-CE kernels are handed the tied
    embedding as ``bf16[50304,1024]`` (the float32 master, 206 MB,
    streamed 32 times a call until PR 41), cast no more than once a
    pass, and the step still fits what it did (7.92 GB before, a
    103 MB copy more)."""
    r = subprocess.run([sys.executable, "-c", _GPT_STEP_CHILD],
                       cwd=str(REPO), capture_output=True, text=True,
                       timeout=900,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    if "skip" in out:
        pytest.skip(f"no compile-only TPU client: {out['skip']}")
    assert "error" not in out, out
    assert set(out["calls"]) == {"apex_fused_ce_fwd", "apex_fused_ce_dx",
                                 "apex_fused_ce_dembed"}, out
    for kernel, operands in out["calls"].items():
        assert "bf16[50304,1024]" in operands, (kernel, operands)
        assert "f32[50304,1024]" not in operands, (kernel, operands)
    assert 1 <= out["casts"] <= 2, out
    assert out["bytes"] < 8.03e9, out["bytes"]


#: what may carry a pool through a compiled step without copying it
_POOL_PLUMBING = {"parameter", "tuple", "get-tuple-element", "while"}


@pytest.fixture(scope="module",
                params=[_POOL_CHILD, _LATENT_POOL_CHILD, _KDA_POOL_CHILD,
                        _H1_POOL_CHILD],
                ids=["gpt2-large-kv", "latent-one-pool",
                     "kda-state-beside-latent", "h1-state-and-kv-a-layer"])
def serving_programs(request):
    """What the child found in the decode step and the prefill compiled
    for a v5e, once a family for the tests below."""
    return _programs_of(request.param)


@functools.lru_cache(maxsize=None)
def _programs_of(child):
    r = subprocess.run([sys.executable, "-c", child],
                       cwd=str(REPO), capture_output=True, text=True,
                       timeout=600,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    if "skip" in out:
        pytest.skip(f"no compile-only TPU client: {out['skip']}")
    for name in ("decode_step", "prefill"):
        assert "error" not in out[name], (
            f"{name} does not compile: {out[name]}")
    return out


def _moved(instructions, plumbing=_POOL_PLUMBING):
    """Of ``[name, opcode, aliased kernel]`` rows, those that are not
    plumbing nor a Pallas call writing its operand in place."""
    return [(n, op) for n, op, aliased_kernel in instructions
            if op not in plumbing
            and not (op == "custom-call" and aliased_kernel)]


def test_no_program_copies_the_kv_pool(serving_programs):
    """The decode step and the prefill, compiled for a v5e at GPT-2
    large's serving shapes and at the latent-attention family's (one
    pool of 576 values a token a layer, 128 slots), hold no instruction
    that PRODUCES a value as large as one layer's pool: only
    parameters, the tuples and the ``while`` that carry the pools, and
    the aliased Pallas calls that write them in place.  A ``copy``,
    ``fusion``, ``scatter`` or ``dynamic-update-slice`` of that size is
    XLA re-laying out, slicing or rebuilding the pool around a write or
    a read (PERF.md, PR 25: eight such copies were 54% of a decode step
    and held the pool twice).  Temporaries stay under one pool's
    bytes."""
    out = serving_programs
    for name in ("decode_step", "prefill"):
        got = out[name]
        bad = _moved(got["instructions"])
        assert not bad, (
            f"{name}: instructions that produce a pool-sized value: {bad}")
        kernels = [n for n, op, _ in got["instructions"]
                   if op == "custom-call"]
        assert kernels, f"{name}: no aliased kernel writes the pool"
        assert got["temp_bytes"] < out["pool_bytes"], (
            f"{name}: {got['temp_bytes']} B of temporaries, one pool is "
            f"{out['pool_bytes']} B")


# ------------------------------------- the optimizer updates a tree in place
_TREE_OPTIMIZERS = {"FusedAdam": {}, "FusedLAMB": {},
                    "FusedSGD": dict(lr=0.1, momentum=0.9)}
#: the state slot whose leaves stand beside the gradients in the update
_TREE_SLOTS = {"FusedAdam": "exp_avg", "FusedLAMB": "exp_avg",
               "FusedSGD": "momentum_buffer"}


def _tree_optimizer(name):
    """The optimizer's class and the arguments the guards build it with."""
    import apex_tpu.optimizers as optimizers

    return getattr(optimizers, name), _TREE_OPTIMIZERS[name]


def _small_gpt_step(optimizer):
    """``make_train_step`` at the tests' small GPT on one (CPU) device,
    state donated: the compiled step, its parameters and its state."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.gpt import GPTConfig, init_params, make_train_step
    from apex_tpu.transformer import parallel_state as ps

    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_seq_len=16,
                    compute_dtype=F32, checkpoint_layers=False)
    mesh = ps.initialize_model_parallel(
        tensor_model_parallel_size_=1, pipeline_model_parallel_size_=1,
        devices=jax.devices()[:1])
    params = init_params(cfg, jax.random.PRNGKey(0))
    state = optimizer.init(params)
    step = make_train_step(cfg, optimizer, mesh, donate_state=True,
                           opt_state_spec=jax.tree.map(lambda _: P(), state))
    tokens = jnp.zeros((2, 16), I32)
    try:
        lowered = step.lower(params, state, tokens, tokens)
    finally:    # the mesh is process-wide: leave none to the next file
        ps.destroy_model_parallel()
    return lowered, lowered.compile(), params, state


def _flat_f32(compiled, elements):
    """Instructions of the step whose result is a 1-D f32 array of at
    least ``elements`` elements: ``[name, opcode]`` each."""
    from apex_tpu.analysis.lowered import large_result_instructions

    return [[i["name"], i["opcode"]]
            for i in large_result_instructions(compiled, elements)
            if f"f32[{i['elements']}]" in i["line"].split(" = ", 1)[1]
            .split(" ", 1)[0]]


def _gradient_reads(compiled, slot):
    """For each leaf of the state slot ``slot`` (``exp_avg``,
    ``momentum_buffer``): how many instructions of the step's ENTRY read
    that leaf's GRADIENT.  The gradient is what the leaf's update reads
    beside parameters and scalars: a value of the leaf's shape that the
    step itself computed."""
    from apex_tpu.analysis.lowered import entry_instructions, entry_users

    inst = entry_instructions(compiled)
    shape = lambda t: t.split("{")[0]
    reads = {}
    for name, i in inst.items():
        if i["opcode"] != "parameter" or f"opt_state_{slot}__" not in name:
            continue
        users = entry_users(inst, name)
        assert users, f"{name} is read by nothing"
        grads = {o for u in users for o in inst[u]["operands"]
                 if inst[o]["opcode"] != "parameter"
                 and shape(inst[o]["type"]) == shape(i["type"])}
        # a value as large as the leaf made on the way (LAMB's update
        # term) is no gradient: keep what the backward pass handed over
        grads = {g for g in grads
                 if not any("opt_state_" in o for o in inst[g]["operands"])}
        assert grads, f"no gradient beside {name}: {users}"
        reads[name] = max(len(entry_users(inst, g)) for g in grads)
    return reads


@pytest.mark.parametrize("name", sorted(_TREE_OPTIMIZERS))
def test_the_train_step_updates_a_tree_in_place(name):
    """The one-chip GPT step with a default optimizer and per-leaf
    state: the compiled step holds no 1-D f32 value of the tree's size
    (no whole-tree concatenate of the gradients, no flat moment, no
    flat update term: packing the tree into flat buckets made seven and
    a half such copies a step, 10.6 GB of temporaries at GPT-2 medium;
    PERF.md, PR 39);
    ``input_output_alias`` covers every leaf of the parameters and of
    every state slot, so the update is in place; and the gradients are
    read by no more instructions than the per-leaf numerics
    specification (``_leaf_update`` alone) reads them by: the
    dispatch's tail adds no pass of its own,
    and ``offer_local_grad_norm`` traces to nothing with no telemetry
    attached."""
    from apex_tpu.analysis.lowered import assert_donation_covers

    cls, kw = _tree_optimizer(name)
    lowered, compiled, params, state = _small_gpt_step(cls(**kw))
    total = sum(x.size for x in jax.tree.leaves(params))
    assert _flat_f32(compiled, total) == []
    assert_donation_covers(lowered, params, state, compiled=True)

    class Specification(cls):
        def _dispatch(self, grads, state, params, grads_finite=None,
                      lr=None, **kw):
            return (*self._leaf_update(grads, state, params,
                                       grads_finite=grads_finite, lr=lr),
                    None)

    slot = _TREE_SLOTS[name]
    want = _gradient_reads(_small_gpt_step(Specification(**kw))[1], slot)
    got = _gradient_reads(compiled, slot)
    assert got.keys() == want.keys() and len(got) == len(
        jax.tree.leaves(params))
    more = {k: (got[k], want[k]) for k in got if got[k] > want[k]}
    assert not more, f"gradients read more often than specified: {more}"


def test_no_program_casts_or_copies_the_stacked_weights(serving_programs):
    """Given the tree the scheduler holds (``serving_params``: GPT-2
    large's fp32 parameters with the six stacked matrices cast once;
    the latent family's bf16 tree as it is), the compiled decode step
    and prefill hold no instruction that PRODUCES a value with as many
    elements as one stacked matrix (GPT: 36 x 1280 x 1280, 118 MB in
    bf16; latent: more than one layer of its largest leaf, which only a
    whole stack is) but parameters, their plumbing, bitcasts and the
    aliased pool writes.  A ``convert`` there is the weights' cast run
    again in every program (six of them were 61% of the device time of
    both GPT-2 serving cells and 1.42 GB of temporaries; PERF.md, PR
    29); a ``copy`` or ``transpose`` is XLA re-laying a stack out under
    another name (PR 26: the expert weights, 17 ms of a 56 ms step).
    The decode step's temporaries stay under 0.2 GB."""
    out = serving_programs
    for name in ("decode_step", "prefill"):
        bad = _moved(out[name]["matrix_sized"],
                     _POOL_PLUMBING | {"bitcast"})
        assert not bad, (
            f"{name}: instructions that produce a value as large as a "
            f"stacked weight matrix: {bad}")
    assert out["decode_step"]["temp_bytes"] < 0.2e9


def test_no_program_copies_the_recurrent_state():
    """The same for the second kind of cache entry, at the KDA/MLA
    cell's shapes: no instruction of the decode step or the prefill
    but parameters, tuple plumbing and aliased kernels
    (``apex_kda_decode``, ``apex_kda_conv_step``, ``apex_slot_install``)
    produces a value the size of one layer of ``kda_state`` (270 MB) or
    of ``kda_conv``; and the compiled decode step holds between 25% and
    100% of the chip (12.4 GB: weights 6.9, state 2.8, latent pool
    2.7)."""
    out = _programs_of(_KDA_POOL_CHILD)
    for name in ("decode_step", "prefill"):
        for which in ("state_instructions", "tails_instructions"):
            bad = _moved(out[name][which])
            assert not bad, (f"{name}: instructions that produce a value "
                             f"as large as a layer of {which[:-13]}: {bad}")
            assert [n for n, op, _ in out[name][which]
                    if op == "custom-call"], (
                f"{name}: no aliased kernel writes {which[:-13]}")
        assert out[name]["program_bytes"] < out["chip_bytes"]
    assert 0.25 * out["chip_bytes"] < out["decode_step"]["program_bytes"]


def test_a_layer_that_holds_both_kinds_of_entry_copies_neither():
    """The Falcon-H1 cell's decode step and its longest prefill (1,536
    tokens), compiled for a v5e at the committed configuration: every
    layer writes K/V pages AND a slot's recurrent state, and no
    instruction but parameters, tuple plumbing and aliased kernels
    (``apex_kv_write``, ``apex_ssd_decode``, ``apex_slot_install``)
    produces a value the size of one layer of ``ssm_state`` (407 MB) or
    of ``k``/``v``; the step's kernels are the family's; and the step
    holds between 25% and 100% of the chip (13.65 GB: weights 8.22,
    state 3.25, K/V pool 2.15).  The 24 MB of convolution tails are
    NOT held to this: the device's own layout for ``bf16[8, 97,
    15360]`` puts the 8 layers in the sublanes, and XLA copies them to
    the kernel's at the layer loop's entry and exit (PERF.md, Open
    questions)."""
    out = _programs_of(_H1_POOL_CHILD)
    for name in ("decode_step", "prefill"):
        for which in ("state_instructions", "instructions"):
            bad = _moved(out[name][which])
            assert not bad, (f"{name}: instructions that produce a value "
                             f"as large as a layer of the "
                             f"{which[:-13] or 'pool'}: {bad}")
            assert [n for n, op, _ in out[name][which]
                    if op == "custom-call"], (
                f"{name}: no aliased kernel writes it")
        assert out[name]["program_bytes"] < out["chip_bytes"]
    assert {"apex_ssd_decode", "apex_kda_conv_step", "apex_kv_write",
            "apex_decode_attention", "apex_fused_sample"} \
        <= set(out["decode_step"]["kernels"])
    assert {"apex_flash_fwd", "apex_slot_install", "apex_kv_write"} \
        <= set(out["prefill"]["kernels"])
    assert 0.25 * out["chip_bytes"] < out["decode_step"]["program_bytes"]
    assert 8.2e9 < out["weight_bytes"] < 8.25e9
    assert out["state_bytes"] == 97 * 8 * 32 * 128 * 256 * 4


def test_the_block_step_copies_no_pool_and_casts_no_stack():
    """The block-generating cell's block step and its longest prefill
    (768 tokens), compiled for a v5e at the committed configuration: no
    instruction but parameters, tuple plumbing and the aliased
    ``apex_kv_write`` produces a value the size of one layer of ``k`` or
    ``v`` (the block's columns are rewritten IN PLACE every pass), none
    produces one as large as a stacked weight matrix (the held experts'
    stacks reach the grouped matmul whole, with the layer's index; the
    embedding table's prefetch apart); the
    step's kernels are the block attention, the block's write, the
    grouped matmul and the head with its confidence, the prefill's the
    block-causal flash forward; and both hold between 25% and 93% of
    the chip (13.3 GB: weights 9.24, K/V pool 4.03)."""
    out = _programs_of(_BLOCK_POOL_CHILD)
    for name in ("decode_step", "prefill"):
        bad = _moved(out[name]["instructions"])
        assert not bad, (f"{name}: instructions that produce a value as "
                         f"large as a layer of the pool: {bad}")
        assert [n for n, op, _ in out[name]["instructions"]
                if op == "custom-call"], f"{name}: no aliased kernel writes it"
        # but for the 78 MB embedding table, which XLA prefetches into
        # fast memory for the step's 256-row gather (twice: 1% of the
        # step's bytes; PERF.md, Open questions)
        bad = _moved(out[name]["matrix_sized"],
                     _POOL_PLUMBING | {"bitcast", "copy-start", "copy-done"})
        assert not bad, (f"{name}: instructions that produce a value as "
                         f"large as a stacked weight matrix: {bad}")
        assert sum(op == "copy-start"
                   for _, op, _ in out[name]["matrix_sized"]) <= 2
        assert 0.25 * out["chip_bytes"] < out[name]["program_bytes"] \
            < 0.93 * out["chip_bytes"]
    assert {"apex_kv_write", "apex_decode_attention", "apex_fused_sample",
            "gmm"} <= set(out["decode_step"]["kernels"])
    assert {"apex_flash_fwd", "apex_kv_write", "gmm"} \
        <= set(out["prefill"]["kernels"])
    assert out["decode_step"]["temp_bytes"] < 0.2e9
    # 4,620,431,360 parameters in bf16, the router and the gains in float32
    assert 9.26e9 < out["weight_bytes"] < 9.27e9
    assert out["pool_bytes"] == 48 * 320 * 4 * 128 * 128 * 2


# LFM2-8B-A1B as its benchmark cell runs it: published widths, layers
# 1-13, all 32 experts, the whole vocabulary, 256 slots, 3,840 pages of
# 128 over the 3 attention layers, the longest prefill (1,024 tokens)
_LFM2_POOL_CHILD = _DESCRIBED_V5E + """
import jax.numpy as jnp
from pathlib import Path
import apex_tpu.utils.platform as platform
platform.on_tpu = lambda: True      # 'auto' impls: as on the chip
from apex_tpu.analysis.lowered import (
    large_result_instructions, pallas_kernels,
)
from apex_tpu.inference.decode import make_decode_step, make_prefill
from apex_tpu.inference.kv_cache import COUNTERS, alloc_named_pools
from apex_tpu.models.lfm2_moe import init_params
from cellbench.adapters.serve_lfm2_moe import decode_config, model_config

conf = json.loads(Path(
    "cellbench/configs/lfm2-8b-a1b-serve-pp2.json").read_text())
cfg, dcfg = model_config(conf), decode_config(conf, 0)
B, PPS, S = dcfg.max_batch, dcfg.cache.pages_per_seq, dcfg.max_prompt_len
sh = SingleDeviceSharding(dev)
put = lambda tree: jax.tree.map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)
arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
model = cfg.served_model()
params = put(jax.eval_shape(lambda: model.serving_params(
    init_params(cfg, jax.random.PRNGKey(0)))))
pools = put(jax.eval_shape(lambda: dict(
    alloc_named_pools(model.cache_spec(), dcfg.cache, slots=B),
    **{COUNTERS: jnp.zeros((len(model.counter_names),), jnp.int32)})))
I, U = jnp.int32, jnp.uint32
programs = {
    "decode_step": (make_decode_step(cfg, dcfg), (
        params, pools, arg((B,), I), arg((B,), I), arg((B,), jnp.bool_),
        arg((B, PPS), I), arg((B,), U))),
    "prefill": (make_prefill(cfg, dcfg), (
        params, pools, arg((1, S), I), arg((), I), arg((), I),
        arg((PPS,), I), arg((), U), arg((), I))),
}
La, Lc = cfg.count("attn"), cfg.count("conv")
# more than one repeat of a period position's expert stack
matrix = 1 + params["period"][0]["we_gate"].size // cfg.plan[2]
nbytes = lambda a: a.size * a.dtype.itemsize
out = {"pool_bytes": nbytes(pools["k"]), "chip_bytes": 16 * 2 ** 30,
       "weight_bytes": sum(nbytes(a) for a in jax.tree.leaves(params)),
       "tail_bytes": nbytes(pools["conv_tail"]),
       "plan": [len(cfg.plan[0]), len(cfg.plan[1]), cfg.plan[2],
                len(cfg.plan[3])]}
for name, (fn, args) in programs.items():
    try:
        c = fn.lower(*args).compile()
    except Exception as e:
        out[name] = {"error": f"{type(e).__name__}: {e}"[:1500]}
        continue
    show = lambda found: [
        [i["name"], i["opcode"],
         "tpu_custom_call" in i["line"]
         and "output_to_operand_aliasing" in i["line"]]
        for i in found]
    mem = c.memory_analysis()
    out[name] = {
        "kernels": sorted(set(pallas_kernels(c))),
        "temp_bytes": mem.temp_size_in_bytes,
        "program_bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes,
        "instructions": show(large_result_instructions(
            c, pools["k"].size // La,
            containing=(dcfg.cache.num_pages, 8, 64))),
        "tails_instructions": show(large_result_instructions(
            c, pools["conv_tail"].size // Lc, containing=(B + 1, 4096))),
        "matrix_sized": show(large_result_instructions(c, matrix))}
print(json.dumps(out))
"""


def test_the_hybrid_stage_copies_no_pool_and_no_expert_stack():
    """The LFM2 cell's decode step and its longest prefill (1,024
    tokens), compiled for a v5e at the committed configuration (a dense
    layer unrolled, three periods of four layers under one scan): no
    instruction but parameters, tuple plumbing and the aliased
    ``apex_kv_write`` produces a value the size of one layer of ``k`` or
    ``v`` (the pools have the THREE attention layers only); none
    produces one as large as a period position's expert stack (the
    stacks reach the grouped matmul whole, with the repeat's index); the
    step's kernels are the convolution's step, the grouped attention and
    its write, the grouped matmul and the head over 65,536 rows, the
    prefill's the flash forward and the tails' install; and both hold
    between 25% and 100% of the chip (12.3 GB: weights 9.21, K/V pool
    3.02).  The 21 MB of convolution tails are NOT held to this: XLA
    moves them between HBM and its fast memory space around the ten
    in-place steps (``copy-start`` / ``slice-start`` of ``bf16[10, 257,
    4096]``: 26 us each at the chip's bandwidth)."""
    out = _programs_of(_LFM2_POOL_CHILD)
    assert out["plan"] == [1, 4, 3, 0]
    for name in ("decode_step", "prefill"):
        bad = _moved(out[name]["instructions"])
        assert not bad, (f"{name}: instructions that produce a value as "
                         f"large as a layer of the pool: {bad}")
        assert [n for n, op, _ in out[name]["instructions"]
                if op == "custom-call"], f"{name}: no aliased kernel writes it"
        bad = _moved(out[name]["matrix_sized"], _POOL_PLUMBING | {"bitcast"})
        assert not bad, (f"{name}: instructions that produce a value as "
                         f"large as an expert stack: {bad}")
        assert [n for n, op, aliased in out[name]["tails_instructions"]
                if op == "custom-call" and aliased], (
            f"{name}: no aliased kernel writes the tails")
        assert 0.25 * out["chip_bytes"] < out[name]["program_bytes"] \
            < out["chip_bytes"]
    assert {"apex_kda_conv_step", "apex_kv_write", "apex_decode_attention",
            "apex_fused_sample", "gmm"} <= set(out["decode_step"]["kernels"])
    assert {"apex_flash_fwd", "apex_slot_install", "apex_kv_write", "gmm"} \
        <= set(out["prefill"]["kernels"])
    assert out["decode_step"]["temp_bytes"] < 0.2e9
    # 4,606,249,728 parameters in bf16, routers and gains in float32
    assert 9.21e9 < out["weight_bytes"] < 9.22e9
    assert out["pool_bytes"] == 3 * 3840 * 8 * 64 * 128 * 2
    assert out["tail_bytes"] == 10 * 257 * 4096 * 2


_EVA_POOL_CHILD = _DESCRIBED_V5E + """
import jax.numpy as jnp
from pathlib import Path
import apex_tpu.utils.platform as platform
platform.on_tpu = lambda: True      # 'auto' impls: as on the chip
from apex_tpu.analysis.lowered import (
    large_result_instructions, pallas_kernels,
)
from apex_tpu.inference import DecodeConfig, KVCacheConfig
from apex_tpu.inference.decode import make_decode_step, make_prefill
from apex_tpu.inference.kv_cache import COUNTERS, alloc_named_pools
from apex_tpu.models.evabyte import init_params
from cellbench.adapters.serve_evabyte import model_config

B, PAGE, PPS = 20, 128, 9
conf = json.loads(Path(
    "cellbench/configs/evabyte-6.5b-serve-pp4.json").read_text())
cfg = model_config(conf)
dcfg = DecodeConfig(
    cache=KVCacheConfig(num_pages=1 + B * PPS, page_size=PAGE,
                        pages_per_seq=PPS, dtype=jnp.bfloat16),
    max_batch=B, max_prompt_len=16384,
    prefill_buckets=tuple(conf["cellbench"]["args"]["prefill_buckets"]),
    temperature=0.0, attn_impl="pallas", sample_impl="pallas",
    sample_dot_dtype=jnp.float32)
sh = SingleDeviceSharding(dev)
put = lambda tree: jax.tree.map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)
arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
model = cfg.served_model()
params = put(jax.eval_shape(lambda: model.serving_params(
    init_params(cfg, jax.random.PRNGKey(0)))))
pools = put(jax.eval_shape(lambda: dict(
    alloc_named_pools(model.cache_spec(), dcfg.cache, slots=B),
    **{COUNTERS: jnp.zeros((len(model.counter_names),), jnp.int32)})))
I, U = jnp.int32, jnp.uint32
prefill = lambda S: (make_prefill(cfg, dcfg), (
    params, pools, arg((1, S), I), arg((), I), arg((), I),
    arg((PPS,), I), arg((), U), arg((), I)))
programs = {
    "decode_step": (make_decode_step(cfg, dcfg), (
        params, pools, arg((B,), I), arg((B,), I), arg((B,), jnp.bool_),
        arg((B, PPS), I), arg((B,), U))),
    "prefill": prefill(16384), "prefill_2048": prefill(2048),
}
nbytes = lambda a: a.size * a.dtype.itemsize
out = {"pool_bytes": nbytes(pools["k"]), "pool_shape": pools["k"].shape,
       "chip_bytes": 16 * 2 ** 30}
for name, (fn, args) in programs.items():
    try:
        c = fn.lower(*args).compile()
    except Exception as e:
        out[name] = {"error": f"{type(e).__name__}: {e}"[:1500]}
        continue
    mem = c.memory_analysis()
    out[name] = {
        "temp_bytes": mem.temp_size_in_bytes,
        "program_bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes,
        "kernels": sorted(set(pallas_kernels(c))),
        "instructions": [
            [i["name"], i["opcode"], "tpu_custom_call" in i["line"]
             and "output_to_operand_aliasing" in i["line"]]
            for i in large_result_instructions(
                c, pools["k"].size // cfg.num_hidden_layers,
                containing=(pools["k"].shape[1], 32, 128))]}
print(json.dumps(out))
"""


def test_no_program_copies_the_windowed_pool():
    """The third kind of cache entry, at the EvaByte cell's shapes (20
    slots, 8 layers, 32 heads of 128: ONE pool a name of 181 pages of
    pooled columns and 320 of window buffers, 4.2 GB each): no
    instruction of the decode step or of a prefill (the largest bucket
    and the smallest) but parameters, tuple plumbing and aliased kernels
    produces a value the size of one layer of it, so no program copies
    the window buffers or the pooled pages; each program holds the
    kernels it should; the decode step needs under 50 MB of
    temporaries and between 25% and 100% of the chip, the largest
    prefill fits beside it (under 90% of the chip)."""
    out = _programs_of(_EVA_POOL_CHILD)
    assert out["pool_shape"] == [8, 1 + 20 * 9 + 20 * 16, 32, 128, 128]
    for name in ("decode_step", "prefill", "prefill_2048"):
        assert "error" not in out[name], out[name]
        bad = _moved(out[name]["instructions"])
        assert not bad, (f"{name}: instructions that produce a value as "
                         f"large as a layer of the pool: {bad}")
        assert [n for n, op, _ in out[name]["instructions"]
                if op == "custom-call"], f"{name}: no aliased kernel"
        assert out[name]["program_bytes"] < 0.9 * out["chip_bytes"]
    assert out["decode_step"]["kernels"] == [
        "apex_decode_attention", "apex_eva_summarise", "apex_fused_sample",
        "apex_kv_write"]
    assert out["prefill"]["kernels"] == out["prefill_2048"]["kernels"] == [
        "apex_flash_fwd", "apex_fused_sample", "apex_kv_write"]
    assert out["decode_step"]["temp_bytes"] < 50e6
    assert 0.25 * out["chip_bytes"] < out["decode_step"]["program_bytes"]


# ----------------------------------------------------------- compile cache
#: the config option's name, spelled so that a grep for it over the tree
#: still finds only the helper
_OPTION = "jax_compilation_" "cache_dir"


def _cache_dir_seen_by_child(env):
    code = ("import jax; from apex_tpu.utils.compile_cache import "
            "enable_compile_cache as e; p = e(); "
            f"print(p); print(getattr(jax.config, {_OPTION!r}))")
    r = subprocess.run([sys.executable, "-c", code], cwd=str(REPO), env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip().splitlines()[-2:]


def test_compile_cache_env_wins(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    assert _cache_dir_seen_by_child(env) == [str(tmp_path), str(tmp_path)]


def test_compile_cache_defaults_into_the_checkout():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    want = str(REPO / ".jax_cache")
    assert _cache_dir_seen_by_child(env) == [want, want]


def test_no_other_code_sets_a_cache_dir():
    """A grep for the option over the tree finds only the helper."""
    hits = [str(p.relative_to(REPO))
            for p in REPO.rglob("*.py")
            if not {".chip_scratch", "chiprun_out"} & set(p.parts)
            and _OPTION in p.read_text()]
    assert hits == ["apex_tpu/utils/compile_cache.py"], hits


# ------------------------------------------------------------- chip_smoke
def test_chip_smoke_refuses_to_run_on_cpu():
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and "cpu" in r.stderr
    assert r.stdout.strip() == "", "no result may be printed without a chip"


def test_platform_probe_lets_backend_errors_out(monkeypatch):
    """``on_tpu`` must not turn "the backend failed to start" into "not
    a TPU, use the reference"."""
    from apex_tpu.utils import platform

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        platform.on_tpu()
