"""Pallas TPU flash attention (fwd + bwd kernels).

Reference: ``apex/contrib/fmha`` (CUDA flash-style fused MHA, seqlen
≤512) and ``apex/contrib/multihead_attn`` fused attention.  TPU
redesign: one VMEM-resident online-softmax kernel — the (bq, bk) score
tile never touches HBM, running max/sum live in VMEM scratch across the
sequential k-block grid steps, and the causal upper triangle is skipped
block-wholesale via ``pl.when`` on grid indices.

Three kernels, the standard flash decomposition:

- forward: grid ``(batch·heads, q_blocks, k_blocks)``, out block revisited
  across the k dimension, accumulator/max/sum in f32 scratch, writes
  ``out`` and the per-row logsumexp.
- dq backward: same grid; recomputes the score tile from (q, k, lse),
  accumulates ``dq`` in scratch.
- dk/dv backward: grid ``(batch·heads, k_blocks, q_blocks)`` (k outer),
  accumulates ``dk``/``dv`` in scratch.

``delta = rowsum(dout · out)`` is precomputed by XLA (it fuses into the
preceding op).  ``q_offset``/``k_offset`` place the local blocks in the
global sequence so ring attention's cross-device causal masks work.

The ``lax.scan`` composite in :mod:`apex_tpu.ops.attention` remains the
numerics specification and the universal fallback (CPU, odd shapes).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._pallas_tiling import LANES as _LANES
from apex_tpu.ops._pallas_tiling import VMEM_BUDGET as _VMEM_BUDGET
from apex_tpu.ops._pallas_tiling import flash_vmem_bytes as _flash_vmem_bytes
from apex_tpu.ops._pallas_tiling import sublane as _sublane

NEG_INF = -1e30

# Shared by all three kernels: batch·head and q-block (resp. k-block)
# grid revisits are order-free; only the innermost accumulation dim —
# where the scratch carry, its init, and its finalize live — is
# sequential.  Declaring this lets Mosaic software-pipeline the block
# DMAs across grid steps instead of serializing on the conservative
# default (numerics are identical either way — the arbitrary dim still
# runs in order).
_DIM_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# ------------------------------------------------------------ block tuning
# Measured per-shape block targets, keyed (seq_q, head_dim, dtype name,
# phase) -> (block_q, block_k), phase ∈ {"fwd", "bwd"}.  The phases have
# different VMEM envelopes — the backward kernels keep ~4 (bq, bk) f32
# score temporaries live vs the forward's 2 — so one (bq, bk) cannot
# serve both.  Populated from benchmarks/flash_sweep.py runs on real
# hardware (benchmarks/install_tuned_blocks.py records the provenance
# in a comment at the table's head); consulted by the fwd/bwd entry
# points when the caller passes no explicit blocks, before the
# _pick_block static heuristic.
# Legacy 3-tuple (seq_q, head_dim, dtype) keys are read as fwd-only.
_TUNED_BLOCKS: dict = {}

_PHASES = ("fwd", "bwd")


def tuned_blocks(seq_q, head_dim, dtype, phase="fwd"):
    """(block_q, block_k) measured best for this shape and phase, or
    None.  ``phase="fwd"`` also reads legacy 3-tuple entries (tables
    installed before the per-phase split are forward measurements)."""
    if phase not in _PHASES:
        raise ValueError(f"phase must be one of {_PHASES}, got {phase!r}")
    key = (int(seq_q), int(head_dim), jnp.dtype(dtype).name)
    hit = _TUNED_BLOCKS.get(key + (phase,))
    if hit is None and phase == "fwd":
        hit = _TUNED_BLOCKS.get(key)
    return hit


def set_tuned_blocks(table) -> None:
    """Install sweep-measured block targets: ``{(S, D, dtype[, phase]):
    (bq, bk)}`` or an iterable of ``[[S, D, dtype[, phase]], [bq, bk]]``
    pairs (the exact JSON flash_sweep.py prints as
    ``tuned_blocks_table``).  Three-element keys — the pre-per-phase
    format — install as ``"fwd"`` entries: old sweeps measured the
    forward dispatcher's path.  The dtype key is normalized through
    ``jnp.dtype`` so ``jnp.bfloat16``, ``'bfloat16'``, and ``np.dtype``
    all land on the same entry."""
    items = table.items() if hasattr(table, "items") else table
    for key, val in items:
        if len(key) == 3:
            (s, d, name), phase = key, "fwd"
        else:
            s, d, name, phase = key
        if phase not in _PHASES:
            raise ValueError(
                f"tuned-block phase must be one of {_PHASES}, got {phase!r}")
        bq, bk = val
        _TUNED_BLOCKS[(int(s), int(d), jnp.dtype(name).name, str(phase))] = (
            int(bq), int(bk))


def _pick_block(seq, target, align=_LANES, fits=None):
    """Largest divisor of ``seq`` ≤ target, preferring ``align``-aligned
    divisors (128 for the lane dim, the dtype sublane tile — 8 fp32 /
    16 bf16, via ``_sublane`` — for sublanes) — but only when the
    aligned candidate is at least half the largest divisor: a misaligned
    tile wastes ≤ (align−1) padded lanes, while a much smaller tile
    multiplies grid steps and k/v refetches (e.g. seq=640, target=512:
    320 misaligned beats 128 aligned).

    ``fits``: optional predicate over a candidate block — candidates it
    rejects are dropped BEFORE the size preference runs.  The callers
    pass the APX304 VMEM footprint formula
    (:func:`apex_tpu.ops._pallas_tiling.flash_vmem_bytes` ≤ budget) so
    an over-large target clamps to the biggest block that provably fits
    instead of overflowing when Mosaic first compiles at long seq.
    When NO candidate fits the smallest divisor (1) is returned — the
    least-over-budget choice; Mosaic gets the final word either way."""
    divisors = [b for b in range(1, min(target, seq) + 1) if seq % b == 0]
    if fits is not None:
        divisors = [b for b in divisors if fits(b)] or [1]
    best = divisors[-1]
    aligned = [b for b in divisors if b % align == 0]
    if aligned and 2 * aligned[-1] >= best:
        return aligned[-1]
    return best


def _causal_mask(bq, bk, qi, kj, block_q, block_k, q_offset, k_offset):
    row = q_offset + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = k_offset + kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return row >= col


# ------------------------------------------------------------------ forward
def _fwd_kernel(*refs, scale, causal, has_bias, q_offset, k_offset,
                block_q, block_k, nk):
    if has_bias:
        q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
        b_ref = None
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Fully-masked (above-diagonal) blocks contribute nothing.
    diag_ok = (
        (q_offset + (i + 1) * block_q - 1) >= (k_offset + j * block_k)
        if causal
        else True
    )

    @pl.when(diag_ok)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if b_ref is not None:
            s = s + b_ref[0]  # (1, bk) key bias broadcast over rows
        if causal:
            mask = _causal_mask(q.shape[0], k.shape[0], i, j, block_q, block_k,
                                q_offset, k_offset)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # exp(NEG_INF - NEG_INF) = 1 would give fully-masked rows a
        # spurious uniform distribution; re-mask after the exp.
        p = jnp.exp(s - m_new)
        if causal or has_bias:
            p = jnp.where(s > NEG_INF / 2, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * corr + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)  # fully-masked rows (ring blocks)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, 0:1] + jnp.log(l)


def _kv_row(b, heads, kv_heads):
    """Flattened k/v batch·head row for flattened q row ``b``: grouped-
    query attention maps each q head to its group's shared kv head
    (identity when kv_heads == heads)."""
    if kv_heads == heads:
        return b
    group = heads // kv_heads
    return (b // heads) * kv_heads + (b % heads) // group


def _resolve_targets(sq, sk, d, dtype, block_q, block_k, phase, default):
    """Per-phase block TARGETS: explicit args win, then the phase's
    tuned entry (self-attention shapes only — a block_k tuned for
    Sk == Sq must not leak onto cross-attention key lengths), then the
    static default (fwd 1024 / bwd 512 — the VMEM envelopes differ)."""
    if (block_q is None or block_k is None) and sk == sq:
        tuned = tuned_blocks(sq, d, dtype, phase=phase)
        if tuned is not None:
            block_q = block_q if block_q is not None else tuned[0]
            block_k = block_k if block_k is not None else tuned[1]
    return block_q or default, block_k or default


def _clamped_blocks(sq, sk, d, dtype, block_q, block_k, phase):
    """(bq, bk) divisor blocks for the targets, jointly clamped so the
    APX304-priced footprint of the resulting pallas_call stays inside
    the VMEM budget: pick bq by preference alone, clamp bk against it,
    then re-clamp bq against the chosen bk (a no-op unless the pair
    was over budget)."""

    def fits(b_q, b_k):
        return _flash_vmem_bytes(b_q, b_k, d, phase) <= _VMEM_BUDGET

    bq = _pick_block(sq, block_q, align=_sublane(dtype))
    bk = _pick_block(sk, block_k, fits=lambda b: fits(bq, b))
    bq = _pick_block(sq, block_q, align=_sublane(dtype),
                     fits=lambda b: fits(b, bk))
    return bq, bk


def flash_fwd_pallas(q, k, v, scale, causal, q_offset, k_offset,
                     block_q=None, block_k=None, interpret=False,
                     out_dtype=None, kv_bias=None, heads=1, kv_heads=None):
    """q: (BH, Sq, D); k/v: (B·kv_heads, Sk, D).  Returns
    (out, lse (BH, Sq, 1)).

    ``kv_bias``: optional (B, 1, Sk) f32 additive key bias (0 valid /
    NEG_INF padded; the middle singleton keeps the block sublane-legal);
    ``heads`` maps the flattened batch·head grid index back to the batch
    row (b // heads).  ``kv_heads`` < heads = grouped-query attention:
    the kernel reads each q head's group-shared k/v block directly (no
    materialized head repeat in HBM).

    ``block_q``/``block_k`` default to the shape's tuned ``"fwd"`` entry
    (self-attention shapes) else 1024; either way the candidates are
    clamped against the shared VMEM footprint formula.
    ``out_dtype`` defaults to q.dtype; ring attention requests f32 so
    cross-chunk accumulation never rounds through bf16."""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    kv_heads = kv_heads or heads
    out_dtype = out_dtype or q.dtype
    block_q, block_k = _resolve_targets(
        Sq, Sk, D, q.dtype, block_q, block_k, "fwd", 1024)
    bq, bk = _clamped_blocks(Sq, Sk, D, q.dtype, block_q, block_k, "fwd")
    has_bias = kv_bias is not None

    inputs = (q, k, v) if not has_bias else (q, k, v, kv_bias)
    call = _fwd_call(BH, Sq, Sk, D, heads, kv_heads, float(scale), causal,
                     q_offset, k_offset, bq, bk, has_bias, interpret,
                     jnp.dtype(out_dtype).name)
    # jax.disable_jit(False): pallas_call cannot bind eagerly (its bind
    # params carry a dict), so the kernel stays one jitted op even when a
    # caller runs the surrounding program op-by-op under disable_jit().
    with jax.disable_jit(False):
        out, lse = call(*inputs)
    return out, lse


@functools.lru_cache(maxsize=512)
def _fwd_call(BH, Sq, Sk, D, heads, kv_heads, scale, causal,
              q_offset, k_offset, bq, bk, has_bias, interpret,
              out_dtype_name):
    """The fwd ``pallas_call``, memoized on its static configuration —
    every argument is static by construction (they bake into the kernel
    closure), so eager callers (a ring chunk per hop, interpret-mode
    tests) reuse one traced kernel instead of rebuilding fresh index-map
    closures — and with them the whole compile — per invocation."""
    nq, nk = Sq // bq, Sk // bk

    kv_spec = pl.BlockSpec(
        (1, bk, D),
        lambda b, i, j: (_kv_row(b, heads, kv_heads), j, 0),
        memory_space=pltpu.VMEM,
    )
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM),
        kv_spec,
        kv_spec,
    ]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (b // heads, 0, j), memory_space=pltpu.VMEM)
        )

    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, has_bias=has_bias,
            q_offset=q_offset, k_offset=k_offset, block_q=bq, block_k=bk,
            nk=nk,
        ),
        grid=(BH, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), jnp.dtype(out_dtype_name)),
            jax.ShapeDtypeStruct((BH, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=_DIM_SEMANTICS,
        interpret=interpret,
        name="apex_flash_fwd",
    )


# ----------------------------------------------------------------- backward
def _dq_kernel(*refs, scale, causal, has_bias, q_offset, k_offset,
               block_q, block_k, nk):
    if has_bias:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, b_ref, dq_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref = refs
        b_ref = None
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    diag_ok = (
        (q_offset + (i + 1) * block_q - 1) >= (k_offset + j * block_k)
        if causal
        else True
    )

    @pl.when(diag_ok)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if b_ref is not None:
            s = s + b_ref[0]
        if causal:
            mask = _causal_mask(q.shape[0], k.shape[0], i, j, block_q, block_k,
                                q_offset, k_offset)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])
        if causal or has_bias:  # fully-masked rows have lse == NEG_INF: exp(0) = 1
            p = jnp.where(s > NEG_INF / 2, p, 0.0)
        do = do_ref[0]
        # ring passes an f32 cotangent with bf16 k/v: widen the narrower
        # operand instead of rounding do through bf16
        v = v_ref[0]
        if v.dtype != do.dtype:
            v = v.astype(do.dtype)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0])
        acc_ref[:] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, causal, has_bias, q_offset, k_offset,
                block_q, block_k, nq, nt):
    """k-block outer; the inner dimension ``t`` walks ALL nt = g·nq
    q-blocks that attend to this kv head — for grouped-query attention
    the g q-heads of the group accumulate into the same dk/dv block
    (i = t % nq is the q-block index within the current q head)."""
    if has_bias:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, b_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        b_ref = None
    j, t = pl.program_id(1), pl.program_id(2)
    i = t % nq

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    diag_ok = (
        (q_offset + (i + 1) * block_q - 1) >= (k_offset + j * block_k)
        if causal
        else True
    )

    @pl.when(diag_ok)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if b_ref is not None:
            s = s + b_ref[0]
        if causal:
            mask = _causal_mask(q.shape[0], k.shape[0], i, j, block_q, block_k,
                                q_offset, k_offset)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])
        if causal or has_bias:  # fully-masked rows have lse == NEG_INF: exp(0) = 1
            p = jnp.where(s > NEG_INF / 2, p, 0.0)
        do = do_ref[0]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # widen v rather than rounding an f32 cotangent down (ring path)
        v = v_ref[0]
        if v.dtype != do.dtype:
            v = v.astype(do.dtype)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0])
        dk_acc[:] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(t == nt - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def flash_bwd_pallas(q, k, v, out, lse, do, scale, causal, q_offset, k_offset,
                     block_q=None, block_k=None, interpret=False, delta=None,
                     out_dtype=None, kv_bias=None, heads=1, kv_heads=None):
    # default 512 (not the forward's 1024): the bwd kernels keep ~4
    # (bq, bk) f32 score-sized temporaries live, so smaller tiles stay
    # inside VMEM — the same envelope the "bwd" tuned entries and the
    # footprint clamp price exactly.
    """q/out/do (BH, Sq, D); k/v (B·kv_heads, Sk, D); lse (BH, Sq, 1).
    Returns (dq, dk, dv) with dk/dv shaped like k/v.

    ``block_q``/``block_k`` default to the shape's tuned ``"bwd"`` entry
    (self-attention shapes) else 512 — the backward consults its OWN
    per-phase table, never a forward measurement — and candidates are
    clamped against the bwd VMEM footprint formula.
    ``delta`` (rowsum of do·out over the FULL row) may be passed in when
    ``out`` covers more keys than this call sees — ring attention's
    backward, where each chunk-pair call sees only the local k/v chunk.
    ``out_dtype`` defaults to the input dtypes; ring passes f32.
    ``kv_bias``/``heads``/``kv_heads`` as in :func:`flash_fwd_pallas`;
    with grouped-query attention the dk/dv grid walks every q head of
    the group before finalizing, so the group sum happens in VMEM.
    """
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    kv_heads = kv_heads or heads
    group = heads // kv_heads
    BKV = k.shape[0]
    dq_dtype = out_dtype or q.dtype
    dk_dtype = out_dtype or k.dtype
    dv_dtype = out_dtype or v.dtype
    block_q, block_k = _resolve_targets(
        Sq, Sk, D, q.dtype, block_q, block_k, "bwd", 512)
    bq, bk = _clamped_blocks(Sq, Sk, D, q.dtype, block_q, block_k, "bwd")
    nq, nk = Sq // bq, Sk // bk
    has_bias = kv_bias is not None

    if delta is None:
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1, keepdims=True)

    inputs = (q, k, v, do, lse, delta)
    if has_bias:
        inputs = inputs + (kv_bias,)
    static = (BH, BKV, Sq, Sk, D, heads, kv_heads, float(scale), causal,
              q_offset, k_offset, bq, bk, has_bias, interpret)
    dq_call = _dq_pallas_call(*static, jnp.dtype(dq_dtype).name)
    dkv_call = _dkv_pallas_call(*static, jnp.dtype(dk_dtype).name,
                                jnp.dtype(dv_dtype).name)
    # jax.disable_jit(False): see flash_fwd_pallas — pallas_call cannot
    # bind eagerly, so both backward kernels stay jitted ops.
    with jax.disable_jit(False):
        dq = dq_call(*inputs)
        dk, dv = dkv_call(*inputs)
    return dq, dk, dv


@functools.lru_cache(maxsize=512)
def _dq_pallas_call(BH, BKV, Sq, Sk, D, heads, kv_heads, scale, causal,
                    q_offset, k_offset, bq, bk, has_bias, interpret,
                    dq_dtype_name):
    """The dq ``pallas_call``, memoized like :func:`_fwd_call`."""
    nq, nk = Sq // bq, Sk // bk
    q_spec = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec(
        (1, bk, D),
        lambda b, i, j: (_kv_row(b, heads, kv_heads), j, 0),
        memory_space=pltpu.VMEM,
    )
    r_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM)

    in_specs = [q_spec, k_spec, k_spec, q_spec, r_spec, r_spec]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (b // heads, 0, j), memory_space=pltpu.VMEM)
        )

    return pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, has_bias=has_bias,
            q_offset=q_offset, k_offset=k_offset, block_q=bq, block_k=bk,
            nk=nk,
        ),
        grid=(BH, nq, nk),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), jnp.dtype(dq_dtype_name)),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_DIM_SEMANTICS,
        interpret=interpret,
        name="apex_flash_dq",
    )


@functools.lru_cache(maxsize=512)
def _dkv_pallas_call(BH, BKV, Sq, Sk, D, heads, kv_heads, scale, causal,
                     q_offset, k_offset, bq, bk, has_bias, interpret,
                     dk_dtype_name, dv_dtype_name):
    """The dk/dv ``pallas_call``, memoized like :func:`_fwd_call`."""
    nq, nk = Sq // bq, Sk // bk
    group = heads // kv_heads

    # k-outer grid over the KV rows: index maps see (b, j, t) with
    # t ∈ [0, group·nq) walking q-blocks of every q head in the group
    # (qh = t // nq, qi = t % nq); the q row is the group member's.
    def _q_row(b, t):
        if group == 1:
            return b
        return (b // kv_heads) * heads + (b % kv_heads) * group + t // nq

    qT_spec = pl.BlockSpec(
        (1, bq, D), lambda b, j, t: (_q_row(b, t), t % nq, 0),
        memory_space=pltpu.VMEM,
    )
    kT_spec = pl.BlockSpec((1, bk, D), lambda b, j, t: (b, j, 0), memory_space=pltpu.VMEM)
    rT_spec = pl.BlockSpec(
        (1, bq, 1), lambda b, j, t: (_q_row(b, t), t % nq, 0),
        memory_space=pltpu.VMEM,
    )

    in_specsT = [qT_spec, kT_spec, kT_spec, qT_spec, rT_spec, rT_spec]
    if has_bias:
        in_specsT.append(
            pl.BlockSpec((1, 1, bk), lambda b, j, t: (b // kv_heads, 0, j), memory_space=pltpu.VMEM)
        )

    return pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, has_bias=has_bias,
            q_offset=q_offset, k_offset=k_offset, block_q=bq, block_k=bk,
            nq=nq, nt=group * nq,
        ),
        grid=(BKV, nk, group * nq),
        in_specs=in_specsT,
        out_specs=[kT_spec, kT_spec],
        out_shape=[
            jax.ShapeDtypeStruct((BKV, Sk, D), jnp.dtype(dk_dtype_name)),
            jax.ShapeDtypeStruct((BKV, Sk, D), jnp.dtype(dv_dtype_name)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=_DIM_SEMANTICS,
        interpret=interpret,
        name="apex_flash_dkv",
    )


# ---------------------------------------------------------------- dispatch
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12))
def _flash_pallas(q, k, v, kv_bias, scale, causal, q_offset, k_offset,
                  block_q, block_k, interpret, heads, kv_heads):
    out, _ = flash_fwd_pallas(q, k, v, scale, causal, q_offset, k_offset,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret, kv_bias=kv_bias, heads=heads,
                              kv_heads=kv_heads)
    return out


def _flash_pallas_fwd(q, k, v, kv_bias, scale, causal, q_offset, k_offset,
                      block_q, block_k, interpret, heads, kv_heads):
    out, lse = flash_fwd_pallas(q, k, v, scale, causal, q_offset, k_offset,
                                block_q=block_q, block_k=block_k,
                                interpret=interpret, kv_bias=kv_bias, heads=heads,
                                kv_heads=kv_heads)
    return out, (q, k, v, kv_bias, out, lse)


def _flash_pallas_bwd(scale, causal, q_offset, k_offset, block_q, block_k,
                      interpret, heads, kv_heads, res, g):
    q, k, v, kv_bias, out, lse = res
    # the nondiff blocks are the CALLER's (None = untuned): an explicit
    # block keeps the documented 512 cap (more score-sized f32
    # temporaries live in the bwd); None defers to flash_bwd_pallas's
    # own per-phase tuned entry — a forward measurement never leaks
    # onto the backward's different VMEM envelope
    dq, dk, dv = flash_bwd_pallas(q, k, v, out, lse, g, scale, causal,
                                  q_offset, k_offset,
                                  block_q=None if block_q is None else min(block_q, 512),
                                  block_k=None if block_k is None else min(block_k, 512),
                                  interpret=interpret, kv_bias=kv_bias,
                                  heads=heads, kv_heads=kv_heads)
    # the mask bias is data, not a trainable input: zero cotangent
    return (dq, dk, dv, None if kv_bias is None else jnp.zeros_like(kv_bias))


_flash_pallas.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


def flash_attention_pallas(q, k, v, causal=True, softmax_scale=None,
                           q_offset=0, k_offset=0, block_q=None, block_k=None,
                           interpret=False, kv_mask=None):
    """(B, H, S, D) flash attention via the Pallas kernels.

    ``kv_mask``: optional (B, Sk) bool key-validity mask (True = valid) —
    the fmha varlen/padding semantics (``apex/contrib/fmha/fmha.py:33-60``)
    expressed as a dense mask folded into the kernel.

    Grouped-query attention: k/v may carry fewer heads than q
    ((B, H_kv, Sk, D) with H % H_kv == 0) — the kernels index each q
    head's group-shared k/v block directly, so GQA costs no HBM head
    repeat and dk/dv group sums happen in VMEM scratch."""
    B, H, Sq, D = q.shape
    Hkv = k.shape[1]
    if H % Hkv != 0:
        raise ValueError(f"q heads ({H}) not divisible by kv heads ({Hkv})")
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(D)
    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * Hkv, k.shape[2], D)
    vf = v.reshape(B * Hkv, v.shape[2], D)
    if kv_mask is None:
        bias = None
    else:
        from apex_tpu.ops.attention import padding_bias

        bias = padding_bias(kv_mask)[:, None, :]
    # the RAW (possibly-None) blocks thread through the custom_vjp's
    # nondiff args: each phase resolves its own tuned entry at its own
    # entry point, so a forward-tuned (bq, bk) never leaks onto the
    # backward kernels' different VMEM envelope
    out = _flash_pallas(qf, kf, vf, bias, scale, causal, q_offset, k_offset,
                        block_q, block_k, interpret, H, Hkv)
    return out.reshape(B, H, Sq, D)


def pallas_flash_available(q, k) -> bool:
    """Kernel path: real TPU, lane-aligned sequence blocks, ≥8 head dim."""
    from apex_tpu.utils.platform import on_tpu

    return (
        on_tpu()
        and q.shape[2] % 128 == 0
        and k.shape[2] % 128 == 0
        and q.shape[3] % 8 == 0
        and q.dtype in (jnp.float32, jnp.bfloat16)
    )
