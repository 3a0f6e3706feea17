"""Flash (blockwise, online-softmax) attention.

Reference: ``apex/contrib/fmha`` (flash-style fused MHA for BERT,
seqlen ≤ 512, ``fmhalib``) and ``apex/contrib/multihead_attn`` (fused
self/enc-dec attention kernels).  The reference kernels exist to avoid
materializing the (sq, sk) score matrix in HBM; this implementation does
the same thing TPU-style: k-blockwise ``lax.scan`` with online softmax
(running max + running sum), O(seq) activation memory, and a custom
blockwise backward (the flash-attention recompute recipe) — all shapes
static so XLA tiles every block matmul onto the MXU.

Layout: ``(batch, heads, seq, head_dim)``.  No seqlen-512 limit.

Returns optionally the per-row logsumexp so ring attention
(:mod:`apex_tpu.transformer.context_parallel`) can merge partial results
across devices.
"""

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def _block_sizes(sk, block_k):
    bk = min(block_k, sk)
    while sk % bk:
        bk -= 1
    return bk


def padding_bias(kv_mask):
    """(B, Sk) bool key-validity mask (True = valid) → f32 additive score
    bias (B, Sk): 0 for valid keys, NEG_INF for padded ones."""
    return jnp.where(kv_mask, 0.0, NEG_INF).astype(jnp.float32)


def repeat_kv_heads(q, k, v):
    """Grouped-query attention on paths that want full-width kv: repeat
    each kv head across its q-head group (identity when the head counts
    already match).  dk/dv cotangents through the repeat sum over the
    group — the GQA backward semantics — via ``jnp.repeat``'s transpose."""
    H, Hkv = q.shape[1], k.shape[1]
    if Hkv == H:
        return k, v
    if H % Hkv != 0:
        raise ValueError(f"q heads ({H}) not divisible by kv heads ({Hkv})")
    g = H // Hkv
    return jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)


def _bias_blocks(kv_bias, B, nblocks, bk):
    """Split an additive score bias into k-blocks for the scan.

    Accepts (B, Sk) key-only bias or a broadcastable 4D bias
    (B or 1, H or 1, Sq or 1, Sk); returns a scan input whose element is
    broadcastable against the (B, H, Sq, bk) score block."""
    if kv_bias.ndim == 2:
        kv_bias = kv_bias[:, None, None, :]
    b0, h0, q0, Sk = kv_bias.shape
    blocks = kv_bias.reshape(b0, h0, q0, nblocks, bk)
    return jnp.moveaxis(blocks, 3, 0)  # (nblocks, b0, h0, q0, bk)


def _causal_mask(q_pos, k_pos, window):
    """Key ``j`` visible to query ``i``: ``j <= i``, and under a sliding
    ``window`` ``i - j < window`` too."""
    gap = q_pos[:, None] - k_pos[None, :]
    return gap >= 0 if window is None else (gap >= 0) & (gap < window)


def _attend_fwd_scan(q, k, v, scale, causal, q_offset, k_offset, block_k,
                     kv_bias=None, window=None):
    """Online-softmax forward.  q: (B,H,Sq,D), k/v: (B,H,Sk,D).
    ``kv_bias``: optional (B, Sk) f32 additive key bias (padding masks).
    ``window``: a sliding window over a causal call (:func:`_causal_mask`).
    Returns (out, lse) with lse = log Σ exp(s·scale) per row."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bk = _block_sizes(Sk, block_k)
    nblocks = Sk // bk

    kb = k.reshape(B, H, nblocks, bk, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, nblocks, bk, D).transpose(2, 0, 1, 3, 4)

    q_pos = q_offset + jnp.arange(Sq)
    remask = causal or kv_bias is not None

    def body(carry, inp):
        m, l, acc = carry
        if kv_bias is None:
            kblk, vblk, blk_idx = inp
            bblk = None
        else:
            kblk, vblk, blk_idx, bblk = inp
        k_pos = k_offset + blk_idx * bk + jnp.arange(bk)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kblk) * scale
        if bblk is not None:
            s = s + bblk
        if causal:
            s = jnp.where(_causal_mask(q_pos, k_pos, window), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # exp(NEG_INF - NEG_INF) = 1 would give fully-masked rows (ring
        # warmup blocks, fully-padded batch entries) a spurious uniform
        # distribution; re-mask.
        p = jnp.exp(s - m_new[..., None])
        if remask:
            p = jnp.where(s > NEG_INF / 2, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vblk)
        return (m_new, l_new, acc_new), None

    xs = (kb.astype(jnp.float32), vb.astype(jnp.float32), jnp.arange(nblocks))
    if kv_bias is not None:
        xs = xs + (_bias_blocks(kv_bias, B, nblocks, bk),)
    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    acc0 = jnp.zeros((B, H, Sq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), xs)
    l = jnp.maximum(l, 1e-30)  # fully-masked rows (causal ring blocks)
    out = acc / l[..., None]
    lse = m + jnp.log(l)
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, kv_bias, scale, causal, q_offset, k_offset, block_k,
           window=None):
    out, _ = _attend_fwd_scan(q, k, v, scale, causal, q_offset, k_offset,
                              block_k, kv_bias=kv_bias, window=window)
    return out.astype(q.dtype)


def _flash_fwd(q, k, v, kv_bias, scale, causal, q_offset, k_offset, block_k,
               window=None):
    out, lse = _attend_fwd_scan(q, k, v, scale, causal, q_offset, k_offset,
                                block_k, kv_bias=kv_bias, window=window)
    return out.astype(q.dtype), (q, k, v, kv_bias, out, lse)


def flash_bwd_from_lse(q, k, v, g, lse, delta, scale, causal, q_offset=0,
                       k_offset=0, block_k=256, kv_bias=None, window=None):
    """Blockwise flash backward from (lse, delta): dV = PᵀdO;
    dS = P∘(dOVᵀ − Δ); dQ = dS·K·scale; dK = dSᵀ·Q·scale with
    Δ = rowsum(dO∘O) over the FULL row — pass it in when this call sees
    only a slice of the keys (ring attention's per-chunk backward).
    Returns f32 (dq, dk, dv); memory O(Sq·block_k)."""
    B, H, Sq, Dd = q.shape
    Sk = k.shape[2]
    bk = _block_sizes(Sk, block_k)
    nblocks = Sk // bk

    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    q_pos = q_offset + jnp.arange(Sq)
    remask = causal or kv_bias is not None

    kb = k.reshape(B, H, nblocks, bk, Dd).transpose(2, 0, 1, 3, 4).astype(jnp.float32)
    vb = v.reshape(B, H, nblocks, bk, Dd).transpose(2, 0, 1, 3, 4).astype(jnp.float32)

    if kv_bias is not None:
        bias4 = kv_bias if kv_bias.ndim == 4 else kv_bias[:, None, None, :]
        # d_bias = dS reduced over the dims the bias broadcast along
        bias_reduce = tuple(i for i in range(3) if bias4.shape[i] == 1)

    def body(dq, inp):
        if kv_bias is None:
            kblk, vblk, blk_idx = inp
            bblk = None
        else:
            kblk, vblk, blk_idx, bblk = inp
        k_pos = k_offset + blk_idx * bk + jnp.arange(bk)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kblk) * scale
        if bblk is not None:
            s = s + bblk
        if causal:
            s = jnp.where(_causal_mask(q_pos, k_pos, window), s, NEG_INF)
        p = jnp.exp(s - lse[..., None])  # (B,H,Sq,bk)
        if remask:  # fully-masked rows have lse == NEG_INF: exp(0) = 1
            p = jnp.where(s > NEG_INF / 2, p, 0.0)
        dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vblk)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kblk) * scale
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf) * scale
        if bblk is None:
            return dq, (dk, dv)
        dbias = jnp.sum(ds, axis=bias_reduce, keepdims=True) if bias_reduce else ds
        return dq, (dk, dv, dbias)

    xs = (kb, vb, jnp.arange(nblocks))
    if kv_bias is not None:
        xs = xs + (_bias_blocks(kv_bias, B, nblocks, bk),)
    dq0 = jnp.zeros_like(qf)
    if kv_bias is None:
        dq, (dks, dvs) = jax.lax.scan(body, dq0, xs)
    else:
        dq, (dks, dvs, dbs) = jax.lax.scan(body, dq0, xs)
    dk = dks.transpose(1, 2, 0, 3, 4).reshape(B, H, Sk, Dd)
    dv = dvs.transpose(1, 2, 0, 3, 4).reshape(B, H, Sk, Dd)
    if kv_bias is None:
        return dq, dk, dv
    # assemble d_bias: (nblocks, b0, h0, q0, bk) -> (b0, h0, q0, Sk) -> bias shape
    db = jnp.moveaxis(dbs, 0, 3).reshape(*bias4.shape[:3], Sk)
    if kv_bias.ndim == 2:
        db = db[:, 0, 0, :]
    return dq, dk, dv, db


def _flash_bwd(scale, causal, q_offset, k_offset, block_k, window, res, g):
    q, k, v, kv_bias, out, lse = res
    delta = jnp.sum(g.astype(jnp.float32) * out, axis=-1)  # (B,H,Sq)
    outs = flash_bwd_from_lse(
        q, k, v, g, lse, delta, scale, causal, q_offset, k_offset, block_k,
        kv_bias=kv_bias, window=window,
    )
    if kv_bias is None:
        dq, dk, dv = outs
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None
    dq, dk, dv, db = outs
    # a trained bias (OpenFold pair bias) gets its real cotangent; a
    # padding-mask bias's consumer (jnp.where over a bool mask) drops it
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            db.astype(kv_bias.dtype))


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    block_k: Optional[int] = None,
    q_offset: int = 0,
    k_offset: int = 0,
    impl: str = "auto",
    block_q: Optional[int] = None,
    kv_mask: Optional[jnp.ndarray] = None,
    attn_bias: Optional[jnp.ndarray] = None,
    window: Optional[int] = None,
):
    """Memory-efficient attention, (B, H, S, D) layout.

    ``window``: a static sliding window over a causal call: key ``j`` is
    visible to query ``i`` iff ``0 <= i - j < window`` (the query's own
    key among the ``window``).  The Pallas kernels (forward, dq, dkv)
    visit the band's blocks and sub-tiles only; the scan path masks.

    ``q_offset``/``k_offset`` give the global sequence positions of the
    local blocks (used by ring attention for cross-device causal masks).

    ``kv_mask``: optional (B, Sk) bool key-validity mask, True = valid —
    padded keys are excluded from every row's softmax (the varlen/
    padding support of ``apex/contrib/fmha/fmha.py:33-60``, expressed as
    a dense mask instead of cu_seqlens because packed ragged layouts are
    hostile to XLA's static shapes).

    ``attn_bias``: optional additive score bias broadcastable as
    (B|1, H|1, Sq|1, Sk) — OpenFold-style pair bias
    (``apex/contrib/openfold_triton/mha.py``); differentiable (its
    cotangent is dS reduced over the broadcast dims).  Runs on the scan
    path (the bias tensor already exists at (…, Sk) granularity, so the
    kernel's HBM saving does not apply to it).

    ``impl``: "pallas" (TPU kernel), "scan" (lax.scan composite), or
    "auto" — the Pallas kernel on TPU with kernel-friendly shapes, the
    scan path everywhere else.  ``block_q``/``block_k`` default to each
    implementation's tuned tile size (scan: 256; pallas: 1024 fwd).

    Grouped-query attention: k/v may carry fewer heads than q (H_kv
    divides H).  The Pallas kernels read the group-shared kv blocks
    directly; the scan path repeats kv heads (its backward sums the
    group — the same semantics).
    """
    if impl not in ("auto", "pallas", "scan"):
        raise ValueError(f"impl must be 'auto', 'pallas', or 'scan'; got {impl!r}")
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(q.shape[-1])
    if window is not None and (not causal or int(window) < 1):
        raise ValueError(f"a window ({window}) needs causal=True and at "
                         f"least one key")
    window = None if window is None else int(window)

    def scan_impl(q=q, k=k, v=v, attn_bias=attn_bias):
        k, v = repeat_kv_heads(q, k, v)
        bias = None
        if attn_bias is not None:
            while attn_bias.ndim < 4:
                attn_bias = attn_bias[None]
            bias = attn_bias.astype(jnp.float32)
        if kv_mask is not None:
            pad = padding_bias(kv_mask)
            bias = pad if bias is None else bias + pad[:, None, None, :]
        return _flash(q, k, v, bias, scale, causal, q_offset, k_offset,
                      block_k or 256, window)

    if impl != "scan" and attn_bias is None:
        from apex_tpu.ops.flash_attention_pallas import (
            flash_attention_pallas,
            pallas_flash_available,
        )

        if impl == "pallas" or pallas_flash_available(q, k):
            # the scan composite is the numerics specification, so a
            # Mosaic/launch failure degrades through the fallback
            # registry (one structured warning) instead of killing the
            # run (apex_tpu.resilience.fallback) — unless the caller
            # FORCED impl="pallas", which must fail loudly (a silent
            # degrade would turn kernel-vs-oracle tests and pallas-vs-
            # scan benchmarks into the reference checking itself)
            from apex_tpu.resilience.fallback import (
                get_registry,
                registry_engaged,
            )

            def kernel_impl():
                return flash_attention_pallas(
                    q, k, v, causal=causal, softmax_scale=scale,
                    q_offset=q_offset, k_offset=k_offset,
                    block_q=block_q, block_k=block_k, kv_mask=kv_mask,
                    window=window,
                )

            if registry_engaged(forced=(impl == "pallas")):
                return get_registry().call(
                    "flash_attention", kernel_impl, scan_impl)
            return kernel_impl()
    return scan_impl()


def flash_attention_with_lse(
    q, k, v, causal=True, softmax_scale=None, block_k: int = 256, q_offset=0, k_offset=0
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Forward returning (out, lse) for cross-device merging (no vjp —
    ring attention differentiates through its own scan)."""
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(q.shape[-1])
    out, lse = _attend_fwd_scan(q, k, v, scale, causal, q_offset, k_offset, block_k)
    return out, lse


def mha_reference(q, k, v, causal=True, softmax_scale=None, kv_mask=None,
                  window=None):
    """Naive O(S²)-memory oracle for tests (GQA via head repeat)."""
    k, v = repeat_kv_heads(q, k, v)
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq - window)
        s = jnp.where(mask, s, NEG_INF)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def block_causal_attention(q, k, v, block: int, softmax_scale=None,
                           impl: str = "auto"):
    """Attention that is causal by BLOCK, forward only, (B, H, S, D):
    key ``j`` is visible to query ``i`` iff ``j // block <= i // block``
    (bidirectional inside a block of ``block`` positions, causal across
    blocks): what a model that generates by diffusion over blocks
    prefills its prompt with.  k/v may carry fewer heads than q.

    ``impl``: "auto" (the flash forward with its ``block`` mask on a TPU
    with kernel-friendly shapes, through the fallback registry as
    :func:`flash_attention`), "pallas" / "interpret" (the kernel,
    forced), "xla" the dense softmax in float32 (the numerics
    specification; the CPU path)."""
    scale = softmax_scale if softmax_scale is not None \
        else 1.0 / np.sqrt(q.shape[-1])
    B, H, S, D = q.shape
    Hkv = k.shape[1]

    def dense_impl():
        kk, vv = k, v
        if Hkv != H:
            kk = jnp.repeat(k, H // Hkv, axis=1)
            vv = jnp.repeat(v, H // Hkv, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       kk.astype(jnp.float32)) * scale
        i = jnp.arange(S, dtype=jnp.int32)
        seen = (i[None, :] // block) <= (i[:, None] // block)
        p = jax.nn.softmax(jnp.where(seen, s, NEG_INF), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), vv)

    def kernel_impl():
        from apex_tpu.ops.flash_attention_pallas import flash_fwd_pallas

        out, _ = flash_fwd_pallas(
            q.reshape(B * H, S, D), k.reshape(B * Hkv, S, D),
            v.reshape(B * Hkv, S, D), scale, True, 0, 0,
            interpret=(impl == "interpret"), heads=H, kv_heads=Hkv,
            block=block)
        return out.reshape(B, H, S, D)

    from apex_tpu.ops.decode_attention_pallas import dispatch_kernel
    from apex_tpu.ops.flash_attention_pallas import pallas_flash_available

    return dispatch_kernel("flash_attention", impl,
                           lambda: pallas_flash_available(q, k),
                           kernel_impl, dense_impl)
