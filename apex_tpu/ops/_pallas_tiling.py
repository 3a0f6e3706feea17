"""Shared Mosaic tiling facts for the Pallas kernels.

One copy of the hardware contract: Mosaic lays VMEM blocks out in
dtype-dependent (sublane, 128-lane) tiles — fp32 (8, 128), bf16/fp16
(16, 128), int8/fp8 (32, 128).  Both kernel families
(``fused_ce_pallas``, ``flash_attention_pallas``) size their row blocks
from this table; keeping it in one place is exactly the per-dtype drift
the analyzer's APX302 rule polices at the call sites.
"""

import math

import jax.numpy as jnp

LANES = 128

#: per-``pallas_call`` VMEM budget (bytes) — the analyzer's APX304
#: default (~16 MiB/core); block pickers clamp candidates against it
#: instead of discovering the overflow when Mosaic first compiles the
#: kernel on the chip.
VMEM_BUDGET = 16 * 2 ** 20


def sublane(dtype) -> int:
    """The dtype's sublane tile.  Unknown itemsizes (f64 under
    jax_enable_x64 in CPU/interpret numerics checks — no TPU tile
    exists) fall back to the minimum 8 rather than crashing."""
    return {4: 8, 2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)


#: flash attention's in-kernel sub-tile (rows and columns of one score
#: tile): a grid block is walked in squares of it, and only the live
#: ones (``flash_attention_pallas.live_subtiles``).  A tuned-table row
#: may name another for its shape.
SUBTILE = 256


#: the longest run of sub-tiles the diagonal does not touch that a
#: flash kernel computes as ONE product and one update, in columns of
#: the score piece (:func:`flash_run_cap`): what bounds
#: the code a kernel emits (a body a run, not a sub-tile) and the size
#: of the piece's float32 temporaries.
RUN_COLUMNS = 1024


def flash_run_cap(sub) -> int:
    """Sub-tiles of side ``sub`` in the longest run (``RUN_COLUMNS``
    columns); a block that is one tile (``sub`` None) has runs of one."""
    return max(1, RUN_COLUMNS // sub) if sub else 1


def flash_subtile(block_q: int, block_k: int, target: int = SUBTILE):
    """The side of the square sub-tiles a ``(block_q, block_k)`` grid
    block is walked in: the largest multiple of the lane tile that
    divides both and is at most ``target``; ``None`` where there is
    none (a block that is not a multiple of 128 is one tile, whole)."""
    g = math.gcd(int(block_q), int(block_k))
    fit = [s for s in range(LANES, min(int(target), g) + 1, LANES)
           if g % s == 0]
    return fit[-1] if fit else None


def flash_vmem_bytes(block_q: int, block_k: int, head_dim: int,
                     phase: str = "fwd", sub=None) -> int:
    """APX304-style lower-bound VMEM footprint (bytes) of one flash
    attention ``pallas_call`` at ``(block_q, block_k)``.

    The same pricing the analyzer applies: BlockSpec elements at
    4 B/element, f32 scratch at 4 B — plus the score-sized f32
    temporaries the kernel body keeps live.  Since the kernels walk a
    block in sub-tiles those are a run's, ``sub`` rows by at most
    ``RUN_COLUMNS`` columns, not ``(bq, bk)``: 3 in the forward (s, p,
    the diagonal's index difference), 5 in each backward kernel (s, p,
    dp, ds and the difference).  ``sub`` defaults to
    :func:`flash_subtile`'s choice for the block, as the kernels' does;
    a block it cannot cut is one tile.
    ``phase="bwd"`` prices the larger of the dq / dkv calls.
    Shared between ``flash_attention_pallas._pick_block`` (clamping
    candidates up front) and the tests that pin the clamp.
    """
    bq, bk, d = int(block_q), int(block_k), int(head_dim)
    sub = sub or flash_subtile(bq, bk)
    tile = (sub * min(flash_run_cap(sub) * sub, max(bq, bk))
            if sub else bq * bk)
    if phase == "fwd":
        # blocks: q, out (bq·d each), k, v (bk·d each), lse (bq·1);
        # scratch: m, l (bq·LANES each), acc (bq·d) — all f32
        blocks = 2 * bq * d + 2 * bk * d + bq
        scratch = 2 * bq * LANES + bq * d
        return 4 * (blocks + scratch + 3 * tile)
    if phase != "bwd":
        raise ValueError(f"phase must be 'fwd' or 'bwd', got {phase!r}")
    # dq call: q, do, dq out, acc scratch (bq·d each), k, v (bk·d each),
    # lse, delta (bq·1 each); dkv call: q, do (bq·d), k, v, dk, dv outs
    # and two accumulators (bk·d each), lse, delta (bq·1 each)
    dq_call = 4 * bq * d + 2 * bk * d + 2 * bq
    dkv_call = 2 * bq * d + 6 * bk * d + 2 * bq
    return 4 * (max(dq_call, dkv_call) + 5 * tile)
