"""Pallas TPU kernels for fused LayerNorm/RMSNorm.

Reference: ``csrc/layer_norm_cuda_kernel.cu`` (1,286 LoC of Welford
row-stat kernels).  TPU version: the row dimension is blocked over the
grid; each program loads a ``(BLOCK_R, H)`` tile into VMEM, computes
row statistics on the VPU in fp32, and writes the normalized tile — one
HBM round trip for the whole op (the fusion the CUDA kernel exists for).

The backward kernel computes ``dx`` per tile plus *per-block partial*
``dw``/``db`` (grid-indexed rows of a partials buffer) that are summed
by XLA afterwards — the Pallas analog of the CUDA kernel's two-stage
part-reduction (``layer_norm_cuda_kernel.cu`` cuComputePartGradGammaBeta).

Used by :mod:`apex_tpu.normalization` when running on TPU with
lane-aligned hidden sizes; the jnp path remains the universal fallback
and the numerics specification.
"""


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_R = 256


def _pick_block_r(R, H, block_r):
    """Block rows sized to the ~16MB VMEM budget: the bwd kernel holds
    roughly 6-8 fp32 (br, H) live tiles, so keep br*H*32B ≤ 4MB."""
    budget = max(8, (4 * 1024 * 1024) // (32 * H) * 8 // 8)
    br = min(block_r, budget, R)
    br = max(8, (br // 8) * 8) if R % 8 == 0 else br
    while R % br:
        br -= 1
    return br


def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps, affine, rms):
    x = x_ref[:].astype(jnp.float32)
    if rms:
        mean = jnp.zeros((x.shape[0], 1), jnp.float32)
        var = jnp.mean(x * x, axis=1, keepdims=True)
    else:
        mean = jnp.mean(x, axis=1, keepdims=True)
        xc = x - mean
        var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mean) * rstd
    if affine:
        y = y * w_ref[:].astype(jnp.float32)
        if b_ref is not None:
            y = y + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def layer_norm_fwd_pallas(x2, weight, bias, eps, rms=False, block_r=DEFAULT_BLOCK_R, interpret=False):
    """x2: (R, H) pre-flattened.  Returns (y, mean (R,1), rstd (R,1))."""
    R, H = x2.shape
    br = _pick_block_r(R, H, block_r)
    grid = (R // br,)
    affine = weight is not None

    w2 = weight.reshape(1, H) if affine else None
    b2 = bias.reshape(1, H) if bias is not None else None

    in_specs = [pl.BlockSpec((br, H), lambda i: (i, 0), memory_space=pltpu.VMEM)]
    args = [x2]
    if affine:
        in_specs.append(pl.BlockSpec((1, H), lambda i: (0, 0), memory_space=pltpu.VMEM))
        args.append(w2)
    if b2 is not None:
        in_specs.append(pl.BlockSpec((1, H), lambda i: (0, 0), memory_space=pltpu.VMEM))
        args.append(b2)

    def kernel(*refs):
        if affine and b2 is not None:
            x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref = refs
        elif affine:
            x_ref, w_ref, y_ref, mean_ref, rstd_ref = refs
            b_ref = None
        else:
            x_ref, y_ref, mean_ref, rstd_ref = refs
            w_ref = b_ref = None
        _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, eps=eps, affine=affine, rms=rms)

    y, mean, rstd = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((br, H), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((br, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((br, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, H), x2.dtype),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=interpret,
        name="apex_ln_fwd",
    )(*args)
    return y, mean, rstd


def _ln_bwd_kernel(x_ref, w_ref, dy_ref, mean_ref, rstd_ref, dx_ref, dw_ref, db_ref, *, affine, rms):
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    mean = mean_ref[:]
    rstd = rstd_ref[:]
    xhat = (x - mean) * rstd
    gw = dy * w_ref[:].astype(jnp.float32) if affine else dy

    if rms:
        m2 = jnp.mean(gw * xhat, axis=1, keepdims=True)
        dx = (gw - xhat * m2) * rstd
    else:
        m1 = jnp.mean(gw, axis=1, keepdims=True)
        m2 = jnp.mean(gw * xhat, axis=1, keepdims=True)
        dx = (gw - m1 - xhat * m2) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)
    if affine:
        # TPU grid steps run sequentially on a core, so accumulating into
        # one (8, H) buffer is race-free (8 rows for sublane alignment;
        # row 0 carries the value).
        @pl.when(pl.program_id(0) == 0)
        def _init():
            dw_ref[:] = jnp.zeros_like(dw_ref)
            if db_ref is not None:
                db_ref[:] = jnp.zeros_like(db_ref)

        dw_ref[0:1, :] += jnp.sum(dy * xhat, axis=0, keepdims=True)
        if db_ref is not None:
            db_ref[0:1, :] += jnp.sum(dy, axis=0, keepdims=True)


def layer_norm_bwd_pallas(
    x2, weight, dy2, mean, rstd, rms=False, with_bias=True, block_r=DEFAULT_BLOCK_R, interpret=False
):
    """Returns (dx (R,H), dw_acc, db_acc) — accumulators shaped (8, H)
    with the value in row 0 (rows 1-7 zero); callers ``sum(0)``."""
    R, H = x2.shape
    br = _pick_block_r(R, H, block_r)
    grid = (R // br,)
    affine = weight is not None

    in_specs = [pl.BlockSpec((br, H), lambda i: (i, 0), memory_space=pltpu.VMEM)]
    args = [x2]
    if affine:
        in_specs.append(pl.BlockSpec((1, H), lambda i: (0, 0), memory_space=pltpu.VMEM))
        args.append(weight.reshape(1, H))
    in_specs += [
        pl.BlockSpec((br, H), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((br, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((br, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
    ]
    args += [dy2, mean, rstd]

    out_specs = [pl.BlockSpec((br, H), lambda i: (i, 0), memory_space=pltpu.VMEM)]
    out_shape = [jax.ShapeDtypeStruct((R, H), x2.dtype)]
    if affine:
        out_specs.append(pl.BlockSpec((8, H), lambda i: (0, 0), memory_space=pltpu.VMEM))
        out_shape.append(jax.ShapeDtypeStruct((8, H), jnp.float32))
        if with_bias:
            out_specs.append(pl.BlockSpec((8, H), lambda i: (0, 0), memory_space=pltpu.VMEM))
            out_shape.append(jax.ShapeDtypeStruct((8, H), jnp.float32))

    def kernel(*refs):
        if affine and with_bias:
            x_ref, w_ref, dy_ref, mean_ref, rstd_ref, dx_ref, dw_ref, db_ref = refs
        elif affine:
            x_ref, w_ref, dy_ref, mean_ref, rstd_ref, dx_ref, dw_ref = refs
            db_ref = None
        else:
            x_ref, dy_ref, mean_ref, rstd_ref, dx_ref = refs
            w_ref = dw_ref = db_ref = None
        _ln_bwd_kernel(x_ref, w_ref, dy_ref, mean_ref, rstd_ref, dx_ref, dw_ref, db_ref, affine=affine, rms=rms)

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="apex_ln_bwd",
    )(*args)
    if not affine:
        return outs[0], None, None
    if with_bias:
        return outs[0], outs[1], outs[2]
    return outs[0], outs[1], None


def pallas_available(x2, normalized_size: int) -> bool:
    """Use the kernels on real TPU with lane-aligned hidden sizes.
    Disable with APEX_TPU_PALLAS_NORM=0 (XLA's fusion of the jnp path is
    the fallback and is equally memory-bound)."""
    import os

    from apex_tpu.utils.platform import on_tpu

    if os.environ.get("APEX_TPU_PALLAS_NORM", "1") == "0":
        return False
    return on_tpu() and normalized_size % 128 == 0 and x2.dtype in (jnp.float32, jnp.bfloat16)
