"""Quantized gradient synchronization on the bucket plan: int8/fp8
wire traffic with shared per-block scales and error-feedback residuals.

Reference: ``apex/contrib/optimizers/distributed_fused_adam.py``
reserves fp8 gradient buffers with per-bucket amax scaling
(``grad_sync_dtype=torch.float8_*`` + ``_fp8_scale``/amax history);
ground papers: "DynamiQ: Accelerating Gradient Synchronization using
Compressed Multi-hop All-reduce" (PAPERS.md, arXiv 2602.08923 —
quantize at the collective, carry the quantization error forward) and
the ZeRO basis arXiv 2004.13336 whose per-bucket reduce-scatters make
the wire format pluggable here.

The TPU-shaped scheme (what makes a REAL ``reduce_scatter`` with an
int8/fp8 operand element type numerically safe — the sum happens on
the wire, in the wire dtype):

- **Shared per-block scales.**  Each bucket splits into fixed
  :data:`QBLOCK`-element blocks.  Every rank computes its local amax
  per block; one small fp32 ``psum`` (the only full-precision
  collective, ~``4/QBLOCK`` of the payload bytes) yields the SUM of
  amaxes, and the shared scale is ``s = Σ_r amax_r / qmax``.  Each
  rank additionally clips its quantized block to
  ``±⌊qmax · amax_r / Σ amax_r⌋``, so the dp-sum of everyone's
  quantized values is bounded by ``qmax`` **by construction** — int8
  accumulation cannot wrap at any world size (integer adds are exact
  and every partial sum obeys the same bound).  fp8 wire dtypes halve
  ``qmax`` as headroom for the per-add rounding of float8
  accumulation.
- **Error-feedback residuals.**  Quantization error does not average
  out: without feedback the bias accumulates in the trajectory.  Each
  rank keeps ``residual = h - dequantize(quantize(h))`` as RESIDENT
  per-bucket optimizer state (stored in the bucket's storage dtype,
  donated through jit like m/v) and adds it back into the next step's
  gradient before quantizing — the one sharded grad read.  The
  telescoping identity ``Σ_steps transmitted = Σ_steps grads −
  final_residual`` holds exactly on exactly-representable inputs
  (``tests/test_distributed_optimizers.py`` pins it bitwise).
- **Dequantize into fp32.**  The owner shard dequantizes with its
  slice of the shared scale vector and the optimizer math proceeds in
  fp32 exactly as for the wide wire dtypes (LAMB's trust-ratio segment
  sums read the dequantized fp32 shard, unchanged).

Scales must stay fp32 (a half-precision scale re-quantizes the
quantizer) and residuals must match the bucket storage dtype — the
static analyzer's APX305 pins both at the source level.
"""

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.optimizers import bucketing

__all__ = [
    "QBLOCK", "QSpec", "qspec_of", "is_quantized", "block_scales",
    "quantize", "dequantize", "quantized_reduce_scatter",
    "quantized_pmean", "quantized_pmean_bucket", "grad_sync_bytes",
]

#: Elements per scale block.  Divides every bucket's dp shard: bucket
#: totals are padded to (sublane × 128)-tile × world multiples and the
#: smallest tile (fp32) is 1024 elements.  4 B of fp32 scale per 1024
#: payload elements keeps the scale vector at ~0.4% of an int8 wire.
QBLOCK = 1024


@dataclasses.dataclass(frozen=True)
class QSpec:
    """One quantized wire format: its dtype name, the effective clip
    bound ``qmax`` (fp8 formats carry a 2x margin under their finite
    max as headroom for float accumulation rounding inside the
    reduce), and whether rounding is to-integer."""

    name: str
    qmax: float
    is_int: bool

    @property
    def wire_dtype(self):
        return jnp.dtype(self.name)


_QSPECS = {
    "int8": QSpec("int8", 127.0, True),
    # e4m3 max finite 448, e5m2 max finite 57344; half of each leaves
    # headroom so the in-reduce float8 rounding cannot overflow (e4m3
    # has no inf — an overflow saturates to nan and poisons the shard)
    "float8_e4m3fn": QSpec("float8_e4m3fn", 224.0, False),
    "float8_e5m2": QSpec("float8_e5m2", 28672.0, False),
}


def qspec_of(dtype) -> Optional[QSpec]:
    """The :class:`QSpec` for a quantized wire dtype, None for wide
    (fp32/bf16/fp16) sync dtypes."""
    if dtype is None:
        return None
    return _QSPECS.get(jnp.dtype(dtype).name)


def is_quantized(dtype) -> bool:
    return qspec_of(dtype) is not None


def block_scales(h, axis_name: str, spec: QSpec,
                 block: int = QBLOCK) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(scales, bounds)`` for one bucket, both fp32 of length
    ``len(h)//block``:

    - ``scales[b] = Σ_ranks amax_r[b] / qmax`` — SHARED across ranks
      (one small fp32 psum), chosen so the wire-dtype SUM of every
      rank's quantized block is bounded by ``qmax``;
    - ``bounds[b] = qmax · amax_r[b] / Σ amax_r[b]`` — this rank's
      per-block clip, whose dp-sum is ≤ ``qmax`` by construction.

    An all-zero block gets scale 1 and bound 0 (quantizes to exact
    zeros).  Non-finite amaxes propagate — the caller's finite vote on
    the PRE-quantization values gates the commit."""
    a_loc = jnp.max(jnp.abs(h.reshape(-1, block)), axis=1)
    a_sum = jax.lax.psum(a_loc, axis_name)
    denom = jnp.where(a_sum > 0, a_sum, 1.0)
    scales = jnp.where(a_sum > 0, a_sum / spec.qmax, 1.0)
    bounds = spec.qmax * (a_loc / denom)
    return scales, bounds


def quantize(h, scales, bounds, spec: QSpec, block: int = QBLOCK):
    """One bucket to the wire dtype: divide by the shared per-block
    scale, round (int wires; fp8 rounds in the cast), clip to this
    rank's bound so the cross-rank sum stays in range."""
    y = h.reshape(-1, block) / scales[:, None]
    if spec.is_int:
        b = jnp.floor(bounds)[:, None]
        q = jnp.clip(jnp.round(y), -b, b)
    else:
        b = bounds[:, None]
        q = jnp.clip(y, -b, b)
    return q.reshape(-1).astype(spec.wire_dtype)


def dequantize(q, scales, block: int = QBLOCK) -> jnp.ndarray:
    """Wire values back to fp32: per-block multiply by the (fp32)
    scale slice covering ``q``'s position."""
    return (q.astype(jnp.float32).reshape(-1, block)
            * scales[:, None]).reshape(-1)


def _check_block(n: int, block: int, world: int) -> None:
    if n % (block * max(world, 1)):
        raise ValueError(
            f"bucket of {n} elements does not split into {block}-element "
            f"scale blocks per {world}-way shard — bucket totals must be "
            "padded with bucketing.padded_total(shard_pad=world)")


def quantized_reduce_scatter(h, axis_name: str, spec: QSpec, rank, world,
                             block: int = QBLOCK):
    """The quantized grad sync of one bucket: returns
    ``(sum_shard_f32, residual_f32)`` where ``sum_shard_f32`` is this
    rank's 1/world shard of the dp-SUM of every rank's ``h`` (to the
    wire precision) and ``residual_f32 = h − dequantize(quantize(h))``
    is the local quantization error to carry into the next step.

    The payload crosses the wire in ``spec.wire_dtype`` — the lowering
    shows a ``reduce_scatter`` with an int8/fp8 operand element type —
    plus the fp32 scale psum from :func:`block_scales`."""
    _check_block(h.shape[0], block, world)
    scales, bounds = block_scales(h, axis_name, spec, block)
    q = quantize(h, scales, bounds, spec, block)
    residual = h - dequantize(q, scales, block)
    q_shard = jax.lax.psum_scatter(q, axis_name, scatter_dimension=0,
                                   tiled=True)
    nb_shard = (h.shape[0] // block) // world
    s_shard = jax.lax.dynamic_slice_in_dim(scales, rank * nb_shard, nb_shard)
    return dequantize(q_shard, s_shard, block), residual


def quantized_pmean(grads, axis_name: str, spec: QSpec, world: int,
                    block: int = QBLOCK):
    """Quantized gradient all-reduce for the REPLICATED data-parallel
    path (non-ZeRO): pack the grad tree into bucket-plan buckets,
    quantized reduce-scatter + all-gather — both collectives on the
    wire dtype (the gathered SUM is still bounded by ``qmax``, so the
    gather needs no re-quantization) — dequantize with the shared
    scales, divide by ``world``, unpack to storage dtypes.

    Stateless: the replicated step has no optimizer-state channel, so
    there is NO error-feedback residual here — per-step quantization
    error is unbiased-ish but uncompensated.  ZeRO
    (``DistributedFusedAdam(grad_sync_dtype=...)``) is the compressed
    path with feedback; this serves plain-DP runs that want the wire
    cut and accept the looser numerics."""
    plan = bucketing.plan_of(grads, shard_pad=world)
    leaves = jax.tree.leaves(grads)
    out = [quantized_pmean_bucket(bucketing.pack_bucket(b, leaves,
                                                        jnp.float32),
                                  axis_name, spec, world, block)
           for b in plan.buckets]
    return bucketing.unpack(plan, out)


def quantized_pmean_bucket(h, axis_name: str, spec: QSpec, world: int,
                           block: int = QBLOCK) -> jnp.ndarray:
    """One packed fp32 bucket's quantized all-reduce — the per-bucket
    body of :func:`quantized_pmean`, exposed on its own so the
    backward-overlapped train step (``make_train_step(overlap_grad_sync
    =True)``) can issue each bucket's collective the moment its
    cotangents materialize instead of after the whole backward."""
    _check_block(h.shape[0], block, world)
    scales, bounds = block_scales(h, axis_name, spec, block)
    q = quantize(h, scales, bounds, spec, block)
    q_shard = jax.lax.psum_scatter(q, axis_name, scatter_dimension=0,
                                   tiled=True)
    q_full = jax.lax.all_gather(q_shard, axis_name, axis=0, tiled=True)
    return dequantize(q_full, scales, block) * (1.0 / world)


def grad_sync_bytes(total: int, sync_dtype, block: int = QBLOCK,
                    hier=None, flat_hop: str = "dp"):
    """PER-HOP ``{hop: {"payload": bytes, "scales": bytes}}`` one
    bucket's grad sync puts on the wire per step (per rank: what this
    rank contributes to each hop's collective).  The scale-vector bytes
    of the quantized wires (the fp32 per-block amax psum) are EXPLICIT
    per hop — never folded into a payload approximation — so the
    ratios of ``ZeroOptimizerBase.wire_bytes_per_step`` (≈2x int8 vs
    bf16, ≈4x vs fp32, the ``1/dp_inner`` cross-slice cut) are exact
    (``tests/test_distributed_optimizers.py`` pins them; the time they
    save is not measured: no cell has a dp axis yet).

    - flat (``hier=None``): one hop keyed ``flat_hop`` with the full
      ``total``-element payload in the sync dtype;
    - hierarchical (``hier`` a :class:`~apex_tpu.contrib.optimizers
      ._hierarchical_sync.HierarchicalSyncPlan`): the fast inner hop
      carries the full bucket and each slower hop the chunk already
      scattered by every faster hop — ALL at the wire dtype, each with
      its own per-hop-sized scale vector, so the slow-hop bytes are
      exactly ``1/prod(faster sizes)`` of the flat plan's at equal wire
      dtype (two-level: ``1/dp_inner`` cross-slice; three-level
      additionally ``1/(dp_in * dp_out)`` cross-DCN)."""
    spec = qspec_of(sync_dtype)
    item = (spec.wire_dtype.itemsize if spec is not None
            else jnp.dtype(sync_dtype).itemsize)
    f32 = jnp.dtype(jnp.float32).itemsize

    def hop(n):
        return {"payload": n * item,
                "scales": (n // block) * f32 if spec is not None else 0}

    if hier is None:
        return {flat_hop: hop(total)}
    out, n = {}, total
    for axis, size in zip(reversed(hier.hop_axes),
                          reversed(hier.hop_sizes)):  # fast -> slow
        out[axis] = hop(n)
        n //= max(size, 1)
    return out
