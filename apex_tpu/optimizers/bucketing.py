"""Bucket plans: the TPU-native form of ``multi_tensor_apply``.

Reference: ``apex/multi_tensor_apply/multi_tensor_apply.py`` +
``csrc/multi_tensor_apply.cuh``.  The reference packs ≤110 tensor
pointers and a chunk table into kernel-launch metadata so one CUDA
launch sweeps many tensors.  The XLA analogue is a **bucket plan**:
at optimizer init (or at first trace) the param pytree is flattened in
stable ``tree_flatten`` order into a few dtype-homogeneous 1-D buckets
— per-leaf offset table, tail padded to the dtype's (sublane × 128)
tile (``ops/_pallas_tiling``) — and a sweep becomes one fused
elementwise pass per bucket instead of one op chain per leaf.
The layout is the prerequisite for cross-replica sharded weight
updates (PAPERS: arXiv 2004.13336): an equal-size 1-D bucket is what a
``psum_scatter`` shards cleanly.

The layout belongs to what SHARDS or SYNCS a whole tree at once: the
ZeRO engine (``contrib.optimizers._zero_engine``), the quantized and
hierarchical gradient syncs beside it, ``make_train_step``'s
backward-overlapped sync and the elastic resharder; ``Buckets`` is also
the view the ``multi_tensor_*`` ops accept.  The fused optimizers of
this package keep per-leaf state and never build a plan.

The ZeRO optimizers (``contrib.optimizers``) build their plans with two
extra knobs: ``shard_pad`` pads every bucket so it splits evenly into
``dp`` tile-aligned shards (the layout a per-bucket ``psum_scatter``
scatters cleanly), and ``cap_bytes`` (the reference's ``bucket_cap_mb``)
splits an oversized dtype bucket into several collective-sized buckets
at leaf granularity — each bucket then gets its own reduce-scatter /
all-gather, which is what lets XLA's latency-hiding scheduler overlap
one bucket's collective with another's math (and, inside a train step,
with the remaining backward).
"""

import dataclasses
import functools
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.ops._pallas_tiling import LANES, sublane

Tree = Any

__all__ = [
    "BucketLeaf", "BucketSpec", "BucketPlan", "Buckets", "plan_of",
    "plan_of_shapes", "padded_total", "pack", "pack_bucket", "unpack",
    "per_leaf_reduce", "seg_broadcast", "seg_ids",
    "buckets_by_stage",
]


@dataclasses.dataclass(frozen=True)
class BucketLeaf:
    """One leaf's slot inside a bucket."""

    leaf_id: int          # position in tree_flatten order
    shape: Tuple[int, ...]
    offset: int           # element offset into the bucket

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    """One dtype-homogeneous bucket: leaves back-to-back, padded tail."""

    dtype: str            # canonical storage dtype name (e.g. "float32")
    leaves: Tuple[BucketLeaf, ...]
    size: int             # payload elements (sum of leaf sizes)
    total: int            # padded length: size rounded up to the tile

    @property
    def pad(self) -> int:
        return self.total - self.size


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """The static layout: which leaf lives where.  Hashable (jit-cache
    friendly) and buildable from shapes alone — no arrays are held."""

    treedef: Any
    leaf_dtypes: Tuple[str, ...]          # storage dtype per leaf
    buckets: Tuple[BucketSpec, ...]

    @property
    def n_leaves(self) -> int:
        return len(self.leaf_dtypes)

    def __hash__(self):
        return hash((self.treedef, self.leaf_dtypes, self.buckets))


def _tile(dtype_name: str) -> int:
    """Pad-to size: the dtype's (sublane × 128) VMEM tile in elements."""
    return sublane(jnp.dtype(dtype_name)) * LANES


def padded_total(size: int, dtype_name: str, shard_pad: int = 1) -> int:
    """The bucket length for ``size`` payload elements: rounded up to
    the dtype tile × ``shard_pad``, so every 1/shard_pad shard is itself
    tile-aligned.  The ONE padding formula — the plan builder and the
    ZeRO checkpoint resharder (which re-pads a saved payload for a new
    world size) must agree or a resumed state silently misaligns."""
    unit = _tile(dtype_name) * max(1, int(shard_pad))
    return ((size + unit - 1) // unit) * unit if size else 0


@functools.lru_cache(maxsize=64)
def _plan_from_key(treedef, shapes_dtypes, cap_bytes=None,
                   shard_pad=1) -> BucketPlan:
    by_dtype: dict = {}
    order: List[str] = []  # first-appearance bucket order, deterministic
    for i, (shape, dt) in enumerate(shapes_dtypes):
        if dt not in by_dtype:
            by_dtype[dt] = []
            order.append(dt)
        by_dtype[dt].append((i, shape))
    buckets = []
    for dt in order:
        cap = None
        if cap_bytes is not None:
            # cap in elements of THIS dtype; at least one tile so a cap
            # smaller than the alignment unit still makes progress
            cap = max(int(cap_bytes) // jnp.dtype(dt).itemsize, _tile(dt))
        groups: List[List] = [[]]
        off = 0
        for i, shape in by_dtype[dt]:
            n = int(np.prod(shape)) if shape else 1
            # split at LEAF granularity (the reference splits params into
            # fragments; a leaf spanning buckets would break the static
            # per-leaf offset table every norm/unpack path slices by, so
            # an over-cap leaf gets a bucket of its own instead)
            if cap is not None and off and off + n > cap:
                groups.append([])
                off = 0
            groups[-1].append((i, shape, off))
            off += n
        for group in groups:
            if not group:
                continue
            leaves = tuple(BucketLeaf(leaf_id=i, shape=shape, offset=o)
                           for i, shape, o in group)
            size = sum(bl.size for bl in leaves)
            buckets.append(BucketSpec(
                dtype=dt, leaves=leaves, size=size,
                total=padded_total(size, dt, shard_pad)))
    return BucketPlan(
        treedef=treedef,
        leaf_dtypes=tuple(dt for _, dt in shapes_dtypes),
        buckets=tuple(buckets),
    )


def plan_of(tree: Tree, cap_bytes: Optional[int] = None,
            shard_pad: int = 1) -> BucketPlan:
    """The bucket plan for ``tree``'s (treedef, shapes, dtypes) — cached,
    so repeated traces of the same step reuse one plan object.

    ``cap_bytes`` splits oversized dtype buckets at leaf granularity
    (the reference's ``bucket_cap_mb``); ``shard_pad`` pads each bucket
    to split evenly into that many tile-aligned shards (the ZeRO dp
    shard count)."""
    leaves, treedef = jax.tree.flatten(tree)
    key = tuple((tuple(x.shape), jnp.dtype(x.dtype).name) for x in leaves)
    return _plan_from_key(treedef, key, cap_bytes, shard_pad)


def plan_of_shapes(treedef, shapes_dtypes: Sequence[Tuple[Tuple[int, ...], str]],
                   cap_bytes: Optional[int] = None,
                   shard_pad: int = 1) -> BucketPlan:
    """:func:`plan_of` from ``(shape, dtype_name)`` pairs alone — the
    ZeRO ``init`` path builds the plan for the LOCAL (model-sharded)
    leaf shapes before any local array exists."""
    return _plan_from_key(treedef, tuple(
        (tuple(s), str(d)) for s, d in shapes_dtypes), cap_bytes, shard_pad)


class Buckets:
    """A tree of 1-D bucket buffers + its plan, registered as a pytree
    (children = the buffers, aux = the plan).  ``jax.tree.map`` over a
    ``Buckets`` maps over the buffers, so the amp scaler, ``clip_grad``,
    and the ``multi_tensor_*`` ops all operate on bucket views with no
    special cases."""

    __slots__ = ("plan", "arrays")

    def __init__(self, plan: BucketPlan, arrays: Sequence):
        self.plan = plan
        self.arrays = tuple(arrays)

    def __repr__(self):
        shapes = [getattr(a, "shape", ()) for a in self.arrays]
        return f"Buckets({[b.dtype for b in self.plan.buckets]}, {shapes})"


jax.tree_util.register_pytree_node(
    Buckets,
    lambda b: (b.arrays, b.plan),
    lambda plan, arrays: Buckets(plan, arrays),
)


def pack_bucket(bucket: BucketSpec, leaves: Sequence, dtype=jnp.float32,
                scale=None) -> jnp.ndarray:
    """ONE bucket's flat concat from the tree_flatten ``leaves``, cast
    to ``dtype``, optional scalar multiply fused in, zero-padded tail —
    the per-bucket unit both :func:`pack` and the ZeRO/quantized sync
    paths read grads through (per-bucket and in the sync dtype, never a
    whole-tree flatten)."""
    parts = [jnp.ravel(leaves[bl.leaf_id]).astype(dtype)
             for bl in bucket.leaves]
    arr = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    if scale is not None:
        arr = arr * jnp.asarray(scale, dtype)
    if bucket.pad:
        arr = jnp.pad(arr, (0, bucket.pad))
    return arr


def pack(plan: BucketPlan, tree: Tree, dtype=jnp.float32,
         scale=None) -> List[jnp.ndarray]:
    """Flatten ``tree`` into ``plan``'s buckets, cast to the math dtype,
    with an optional scalar multiply (the loss-scale unscale) fused into
    the same pass.  Padding is zero-filled, so an all-finite vote over a
    packed bucket is exactly the vote over the leaves."""
    leaves = jax.tree.leaves(tree)
    if len(leaves) != plan.n_leaves:
        raise ValueError(
            f"tree has {len(leaves)} leaves; plan expects {plan.n_leaves}")
    return [pack_bucket(b, leaves, dtype, scale=scale)
            for b in plan.buckets]


def unpack(plan: BucketPlan, arrays: Sequence, dtype=None) -> Tree:
    """Slice the buckets back into the per-leaf tree.  ``dtype=None``
    casts each leaf to its storage dtype from the plan; pass
    ``jnp.float32`` for fp32 state slots."""
    leaves: List[Optional[jnp.ndarray]] = [None] * plan.n_leaves
    for b, arr in zip(plan.buckets, arrays):
        for bl in b.leaves:
            dt = dtype if dtype is not None else plan.leaf_dtypes[bl.leaf_id]
            leaves[bl.leaf_id] = jax.lax.slice(
                arr, (bl.offset,), (bl.offset + bl.size,)
            ).reshape(bl.shape).astype(dt)
    return jax.tree.unflatten(plan.treedef, leaves)


def per_leaf_reduce(plan: BucketPlan, arrays: Sequence,
                    fn: Callable) -> List[jnp.ndarray]:
    """``fn`` over each leaf's flat slice, returned in tree_flatten
    order.  This is how per-tensor reductions (the ``multi_tensor_*``
    ops' per-leaf l2 norms) read a bucket: static slices, so the
    reduction order per leaf matches a reduction over the leaf."""
    out: List[Optional[jnp.ndarray]] = [None] * plan.n_leaves
    for b, arr in zip(plan.buckets, arrays):
        for bl in b.leaves:
            out[bl.leaf_id] = fn(
                jax.lax.slice(arr, (bl.offset,), (bl.offset + bl.size,)))
    return out


def seg_ids(plan: BucketPlan, bucket: BucketSpec) -> np.ndarray:
    """Static leaf-id per element of one bucket (pad → ``n_leaves``
    sentinel): the segment map a dp-scattered shard's per-leaf
    reductions (``segment_sum``) read, since a 1/dp shard does not
    align to leaf boundaries the way :func:`per_leaf_reduce`'s static
    slices need."""
    parts = [np.full(bl.size, bl.leaf_id, np.int32) for bl in bucket.leaves]
    if bucket.pad:
        parts.append(np.full(bucket.pad, plan.n_leaves, np.int32))
    return np.concatenate(parts) if parts else np.zeros(0, np.int32)


def buckets_by_stage(plan: BucketPlan, leaf_stages: Sequence[int],
                     n_stages: int) -> List[List[int]]:
    """Group bucket indices by gradient-readiness stage for the
    backward-overlapped sync: a bucket can only be packed and wired
    once EVERY leaf in it has a cotangent, so its stage is the max of
    its leaves' (``leaf_stages`` indexed by ``leaf_id``).  Each stage's
    list keeps ascending bucket order — the stable (readiness,
    bucket_index) wire order of ``make_train_step(overlap_grad_sync=
    True)``."""
    out: List[List[int]] = [[] for _ in range(n_stages)]
    for bi, b in enumerate(plan.buckets):
        out[max(leaf_stages[bl.leaf_id] for bl in b.leaves)].append(bi)
    return out


def seg_broadcast(bucket: BucketSpec, per_leaf: Sequence):
    """Broadcast traced per-leaf scalars (indexed by ``leaf_id``) to a
    per-element bucket vector via a static-repeats gather (pad = 0)."""
    vals = [per_leaf[bl.leaf_id] for bl in bucket.leaves]
    sizes = [bl.size for bl in bucket.leaves]
    stacked = jnp.stack([jnp.asarray(v, jnp.float32) for v in vals]
                        + [jnp.float32(0.0)])
    reps = np.asarray(sizes + [bucket.pad])
    return jnp.repeat(stacked, reps, total_repeat_length=bucket.total)
