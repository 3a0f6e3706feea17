"""The ZeRO bucket engine: resident dp-sharded optimizer state on the
:class:`~apex_tpu.optimizers.bucketing.BucketPlan` layout.

Reference: ``apex/contrib/optimizers/distributed_fused_adam.py`` (3,078
LoC) — ``ParameterFragment``/``StateBucket`` fragment maps, fixed-size
buckets, reduce-scatter grad sync overlapped with backward, all-gather
param sync optionally overlapped with forward, optimizer state sharded
over the distributed process group.

TPU shape of that machinery (this module):

- **the bucket plan IS the fragment map**: params flatten (in
  ``tree_flatten`` order) into dtype-homogeneous 1-D buckets, split by
  ``bucket_cap_mb`` at leaf granularity and padded so each bucket slices
  into ``dp`` tile-aligned shards (``bucketing.plan_of(cap_bytes=...,
  shard_pad=dp)``);
- **state is resident as the local 1/dp shard of each bucket**: m/v
  (and the fp32 master or the uint16 param remainders) are per-bucket
  flat arrays sharded over ``(model axes…, dp)`` — no per-step tree
  flatten, no whole-tree fp32 concat, and the buffers donate through
  ``jax.jit`` like any other state leaf;
- **grad sync is one ``psum_scatter`` per bucket in
  ``grad_sync_dtype``** (storage dtype for half buckets by default — a
  bf16 bucket's gradient crosses the wire in bf16, half the traffic of
  the old monolithic fp32 concat), so XLA's latency-hiding scheduler
  can overlap each bucket's collective with the remaining backward and
  with other buckets' math; ``grad_sync_dtype`` of ``int8`` /
  ``float8_e4m3fn`` / ``float8_e5m2`` engages the QUANTIZED wire
  (:mod:`apex_tpu.contrib.optimizers._quantized_sync`): shared
  per-block fp32 scales from an amax psum, the narrow payload
  reduce-scattered in the wire dtype, and the per-rank quantization
  error carried as a resident error-feedback residual bucket (stored
  in the bucket's storage dtype, donated through jit like m/v);
- **param sync is one ``all_gather`` per bucket in
  ``param_sync_dtype``**; with ``overlap_param_sync`` the gather runs
  on the pre-commit update (before the cross-rank finite vote
  completes) and the commit is predicated per leaf afterwards, so the
  gather is not serialized behind the vote's collectives;
- **``dp_axes=(outer, inner)`` makes both syncs topology-aware**
  (:mod:`apex_tpu.contrib.optimizers._hierarchical_sync`): per bucket
  the grad sync becomes a TWO-HOP reduce-scatter — intra-slice on the
  fast inner axis, cross-slice on the slow outer axis at the same
  wire dtype (quantized wires requantize the partial sums against
  fresh outer-shared scales and fold the requantization error into
  the same residual channel) — and the param gathers mirror in
  reverse.  Shard ownership keeps the FLAT chunk-per-rank layout and
  the one ``bucketing.padded_total`` formula, so checkpoints reshard
  across flat <-> hierarchical worlds unchanged; cross-slice wire
  bytes drop by exactly ``1/dp_inner`` (per-hop accounting in
  :meth:`ZeroOptimizerBase.wire_bytes_per_step`).

Fail-fast contract: the collectives live INSIDE the optimizer, so this
engine never routes through the per-process
:mod:`apex_tpu.resilience.fallback` registry — a per-process degrade
would lower divergent SPMD programs (mismatched collective counts
deadlock the pod device-side, the exact hazard ``registry_engaged``
documents).  An engine failure surfaces loudly and ``--auto-resume``
restarts the job.
"""

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.contrib.optimizers import _hierarchical_sync as hs
from apex_tpu.contrib.optimizers import _quantized_sync as qs
from apex_tpu.observability import stepstats as _stepstats
from apex_tpu.optimizers import bucketing
from apex_tpu.optimizers.base import bias_corrections
from apex_tpu.transformer.parallel_state import DATA_AXIS

Tree = Any

#: Wide sync dtypes: the wire carries the values themselves.
_SUPPORTED_SYNC = ("float32", "bfloat16", "float16")

#: Quantized wire dtypes (grad sync ONLY): shared per-block fp32
#: scales + error-feedback residuals (``_quantized_sync``).  int8 is
#: the only legal integer — wider ints have no scaled-sum story and
#: narrower ones no wire support.
_QUANTIZED_GRAD_SYNC = ("int8", "float8_e4m3fn", "float8_e5m2")


def resolve_sync_dtype(value, knob: str):
    """Validate a ``grad_sync_dtype``/``param_sync_dtype`` knob; None
    means the per-bucket default (the bucket's storage dtype for half
    buckets, fp32 otherwise).  ``grad_sync_dtype`` additionally accepts
    the quantized wire dtypes ``int8``/``float8_e4m3fn``/
    ``float8_e5m2``; ``param_sync_dtype`` never does."""
    if value is None:
        return None
    dt = jnp.dtype(value)
    if dt.name in _SUPPORTED_SYNC:
        return dt
    if dt.name in _QUANTIZED_GRAD_SYNC:
        if knob == "grad_sync_dtype":
            return dt
        raise ValueError(
            f"{knob}={dt.name!r}: quantized sync is gradient-only — a "
            "param all-gather has no error-feedback channel (a gather "
            "is not a sum: each step's quantization error would land in "
            "the params with no residual to carry it to the next step); "
            f"pass one of {_SUPPORTED_SYNC} or None")
    raise ValueError(
        f"{knob}={dt.name!r} is not supported: pass one of "
        f"{_SUPPORTED_SYNC}, None (per-bucket default: the bucket's "
        "storage dtype for bf16/fp16 buckets, float32 otherwise), or — "
        f"for grad_sync_dtype only — a quantized wire dtype "
        f"{_QUANTIZED_GRAD_SYNC} (int8 is the only supported integer; "
        "per-block fp32 scales + error-feedback residuals ride the "
        "bucket plan)")


def _spec_dim_axes(entry) -> Tuple[str, ...]:
    return tuple(ax for ax in (entry if isinstance(entry, tuple) else (entry,))
                 if ax is not None)


def local_leaf_info(params, param_specs, axis_sizes, zero_axis):
    """Per-leaf LOCAL shard shapes when ``params`` are sharded over
    model-parallel mesh axes per ``param_specs``, plus the sorted model
    axes and — per leaf — the replication factor a psum over those axes
    over-counts it by (1 for fully sharded leaves).  ``zero_axis`` may
    be one axis name or the hierarchical ``(outer, inner)`` pair.
    Raises if a param is sharded over any ZeRO axis itself, or if any
    sharded DIMENSION is indivisible (floor division would silently
    misalign the flat layout)."""
    zero_axes = set(zero_axis) if isinstance(zero_axis, (tuple, list)) \
        else {zero_axis}
    leaves, treedef = jax.tree.flatten(params)
    spec_leaves = treedef.flatten_up_to(param_specs)
    used_axes: List[str] = []
    leaf_axes = []
    local_shapes = []
    for leaf, spec in zip(leaves, spec_leaves):
        shape = list(leaf.shape)
        axes_here = set()
        for dim, entry in enumerate(tuple(spec)):
            dim_axes = _spec_dim_axes(entry)
            if not dim_axes:
                continue
            for ax in dim_axes:
                if ax in zero_axes:
                    raise ValueError(
                        f"params must not be sharded over the ZeRO axis {ax!r}")
            shard = int(np.prod([axis_sizes[ax] for ax in dim_axes]))
            # per-DIMENSION check: a divisible total with an indivisible
            # sharded dim (e.g. (13, 5) split 5-way on dim 0) still
            # pads/misaligns the flat layout
            if leaf.shape[dim] % shard != 0:
                raise ValueError(
                    f"param dim {dim} of shape {leaf.shape} is not divisible "
                    f"by mesh axes {dim_axes!r} (total size {shard}); the "
                    "flat ZeRO layout would silently misalign")
            shape[dim] //= shard
            for ax in dim_axes:
                axes_here.add(ax)
                if ax not in used_axes:
                    used_axes.append(ax)
        leaf_axes.append(axes_here)
        local_shapes.append(tuple(shape))
    model_axes = tuple(sorted(used_axes))
    repl = [
        int(np.prod([axis_sizes[ax] for ax in model_axes if ax not in s]
                    or [1]))
        for s in leaf_axes
    ]
    return local_shapes, model_axes, repl


def _leaf_shard_np(leaf, spec, combo: Dict[str, int], axis_sizes):
    """The numpy block of ``leaf`` that mesh-rank ``combo`` holds under
    ``spec`` — jax shards each dim into row-major blocks, multi-axis
    dims major-to-minor left to right, which this mirrors exactly."""
    x = np.asarray(leaf)
    for dim, entry in enumerate(tuple(spec)):
        dim_axes = _spec_dim_axes(entry)
        if not dim_axes:
            continue
        n_shards = int(np.prod([axis_sizes[ax] for ax in dim_axes]))
        size = x.shape[dim] // n_shards
        idx = 0
        for ax in dim_axes:
            idx = idx * axis_sizes[ax] + combo[ax]
        x = np.take(x, range(idx * size, (idx + 1) * size), axis=dim)
    return x


class ZeroOptimizerBase:
    """Shared constructor plumbing + the bucket-shard machinery for the
    ZeRO optimizers.  Subclasses implement ``_shard_update`` (the
    per-shard math, reusing the per-leaf oracle's expression trees) and
    their state NamedTuple."""

    #: ``update_scaled`` covers the full step: the gpt step builders
    #: fold unscale/clip/finite-vote into the sharded grad read.
    supports_update_scaled = True

    def __init__(
        self,
        lr: float,
        weight_decay: float,
        axis_name: str = DATA_AXIS,
        grad_average: bool = True,
        overlap_grad_sync: bool = True,
        overlap_param_sync: bool = False,
        bucket_cap_mb: float = 100.0,
        grad_sync_dtype=None,
        param_sync_dtype=None,
        store_param_remainders: bool = False,
        dtype=jnp.float32,
        dp_axes: Optional[Sequence[str]] = None,
        process_group=None,
        distributed_process_group=None,
        redundant_process_group=None,
    ):
        self.lr = lr
        self.weight_decay = weight_decay
        self.axis_name = axis_name
        # hierarchical (slow, ..., fast) dp split: grad sync becomes
        # the multi-hop reduce-scatter of _hierarchical_sync —
        # intra-slice on the fast inner axis first, each slower axis
        # (cross-slice dp_out, cross-pod dcn) on the shrinking chunk at
        # the same wire dtype — param sync the mirrored gathers.  The
        # HierarchicalSyncPlan itself is built at init (it needs the
        # axis sizes); ownership keeps the FLAT chunk-per-rank layout,
        # so checkpoints reshard flat <-> two-level <-> three-level
        # unchanged.
        if dp_axes is not None:
            dp_axes = tuple(dp_axes)
            if not (2 <= len(dp_axes) <= 3) \
                    or len(set(dp_axes)) != len(dp_axes) \
                    or not all(isinstance(a, str) for a in dp_axes):
                raise ValueError(
                    f"dp_axes must be two or three distinct mesh axis "
                    f"names ordered slow to fast — (outer, inner) or "
                    f"(dcn, dp_out, dp_in) — got {dp_axes!r}")
        self.dp_axes = dp_axes
        self._hier_plan: Optional[hs.HierarchicalSyncPlan] = None
        self.grad_average = grad_average
        # per-bucket collectives are independently schedulable by
        # construction — overlap_grad_sync here is the reference's knob
        # for its side-stream engine and stays structural (recorded for
        # parity); the REAL backward-overlap seam is the step builder's
        # default-off ``make_train_step(overlap_grad_sync=True)``,
        # which issues each bucket's wire (``bucket_grad_wire``) inside
        # the backward and hands the engine pre-scattered shards via
        # ``presynced=``.  overlap_param_sync is real: True gathers the
        # PRE-commit update so the all-gather is not serialized behind
        # the finite vote (per-leaf predicated select afterwards).
        self.overlap_grad_sync = overlap_grad_sync
        self.overlap_param_sync = overlap_param_sync
        if bucket_cap_mb is not None and bucket_cap_mb <= 0:
            raise ValueError(f"bucket_cap_mb must be positive, got {bucket_cap_mb}")
        self.bucket_cap_mb = bucket_cap_mb
        self._cap_bytes = (None if bucket_cap_mb is None
                           else int(bucket_cap_mb * 2 ** 20))
        self.grad_sync_dtype = resolve_sync_dtype(grad_sync_dtype,
                                                  "grad_sync_dtype")
        self.param_sync_dtype = resolve_sync_dtype(param_sync_dtype,
                                                   "param_sync_dtype")
        # halve master-weight memory for bf16 params: store only the 16
        # mantissa bits the bf16 param is missing (reference
        # ``store_param_remainders``); param sync gathers bf16
        self.store_param_remainders = store_param_remainders
        if store_param_remainders and self.param_sync_dtype not in (
                None, jnp.dtype(jnp.bfloat16)):
            raise ValueError(
                "store_param_remainders gathers the master's bf16 high "
                "half; param_sync_dtype must be None or bfloat16, got "
                f"{self.param_sync_dtype.name!r}")

    # ------------------------------------------------------------- plan
    def _plan_of_local(self, params) -> bucketing.BucketPlan:
        """The plan over the LOCAL (model-sharded) param leaves — inside
        shard_map the traced leaves already have local shapes, so this
        is the same cached object ``init`` built."""
        world = getattr(self, "_world", None)
        if world is None:
            raise ValueError("call init() before update: the bucket plan "
                             "and dp shard layout live on the optimizer")
        return bucketing.plan_of(params, cap_bytes=self._cap_bytes,
                                 shard_pad=world)

    def _grad_dtype(self, bucket) -> jnp.dtype:
        if self.grad_sync_dtype is not None:
            return self.grad_sync_dtype
        dt = jnp.dtype(bucket.dtype)
        return dt if dt in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)) \
            else jnp.dtype(jnp.float32)

    @property
    def _quantized(self) -> bool:
        """True when grad sync runs the quantized wire (int8/fp8) —
        the optimizer then carries error-feedback residual buckets."""
        return qs.is_quantized(self.grad_sync_dtype)

    @property
    def _dp_sync_axes(self):
        """The axis-name argument dp-wide scalar collectives (finite
        pmin, clip psum) take: the flat axis name, or the hierarchical
        ``(outer, inner)`` tuple — one collective over the product
        group either way."""
        return self.dp_axes if self.dp_axes is not None else self.axis_name

    @property
    def hier_plan(self) -> Optional[hs.HierarchicalSyncPlan]:
        """The :class:`~apex_tpu.contrib.optimizers._hierarchical_sync
        .HierarchicalSyncPlan` built at ``init`` (None on flat dp)."""
        return self._hier_plan

    def _param_dtype(self, bucket) -> jnp.dtype:
        if self.param_sync_dtype is not None:
            return self.param_sync_dtype
        return jnp.dtype(bucket.dtype)

    # ------------------------------------------------------------- init
    def _init_plan(self, params, world_size, param_specs, axis_sizes):
        if world_size is None:
            raise ValueError("pass world_size= (the dp axis size)")
        self._world = int(world_size)
        if self.dp_axes is not None:
            self._hier_plan = hs.hierarchical_plan(
                self.dp_axes, axis_sizes,
                grad_wire_dtype=self.grad_sync_dtype,
                param_wire_dtype=self.param_sync_dtype)
            if self._hier_plan.world != self._world:
                raise ValueError(
                    f"dp_axes={self.dp_axes!r} sizes "
                    f"{self._hier_plan.hop_sizes} multiply to "
                    f"{self._hier_plan.world}, but world_size="
                    f"{self._world}: the hierarchical split must cover "
                    "exactly the flat dp world (same 1/dp shards, same "
                    "padded_total formula)")
        if param_specs is not None:
            if axis_sizes is None:
                raise ValueError("param_specs requires axis_sizes")
            local_shapes, self._model_axes, self._leaf_repl = \
                local_leaf_info(params, param_specs, axis_sizes,
                                self.dp_axes or self.axis_name)
        else:
            local_shapes = [tuple(l.shape) for l in jax.tree.leaves(params)]
            self._model_axes, self._leaf_repl = (), None
        self._axis_sizes = dict(axis_sizes or {})
        self._model_mult = int(np.prod(
            [self._axis_sizes[ax] for ax in self._model_axes] or [1]))
        leaves, treedef = jax.tree.flatten(params)
        if self._leaf_repl is None:
            self._leaf_repl = [1] * len(leaves)
        if self.store_param_remainders:
            bad = [l.dtype for l in leaves if l.dtype != jnp.bfloat16]
            if bad:
                raise ValueError(
                    f"store_param_remainders requires bf16 params (got "
                    f"{bad[:3]}): the master's high 16 bits must BE the "
                    "param")
        self._plan = bucketing.plan_of_shapes(
            treedef,
            [(s, jnp.dtype(l.dtype).name) for s, l in zip(local_shapes, leaves)],
            cap_bytes=self._cap_bytes, shard_pad=self._world)
        self._param_spec_leaves = (
            treedef.flatten_up_to(param_specs) if param_specs is not None
            else None)
        # record-only uniformity seam: the bucket plan IS the step's
        # collective schedule (one reduce_scatter/all_gather pair per
        # bucket), so a per-process plan difference — divergent
        # cap_bytes from env, divergent world, divergent leaf shapes —
        # wedges the pod; check_uniform() names this tag instead
        from apex_tpu.resilience.uniformity import assert_uniform
        assert_uniform("zero.bucket_plan", self.plan_fingerprint())
        return self._plan

    def plan_fingerprint(self) -> dict:
        """The rank-uniformity identity of the sharding layout: every
        input that shapes the lowered collective schedule (bucket
        count/sizes/dtypes, the dp world, the hierarchical split) in a
        digestable dict — what ``assert_uniform('zero.bucket_plan')``
        records and what tests pin across processes."""
        plan = self._require_plan()
        hier = self._hier_plan
        return {
            "world": self._world,
            "cap_bytes": self._cap_bytes,
            "model_mult": self._model_mult,
            "hier": None if hier is None else
                [list(hier.shard_axes), *hier.hop_sizes],
            "buckets": [[b.dtype, b.size, b.total, len(b.leaves)]
                        for b in plan.buckets],
        }

    def _zero_slot(self, dtype=jnp.float32) -> Tuple[jnp.ndarray, ...]:
        """One zeroed state slot: a flat (model_mult · bucket_total,)
        array per bucket, to be sharded over (model axes…, dp)."""
        return tuple(jnp.zeros((self._model_mult * b.total,), dtype)
                     for b in self._plan.buckets)

    def _residual_slot(self) -> Tuple[jnp.ndarray, ...]:
        """The error-feedback residuals for quantized grad sync — or
        the empty tuple on wide wires (the residual field stays in the
        state NamedTuple with zero leaves, so specs/donation/pytree
        plumbing need no special case).

        Residuals are PER-RANK FULL-BUCKET (each rank quantizes the
        whole local gradient it contributes, so its error covers every
        element — the 1-bit-Adam/EF-SGD shape), stored in the bucket's
        STORAGE dtype: globally (model_mult · dp · total,) sharded over
        (model axes…, dp), i.e. each rank resides its own (total,)
        error vector — bucket-sized like one grad copy, not
        state-sized."""
        if not self._quantized:
            return ()
        return tuple(
            jnp.zeros((self._model_mult * self._world * b.total,),
                      jnp.dtype(b.dtype))
            for b in self._plan.buckets)

    def _master_slot(self, params) -> Tuple[jnp.ndarray, ...]:
        """The resident master: fp32 pack of every mesh rank's local
        leaf shards, model-major per bucket (the layout
        ``P((*model_axes, dp))`` slices back into exactly each rank's
        shard), or zeroed uint16 remainders (zero remainder ≡ the fp32
        extension of the bf16 param — no lazy init needed)."""
        if self.store_param_remainders:
            return self._zero_slot(jnp.uint16)
        plan = self._plan
        leaves = jax.tree.leaves(params)
        if self._param_spec_leaves is None:
            return tuple(jnp.asarray(a) for a in
                         bucketing.pack(plan, params, dtype=jnp.float32))
        combos = [dict(zip(self._model_axes, c)) for c in np.ndindex(
            *[self._axis_sizes[ax] for ax in self._model_axes])] or [{}]
        out = []
        for b in plan.buckets:
            segs = []
            for cmap in combos:
                parts = [
                    _leaf_shard_np(leaves[bl.leaf_id],
                                   self._param_spec_leaves[bl.leaf_id],
                                   cmap, self._axis_sizes)
                    .astype(np.float32).reshape(-1)
                    for bl in b.leaves
                ]
                seg = np.concatenate(parts) if parts else np.zeros(0, np.float32)
                segs.append(np.pad(seg, (0, b.total - seg.size)))
            out.append(jnp.asarray(np.concatenate(segs)))
        return tuple(out)

    def _flat_spec(self):
        from jax.sharding import PartitionSpec as P

        axes = getattr(self, "_model_axes", ())
        # hierarchical shard ownership: (inner, outer) partition order
        # places flat chunk i*dp_outer + o on mesh rank (o, i) — the
        # chunk the two-hop scatter delivers there, and the SAME global
        # chunk-per-rank layout the flat plan has
        dp = self._hier_plan.shard_axes if self._hier_plan is not None \
            else (self.axis_name,)
        flat = P((*axes, *dp)) if (axes or self._hier_plan is not None) \
            else P(self.axis_name)
        return tuple(flat for _ in self._require_plan().buckets)

    @property
    def world_size(self) -> Optional[int]:
        """The dp world this optimizer's plan/state were built for
        (None before ``init``).  The elastic controller
        (:mod:`apex_tpu.resilience.elastic`) compares this against the
        LIVE world before resharding a checkpoint — a mismatch at
        restore time means ``init`` ran for the wrong mesh and the
        bucket plan would disagree with the resharded state at first
        trace."""
        return getattr(self, "_world", None)

    def _require_plan(self) -> bucketing.BucketPlan:
        plan = getattr(self, "_plan", None)
        if plan is None:
            raise ValueError("call init() first: the shard layout (bucket "
                             "plan / total_numel) lives on the optimizer")
        return plan

    def state_partition_spec(self):
        """The shard_map / pjit PartitionSpec tree for the state: each
        bucket's flat array sharded jointly over (model axes…, dp) —
        model-major, matching the layout ``init`` builds.  The residual
        field shares the flat spec (its global arrays are dp-times
        longer, each rank residing its full-bucket error vector) and is
        the empty tuple on wide wires."""
        from jax.sharding import PartitionSpec as P

        flat = self._flat_spec()
        fields = {"step": P()}
        for f in [f for f in self._STATE_CLS._fields if f != "step"]:
            fields[f] = flat
        if "residual" in self._STATE_CLS._fields and not self._quantized:
            fields["residual"] = ()
        return self._STATE_CLS(**fields)

    # ---------------------------------------------------------- prepare
    def _check_state_shards(self, plan, slot, world, name):
        if len(slot) != len(plan.buckets):
            raise ValueError(
                f"optimizer state has {len(slot)} {name} buckets but the "
                f"param tree plans {len(plan.buckets)} (bucket_cap_mb or "
                "the param tree changed since this state was created — "
                "reshard it with load_sharded_state_dicts)")
        for arr, b in zip(slot, plan.buckets):
            if arr.shape[0] != b.total // world:
                raise ValueError(
                    f"{name} bucket shard has {arr.shape[0]} elements; the "
                    f"plan expects {b.total // world} (= {b.total}/dp={world})"
                    " — state saved at a different dp world size must be "
                    "resharded with load_sharded_state_dicts")

    def _check_master_precision(self, master_slot):
        """A state restored from a checkpoint saved in the OTHER master
        precision must fail with this message at trace time, never a
        shape/NoneType crash deep in the math: the bit patterns cannot
        be value-converted silently (uint16 remainders are mantissa
        bits, not numbers)."""
        want = jnp.dtype(jnp.uint16 if self.store_param_remainders
                         else jnp.float32)
        for arr in master_slot:
            if arr.dtype != want:
                have_kind = ("remainder_u16" if arr.dtype == jnp.uint16
                             else str(arr.dtype))
                raise ValueError(
                    f"master-precision mismatch: optimizer state holds "
                    f"{have_kind} master shards but this optimizer runs "
                    f"with store_param_remainders="
                    f"{self.store_param_remainders} (expects {want.name}); "
                    "a checkpoint saved in the other master precision "
                    "cannot be value-converted silently — construct the "
                    "optimizer with the matching store_param_remainders")

    def _pack_bucket(self, leaves, bucket, dtype, scale=None):
        """One bucket's concat in ``dtype`` (the grad read / the bf16
        param read of remainder mode) — per-BUCKET and in the sync
        dtype, never a whole-tree fp32 flatten."""
        return bucketing.pack_bucket(bucket, leaves, dtype, scale=scale)

    def _check_residual_state(self, plan, residuals) -> None:
        """The error-feedback residuals must exist exactly when the
        wire is quantized — a compressed checkpoint restored into an
        uncompressed optimizer (or vice versa) fails HERE, at trace
        time with the knob named, mirroring the remainder-master
        check."""
        n = len(residuals) if residuals is not None else 0
        if not self._quantized:
            if n:
                raise ValueError(
                    "optimizer state carries error-feedback residual "
                    "buckets but this optimizer's grad_sync_dtype="
                    f"{getattr(self.grad_sync_dtype, 'name', None)!r} is "
                    "not quantized: a compressed (int8/fp8) checkpoint "
                    "cannot be value-converted silently — construct the "
                    "optimizer with the matching grad_sync_dtype")
            return
        if n != len(plan.buckets):
            raise ValueError(
                f"grad_sync_dtype={self.grad_sync_dtype.name!r} needs one "
                f"error-feedback residual per bucket ({len(plan.buckets)}), "
                f"state has {n}: this state was saved by an uncompressed "
                "run (or a different bucket layout) — resume with the "
                "matching grad_sync_dtype or reshard with "
                "load_sharded_state_dicts")
        for arr, b in zip(residuals, plan.buckets):
            if arr.shape[0] != b.total:
                raise ValueError(
                    f"residual bucket holds {arr.shape[0]} elements; each "
                    f"rank resides its FULL local bucket ({b.total}) — "
                    "state saved at another world size must be resharded "
                    "with load_sharded_state_dicts")
            if arr.dtype != jnp.dtype(b.dtype):
                raise ValueError(
                    f"residual bucket dtype {arr.dtype} must match the "
                    f"bucket storage dtype {b.dtype} (the APX305 "
                    "contract: a narrower residual re-quantizes the "
                    "feedback)")

    def _dp_rank_world(self):
        """``(rank, world)`` of this shard_map instance on the dp
        group: flat ``axis_index``/``axis_size``, or the hierarchical
        Horner rank over the hop axes (fast-major — the SAME global
        chunk-per-rank layout the flat plan has, at any hop depth)."""
        hier = self._hier_plan
        if hier is not None:
            world = 1
            for s in hier.traced_sizes():
                world = world * s
            return hier.zero_rank(), world
        ax = self._dp_sync_axes
        return jax.lax.axis_index(ax), jax.lax.axis_size(ax)

    def bucket_grad_wire(self, b, leaves, scale=None, residual=None):
        """ONE bucket's gradient wire — the factored per-bucket body of
        :meth:`_prepare_grads`, public so the backward-overlapped step
        builders (``make_train_step(overlap_grad_sync=True)``) can
        issue it INSIDE the backward as soon as this bucket's leaf
        cotangents materialize, then hand the engine the results via
        ``_prepare_grads(presynced=...)``.

        ``leaves`` is the flat leaf list in plan order — only the
        entries named by ``b.leaves[*].leaf_id`` are read, so a caller
        mid-backward may pass a partially-filled list.  ``residual`` is
        this bucket's error-feedback state (required exactly when the
        wire is quantized).  Returns ``(g32_shard, new_residual,
        pre_wire)`` — the fp32 1/dp shard of the synced grad, and on
        quantized wires the UNCOMMITTED refreshed residual plus the
        fp32 pre-quantization bucket for the caller's finite vote
        (both ``None`` on wide wires).

        The ops and their order inside one bucket are IDENTICAL to the
        unoverlapped path — overlap only moves whole-bucket wires
        earlier in the trace, so fp32 results stay bitwise equal."""
        ax = self._dp_sync_axes
        hier = self._hier_plan
        rank, world = self._dp_rank_world()
        sdt = self._grad_dtype(b)
        spec = qs.qspec_of(sdt)
        if spec is not None:
            # quantized wire: unscale BEFORE quantizing (the residual
            # must be in loss-scale-free units — a scaler backoff
            # between steps must not re-weight carried error), add the
            # residual, quantize against the shared per-block scales,
            # reduce-scatter int8/fp8
            if residual is None:
                raise ValueError(
                    "bucket_grad_wire on a quantized wire needs this "
                    "bucket's error-feedback residual (state.residual[bi])")
            h = self._pack_bucket(
                leaves, b, jnp.float32,
                scale=(1.0 / scale) if scale is not None else None)
            h = h + residual.astype(jnp.float32)
            if hier is not None:
                g_sum, res_new = hs.quantized_multi_hop_reduce_scatter(
                    h, hier, spec)
            else:
                g_sum, res_new = qs.quantized_reduce_scatter(
                    h, ax, spec, rank, world)
            g32 = g_sum / world if self.grad_average else g_sum
            return g32, res_new.astype(jnp.dtype(b.dtype)), h
        # fp16 sync pre-divides (the reference's predivide: the
        # world-sized sum would overflow fp16's range); fp32/bf16
        # sync post-divides in fp32 — same association the
        # replicated path's psum-then-pmean takes, so ZeRO vs
        # replicated trajectories agree to the grad's own rounding
        predivide = (self.grad_average
                     and sdt == jnp.dtype(jnp.float16))
        bucket = self._pack_bucket(
            leaves, b, sdt, scale=(1.0 / world) if predivide else None)
        # ZeRO grad sync: each rank owns 1/dp of the dp-SUM — the
        # one collective read of this bucket's gradient (plain hops
        # fast-to-slow on a hierarchical mesh, same wire dtype each)
        if hier is not None:
            g_loc = hs.multi_hop_reduce_scatter(bucket, hier)
        else:
            g_loc = jax.lax.psum_scatter(bucket, ax,
                                         scatter_dimension=0,
                                         tiled=True)
        g32 = g_loc.astype(jnp.float32)
        if self.grad_average and not predivide:
            g32 = g32 / world
        if scale is not None:
            # loss-scale unscale AFTER the sync, in fp32: half-dtype
            # wires carry the scaled grads (no underflow), the math
            # sees unscaled fp32
            g32 = g32 * (1.0 / scale)
        return g32, None, None

    def _prepare_grads(self, plan, grads, scale, clip_norm, finite_sync,
                       want_finite, grads_finite, sumsq_reduce,
                       residuals=None, presynced=None):
        """The sharded grad read: per-bucket reduce-scatter in
        ``grad_sync_dtype`` (grad-average pre-division folded in — the
        reference's predivide, overflow-safe for large worlds), fp32
        unscale on the 1/dp shard, the all-finite vote, and the
        global-l2 clip with per-leaf Σx² recovered from the shards via
        the plan's static segment map.

        With a quantized wire the same single read additionally folds
        the error-feedback residual add (``h = g/scale + residual``),
        the shared-scale quantization, and the residual refresh — the
        wire carries int8/fp8 plus the small fp32 scale psum, and the
        UNSCALED error lives in the residual so loss-scale changes
        between steps cannot change its units.  Returns
        ``(g32_shards, new_residuals, pred, rank, world)`` —
        ``new_residuals`` is ``()`` on wide wires, UNCOMMITTED (the
        caller predicates it on the finite vote: a skipped step leaves
        residuals untouched).

        With ``dp_axes=(outer, inner)`` every dp collective here is the
        TWO-HOP form (:mod:`~apex_tpu.contrib.optimizers
        ._hierarchical_sync`): reduce-scatter intra-slice on the fast
        inner axis, then cross-slice on the slow outer axis at the same
        wire dtype — on quantized wires the partial sums requantize
        against fresh outer-shared scales and the requantization error
        folds into the SAME residual channel.

        ``presynced=(g_shards, new_residuals, pre_wire)`` is the
        backward-overlap handoff: the step builder already issued every
        bucket's wire (:meth:`bucket_grad_wire` inside the backward, in
        reverse-backward bucket order), so the wire loop is skipped and
        everything AFTER it — the finite vote, the clip, the telemetry
        — runs here unchanged on identical values (``grads`` may be
        ``None`` then)."""
        ax = self._dp_sync_axes
        rank, world = self._dp_rank_world()
        self._check_residual_state(plan, residuals)
        if presynced is not None:
            pre_g, pre_res, pre_h = presynced
            if len(pre_g) != len(plan.buckets):
                raise ValueError(
                    f"presynced carries {len(pre_g)} bucket shards; the "
                    f"plan has {len(plan.buckets)} buckets")
            g_shards = list(pre_g)
            new_residuals = [r for r in pre_res if r is not None]
            pre_wire = [h for h in pre_h if h is not None]
        else:
            leaves = jax.tree.leaves(grads)
            if len(leaves) != plan.n_leaves:
                raise ValueError(f"grad tree has {len(leaves)} leaves; plan "
                                 f"expects {plan.n_leaves}")
            g_shards = []
            new_residuals = []
            pre_wire = []  # fp32 pre-quantization buckets, for the vote
            for bi, b in enumerate(plan.buckets):
                g32, res_new, h = self.bucket_grad_wire(
                    b, leaves, scale=scale,
                    residual=residuals[bi] if self._quantized else None)
                g_shards.append(g32)
                if res_new is not None:
                    new_residuals.append(res_new)
                if h is not None:
                    # a non-finite grad quantizes to garbage the wire
                    # may MASK (nan -> int8 is finite): vote on the
                    # pre-quantization values, not just the shards
                    pre_wire.append(h)

        pred = grads_finite
        if want_finite:
            from apex_tpu.amp.scaler import all_finite

            finite = all_finite(list(g_shards) + pre_wire)
            if finite_sync is not None:
                # the caller's vote MUST include the ZeRO axis: shards
                # are dp-disjoint, so ranks can disagree (the gpt step
                # builders append dp to sync_axes for ZeRO optimizers)
                finite = finite_sync(finite)
            else:
                finite = jax.lax.pmin(finite.astype(jnp.int32),
                                      ax).astype(jnp.bool_)
            pred = finite

        if clip_norm is not None:
            from apex_tpu.optimizers.base import _clip_coef

            leaf_sq = self._per_leaf_sumsq(plan, g_shards, rank, world)
            leaf_sq = jax.lax.psum(leaf_sq, ax)  # assemble dp-disjoint shards
            total_sq = (sumsq_reduce([leaf_sq[i] for i in range(plan.n_leaves)])
                        if sumsq_reduce is not None else jnp.sum(leaf_sq))
            # the telemetry seam reuses the clip's globally agreed norm
            # (the observability.stepstats no-new-HBM-pass contract)
            _stepstats.offer("grad_norm", jnp.sqrt(total_sq))
            # ONE clip expression (torch semantics) with the replicated
            # engine — the two trajectories must not drift
            coef = _clip_coef(jnp.sqrt(total_sq), clip_norm)
            g_shards = [g * coef for g in g_shards]
        else:
            # no clip to reuse: the shared rank-local fold — no dp psum
            # (the stat must add zero collectives), so this is this
            # rank's 1/dp-shard norm, documented
            _stepstats.offer_local_grad_norm(g_shards)
        return g_shards, tuple(new_residuals), pred, rank, world

    def _commit_residuals(self, new_residuals, old_residuals, pred):
        """The residual commit, predicated like every other state slot:
        a skipped (non-finite) step leaves the carried error untouched
        — a nan must never poison the feedback channel."""
        if not self._quantized:
            return ()
        return tuple(self._select(pred, list(new_residuals),
                                  list(old_residuals)))

    def _per_leaf_sumsq(self, plan, shards, rank, world):
        """Per-ORIGINAL-leaf Σx² of per-bucket 1/dp shards, via the
        static segment map sliced to this rank's window (a dp shard
        does not align to leaf boundaries) — LOCAL partial sums; psum
        over dp (and model axes, per caller semantics) completes them."""
        out = jnp.zeros((plan.n_leaves,), jnp.float32)
        for bi, b in enumerate(plan.buckets):
            ids = jnp.asarray(bucketing.seg_ids(plan, b))
            shard = b.total // world
            ids_loc = jax.lax.dynamic_slice_in_dim(ids, rank * shard, shard)
            out = out + jax.ops.segment_sum(
                jnp.square(shards[bi]), ids_loc,
                num_segments=plan.n_leaves + 1)[:plan.n_leaves]
        return out

    def _owned_param_shards(self, plan, params, rank, world):
        """The rank's bf16 param shard per bucket (remainder mode's
        master reconstruction input): per-BUCKET bf16 concat + dynamic
        slice — bf16 traffic only, no fp32 up-cast."""
        leaves = jax.tree.leaves(params)
        out = []
        for b in plan.buckets:
            bucket = self._pack_bucket(leaves, b, jnp.bfloat16)
            shard = b.total // world
            out.append(jax.lax.dynamic_slice_in_dim(bucket, rank * shard,
                                                    shard))
        return out

    # ------------------------------------------------------------- emit
    def _emit_params(self, plan, shard_out, params, pred):
        """ZeRO param sync: one ``all_gather`` per bucket in
        ``param_sync_dtype``, sliced back into the leaf tree through the
        plan's offset table (static slices — never a whole-tree
        concat/flatten).

        ``shard_out`` is the UNCOMMITTED updated shard per bucket when
        ``overlap_param_sync`` (the gather starts without waiting for
        the finite vote; ``pred`` then selects per leaf against the old
        params), else the committed shard (``pred`` None here).

        On a hierarchical mesh the gather MIRRORS the two-hop scatter:
        outer (slow) hop first — the slice-shared shard, ``1/dp_inner``
        of the bucket crossing slices — then the inner (fast) hop."""
        ax = self.axis_name
        hier = self._hier_plan
        leaves = jax.tree.leaves(params)
        new_leaves: List[Optional[jnp.ndarray]] = [None] * plan.n_leaves
        for bi, b in enumerate(plan.buckets):
            shard = shard_out[bi].astype(self._param_dtype(b))
            if hier is not None:
                full = hs.two_hop_all_gather(shard, hier)
            else:
                full = jax.lax.all_gather(shard, ax, axis=0, tiled=True)
            for bl in b.leaves:
                leaf = jax.lax.slice(
                    full, (bl.offset,), (bl.offset + bl.size,)
                ).reshape(bl.shape).astype(leaves[bl.leaf_id].dtype)
                if pred is not None:
                    leaf = jnp.where(jnp.asarray(pred), leaf,
                                     leaves[bl.leaf_id])
                new_leaves[bl.leaf_id] = leaf
        return jax.tree.unflatten(plan.treedef, new_leaves)

    @staticmethod
    def _select(pred, new, old):
        if pred is None:
            return list(new)
        p = jnp.asarray(pred)
        return [jnp.where(p, n, o) for n, o in zip(new, old)]

    def _bias_corrections(self, step):
        return bias_corrections(step, self.bias_correction,
                                self.beta1, self.beta2)

    # ------------------------------------------------------- public API
    def update(self, grads, state, params, grads_finite=None, lr=None,
               clip_norm=None, sumsq_reduce=None, presynced=None):
        """One ZeRO step inside shard_map.  ``grads`` are this rank's
        LOCAL grads (the optimizer's reduce-scatter IS the dp gradient
        sync); ``grads_finite`` (already agreed across every axis)
        predicates the commit; ``clip_norm`` folds a global-l2 clip
        (torch semantics) into the sharded grad read with
        ``sumsq_reduce`` supplying the model-axes Σx² agreement.
        ``presynced`` hands over wires already issued inside the
        backward (:meth:`bucket_grad_wire`); ``grads`` may be None."""
        p, s, _ = self._zero_step(grads, state, params,
                                  grads_finite=grads_finite, lr=lr,
                                  clip_norm=clip_norm,
                                  sumsq_reduce=sumsq_reduce,
                                  want_finite=False, presynced=presynced)
        return p, s

    def update_scaled(self, grads, state, params, scale=None,
                      clip_norm=None, finite_sync=None, lr=None,
                      sumsq_reduce=None, presynced=None):
        """The fused amp step on the sharded grad read: per-bucket
        reduce-scatter, fp32 unscale of the 1/dp shard, the all-finite
        vote (``finite_sync`` must agree it over the model axes AND
        dp), optional global-l2 clip, predicated commit.  Returns
        ``(new_params, new_state, all_finite)``.  ``presynced`` hands
        over wires already issued inside the backward
        (:meth:`bucket_grad_wire`); ``grads``/``scale`` consumed there."""
        return self._zero_step(grads, state, params, scale=scale,
                               clip_norm=clip_norm, finite_sync=finite_sync,
                               lr=lr, sumsq_reduce=sumsq_reduce,
                               want_finite=True, presynced=presynced)

    def step(self, grads, state, params, **kw):
        return self.update(grads, state, params, **kw)

    def _zero_step(self, grads, state, params, grads_finite=None, lr=None,
                   scale=None, clip_norm=None, finite_sync=None,
                   sumsq_reduce=None, want_finite=False, presynced=None):
        raise NotImplementedError  # pragma: no cover - abstract

    # ----------------------------------------------------- state dicts
    #: v3 adds the error-feedback residual buckets (full local bucket
    #: per rank, storage dtype) + ``residual_kind`` metadata.  v2
    #: (pre-quantization) checkpoints still load — into uncompressed
    #: optimizers only.
    SHARD_FORMAT = "apex_tpu_zero2_v3"
    _READ_FORMATS = ("apex_tpu_zero2_v2", "apex_tpu_zero2_v3")

    @property
    def _master_kind(self) -> str:
        return "remainder_u16" if self.store_param_remainders else "fp32"

    @property
    def _residual_kind(self) -> str:
        """``"ef"`` when the quantized wire carries error-feedback
        residual state, ``"none"`` otherwise — the save/restore
        compatibility key (mirrors ``master_kind``)."""
        return "ef" if self._quantized else "none"

    def _check_residual_kind(self, d) -> None:
        kind = d.get("residual_kind")
        if kind is None:  # v2 checkpoints never carried residuals
            kind = "none"
        if kind != self._residual_kind:
            have = ("a compressed (error-feedback) checkpoint"
                    if kind == "ef" else "an uncompressed checkpoint")
            raise ValueError(
                f"checkpoint residual_kind {kind!r} does not match this "
                f"optimizer's ({self._residual_kind!r}): {have} cannot "
                "restore into an optimizer whose grad_sync_dtype="
                f"{getattr(self.grad_sync_dtype, 'name', None)!r} — "
                "construct the optimizer with the matching "
                "grad_sync_dtype (quantized <-> not is a state-layout "
                "change, like store_param_remainders)")

    def _check_master_kind(self, d):
        """A store_param_remainders mismatch between save and load would
        value-convert master bit patterns silently — refuse instead."""
        kind = d.get("master_kind")
        if kind is None:  # pre-remainder checkpoints were always fp32
            kind = "fp32"
        if kind != self._master_kind:
            raise ValueError(
                f"checkpoint master_kind {kind!r} does not match this "
                f"optimizer's ({self._master_kind!r}): set "
                f"store_param_remainders={kind == 'remainder_u16'}")

    def _bucket_meta(self):
        plan = self._require_plan()
        return [{"dtype": b.dtype, "size": b.size, "total": b.total}
                for b in plan.buckets]

    def wire_bytes_per_step(self) -> Dict[str, Any]:
        """Static per-step wire accounting off the bucket plan, per
        sync mode (byte counts from shapes; collective time on the chip
        is not measured until a four-chip cell exists):

        - ``grad_payload``: Σ bucket totals × the grad wire itemsize
          (1 B for int8/fp8), summed over every hop;
        - ``grad_scales``: the quantized wires' fp32 per-block scale
          psums (0 on wide wires), one per hop — counted so the
          reported cut is honest (int8 ≈ 2x vs bf16, ≈ 4x vs fp32,
          minus ~0.4% scales);
        - ``grad_sync`` = payload + scales; ``param_sync``: the
          all-gather payload in ``param_sync_dtype``; ``total``;
        - ``hops``: the PER-HOP split ``{axis: {grad_payload,
          grad_scales, grad_sync, param_sync, total}}`` — one entry
          (the flat dp axis) on a flat plan, ``{inner, outer}`` axes on
          a hierarchical one.  The slow (outer/cross-slice) hop's entry
          is exactly ``1/dp_inner`` of the flat plan's bytes at equal
          wire dtype, scales included."""
        plan = self._require_plan()
        hier = self._hier_plan
        hops: Dict[str, Dict[str, int]] = {}

        def add(hop, key, n):
            d = hops.setdefault(hop, {"grad_payload": 0, "grad_scales": 0,
                                      "param_sync": 0})
            d[key] += n

        for b in plan.buckets:
            for hop, hb in qs.grad_sync_bytes(
                    b.total, self._grad_dtype(b), hier=hier,
                    flat_hop=self.axis_name).items():
                add(hop, "grad_payload", hb["payload"])
                add(hop, "grad_scales", hb["scales"])
            p_item = self._param_dtype(b).itemsize
            if hier is not None:
                # mirrored gathers: the fast hop reassembles the full
                # bucket, each slower hop moves the chunk already
                # scattered by every faster hop (three-level: the dcn
                # hop carries exactly 1/(dp_in*dp_out) of the bucket)
                n = b.total
                for axis, size in zip(reversed(hier.hop_axes),
                                      reversed(hier.hop_sizes)):
                    add(axis, "param_sync", n * p_item)
                    n //= max(size, 1)
            else:
                add(self.axis_name, "param_sync", b.total * p_item)

        for d in hops.values():
            d["grad_sync"] = d["grad_payload"] + d["grad_scales"]
            d["total"] = d["grad_sync"] + d["param_sync"]
        out: Dict[str, Any] = {
            k: sum(d[k] for d in hops.values())
            for k in ("grad_payload", "grad_scales", "grad_sync",
                      "param_sync", "total")}
        out["hops"] = hops
        return out

    def _state_arrays(self, state) -> Dict[str, Sequence]:
        """name -> per-bucket arrays, in the subclass's field order."""
        return {f: getattr(state, f) for f in state._fields if f != "step"}

    def state_dict(self, state):
        """Whole-state dict (the reference's ``gather_on_root=True``
        mode, distributed_fused_adam.py:2527).  For the per-rank
        protocol use :meth:`sharded_state_dict`."""
        d = {
            "format": self.SHARD_FORMAT,
            "step": int(state.step),
            "master_kind": self._master_kind,
            "residual_kind": self._residual_kind,
            "buckets": self._bucket_meta(),
        }
        for name, slot in self._state_arrays(state).items():
            d[name] = [np.asarray(a) for a in slot]
        return d

    #: the state NamedTuple class (subclasses set it)
    _STATE_CLS = None

    def load_state_dict(self, d):
        fmt = d.get("format")
        fmt = np.asarray(fmt).item() if isinstance(fmt, np.ndarray) else fmt
        if fmt not in self._READ_FORMATS:
            # a pre-bucket (v1 flat-array) dict would otherwise iterate
            # its flat slot into thousands of 0-d scalars and fail later
            # with a misleading bucket-layout error
            raise ValueError(
                f"unrecognized state_dict format {fmt!r}: this optimizer "
                f"reads {self._READ_FORMATS} (per-bucket arrays); "
                "pre-bucket-plan (flat v1) checkpoints cannot be loaded")
        self._check_master_kind(d)
        self._check_residual_kind(d)
        fields = {"step": jnp.int32(d["step"])}
        for f in [f for f in self._STATE_CLS._fields if f != "step"]:
            # ONLY residual may be absent (v2 dicts predate it; empty
            # on wide wires) — a missing m/v/master slot is corruption
            # and must stay a loud KeyError here, not a misleading
            # bucket-layout error at first trace
            src = d.get(f, ()) if f == "residual" else d[f]
            fields[f] = tuple(jnp.asarray(a) for a in src)
        return self._STATE_CLS(**fields)

    def sharded_state_dict(self, state, rank: int, world_size: int):
        """Per-rank shard of the state + the layout metadata needed to
        reshard on load (reference ``state_dict(gather_on_root=False)``,
        distributed_fused_adam.py:2527; redistribution :2959).  Each
        bucket's piece is ``(model_mult, shard)`` — the model segments
        kept separate so a dp=4 save reshard-loads at dp=2 without
        scrambling the model-major layout."""
        plan = self._require_plan()
        if world_size != self._world:
            raise ValueError(
                f"state was built for dp={self._world}; sharded_state_dict "
                f"slices that layout (got world_size={world_size})")
        d = {
            "format": self.SHARD_FORMAT,
            "master_kind": self._master_kind,
            "residual_kind": self._residual_kind,
            "rank": int(rank),
            "world_size": int(world_size),
            "model_mult": self._model_mult,
            "step": int(state.step),
            "buckets": self._bucket_meta(),
            "total_numel": int(sum(b.size for b in plan.buckets)),
        }
        for name, slot in self._state_arrays(state).items():
            pieces = []
            for arr, b in zip(slot, plan.buckets):
                if name == "residual":
                    # each rank resides its FULL local bucket: the
                    # global layout is (model_mult, world, total) and
                    # rank r's piece is the (model_mult, total) block
                    a = np.asarray(arr).reshape(
                        self._model_mult, world_size, b.total)
                    pieces.append(a[:, rank, :].copy())
                    continue
                shard = b.total // world_size
                a = np.asarray(arr).reshape(self._model_mult, b.total)
                pieces.append(a[:, rank * shard:(rank + 1) * shard].copy())
            d[name] = pieces
        return d

    #: sentinel: "caller did not say" (None is a meaningful value — an
    #: uncompressed optimizer)
    _UNSPECIFIED = object()

    @classmethod
    def load_sharded_state_dicts(cls, shards, world_size: int,
                                 store_param_remainders: Optional[bool] = None,
                                 grad_sync_dtype=_UNSPECIFIED):
        """Reassemble a full state from per-rank shard dicts and reshard
        it for ``world_size`` ranks (which may differ from the saved
        world — save at dp=4, load at dp=2): per bucket and per model
        segment, concat the saved dp slices, trim to the payload, and
        re-pad with the plan's own formula
        (:func:`bucketing.padded_total`) for the new world.

        Error-feedback residuals (quantized grad sync, format v3)
        reshard with the SAME pad formula: at the saved world size each
        rank's full-bucket residual round-trips bitwise; at a different
        world size the per-rank errors are summed into the new rank
        0's residual (zeros elsewhere) — what the optimizer trajectory
        sees is ``Σ_r (g_r + residual_r)``, so the sum-collapse
        preserves the carried error exactly while the per-rank
        attribution (which no longer exists) is dropped.

        Pass ``grad_sync_dtype=`` to assert the target optimizer's wire
        up front (mirrors ``store_param_remainders``): a compressed
        checkpoint refuses to reshard for an uncompressed optimizer and
        vice versa."""
        def _py(v):
            """io round-trips scalars/strings as 0-d numpy arrays —
            coerce metadata back to python before comparisons."""
            v = np.asarray(v).item() if isinstance(v, np.ndarray) else v
            return v

        skip = set(cls._STATE_CLS._fields) | {"buckets"}
        shards = [{k: _py(v) if k not in skip else v
                   for k, v in d.items()} for d in shards]
        for d in shards:
            d["buckets"] = [{k: _py(v) for k, v in bm.items()}
                            for bm in d["buckets"]]
        shards = sorted(shards, key=lambda d: d["rank"])
        if not shards:
            raise ValueError("no shards given")
        meta = shards[0]
        if meta.get("format") not in cls._READ_FORMATS:
            raise ValueError(
                f"unrecognized shard format {meta.get('format')!r} (pre-"
                f"bucket-plan checkpoints cannot be resharded by this "
                "version)")
        saved_world = meta["world_size"]
        if [d["rank"] for d in shards] != list(range(saved_world)):
            raise ValueError(
                f"incomplete shard set: got ranks {[d['rank'] for d in shards]}, "
                f"saved world size is {saved_world}")
        for d in shards:
            for key in ("model_mult", "total_numel", "step", "world_size"):
                if d[key] != meta[key]:
                    raise ValueError(f"shard {d['rank']} disagrees on {key}")
            for kind_key, default in (("master_kind", "fp32"),
                                      ("residual_kind", "none")):
                if d.get(kind_key, default) != meta.get(kind_key, default):
                    raise ValueError(
                        f"shard {d['rank']} disagrees on {kind_key}")
        if store_param_remainders is not None:
            want = "remainder_u16" if store_param_remainders else "fp32"
            got = meta.get("master_kind", "fp32")
            if got != want:
                raise ValueError(
                    f"checkpoint master_kind {got!r} does not match "
                    f"store_param_remainders={store_param_remainders}")
        res_kind = meta.get("residual_kind", "none")
        if grad_sync_dtype is not cls._UNSPECIFIED:
            resolved = resolve_sync_dtype(grad_sync_dtype, "grad_sync_dtype")
            want_kind = "ef" if qs.is_quantized(resolved) else "none"
            if res_kind != want_kind:
                raise ValueError(
                    f"checkpoint residual_kind {res_kind!r} does not match "
                    f"grad_sync_dtype={getattr(resolved, 'name', None)!r}: "
                    "compressed (error-feedback) and uncompressed states "
                    "cannot be value-converted silently")

        mm = meta["model_mult"]
        buckets = meta["buckets"]
        fields = {"step": jnp.int32(meta["step"])}
        state_cls = cls._STATE_CLS
        for name in [f for f in state_cls._fields if f != "step"]:
            if name == "residual":
                fields[name] = cls._reshard_residuals(
                    shards, meta, world_size) if res_kind == "ef" else ()
                continue
            out = []
            for bi, bm in enumerate(buckets):
                # (model_mult, saved_total) from the saved dp slices
                full = np.concatenate([d[name][bi] for d in shards], axis=1)
                payload = full[:, :bm["size"]]
                new_total = bucketing.padded_total(
                    bm["size"], bm["dtype"], world_size)
                padded = np.zeros((mm, new_total), payload.dtype)
                padded[:, :bm["size"]] = payload
                out.append(jnp.asarray(padded.reshape(-1)))
            fields[name] = tuple(out)
        return state_cls(**fields)

    @classmethod
    def _reshard_residuals(cls, shards, meta, world_size: int):
        """Residual buckets for the new world (see
        :meth:`load_sharded_state_dicts`): bitwise per-rank restore at
        the saved world, trajectory-sum-preserving collapse onto the
        new rank 0 otherwise.  Pads with the ONE
        :func:`bucketing.padded_total` formula."""
        mm = meta["model_mult"]
        saved_world = meta["world_size"]
        out = []
        for bi, bm in enumerate(meta["buckets"]):
            pieces = [np.asarray(d["residual"][bi]) for d in shards]
            new_total = bucketing.padded_total(
                bm["size"], bm["dtype"], world_size)
            new = np.zeros((mm, world_size, new_total), pieces[0].dtype)
            if world_size == saved_world:
                for r, piece in enumerate(pieces):
                    new[:, r, :bm["size"]] = piece[:, :bm["size"]]
            else:
                summed = sum(p[:, :bm["size"]].astype(np.float32)
                             for p in pieces)
                new[:, 0, :bm["size"]] = summed.astype(pieces[0].dtype)
            out.append(jnp.asarray(new.reshape(-1)))
        return tuple(out)
