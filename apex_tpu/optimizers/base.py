"""Shared machinery for the fused optimizers.

Reference: ``apex/optimizers/*`` — each optimizer gathers params into
dtype-grouped flat lists and fires one multi-tensor CUDA kernel.  On TPU
the whole step is one XLA program, so each optimizer here is a pure
function over pytrees; "fused" survives as (a) math done in fp32 regardless
of storage dtype, exactly as the kernels' ``MATH_T=float``, (b) a single
jit region with no host sync, and (c) the capturable design: the update is
*predicated* on a device-resident ``grads_finite`` flag instead of a host
decision (``fused_adam.py:199-263``, ``multi_tensor_adam.cu:130``).

Master weights: when params are stored in half precision and
``master_weights=True``, an fp32 master copy lives in the optimizer state;
math reads/writes the master and the returned params are the master cast
back to storage dtype (reference: ``AdamCapturableMasterFunctor``,
``multi_tensor_adam.cu:243``; ``fp16_utils/fp16_optimizer.py``).

Two layouts of state, one route each: ``update`` dispatches through
:meth:`OptimizerBase._dispatch`, which looks at the state it is handed.

- **Tree state** (``init(params)``, the default): the update runs a
  leaf at a time (``_leaf_update``, the numerics specification).  The
  unscale, the all-finite vote, the clip's Σx² and the update are
  elementwise chains and reductions over the same leaf, so inside one
  jitted step XLA fuses them per leaf: each gradient is read once by
  the vote/clip reductions where there are any and once by the update,
  ``p``/``m``/``v`` are read once and written once, and with donation
  the new leaves alias the old ones.  No whole-model flat copy exists.
- **Bucket-resident state** (``init(params, bucketed=True)``): the
  slots ARE a few dtype-homogeneous 1-D buckets
  (:mod:`apex_tpu.optimizers.bucketing`) and the step is one fused
  elementwise pass per bucket (``_bucket_update``), with the unscale,
  the clip and the vote folded into the gradients' pack
  (:func:`prepare_grads_bucketed`).  This is the layout the ZeRO
  engines shard (an equal-size 1-D bucket is what a ``psum_scatter``
  splits cleanly); here it is kept for state that already lives flat.

Until PR 39 tree state ran on the bucket engine too (a port of the
reference's one-launch-for-many-tensors idea): every step packed the
gradients and each state slot into whole-model flat copies, ran the
fused pass, and sliced the results back into leaves.  On a TPU a leaf
in its tiled layout is not a row-major run of memory, so each pack was
two physical copies and each unpack one: at GPT-2 medium 85 ms of a
298.6 ms step against the per-leaf update's 14.8 (9.9 GB at the HBM
roofline), and 10.6 GB of temporaries (PERF.md, PR 39).  Inside one
XLA program there are no launches to save.  Both routes are bit-exact in fp32 (same elementwise expression
trees; ``tests/test_bucketed_engine`` pins it).
"""

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.observability import stepstats as _stepstats
from apex_tpu.optimizers import bucketing

Tree = Any


def is_half(x) -> bool:
    return x.dtype in (jnp.float16, jnp.bfloat16)


def make_master(params: Tree, master_weights: bool) -> Optional[Tree]:
    """fp32 master COPY of the params.  ``copy=True`` is load-bearing:
    ``astype`` on an already-fp32 leaf returns the same buffer, and a
    master that aliases its param makes ``donate_argnums`` over
    (params, state) donate one buffer twice — an Execute()-time crash
    (first seen on the resnet amp-O2 step)."""
    if not master_weights:
        return None
    return jax.tree.map(lambda p: jnp.array(p, jnp.float32, copy=True),
                        params)


def math_params(params: Tree, master: Optional[Tree]) -> Tree:
    """The tree the optimizer math should read (master if present)."""
    return master if master is not None else params


def emit_params(new_math_params: Tree, params: Tree, master: Optional[Tree]):
    """Return (new_params_in_storage_dtype, new_master)."""
    if master is None:
        return jax.tree.map(lambda n, p: n.astype(p.dtype), new_math_params, params), None
    new_params = jax.tree.map(lambda m, p: m.astype(p.dtype), new_math_params, params)
    return new_params, new_math_params


def predicate_step(grads_finite, step: jnp.ndarray) -> jnp.ndarray:
    """step advances only on finite grads (fused_adam.py:262:
    ``group['step'] += (_dummy_overflow_buf != 1)``)."""
    if grads_finite is None:
        return step + 1
    return step + jnp.asarray(grads_finite).astype(step.dtype)


def select(grads_finite, new: Tree, old: Tree) -> Tree:
    """Predicated commit: keep old values on overflow (noop_flag set)."""
    if grads_finite is None:
        return new
    pred = jnp.asarray(grads_finite)
    return jax.tree.map(lambda n, o: jnp.where(pred, n, o.astype(n.dtype)), new, old)


def f32(tree: Tree) -> Tree:
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def bias_corrections(step, bias_correction: bool, beta1, beta2):
    """Adam-family ``(1-β1^t, 1-β2^t)`` — module-level so the ZeRO
    optimizers (which are not :class:`OptimizerBase` subclasses) share
    the exact expression the per-leaf oracle evaluates."""
    if not bias_correction:
        return jnp.float32(1.0), jnp.float32(1.0)
    t = step.astype(jnp.float32)
    return (1.0 - jnp.power(beta1, t), 1.0 - jnp.power(beta2, t))


class HyperLeaf(dict):
    """An override dict that is a pytree *leaf* (unregistered dict
    subclass), so a tree of them can ride through ``jax.tree.map``
    alongside array trees."""


#: override keys every optimizer understands
_BASE_HYPER_KEYS = frozenset({"lr", "lr_scale", "weight_decay"})


def leaf_hypers(params: Tree, param_group_fn, group_hypers,
                extra_keys=()) -> Optional[Tree]:
    """Per-leaf hyperparameter overrides — the functional form of torch
    ``param_groups`` (reference optimizers iterate
    ``self.param_groups`` with per-group lr/weight_decay,
    fused_adam.py:127+).

    ``param_group_fn(path_str, leaf) -> group_name`` assigns each param
    leaf to a named group at trace time (paths are static);
    ``group_hypers[group_name]`` is a dict of overrides (``lr``
    (absolute — replaces any runtime schedule for that group),
    ``lr_scale`` (multiplies the runtime lr), ``weight_decay``,
    optimizer-specific keys).  Returns a tree of :class:`HyperLeaf`
    matching ``params``, or None when no grouping is configured.
    Raises if a ``group_hypers`` key names a group no param maps to
    (a typo'd group name must not silently disable its overrides), and
    if any override key inside a group is not one the calling optimizer
    reads (``lr``/``lr_scale``/``weight_decay`` plus ``extra_keys``) —
    a typo like ``weight_dacay`` must not be silently ignored.
    When no grouping is configured, returns a tree of empty overrides
    (so optimizers have one code path).
    """
    allowed = _BASE_HYPER_KEYS | set(extra_keys)
    for gname, overrides in (group_hypers or {}).items():
        unknown = set(overrides) - allowed
        if unknown:
            raise ValueError(
                f"group_hypers[{gname!r}] has unknown override keys "
                f"{sorted(unknown)}; this optimizer supports {sorted(allowed)}"
            )
    if param_group_fn is None:
        if group_hypers:
            raise ValueError(
                "group_hypers given without param_group_fn — no param can "
                "map to any group, so the overrides would be silently ignored"
            )
        return jax.tree.map(lambda _: HyperLeaf(), params)
    group_hypers = group_hypers or {}
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    seen = set()
    out = []
    for kp, leaf in flat:
        g = param_group_fn(jax.tree_util.keystr(kp), leaf)
        seen.add(g)
        out.append(HyperLeaf(group_hypers.get(g, {})))
    unused = set(group_hypers) - seen
    if unused:
        raise ValueError(
            f"group_hypers keys {sorted(unused)} match no param group "
            f"(param_group_fn produced {sorted(seen)})"
        )
    return jax.tree_util.tree_unflatten(treedef, out)


def leaf_lr(h: dict, lr):
    """Resolve a leaf's lr: absolute ``lr`` override wins, else the
    runtime lr scaled by ``lr_scale``."""
    if "lr" in h:
        return h["lr"]
    return lr * h.get("lr_scale", 1.0)


class PreparedGrads(NamedTuple):
    """Grads after the fused prepare pass: packed into ``plan``'s f32
    buckets with loss-scale unscale and global-norm clip folded in, plus
    the (synced) all-finite vote — the one read of the grad tree."""

    plan: Any
    g: Tuple
    finite: Optional[jnp.ndarray]


def _bucket_all_finite(bucket_arrays) -> jnp.ndarray:
    """All-finite vote over packed buckets (pad regions are zero-filled
    by :func:`bucketing.pack`, so they never mask a leaf's inf/nan).
    ONE vote implementation — the amp scaler's (a list of arrays is a
    tree), so the engine's step predicate and the scaler's found-inf
    decision can never diverge."""
    from apex_tpu.amp.scaler import all_finite

    return all_finite(list(bucket_arrays))


def _clip_coef(total_norm, clip_norm):
    """torch ``clip_grad_norm_`` semantics (contrib/clip_grad):
    ``min(max_norm / (total_norm + 1e-6), 1.0)``."""
    return jnp.minimum(clip_norm / (total_norm + 1e-6), jnp.float32(1.0))


def prepare_grads_bucketed(params, grads, scale=None, clip_norm=None,
                           finite_sync=None, want_finite=False,
                           prescale=None, sumsq_reduce=None) -> PreparedGrads:
    """The fused prepare pass: one read of the grad tree produces the
    unscaled (``scale``), clipped (``clip_norm``) f32 buckets and the
    agreed all-finite predicate — replacing the reference's three
    separate ``multi_tensor_scale`` / ``multi_tensor_l2norm`` /
    noop-flag sweeps (``apex/amp/scaler.py:94-119``,
    ``contrib/clip_grad``).

    ``sumsq_reduce(per_leaf_sumsq) -> total_sumsq``: overrides the
    plain stack-and-sum for sharded steps — inside a shard_map a
    tp/pp/ep-sharded leaf's grads are LOCAL shards, so the true global
    norm needs a psum of those leaves' Σx² across their sharding axes
    (:func:`apex_tpu.models.gpt.clip_sumsq_reduce` builds this from
    the param PartitionSpecs)."""
    plan = bucketing.plan_of(params)
    mult = None
    if scale is not None:
        mult = 1.0 / scale
    if prescale is not None:
        mult = prescale if mult is None else mult * prescale
    g = bucketing.pack(plan, grads, scale=mult)
    finite = None
    if want_finite:
        finite = _bucket_all_finite(g)
        if finite_sync is not None:
            finite = finite_sync(finite)
    if clip_norm is not None:
        sq = bucketing.per_leaf_reduce(
            plan, g, lambda x: jnp.sum(jnp.square(x)))
        total_sq = (jnp.stack(sq).sum() if sumsq_reduce is None
                    else sumsq_reduce(sq))
        # the telemetry seam reuses the clip's (globally agreed) norm —
        # the "no new HBM pass" contract of observability.stepstats
        _stepstats.offer("grad_norm", jnp.sqrt(total_sq))
        coef = _clip_coef(jnp.sqrt(total_sq), clip_norm)
        g = [a * coef for a in g]
    else:
        # no clip to reuse: the shared rank-local fold (no-op unless a
        # telemetry wrapper captures; docs/observability.md)
        _stepstats.offer_local_grad_norm(g)
    return PreparedGrads(plan=plan, g=tuple(g), finite=finite)


class OptimizerBase:
    """Common constructor plumbing + the dispatch.  Subclasses
    implement ``init``, ``_leaf_update`` (the per-leaf update: the
    numerics specification, and the route of tree state), and
    ``_bucket_update`` (the fused pass over bucket-resident state)."""

    #: state field holding the slot that is a :class:`bucketing.Buckets`
    #: when the state is bucket-resident (subclasses override)
    _BUCKET_SLOT: Optional[str] = None

    #: True when :meth:`update_scaled` covers this optimizer's FULL
    #: step semantics.  A subclass whose ``update`` override maintains
    #: extra state the fused tail doesn't know about (e.g. contrib
    #: ``FusedAdamSWA``'s SWA average) must set this False so train
    #: steps route through its ``update`` with the explicit sweep
    #: composition instead of bypassing the override.
    supports_update_scaled: bool = True

    def __init__(self, lr: float, weight_decay: float = 0.0,
                 master_weights: bool = False, use_buckets: bool = True):
        """``use_buckets`` is accepted and means nothing since PR 39:
        the route follows the layout of the state (:meth:`_dispatch`),
        so there is nothing left for it to choose.  It stays in the
        signatures because benchmark code passes it
        (``cellbench/adapters/train_afmoe.py``); ROADMAP D4 lists it
        for a ``simplicity`` PR to delete together with that keyword."""
        self.lr = lr
        self.weight_decay = weight_decay
        self.master_weights = master_weights

    # ------------------------------------------------------------ engine
    def _state_is_bucketed(self, state) -> bool:
        if self._BUCKET_SLOT is None:
            return False
        return isinstance(getattr(state, self._BUCKET_SLOT, None),
                          bucketing.Buckets)

    def _leaf_update(self, grads, state, params, grads_finite=None,
                     lr=None, **kw):  # pragma: no cover - abstract
        raise NotImplementedError

    def _bucket_update(self, prep: PreparedGrads, state, params, pred,
                       lr=None, **kw):  # pragma: no cover - abstract
        raise NotImplementedError

    def _dispatch(self, grads, state, params, grads_finite=None, lr=None,
                  scale=None, clip_norm=None, finite_sync=None,
                  want_finite=False, prescale=None, sumsq_reduce=None,
                  **kw):
        """Route one step by the layout of ``state``: bucket-resident
        slots (``init(..., bucketed=True)``) → the bucket engine, which
        alone can read flat slots; a tree of per-leaf slots → the
        per-leaf update, in place under donation.  Returns
        ``(new_params, new_state, finite)``."""

        def leaf_path():
            g, finite = grads, grads_finite
            if scale is not None or prescale is not None:
                mult = 1.0 if scale is None else 1.0 / scale
                if prescale is not None:
                    mult = mult * prescale
                g = jax.tree.map(
                    lambda x: x.astype(jnp.float32) * mult, g)
            if want_finite:
                from apex_tpu.amp.scaler import all_finite

                finite = all_finite(g)
                if finite_sync is not None:
                    finite = finite_sync(finite)
            if clip_norm is not None:
                sq = [jnp.sum(jnp.square(x.astype(jnp.float32)))
                      for x in jax.tree.leaves(g)]
                total_sq = (jnp.stack(sq).sum() if sumsq_reduce is None
                            else sumsq_reduce(sq))
                _stepstats.offer("grad_norm", jnp.sqrt(total_sq))
                coef = _clip_coef(jnp.sqrt(total_sq), clip_norm)
                g = jax.tree.map(
                    lambda x: x.astype(jnp.float32) * coef, g)
            else:
                _stepstats.offer_local_grad_norm(jax.tree.leaves(g))
            p, s = self._leaf_update(g, state, params,
                                     grads_finite=finite, lr=lr, **kw)
            return p, s, finite

        def bucket_path():
            prep = prepare_grads_bucketed(
                params, grads, scale=scale, clip_norm=clip_norm,
                finite_sync=finite_sync, want_finite=want_finite,
                prescale=prescale, sumsq_reduce=sumsq_reduce)
            pred = prep.finite if want_finite else grads_finite
            p, s = self._bucket_update(prep, state, params, pred, lr=lr,
                                       **kw)
            return p, s, pred

        if self._state_is_bucketed(state):
            return bucket_path()
        return leaf_path()

    def _init_bucket_slots(self, params, n_slots):
        """The shared resident-state constructor: ``n_slots`` zeroed
        f32 bucket slots for ``params``' plan, plus the packed fp32
        master when ``master_weights`` — ONE place to change the
        resident layout (e.g. future sharded buckets)."""
        plan = bucketing.plan_of(params)
        slots = [
            bucketing.Buckets(plan, [jnp.zeros((b.total,), jnp.float32)
                                     for b in plan.buckets])
            for _ in range(n_slots)
        ]
        master = (bucketing.Buckets(plan, bucketing.pack(plan, params))
                  if self.master_weights else None)
        return slots, master

    def _bias_corrections(self, step):
        """Adam-family ``(1-β1^t, 1-β2^t)`` — reads the subclass's
        ``bias_correction``/``beta1``/``beta2`` attributes (NovoGrad
        overrides: its second correction is the sqrt form)."""
        return bias_corrections(step, self.bias_correction,
                                self.beta1, self.beta2)

    # --------------------------------------------------------- public API
    def init(self, params, bucketed: bool = False):  # pragma: no cover
        raise NotImplementedError

    def update(self, grads, state, params, grads_finite=None, lr=None,
               clip_norm=None, sumsq_reduce=None, **kw):
        """One optimizer step (optax-style signature).  ``grads_finite``
        predicates the whole commit device-side (the capturable
        noop_flag design); ``clip_norm`` folds a global-l2 grad clip
        (torch ``clip_grad_norm_`` semantics) into the grad read, with
        ``sumsq_reduce`` supplying the cross-rank Σx² agreement inside
        sharded steps (see :func:`prepare_grads_bucketed`)."""
        p, s, _ = self._dispatch(grads, state, params,
                                 grads_finite=grads_finite, lr=lr,
                                 clip_norm=clip_norm,
                                 sumsq_reduce=sumsq_reduce, **kw)
        return p, s

    def update_scaled(self, grads, state, params, scale=None,
                      clip_norm=None, finite_sync=None, lr=None,
                      sumsq_reduce=None, **kw):
        """The fused amp step: unscale by ``1/scale``, (optionally) clip
        to ``clip_norm`` (global l2, torch semantics), vote all-finite,
        agree the vote via ``finite_sync`` (the model-parallel pmax),
        and commit the update predicated on it — one pass over the
        grads instead of the reference's four separate sweeps
        (``apex/amp/handle.py:119-158``).  Returns
        ``(new_params, new_state, all_finite)``; feed ``all_finite`` to
        :meth:`apex_tpu.amp.DynamicLossScaler.update` and the step
        guard.  ``scale=None`` skips the unscale (the bf16/fp32 guarded
        path) but still folds the finite vote into the pass."""
        return self._dispatch(grads, state, params, lr=lr, scale=scale,
                              clip_norm=clip_norm, finite_sync=finite_sync,
                              want_finite=True, sumsq_reduce=sumsq_reduce,
                              **kw)

    def step(self, grads, state, params, **kw):
        """Alias matching the reference's ``optimizer.step()`` naming."""
        return self.update(grads, state, params, **kw)

    # ------------------------------------------------- bucket-side helpers
    @staticmethod
    def _hyper_leaves(hypers):
        """The static per-leaf override dicts in tree_flatten order."""
        return jax.tree.leaves(
            hypers, is_leaf=lambda x: isinstance(x, HyperLeaf))

    @staticmethod
    def _bucket_lr(bucket, hyper_leaves, lr):
        """Per-element lr operand for one bucket: the runtime scalar
        when no group overrides it, else a broadcast per-leaf vector
        (absolute ``lr`` wins; ``lr_scale`` multiplies — exactly
        :func:`leaf_lr`)."""
        if not any(("lr" in h or "lr_scale" in h) for h in hyper_leaves):
            return lr
        per = [leaf_lr(h, lr) for h in hyper_leaves]
        return bucketing.seg_broadcast(bucket, per)


def bucket_select(pred, new_arrays, old_arrays):
    """Predicated commit on bucket buffers (the flat form of
    :func:`select`)."""
    if pred is None:
        return list(new_arrays)
    p = jnp.asarray(pred)
    return [jnp.where(p, n, o) for n, o in zip(new_arrays, old_arrays)]
