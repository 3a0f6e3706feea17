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

State is a tree of per-leaf slots (``init(params)``) and the update
runs a leaf at a time: ``update`` and ``update_scaled`` go through
:meth:`OptimizerBase._dispatch` to ``_leaf_update``, the numerics
specification.  The unscale, the all-finite vote, the clip's Σx² and the
update are elementwise chains and reductions over the same leaf, so
inside one jitted step XLA fuses them per leaf: each gradient is read
once by the vote/clip reductions where there are any and once by the
update, ``p``/``m``/``v`` are read once and written once, and with
donation the new leaves alias the old ones.  No whole-model flat copy
exists.  Flat buckets (:mod:`apex_tpu.optimizers.bucketing`) are the
layout of SHARDED state: the ZeRO engine and the bucketed gradient
syncs of ``contrib.optimizers``, not of these optimizers.
"""

from typing import Any, Optional

import jax
import jax.numpy as jnp

from apex_tpu.observability import stepstats as _stepstats

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


def _clip_coef(total_norm, clip_norm):
    """torch ``clip_grad_norm_`` semantics (contrib/clip_grad):
    ``min(max_norm / (total_norm + 1e-6), 1.0)``."""
    return jnp.minimum(clip_norm / (total_norm + 1e-6), jnp.float32(1.0))


class OptimizerBase:
    """Common constructor plumbing + the dispatch.  Subclasses
    implement ``init`` and ``_leaf_update`` (the per-leaf update: the
    numerics specification)."""

    #: True when :meth:`update_scaled` covers this optimizer's FULL
    #: step semantics.  A subclass whose ``update`` override maintains
    #: extra state the fused tail doesn't know about (e.g. contrib
    #: ``FusedAdamSWA``'s SWA average) must set this False so train
    #: steps route through its ``update`` with the explicit sweep
    #: composition instead of bypassing the override.
    supports_update_scaled: bool = True

    def __init__(self, lr: float, weight_decay: float = 0.0,
                 master_weights: bool = False):
        self.lr = lr
        self.weight_decay = weight_decay
        self.master_weights = master_weights

    def _leaf_update(self, grads, state, params, grads_finite=None,
                     lr=None, **kw):  # pragma: no cover - abstract
        raise NotImplementedError

    def _dispatch(self, grads, state, params, grads_finite=None, lr=None,
                  scale=None, clip_norm=None, finite_sync=None,
                  want_finite=False, prescale=None, sumsq_reduce=None,
                  **kw):
        """One step on a tree of per-leaf slots: the unscale
        (``scale``, ``prescale``), the all-finite vote (``want_finite``,
        agreed through ``finite_sync``) and the global-norm clip
        (``clip_norm``) in front of ``_leaf_update``, in place under
        donation.  Returns ``(new_params, new_state, finite)``.

        ``sumsq_reduce(per_leaf_sumsq) -> total_sumsq`` overrides the
        plain stack-and-sum for sharded steps — inside a shard_map a
        tp/pp/ep-sharded leaf's grads are LOCAL shards, so the true
        global norm needs a psum of those leaves' Σx² across their
        sharding axes (:func:`apex_tpu.models.gpt.clip_sumsq_reduce`
        builds this from the param PartitionSpecs)."""
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
            # the telemetry seam reuses the clip's (globally agreed)
            # norm — the "no new HBM pass" contract of
            # observability.stepstats
            _stepstats.offer("grad_norm", jnp.sqrt(total_sq))
            coef = _clip_coef(jnp.sqrt(total_sq), clip_norm)
            g = jax.tree.map(
                lambda x: x.astype(jnp.float32) * coef, g)
        else:
            # no clip to reuse: the shared rank-local fold (no-op unless
            # a telemetry wrapper captures; docs/observability.md)
            _stepstats.offer_local_grad_norm(jax.tree.leaves(g))
        p, s = self._leaf_update(g, state, params,
                                 grads_finite=finite, lr=lr, **kw)
        return p, s, finite

    def _bias_corrections(self, step):
        """Adam-family ``(1-β1^t, 1-β2^t)`` — reads the subclass's
        ``bias_correction``/``beta1``/``beta2`` attributes (NovoGrad
        overrides: its second correction is the sqrt form)."""
        return bias_corrections(step, self.bias_correction,
                                self.beta1, self.beta2)

    # --------------------------------------------------------- public API
    def init(self, params):  # pragma: no cover - abstract
        raise NotImplementedError

    def update(self, grads, state, params, grads_finite=None, lr=None,
               clip_norm=None, sumsq_reduce=None, **kw):
        """One optimizer step (optax-style signature).  ``grads_finite``
        predicates the whole commit device-side (the capturable
        noop_flag design); ``clip_norm`` folds a global-l2 grad clip
        (torch ``clip_grad_norm_`` semantics) into the grad read, with
        ``sumsq_reduce`` supplying the cross-rank Σx² agreement inside
        sharded steps (see :meth:`_dispatch`)."""
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
