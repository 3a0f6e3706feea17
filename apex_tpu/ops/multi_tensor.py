"""Multi-tensor primitives over pytrees.

Reference: ``csrc/multi_tensor_apply.cuh`` + the ``amp_C`` kernel family
(``csrc/multi_tensor_scale_kernel.cu``, ``..._axpby_kernel.cu``,
``..._l2norm_kernel.cu``).  The reference packs ≤110 tensor pointers and a
chunk table into kernel launch metadata because CUDA needs one launch to
cover many tensors.  Under XLA there is no launch-per-tensor problem —
the whole update is one compiled program and XLA fuses the elementwise
work — so the TPU-native design is simply *tree-level math in one jit
region*.  The ``noop_flag`` output buffer becomes a returned boolean
(non-finite detected), and the early-exit-on-noop semantics become a
``jnp.where`` predication at the caller.

These functions are the building blocks for :mod:`apex_tpu.optimizers`
and :mod:`apex_tpu.amp`.

Bucket views: every op here also accepts a
:class:`apex_tpu.optimizers.bucketing.Buckets` (a bucket
plan's flat dtype-bucket form) anywhere a pytree is accepted —
``Buckets`` is a registered pytree whose leaves are the 1-D bucket
buffers, so the elementwise ops (``scale``/``axpby``) map over the
buffers directly and return ``Buckets`` of the same plan, and the
reductions (``l2norm`` per-tensor, ``norm_blend``) slice the buffers
back into per-leaf views via the plan so their results match the tree
form leaf for leaf.  Padding is zero-filled by ``bucketing.pack``, so
the finite votes and L2 sums over a bucket equal the votes/sums over
its leaves.
"""

from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp

Tree = Any


def _bucket_view(tree):
    """``(plan, arrays)`` when ``tree`` is a Buckets, else ``None`` —
    lazy import so ``ops`` does not import ``optimizers`` at package
    init (bucketing imports ``ops._pallas_tiling``)."""
    from apex_tpu.optimizers.bucketing import Buckets

    if isinstance(tree, Buckets):
        return tree.plan, tree.arrays
    return None


def tree_not_finite(tree: Tree) -> jnp.ndarray:
    """True if ANY element anywhere in the tree is inf/nan (noop_flag=1).
    On a ``Buckets`` the vote is over the bucket buffers — pad regions
    are zero-filled, so the vote equals the per-leaf vote."""
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.bool_(False)
    return ~jnp.stack([jnp.all(jnp.isfinite(x)) for x in leaves]).all()


def multi_tensor_scale(src: Tree, scale, out_dtype=None) -> Tuple[Tree, jnp.ndarray]:
    """``out = src * scale`` with inf/nan detection.

    Reference: ``csrc/multi_tensor_scale_kernel.cu`` (ScaleFunctor) — used
    by the amp unscale path and master↔model param copies.  Returns
    ``(out_tree, found_inf)``.
    """

    def one(x):
        y = x.astype(jnp.float32) * scale
        return y.astype(out_dtype or x.dtype)

    out = jax.tree.map(one, src)
    return out, tree_not_finite(out)


def multi_tensor_axpby(a, x_tree: Tree, b, y_tree: Tree, out_dtype=None) -> Tuple[Tree, jnp.ndarray]:
    """``out = a*x + b*y`` elementwise over matching trees.

    Reference: ``csrc/multi_tensor_axpby_kernel.cu`` (AxpbyFunctor) — used
    by amp's add_scaled paths.
    """

    def one(x, y):
        r = a * x.astype(jnp.float32) + b * y.astype(jnp.float32)
        return r.astype(out_dtype or x.dtype)

    out = jax.tree.map(one, x_tree, y_tree)
    return out, tree_not_finite(out)


def multi_tensor_l2norm(tree: Tree, per_tensor: bool = False):
    """Global L2 norm over all leaves, optionally per-leaf norms too.

    Reference: ``csrc/multi_tensor_l2norm_kernel.cu`` — used by FusedLAMB,
    clip_grad, and DistributedFusedAdam/LAMB.  Math in fp32.
    Returns ``global_norm`` or ``(global_norm, [per_leaf_norms])``.

    On a ``Buckets`` the per-tensor norms are per ORIGINAL LEAF (the
    plan's offset table slices each leaf back out of its bucket), not
    per bucket buffer — same list, same order, as the tree form.
    """
    bv = _bucket_view(tree)
    if bv is not None:
        from apex_tpu.optimizers.bucketing import per_leaf_reduce

        plan, arrays = bv
        sq = per_leaf_reduce(
            plan, [a.astype(jnp.float32) for a in arrays],
            lambda x: jnp.sum(jnp.square(x)))
    else:
        sq = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    if not sq:
        z = jnp.float32(0)
        return (z, []) if per_tensor else z
    total = jnp.sqrt(jnp.stack(sq).sum())
    if per_tensor:
        return total, [jnp.sqrt(s) for s in sq]
    return total


def multi_tensor_norm_blend(old_norms: Sequence[jnp.ndarray], tree: Tree, a: float, b: float, norm_type: int = 2):
    """Blend per-leaf norms with fresh norms of ``tree``.

    Reference: ``multi_tensor_norm_out_cuda`` in
    ``csrc/multi_tensor_novograd.cu:160-164``:
    L2:   ``gn = sqrt(a*gn^2 + b*n^2)``;  L-inf: ``gn = a*gn + b*n``.
    ``old_norms`` is per ORIGINAL LEAF; on a ``Buckets`` the fresh
    norms are taken over the plan's per-leaf slices to match.
    """
    bv = _bucket_view(tree)
    if bv is not None:
        from apex_tpu.optimizers.bucketing import per_leaf_reduce

        plan, arrays = bv
        leaves = per_leaf_reduce(plan, arrays, lambda x: x)
    else:
        leaves = jax.tree.leaves(tree)
    out = []
    for gn, x in zip(old_norms, leaves):
        x32 = x.astype(jnp.float32)
        if norm_type == 2:
            n2 = jnp.sum(jnp.square(x32))
            out.append(jnp.sqrt(a * jnp.square(gn) + b * n2))
        elif norm_type == 0:
            n = jnp.max(jnp.abs(x32))
            out.append(a * gn + b * n)
        else:
            raise ValueError("norm_type must be 0 (L-inf) or 2 (L2)")
    return out


def tree_where(pred, true_tree: Tree, false_tree: Tree) -> Tree:
    """Leafwise ``jnp.where(pred, a, b)`` — the XLA form of the reference's
    early-exit ``if (*noop_gmem) return;`` predication."""
    return jax.tree.map(lambda t, f: jnp.where(pred, t, f.astype(t.dtype)), true_tree, false_tree)
