"""FusedAdagrad.

Reference: ``apex/optimizers/fused_adagrad.py`` and
``csrc/multi_tensor_adagrad.cu`` (AdagradFunctor:24-84).

Elementwise (fp32):
- L2 mode (default, ADAGRAD_MODE_0): ``g += wd·p``; ``h += g²``;
  ``p -= lr·g/(√h + eps)``.
- adagrad_w mode (ADAGRAD_MODE_1): ``h += g²``;
  ``p -= lr·(g/(√h+eps) + wd·p)``.

Tree state updates a leaf at a time, bucket-resident state on the
bucketed multi-tensor engine (see :mod:`apex_tpu.optimizers.base`).
"""

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from apex_tpu.optimizers import base, bucketing


class AdagradState(NamedTuple):
    step: jnp.ndarray
    sum: Any  # h accumulator, fp32 (tree or Buckets)
    master: Optional[Any] = None


class FusedAdagrad(base.OptimizerBase):

    _BUCKET_SLOT = "sum"

    def __init__(
        self,
        lr: float = 1e-2,
        eps: float = 1e-10,
        weight_decay: float = 0.0,
        adagrad_w_mode: bool = False,
        master_weights: bool = False,
        param_group_fn=None,
        group_hypers=None,
        use_buckets: bool = True,
    ):
        super().__init__(lr, weight_decay, master_weights,
                         use_buckets=use_buckets)
        self.eps = eps
        self.adagrad_w_mode = adagrad_w_mode
        self.param_group_fn = param_group_fn
        self.group_hypers = group_hypers

    def init(self, params, bucketed: bool = False) -> AdagradState:
        if bucketed:
            (h,), master = self._init_bucket_slots(params, 1)
            return AdagradState(jnp.int32(0), h, master)
        return AdagradState(
            step=jnp.int32(0),
            sum=jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            master=base.make_master(params, self.master_weights),
        )

    def _adagrad_math(self, g, p32, h, wd_i, lr_i):
        """The shared Adagrad expression tree (per-leaf == bucket)."""
        eps = self.eps
        if not self.adagrad_w_mode:
            g = g + wd_i * p32
            h_new = h + g * g
            p_out = p32 - lr_i * (g / (jnp.sqrt(h_new) + eps))
        else:
            h_new = h + g * g
            p_out = p32 - lr_i * (g / (jnp.sqrt(h_new) + eps) + wd_i * p32)
        return p_out, h_new

    # ------------------------------------------------------- per-leaf path
    def _leaf_update(self, grads, state: AdagradState, params,
                     grads_finite=None, lr=None):
        lr = self.lr if lr is None else lr
        wd = self.weight_decay

        step = base.predicate_step(grads_finite, state.step)
        p_math = base.math_params(params, state.master)
        hypers = base.leaf_hypers(params, self.param_group_fn, self.group_hypers)

        def one(g, p, h, hyp):
            return self._adagrad_math(
                g.astype(jnp.float32), p.astype(jnp.float32), h,
                hyp.get("weight_decay", wd), base.leaf_lr(hyp, lr))

        out = jax.tree.map(one, grads, p_math, state.sum, hypers)
        treedef = jax.tree.structure(grads)
        flat = jax.tree.leaves(out, is_leaf=lambda x: isinstance(x, tuple))
        p_new = jax.tree.unflatten(treedef, [x[0] for x in flat])
        h_new = jax.tree.unflatten(treedef, [x[1] for x in flat])

        p_new = base.select(grads_finite, p_new, p_math)
        h_new = base.select(grads_finite, h_new, state.sum)
        new_params, new_master = base.emit_params(p_new, params, state.master)
        return new_params, AdagradState(step, h_new, new_master)

    # --------------------------------------------------------- bucket path
    def _bucket_update(self, prep: base.PreparedGrads, state: AdagradState,
                       params, pred, lr=None):
        lr = self.lr if lr is None else lr
        wd = self.weight_decay
        plan = prep.plan

        step = base.predicate_step(pred, state.step)
        h_b = state.sum.arrays
        has_master = state.master is not None
        if has_master:
            p_b = state.master.arrays
        else:
            p_b = bucketing.pack(plan, params)
        hl = self._hyper_leaves(
            base.leaf_hypers(params, self.param_group_fn, self.group_hypers))
        wd_leaf = [h.get("weight_decay", wd) for h in hl]

        new_p, new_h = [], []
        for bi, b in enumerate(plan.buckets):
            p_out, h_out = self._adagrad_math(
                prep.g[bi], p_b[bi], h_b[bi],
                bucketing.seg_values(b, wd_leaf),
                self._bucket_lr(b, hl, lr))
            new_p.append(p_out)
            new_h.append(h_out)

        new_p = base.bucket_select(pred, new_p, p_b)
        new_h = base.bucket_select(pred, new_h, h_b)
        new_params = bucketing.unpack(plan, new_p)
        new_master = (bucketing.Buckets(plan, new_p)
                      if has_master else None)
        return new_params, AdagradState(
            step, bucketing.Buckets(plan, new_h), new_master)
