"""FusedAdagrad.

Reference: ``apex/optimizers/fused_adagrad.py`` and
``csrc/multi_tensor_adagrad.cu`` (AdagradFunctor:24-84).

Elementwise (fp32):
- L2 mode (default, ADAGRAD_MODE_0): ``g += wd·p``; ``h += g²``;
  ``p -= lr·g/(√h + eps)``.
- adagrad_w mode (ADAGRAD_MODE_1): ``h += g²``;
  ``p -= lr·(g/(√h+eps) + wd·p)``.

The update runs a leaf at a time (see :mod:`apex_tpu.optimizers.base`).
"""

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from apex_tpu.optimizers import base


class AdagradState(NamedTuple):
    step: jnp.ndarray
    sum: Any  # h accumulator, fp32
    master: Optional[Any] = None


class FusedAdagrad(base.OptimizerBase):

    def __init__(
        self,
        lr: float = 1e-2,
        eps: float = 1e-10,
        weight_decay: float = 0.0,
        adagrad_w_mode: bool = False,
        master_weights: bool = False,
        param_group_fn=None,
        group_hypers=None,
    ):
        super().__init__(lr, weight_decay, master_weights)
        self.eps = eps
        self.adagrad_w_mode = adagrad_w_mode
        self.param_group_fn = param_group_fn
        self.group_hypers = group_hypers

    def init(self, params) -> AdagradState:
        return AdagradState(
            step=jnp.int32(0),
            sum=jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            master=base.make_master(params, self.master_weights),
        )

    def _adagrad_math(self, g, p32, h, wd_i, lr_i):
        """One Adagrad step per element (AdagradFunctor)."""
        eps = self.eps
        if not self.adagrad_w_mode:
            g = g + wd_i * p32
            h_new = h + g * g
            p_out = p32 - lr_i * (g / (jnp.sqrt(h_new) + eps))
        else:
            h_new = h + g * g
            p_out = p32 - lr_i * (g / (jnp.sqrt(h_new) + eps) + wd_i * p32)
        return p_out, h_new

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
