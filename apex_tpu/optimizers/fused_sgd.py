"""FusedSGD — SGD with momentum/nesterov/dampening.

Reference: ``apex/optimizers/fused_sgd.py:6-225`` and
``csrc/multi_tensor_sgd_kernel.cu`` (SGDFunctor:31-150).

Per-element semantics (fp32 math):
- optional grad scale (``1/most_recent_scale``) folded into the load;
- ``wd_after_momentum=False`` (default): ``g += wd·p`` before momentum;
- momentum: first step initializes the buffer to ``g`` (``first_run``),
  otherwise ``buf = μ·buf + (1-dampening)·g``;
- nesterov: ``g += μ·buf`` else ``g = buf``;
- ``wd_after_momentum=True``: ``g += wd·p`` here;
- ``p -= lr·g``.

The first-run distinction is handled branch-free with the step counter
(step==0 ⇒ buf := g), keeping the whole step jit-compatible.  The
update runs a leaf at a time (see :mod:`apex_tpu.optimizers.base`); a
group whose ``momentum`` override is 0 keeps its buffer untouched and
steps on the raw grad.
"""

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from apex_tpu.optimizers import base


class SGDState(NamedTuple):
    step: jnp.ndarray
    momentum_buffer: Any
    master: Optional[Any] = None


class FusedSGD(base.OptimizerBase):

    #: group-override keys beyond the base lr/lr_scale/weight_decay set
    _HYPER_KEYS = ("momentum",)

    def __init__(
        self,
        lr: float,
        momentum: float = 0.0,
        dampening: float = 0.0,
        weight_decay: float = 0.0,
        nesterov: bool = False,
        wd_after_momentum: bool = False,
        master_weights: bool = False,
        param_group_fn=None,
        group_hypers=None,
    ):
        """``param_group_fn``/``group_hypers``: functional param_groups
        (see :class:`~apex_tpu.optimizers.FusedAdam`); per-group keys
        here: ``lr``/``lr_scale``, ``weight_decay``, ``momentum``."""
        if nesterov and (momentum <= 0 or dampening != 0):
            raise ValueError("Nesterov momentum requires a momentum and zero dampening")
        super().__init__(lr, weight_decay, master_weights)
        self.momentum = momentum
        self.dampening = dampening
        self.nesterov = nesterov
        self.wd_after_momentum = wd_after_momentum
        self.param_group_fn = param_group_fn
        self.group_hypers = group_hypers

    def init(self, params) -> SGDState:
        return SGDState(
            step=jnp.int32(0),
            momentum_buffer=jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            ),
            master=base.make_master(params, self.master_weights),
        )

    def update(self, grads, state, params, grads_finite=None, lr=None,
               scale=1.0, clip_norm=None, sumsq_reduce=None, **kw):
        """``scale``: the reference's ``1/most_recent_scale`` grad
        prescale, folded into the one grad read."""
        p, s, _ = self._dispatch(grads, state, params,
                                 grads_finite=grads_finite, lr=lr,
                                 clip_norm=clip_norm,
                                 sumsq_reduce=sumsq_reduce,
                                 prescale=1.0 / scale, **kw)
        return p, s

    def _leaf_update(self, grads, state: SGDState, params,
                     grads_finite=None, lr=None):
        # grads arrive f32 with the prescale already applied (_dispatch)
        lr = self.lr if lr is None else lr
        wd, mu, damp = self.weight_decay, self.momentum, self.dampening
        first_run = state.step == 0

        step = base.predicate_step(grads_finite, state.step)
        p_math = base.math_params(params, state.master)
        hypers = base.leaf_hypers(params, self.param_group_fn, self.group_hypers,
                                  extra_keys=self._HYPER_KEYS)

        def one(g, p, buf, h):
            wd_i = h.get("weight_decay", wd)
            lr_i = base.leaf_lr(h, lr)
            mu_i = h.get("momentum", mu)
            g = g.astype(jnp.float32)
            p32 = p.astype(jnp.float32)
            if not self.wd_after_momentum and wd_i != 0.0:
                g = g + wd_i * p32
            if mu_i != 0.0:
                steady = mu_i * buf + (1.0 - damp) * g
                buf_new = jnp.where(first_run, g, steady)
                if self.nesterov:
                    g = g + mu_i * buf_new
                else:
                    g = buf_new
            else:
                buf_new = buf
            if self.wd_after_momentum and wd_i != 0.0:
                g = g + wd_i * p32
            return p32 - lr_i * g, buf_new

        out = jax.tree.map(one, grads, p_math, state.momentum_buffer, hypers)
        treedef = jax.tree.structure(grads)
        flat = jax.tree.leaves(out, is_leaf=lambda x: isinstance(x, tuple))
        p_new = jax.tree.unflatten(treedef, [x[0] for x in flat])
        buf_new = jax.tree.unflatten(treedef, [x[1] for x in flat])

        p_new = base.select(grads_finite, p_new, p_math)
        buf_new = base.select(grads_finite, buf_new, state.momentum_buffer)
        new_params, new_master = base.emit_params(p_new, params, state.master)
        return new_params, SGDState(step, buf_new, new_master)
