"""FusedLAMB — layerwise-adaptive large-batch optimizer.

Reference: ``apex/optimizers/fused_lamb.py`` (driver: global grad norm
blended across dtype groups, :120-183) and ``csrc/multi_tensor_lamb.cu``
(LAMBStage1Functor :41, LAMBStage2Functor :233, host :330-410).

Two-phase semantics reproduced exactly:

1. Global grad-norm clipping: ``clip = gn/max_grad_norm if gn > max else 1``;
   every grad is divided by ``clip``.
2. Stage 1 per element: Adam-style moments on the clipped grad
   (``m = β1·m + β3·g`` with ``β3 = 1-β1`` if ``grad_averaging``), update
   ``u = m̂/(sqrt(v̂)+eps) (+ wd·p)`` (L2 mode folds wd into g instead).
3. Stage 2 per tensor: trust ratio ``r = ‖p‖/‖u‖`` applied when
   ``use_nvlamb or wd != 0`` and both norms are nonzero;
   ``p -= lr·r·u``.

The update runs a leaf at a time (see :mod:`apex_tpu.optimizers.base`):
stage 1, the two norms of stage 2 and the step fuse per leaf.
"""

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.optimizers import base


class LambState(NamedTuple):
    step: jnp.ndarray
    exp_avg: Any
    exp_avg_sq: Any
    master: Optional[Any] = None


def lamb_stage1_math(g, p32, m, v, wd_i, bc1, bc2, *, beta1, beta2, eps,
                     adam_w_mode, grad_averaging):
    """Stage-1 LAMB per element (LAMBStage1Functor) — module-level so
    the ZeRO-sharded :class:`~apex_tpu.contrib.optimizers.
    DistributedFusedLAMB` evaluates the identical expression tree on
    its dp shards."""
    b3 = (1.0 - beta1) if grad_averaging else 1.0
    if not adam_w_mode:  # MOMENT_MODE_0: L2 on scaled grad
        g = g + wd_i * p32
    m_new = beta1 * m + b3 * g
    v_new = beta2 * v + (1.0 - beta2) * (g * g)
    u = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    if adam_w_mode:  # MOMENT_MODE_1: decoupled
        u = u + wd_i * p32
    return u, m_new, v_new


def lamb_trust_ratio(lr_i, p_norm, u_norm, *, apply_ratio):
    """Stage-2 per-tensor ratio (multi_tensor_lamb.cu:255-262)."""
    if apply_ratio:
        return jnp.where((p_norm != 0.0) & (u_norm != 0.0),
                         lr_i * (p_norm / u_norm), lr_i)
    return jnp.asarray(lr_i, jnp.float32)


def lamb_grad_clip(global_grad_norm, max_grad_norm):
    """fused_lamb.py:121-136: the divide-every-grad-by factor when the
    global norm exceeds the max."""
    return jnp.where(global_grad_norm > max_grad_norm,
                     global_grad_norm / max_grad_norm, jnp.float32(1.0))


class FusedLAMB(base.OptimizerBase):

    #: group-override keys beyond the base lr/lr_scale/weight_decay set
    _HYPER_KEYS = ("use_trust_ratio",)

    def __init__(
        self,
        lr: float = 1e-3,
        bias_correction: bool = True,
        betas: Tuple[float, float] = (0.9, 0.999),
        eps: float = 1e-6,
        weight_decay: float = 0.01,
        amsgrad: bool = False,
        adam_w_mode: bool = True,
        grad_averaging: bool = True,
        max_grad_norm: float = 1.0,
        use_nvlamb: bool = False,
        master_weights: bool = False,
        param_group_fn=None,
        group_hypers=None,
    ):
        """``param_group_fn``/``group_hypers``: functional param_groups
        (see :class:`~apex_tpu.optimizers.FusedAdam`).  LAMB additionally
        honors the per-group key ``use_trust_ratio`` (False → plain lr
        step, the BERT recipe's exclude_from_layer_adaptation for
        norms/biases)."""
        if amsgrad:
            raise RuntimeError("FusedLAMB does not support the AMSGrad variant.")
        super().__init__(lr, weight_decay, master_weights)
        self.bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.grad_averaging = grad_averaging
        self.max_grad_norm = max_grad_norm
        self.use_nvlamb = use_nvlamb
        self.param_group_fn = param_group_fn
        self.group_hypers = group_hypers

    def init(self, params) -> LambState:
        zeros = lambda t: jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), t)
        return LambState(
            step=jnp.int32(0),
            exp_avg=zeros(params),
            exp_avg_sq=zeros(params),
            master=base.make_master(params, self.master_weights),
        )

    def _trust_ratio(self, h, wd_i, lr_i, p_norm, u_norm):
        """Stage-2 per-tensor ratio (multi_tensor_lamb.cu:255-262)."""
        apply = h.get("use_trust_ratio", True) and (
            self.use_nvlamb or wd_i != 0.0)
        return lamb_trust_ratio(lr_i, p_norm, u_norm, apply_ratio=apply)

    def _leaf_update(self, grads, state: LambState, params,
                     grads_finite=None, lr=None):
        lr = self.lr if lr is None else lr
        wd = self.weight_decay

        step = base.predicate_step(grads_finite, state.step)
        bc1, bc2 = self._bias_corrections(step)

        # Global grad norm over every param (fused_lamb.py:121-136).
        g32 = base.f32(grads)
        sq = [jnp.sum(jnp.square(g)) for g in jax.tree.leaves(g32)]
        clip = lamb_grad_clip(jnp.sqrt(jnp.stack(sq).sum()),
                              self.max_grad_norm)

        p_math = base.math_params(params, state.master)
        hypers = base.leaf_hypers(params, self.param_group_fn, self.group_hypers,
                                  extra_keys=self._HYPER_KEYS)
        treedef = jax.tree.structure(grads)

        def stage1(g, p, m, v, h):
            return lamb_stage1_math(
                g.astype(jnp.float32) / clip, p.astype(jnp.float32), m, v,
                h.get("weight_decay", wd), bc1, bc2, beta1=self.beta1,
                beta2=self.beta2, eps=self.eps, adam_w_mode=self.adam_w_mode,
                grad_averaging=self.grad_averaging)

        out = jax.tree.map(stage1, grads, p_math, state.exp_avg, state.exp_avg_sq, hypers)
        flat = jax.tree.leaves(out, is_leaf=lambda x: isinstance(x, tuple))
        updates = jax.tree.unflatten(treedef, [x[0] for x in flat])
        m_new = jax.tree.unflatten(treedef, [x[1] for x in flat])
        v_new = jax.tree.unflatten(treedef, [x[2] for x in flat])

        # Stage 2: per-tensor trust ratio (multi_tensor_lamb.cu:255-262).
        def stage2(p, u, h):
            wd_i = h.get("weight_decay", wd)
            lr_i = base.leaf_lr(h, lr)
            p32 = p.astype(jnp.float32)
            ratio = self._trust_ratio(
                h, wd_i, lr_i,
                jnp.sqrt(jnp.sum(jnp.square(p32))),
                jnp.sqrt(jnp.sum(jnp.square(u))))
            return p32 - ratio * u

        p_new = jax.tree.map(stage2, p_math, updates, hypers)

        p_new = base.select(grads_finite, p_new, p_math)
        m_new = base.select(grads_finite, m_new, state.exp_avg)
        v_new = base.select(grads_finite, v_new, state.exp_avg_sq)

        new_params, new_master = base.emit_params(p_new, params, state.master)
        return new_params, LambState(step, m_new, v_new, new_master)
