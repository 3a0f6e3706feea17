"""FusedNovoGrad — NovoGrad with per-tensor second moments.

Reference: ``apex/optimizers/fused_novograd.py`` and
``csrc/multi_tensor_novograd.cu`` (NovoGradFunctor:33-127, host:129-190).

The second moment is one *scalar per tensor*: a blended norm
``gn = sqrt(β2·gn² + (1-β2)·‖g‖²)`` (L2, ``norm_type=2``) or
``gn = β2·gn + (1-β2)·max|g|`` (L-inf, ``norm_type=0``), updated by
``multi_tensor_norm_out_cuda`` before the elementwise functor.  Initial
value: zero (``init_zero=True``) or the first grad's norm so the first
blend is a no-op (default).

Elementwise (fp32), with ``denom = gn/√(1-β2^t) + eps``:
- ``reg_inside_moment=True`` (MOMENT_MODE_0): ``g' = g/denom + wd·p``;
  ``m = β1·m + β3·g'``; ``p -= lr·m̂``.
- default (MOMENT_MODE_1): ``m = β1·m + β3·g``;
  ``p -= lr·(m̂/denom + wd·p)``.

Note ``bias_correction2 = sqrt(1-β2^t)`` here (unlike Adam) —
``multi_tensor_novograd.cu:150-152``.

The update runs a leaf at a time (see :mod:`apex_tpu.optimizers.base`);
``exp_avg_sq`` is a tree of per-leaf scalars (one float per tensor).
"""

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.optimizers import base


class NovoGradState(NamedTuple):
    step: jnp.ndarray
    exp_avg: Any
    exp_avg_sq: Any  # list-like tree of scalar norms, one per leaf
    master: Optional[Any] = None


class FusedNovoGrad(base.OptimizerBase):

    def __init__(
        self,
        lr: float = 1e-3,
        bias_correction: bool = True,
        betas: Tuple[float, float] = (0.9, 0.999),
        eps: float = 1e-8,
        weight_decay: float = 0.0,
        amsgrad: bool = False,
        reg_inside_moment: bool = False,
        grad_averaging: bool = True,
        norm_type: int = 2,
        init_zero: bool = False,
        master_weights: bool = False,
    ):
        if amsgrad:
            raise RuntimeError("FusedNovoGrad does not support the AMSGrad variant.")
        if norm_type not in (0, 2):
            raise RuntimeError("FusedNovoGrad only supports l2/inf norm.")
        super().__init__(lr, weight_decay, master_weights)
        self.bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.eps = eps
        # moment_mode mirrors fused_novograd.py:89
        self.moment_mode = 0 if reg_inside_moment else 1
        self.grad_averaging = grad_averaging
        self.norm_type = norm_type
        self.init_zero = init_zero

    def init(self, params) -> NovoGradState:
        # -1 sentinel: "not yet initialized"; replaced by the first
        # grad norm unless init_zero (fused_novograd.py:160-180).
        gn0 = jax.tree.map(
            lambda p: jnp.float32(0.0 if self.init_zero else -1.0), params
        )
        return NovoGradState(
            step=jnp.int32(0),
            exp_avg=jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            exp_avg_sq=gn0,
            master=base.make_master(params, self.master_weights),
        )

    def _norm(self, g32):
        if self.norm_type == 2:
            return jnp.sqrt(jnp.sum(jnp.square(g32)))
        return jnp.max(jnp.abs(g32))

    def _blend(self, gn, fresh):
        """Norm blend (multi_tensor_novograd.cu:160-164) with the -1
        lazy-init sentinel resolved."""
        gn0 = jnp.where(gn < 0, fresh, gn)
        if self.norm_type == 2:
            return jnp.sqrt(self.beta2 * jnp.square(gn0)
                            + (1.0 - self.beta2) * jnp.square(fresh))
        return self.beta2 * gn0 + (1.0 - self.beta2) * fresh

    def _bias_corrections(self, step):
        t = step.astype(jnp.float32)
        if self.bias_correction:
            return (1.0 - jnp.power(self.beta1, t),
                    jnp.sqrt(1.0 - jnp.power(self.beta2, t)))
        return jnp.float32(1.0), jnp.float32(1.0)

    def _moment_math(self, g, p32, m, denom, lr, bc1):
        """The elementwise tail; ``denom`` is the leaf's blended norm
        over its bias correction, plus eps (a scalar)."""
        b1, wd = self.beta1, self.weight_decay
        b3 = (1.0 - b1) if self.grad_averaging else 1.0
        if self.moment_mode == 0:
            gp = g / denom + wd * p32
            m_new = b1 * m + b3 * gp
            p_out = p32 - lr * (m_new / bc1)
        else:
            m_new = b1 * m + b3 * g
            update = (m_new / bc1) / denom + wd * p32
            p_out = p32 - lr * update
        return p_out, m_new

    def _leaf_update(self, grads, state: NovoGradState, params,
                     grads_finite=None, lr=None):
        lr = self.lr if lr is None else lr

        step = base.predicate_step(grads_finite, state.step)
        bc1, bc2 = self._bias_corrections(step)
        p_math = base.math_params(params, state.master)

        def one(g, p, m, gn):
            g = g.astype(jnp.float32)
            p32 = p.astype(jnp.float32)
            gn_new = self._blend(gn, self._norm(g))
            denom = gn_new / bc2 + self.eps
            p_out, m_new = self._moment_math(g, p32, m, denom, lr, bc1)
            return p_out, m_new, gn_new

        out = jax.tree.map(one, grads, p_math, state.exp_avg, state.exp_avg_sq)
        treedef = jax.tree.structure(grads)
        flat = jax.tree.leaves(out, is_leaf=lambda x: isinstance(x, tuple))
        p_new = jax.tree.unflatten(treedef, [x[0] for x in flat])
        m_new = jax.tree.unflatten(treedef, [x[1] for x in flat])
        gn_new = jax.tree.unflatten(treedef, [x[2] for x in flat])

        p_new = base.select(grads_finite, p_new, p_math)
        m_new = base.select(grads_finite, m_new, state.exp_avg)
        gn_new = base.select(grads_finite, gn_new, state.exp_avg_sq)

        new_params, new_master = base.emit_params(p_new, params, state.master)
        return new_params, NovoGradState(step, m_new, gn_new, new_master)
