"""FusedAdam — Adam/AdamW with exact reference numerics.

Reference: ``apex/optimizers/fused_adam.py:4-276`` (driver) and
``csrc/multi_tensor_adam.cu`` (AdamFunctor :24, AdamCapturableFunctor
:130, AdamCapturableMasterFunctor :243).

Numerics (MATH_T = fp32, per element):

- L2 mode (``adam_w_mode=False``, ADAM_MODE_0): ``g += wd*p`` before the
  moment updates.
- AdamW mode (default, ADAM_MODE_1): ``update = m̂/(sqrt(v̂)+eps) + wd*p``.
- ``m̂ = m/(1-β1^t)``, ``v̂ = v/(1-β2^t)`` when ``bias_correction``.

The capturable behavior is default here: pass ``grads_finite`` (from
:meth:`apex_tpu.amp.DynamicLossScaler.unscale`) and the whole step —
including the step counter — commits only when grads are finite, exactly
like the reference's device-side noop_flag path.

``init(params)`` makes per-leaf m/v and the step updates a leaf at a
time (see :mod:`apex_tpu.optimizers.base`): one fusion a leaf reads
``g``, ``p``, ``m``, ``v`` once and writes ``p``, ``m``, ``v`` in place
under donation.  Bit-exact in fp32 with ``optax.adamw`` (the
second-moment update is ``(1-β2)·(g·g)``, optax's association).
"""

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.optimizers import base


class AdamState(NamedTuple):
    step: jnp.ndarray  # i32 scalar
    exp_avg: Any  # m, fp32
    exp_avg_sq: Any  # v, fp32
    master: Optional[Any] = None  # fp32 master params (if enabled)


def adam_core(g, m, v, bc1, bc2, *, beta1, beta2, eps):
    """The param-free half of the Adam expression tree: new moments and
    the core update term ``m̂/(sqrt(v̂)+eps)``.  Module-level so the
    ZeRO-sharded :class:`~apex_tpu.contrib.optimizers.
    DistributedFusedAdam` evaluates the IDENTICAL expressions on its dp
    shards (the bit-exact-parity contract)."""
    m_new = beta1 * m + (1.0 - beta1) * g
    # (1-β2)·(g·g): optax's association, pinned for bit-exact parity
    v_new = beta2 * v + (1.0 - beta2) * (g * g)
    core = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    return core, m_new, v_new


def adam_math(g, p32, m, v, wd_i, lr_i, bc1, bc2, *, beta1, beta2, eps,
              adam_w_mode):
    """One Adam step per element (AdamW ADAM_MODE_1 / L2 ADAM_MODE_0) —
    the numerics specification the per-leaf update and the ZeRO shards
    share verbatim, so they cannot drift even by a rounding."""
    if not adam_w_mode:  # ADAM_MODE_0: L2 regularization
        g = g + wd_i * p32
    core, m_new, v_new = adam_core(g, m, v, bc1, bc2,
                                   beta1=beta1, beta2=beta2, eps=eps)
    update = core + wd_i * p32 if adam_w_mode else core
    return p32 - lr_i * update, m_new, v_new


class FusedAdam(base.OptimizerBase):

    def __init__(
        self,
        lr: float = 1e-3,
        bias_correction: bool = True,
        betas: Tuple[float, float] = (0.9, 0.999),
        eps: float = 1e-8,
        adam_w_mode: bool = True,
        weight_decay: float = 0.0,
        amsgrad: bool = False,
        master_weights: bool = False,
        param_group_fn=None,
        group_hypers=None,
        use_buckets: bool = True,
    ):
        """``param_group_fn(path, leaf) -> group_name`` +
        ``group_hypers={name: {"lr": ..., "weight_decay": ...}}`` is the
        functional form of the reference's ``param_groups`` (per-group
        hyperparameters, e.g. no weight decay on norms/biases).
        ``use_buckets``: dead; ``cellbench/adapters/train_afmoe.py`` passes it."""
        if amsgrad:
            raise RuntimeError("FusedAdam does not support the AMSGrad variant.")
        super().__init__(lr, weight_decay, master_weights)
        self.bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.param_group_fn = param_group_fn
        self.group_hypers = group_hypers

    def init(self, params) -> AdamState:
        zeros = lambda t: jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), t)
        return AdamState(
            step=jnp.int32(0),
            exp_avg=zeros(params),
            exp_avg_sq=zeros(params),
            master=base.make_master(params, self.master_weights),
        )

    def _leaf_update(self, grads, state: AdamState, params,
                     grads_finite=None, lr=None):
        lr = self.lr if lr is None else lr
        wd = self.weight_decay

        step = base.predicate_step(grads_finite, state.step)
        bc1, bc2 = self._bias_corrections(step)
        p_math = base.math_params(params, state.master)
        hypers = base.leaf_hypers(params, self.param_group_fn, self.group_hypers)

        def one(g, p, m, v, h):
            return adam_math(
                g.astype(jnp.float32), p.astype(jnp.float32), m, v,
                h.get("weight_decay", wd), base.leaf_lr(h, lr), bc1, bc2,
                beta1=self.beta1, beta2=self.beta2, eps=self.eps,
                adam_w_mode=self.adam_w_mode)

        treedef = jax.tree.structure(grads)
        # tree.map validates all five trees share grads' structure
        out = jax.tree.map(one, grads, p_math, state.exp_avg, state.exp_avg_sq, hypers)
        flat = jax.tree.leaves(out, is_leaf=lambda x: isinstance(x, tuple))
        p_new = jax.tree.unflatten(treedef, [x[0] for x in flat])
        m_new = jax.tree.unflatten(treedef, [x[1] for x in flat])
        v_new = jax.tree.unflatten(treedef, [x[2] for x in flat])

        p_new = base.select(grads_finite, p_new, p_math)
        m_new = base.select(grads_finite, m_new, state.exp_avg)
        v_new = base.select(grads_finite, v_new, state.exp_avg_sq)

        new_params, new_master = base.emit_params(p_new, params, state.master)
        return new_params, AdamState(step, m_new, v_new, new_master)
