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

The route follows the state's layout (see
:mod:`apex_tpu.optimizers.base`).  ``init(params)`` makes per-leaf
m/v and the step updates a leaf at a time: one fusion a leaf reads
``g``, ``p``, ``m``, ``v`` once and writes ``p``, ``m``, ``v`` in place
under donation.  ``init(params, bucketed=True)`` stores m/v (and the
fp32 master) as flat bucket buffers that ride the jit boundary
directly — ``donate_argnums`` then donates the bucket buffers
themselves — and the step is one fused pass per dtype bucket.  Both are
bit-exact in fp32 with each other and with ``optax.adamw`` (the
second-moment update is ``(1-β2)·(g·g)``, optax's association).
"""

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.optimizers import base, bucketing


class AdamState(NamedTuple):
    step: jnp.ndarray  # i32 scalar
    exp_avg: Any  # m, fp32 (tree or Buckets)
    exp_avg_sq: Any  # v, fp32 (tree or Buckets)
    master: Optional[Any] = None  # fp32 master params (if enabled)


def adam_core(g, m, v, bc1, bc2, *, beta1, beta2, eps):
    """The param-free half of the Adam expression tree: new moments and
    the core update term ``m̂/(sqrt(v̂)+eps)``.  Module-level so the
    ZeRO-sharded :class:`~apex_tpu.contrib.optimizers.
    DistributedFusedAdam` evaluates the IDENTICAL expressions on its dp
    shards (the bit-exact-parity contract), and factored away from the
    params so the engine's pack-free emit can apply ``wd``/``lr`` per
    original leaf without materializing a param bucket."""
    m_new = beta1 * m + (1.0 - beta1) * g
    # (1-β2)·(g·g): optax's association, pinned for bit-exact parity
    v_new = beta2 * v + (1.0 - beta2) * (g * g)
    core = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    return core, m_new, v_new


def adam_math(g, p32, m, v, wd_i, lr_i, bc1, bc2, *, beta1, beta2, eps,
              adam_w_mode):
    """One Adam step per element (AdamW ADAM_MODE_1 / L2 ADAM_MODE_0) —
    the numerics specification every path (per-leaf, bucket, ZeRO
    shard) shares verbatim, so they cannot drift even by a rounding."""
    if not adam_w_mode:  # ADAM_MODE_0: L2 regularization
        g = g + wd_i * p32
    core, m_new, v_new = adam_core(g, m, v, bc1, bc2,
                                   beta1=beta1, beta2=beta2, eps=eps)
    update = core + wd_i * p32 if adam_w_mode else core
    return p32 - lr_i * update, m_new, v_new


class FusedAdam(base.OptimizerBase):

    _BUCKET_SLOT = "exp_avg"

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
        ``use_buckets`` is accepted and ignored
        (:class:`~apex_tpu.optimizers.base.OptimizerBase`)."""
        if amsgrad:
            raise RuntimeError("FusedAdam does not support the AMSGrad variant.")
        super().__init__(lr, weight_decay, master_weights,
                         use_buckets=use_buckets)
        self.bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.param_group_fn = param_group_fn
        self.group_hypers = group_hypers

    def init(self, params, bucketed: bool = False) -> AdamState:
        if bucketed:
            (m, v), master = self._init_bucket_slots(params, 2)
            return AdamState(jnp.int32(0), m, v, master)
        zeros = lambda t: jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), t)
        return AdamState(
            step=jnp.int32(0),
            exp_avg=zeros(params),
            exp_avg_sq=zeros(params),
            master=base.make_master(params, self.master_weights),
        )

    def _adam_math(self, g, p32, m, v, wd_i, lr_i, bc1, bc2):
        """The one Adam expression tree — shared verbatim by the
        per-leaf and bucket paths (elementwise code is shape-blind), so
        the two cannot drift even by a rounding."""
        return adam_math(g, p32, m, v, wd_i, lr_i, bc1, bc2,
                         beta1=self.beta1, beta2=self.beta2, eps=self.eps,
                         adam_w_mode=self.adam_w_mode)

    # ------------------------------------------------------- per-leaf path
    def _leaf_update(self, grads, state: AdamState, params,
                     grads_finite=None, lr=None):
        lr = self.lr if lr is None else lr
        wd = self.weight_decay

        step = base.predicate_step(grads_finite, state.step)
        bc1, bc2 = self._bias_corrections(step)
        p_math = base.math_params(params, state.master)
        hypers = base.leaf_hypers(params, self.param_group_fn, self.group_hypers)

        def one(g, p, m, v, h):
            return self._adam_math(
                g.astype(jnp.float32), p.astype(jnp.float32), m, v,
                h.get("weight_decay", wd), base.leaf_lr(h, lr), bc1, bc2)

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

    # --------------------------------------------------------- bucket path
    def _bucket_update_packfree(self, prep: base.PreparedGrads,
                                state: AdamState, params, pred, lr):
        """The emit without a param bucket, for bucket-resident m/v.
        ``pack(params)`` concatenates every leaf into a bucket XLA
        materializes, and ``unpack`` writes it all back — two
        whole-model HBM passes per step.  With no fp32 master and
        decoupled decay (AdamW), the bucket math only needs the GRADS
        in bucket form: m/v/core are computed per bucket
        (:func:`adam_core`), then each param leaf is emitted directly
        from its static core slice — slice + elementwise fuse, and no
        param bucket exists in the HLO.  Bit-exact with the packed path
        (identical expressions per element; only the layout of the
        param read changed)."""
        lr = self.lr if lr is None else lr
        wd = self.weight_decay
        plan = prep.plan
        step = base.predicate_step(pred, state.step)
        bc1, bc2 = self._bias_corrections(step)
        m_b = state.exp_avg.arrays
        v_b = state.exp_avg_sq.arrays
        hl = self._hyper_leaves(
            base.leaf_hypers(params, self.param_group_fn, self.group_hypers))

        cores, new_m, new_v = [], [], []
        for bi, b in enumerate(plan.buckets):
            core, m_out, v_out = adam_core(
                prep.g[bi], m_b[bi], v_b[bi], bc1, bc2,
                beta1=self.beta1, beta2=self.beta2, eps=self.eps)
            cores.append(core)
            new_m.append(m_out)
            new_v.append(v_out)
        new_m = base.bucket_select(pred, new_m, m_b)
        new_v = base.bucket_select(pred, new_v, v_b)

        leaves = jax.tree.leaves(params)
        new_leaves = [None] * plan.n_leaves
        for bi, b in enumerate(plan.buckets):
            for bl in b.leaves:
                p32 = leaves[bl.leaf_id].astype(jnp.float32)
                u = jax.lax.slice(
                    cores[bi], (bl.offset,), (bl.offset + bl.size,)
                ).reshape(bl.shape)
                h = hl[bl.leaf_id]
                p_new = p32 - base.leaf_lr(h, lr) * (
                    u + h.get("weight_decay", wd) * p32)
                if pred is not None:
                    p_new = jnp.where(jnp.asarray(pred), p_new, p32)
                new_leaves[bl.leaf_id] = p_new.astype(leaves[bl.leaf_id].dtype)
        new_params = jax.tree.unflatten(plan.treedef, new_leaves)
        return new_params, AdamState(
            step,
            bucketing.Buckets(plan, new_m),
            bucketing.Buckets(plan, new_v),
            None,
        )

    def _bucket_update(self, prep: base.PreparedGrads, state: AdamState,
                       params, pred, lr=None):
        if state.master is None and self.adam_w_mode:
            return self._bucket_update_packfree(prep, state, params, pred, lr)
        lr = self.lr if lr is None else lr
        wd = self.weight_decay
        plan = prep.plan

        step = base.predicate_step(pred, state.step)
        bc1, bc2 = self._bias_corrections(step)

        m_b = state.exp_avg.arrays
        v_b = state.exp_avg_sq.arrays
        has_master = state.master is not None
        if has_master:
            p_b = state.master.arrays
        else:
            p_b = bucketing.pack(plan, params)
        hl = self._hyper_leaves(
            base.leaf_hypers(params, self.param_group_fn, self.group_hypers))
        wd_leaf = [h.get("weight_decay", wd) for h in hl]

        new_p, new_m, new_v = [], [], []
        for bi, b in enumerate(plan.buckets):
            p_out, m_out, v_out = self._adam_math(
                prep.g[bi], p_b[bi], m_b[bi], v_b[bi],
                bucketing.seg_values(b, wd_leaf),
                self._bucket_lr(b, hl, lr), bc1, bc2)
            new_p.append(p_out)
            new_m.append(m_out)
            new_v.append(v_out)

        new_p = base.bucket_select(pred, new_p, p_b)
        new_m = base.bucket_select(pred, new_m, m_b)
        new_v = base.bucket_select(pred, new_v, v_b)

        new_params = bucketing.unpack(plan, new_p)
        new_master = (bucketing.Buckets(plan, new_p)
                      if has_master else None)
        return new_params, AdamState(
            step,
            bucketing.Buckets(plan, new_m),
            bucketing.Buckets(plan, new_v),
            new_master,
        )
