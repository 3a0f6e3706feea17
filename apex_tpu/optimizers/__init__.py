"""Fused optimizers (reference: ``apex/optimizers``).

Each optimizer is a pure pytree transform with exact reference numerics
(fp32 math regardless of storage dtype), device-side predicated updates
(the capturable/noop_flag design), and optional fp32 master weights.

State is a tree of per-leaf slots (``init(params)``) and all five
update it a leaf at a time, in place under donation; ``update_scaled``
folds the loss-scale unscale, the global-norm grad clip and the
all-finite vote into the update's own read of the gradients.  The
bucket plans exported here are the layout of the ZeRO optimizers and
the bucketed gradient syncs (``contrib.optimizers``).  See
:mod:`apex_tpu.optimizers.base` and ``docs/optimizers.md``.
"""

from apex_tpu.optimizers.bucketing import BucketPlan, Buckets, plan_of
from apex_tpu.optimizers.fused_adam import AdamState, FusedAdam
from apex_tpu.optimizers.fused_adagrad import AdagradState, FusedAdagrad
from apex_tpu.optimizers.fused_lamb import FusedLAMB, LambState
from apex_tpu.optimizers.fused_novograd import FusedNovoGrad, NovoGradState
from apex_tpu.optimizers.fused_sgd import FusedSGD, SGDState
from apex_tpu.optimizers.fused_mixed_precision_lamb import FusedMixedPrecisionLamb

__all__ = [
    "FusedAdam",
    "AdamState",
    "FusedLAMB",
    "LambState",
    "FusedSGD",
    "SGDState",
    "FusedNovoGrad",
    "NovoGradState",
    "FusedAdagrad",
    "AdagradState",
    "FusedMixedPrecisionLamb",
    "BucketPlan",
    "Buckets",
    "plan_of",
]
