"""Shared layer-remat policy for the model families.

``"full"`` saves only the layer inputs across the remat boundary (the
reference's activation-checkpoint semantics,
``apex/transformer/tensor_parallel/random.py:236``) — maximum HBM
savings, re-runs the whole layer forward inside the backward.
``"dots"`` (``jax.checkpoint_policies.dots_with_no_batch_dims_saveable``)
keeps MXU (matmul) outputs and recomputes only the cheap elementwise
work — trades a little HBM for skipping the expensive recompute, often
the best step time on TPU where the backward is MXU-bound.
On the chip only ``"full"`` is measured (the ``gpt2-medium.train-b8``
cell: ``train_tokens_per_s``, ``step_hbm.train``); ``"dots"`` is not
measured.
"""

import jax

POLICIES = ("full", "dots")


def validate_policy(policy: str) -> None:
    """Raise at config construction — a typo'd policy must not silently
    fall back to some default remat behavior."""
    if policy not in POLICIES:
        raise ValueError(
            f"remat_policy must be one of {POLICIES} (got {policy!r})")


def remat_layer(layer, policy: str):
    """Wrap a layer fn in ``jax.checkpoint`` under ``policy``."""
    if policy == "dots":
        return jax.checkpoint(
            layer,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    return jax.checkpoint(layer)
