"""ASP 2:4 sparsity with channel-permutation search — small-model demo.

Mirrors the reference recipe (apex/contrib/sparsity/README.md): train
dense, prune with 2:4 masks, finetune masked.  The permutation search
(permutation_lib.py) picks masks that retain more magnitude, so the
pruned model starts closer to the dense one and finetunes back faster.

Run (CPU is fine):
    JAX_PLATFORMS=cpu python examples/sparsity/asp_permutation.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.contrib.sparsity import ASP, compute_sparse_masks
from apex_tpu.optimizers import FusedAdam


def make_data(rng, n=512, d_in=32):
    x = rng.randn(n, d_in).astype(np.float32)
    w_true = rng.randn(d_in, 1).astype(np.float32)
    y = x @ w_true + 0.01 * rng.randn(n, 1).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


def loss_fn(params, x, y):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return jnp.mean((h @ params["w2"] + params["b2"] - y) ** 2)


def train(params, x, y, steps, masks=None):
    opt = FusedAdam(lr=1e-2)
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        loss, g = jax.value_and_grad(loss_fn)(params, x, y)
        params, state = opt.update(g, state, params)
        if masks is not None:
            params = ASP.apply_masks(params, masks)
        return params, state, loss

    for _ in range(steps):
        params, state, loss = step(params, state)
    return params, float(loss)


def main():
    rng = np.random.RandomState(0)
    x, y = make_data(rng)
    params = {
        "w1": jnp.asarray(rng.randn(32, 64).astype(np.float32) * 0.3),
        "b1": jnp.zeros((64,)),
        "w2": jnp.asarray(rng.randn(64, 1).astype(np.float32) * 0.3),
        "b2": jnp.zeros((1,)),
    }

    params, dense_loss = train(params, x, y, 300)
    print(f"dense loss             {dense_loss:.5f}")

    for label, kw in (("naive 2:4", {}), ("permutation-searched", {"permutation_search": True})):
        masks = compute_sparse_masks(params, **kw)
        pruned, masks = ASP.prune_trained_model(params, masks)
        pruned_loss = float(loss_fn(pruned, x, y))
        finetuned, ft_loss = train(pruned, x, y, 100, masks=masks)
        print(f"{label:22s} pruned {pruned_loss:.5f}  finetuned {ft_loss:.5f}")


if __name__ == "__main__":
    main()
