"""ResNet-50 training throughput (BASELINE configs 1/3 analog), single
chip, synthetic data, amp O2 (bf16 + fp32 BN + fp32 master).

    python benchmarks/resnet_train.py [--batch 64 --iters 20]
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()

    from apex_tpu.models.resnet import ResNet50
    from apex_tpu.optimizers import FusedSGD

    model = ResNet50()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(args.batch, args.image_size, args.image_size, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 1000, size=(args.batch,)))

    variables = model.init(jax.random.PRNGKey(0), x[:2], train=True)
    params, bs = variables["params"], variables["batch_stats"]
    opt = FusedSGD(lr=0.1, momentum=0.9, weight_decay=1e-4, master_weights=True)
    state = opt.init(params)

    @jax.jit
    def step(params, state, bs):
        def loss_fn(p, bs):
            logits, upd = model.apply(
                {"params": p, "batch_stats": bs}, x, train=True, mutable=["batch_stats"]
            )
            onehot = jax.nn.one_hot(y, 1000)
            return -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), -1)), upd["batch_stats"]

        (loss, bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, bs)
        params, state = opt.update(grads, state, params)
        return params, state, bs, loss

    params, state, bs, loss = step(params, state, bs)
    float(loss)  # completion barrier: the readback waits for the step
    t0 = time.perf_counter()
    for _ in range(args.iters):
        params, state, bs, loss = step(params, state, bs)
    float(loss)  # completion barrier: the readback waits for the step
    dt = (time.perf_counter() - t0) / args.iters

    print(
        json.dumps(
            {
                "metric": "resnet50_train_images_per_sec",
                "value": round(args.batch / dt, 1),
                "unit": "images/s",
                "config": {
                    "batch": args.batch,
                    "image_size": args.image_size,
                    "step_ms": round(dt * 1e3, 2),
                    "device": str(jax.devices()[0]),
                },
            }
        )
    )


if __name__ == "__main__":
    main()
