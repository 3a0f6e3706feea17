"""GPT training throughput on the local chip (BASELINE config 4 analog).

Measures tokens/sec for a full train step (fwd + bwd + FusedAdam) of a
GPT-2-small-class model in bf16 with flash attention, single chip.
Prints one JSON line per config.

    python benchmarks/gpt_train.py [--layers 12 --hidden 768 --seq 1024]
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
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--hidden", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=50304)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--flash", action="store_true", default=True)
    ap.add_argument("--no-remat", action="store_true",
                    help="disable per-layer rematerialization (fits in HBM "
                         "for GPT-124M-class models; ~frees the second "
                         "forward pass)")
    args = ap.parse_args()

    from apex_tpu.models.gpt import GPTConfig, gpt_loss, init_params
    from apex_tpu.optimizers import FusedAdam

    cfg = GPTConfig(
        vocab_size=args.vocab,
        hidden_size=args.hidden,
        num_layers=args.layers,
        num_attention_heads=args.heads,
        max_seq_len=args.seq,
        compute_dtype=jnp.bfloat16,
        use_flash_attention=args.flash,
        checkpoint_layers=not args.no_remat,
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    opt = FusedAdam(lr=3e-4, weight_decay=0.1)
    state = opt.init(params)

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(args.batch, args.seq)))
    targets = jnp.roll(tokens, -1, axis=1)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(gpt_loss)(params, tokens, targets, cfg)
        params, state = opt.update(grads, state, params)
        return params, state, loss

    params, state, loss = step(params, state)
    float(loss)  # completion barrier: the readback waits for the step

    t0 = time.perf_counter()
    for _ in range(args.iters):
        params, state, loss = step(params, state)
    float(loss)  # completion barrier: the readback waits for the step
    dt = (time.perf_counter() - t0) / args.iters
    tokens_per_sec = args.batch * args.seq / dt

    print(
        json.dumps(
            {
                "metric": "gpt_train_tokens_per_sec",
                "value": round(tokens_per_sec, 1),
                "unit": "tokens/s",
                "config": {
                    "params_m": round(n_params / 1e6, 1),
                    "layers": args.layers,
                    "hidden": args.hidden,
                    "seq": args.seq,
                    "batch": args.batch,
                    "step_ms": round(dt * 1e3, 2),
                    "device": str(jax.devices()[0]),
                },
            }
        )
    )


if __name__ == "__main__":
    main()
