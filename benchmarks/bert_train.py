"""BERT pretraining throughput on the local chip (BASELINE config 5
analog: BERT + FusedLAMB + O2-style bf16).

Measures tokens/sec for a full MLM train step (fwd + bwd + FusedLAMB)
with padded batches riding the masked flash-attention kernel.

    python benchmarks/bert_train.py [--layers 12 --hidden 768 --seq 512]
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
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=30528)
    ap.add_argument("--iters", type=int, default=15)
    args = ap.parse_args()

    from apex_tpu.models.bert import BertConfig, bert_mlm_loss, init_params
    from apex_tpu.optimizers import FusedLAMB

    cfg = BertConfig(
        vocab_size=args.vocab,
        hidden_size=args.hidden,
        num_layers=args.layers,
        num_attention_heads=args.heads,
        max_seq_len=args.seq,
        compute_dtype=jnp.bfloat16,
        checkpoint_layers=True,
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    opt = FusedLAMB(lr=1e-3, weight_decay=0.01)
    state = opt.init(params)

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(args.batch, args.seq)))
    targets = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(args.batch, args.seq)))
    lengths = rng.randint(args.seq // 2, args.seq + 1, size=args.batch)
    pad = jnp.asarray(np.arange(args.seq)[None, :] < lengths[:, None])
    # MLM: predict at 15% of valid positions
    loss_mask = jnp.asarray(
        (rng.rand(args.batch, args.seq) < 0.15) & np.asarray(pad)
    ).astype(jnp.float32)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(bert_mlm_loss)(
            params, tokens, targets, loss_mask, cfg, pad_mask=pad
        )
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
                "metric": "bert_mlm_train_tokens_per_sec",
                "value": round(tokens_per_sec, 1),
                "unit": "tokens/s",
                "config": {
                    "params_m": round(n_params / 1e6, 1),
                    "layers": args.layers,
                    "hidden": args.hidden,
                    "seq": args.seq,
                    "batch": args.batch,
                    "mean_valid": round(float(pad.mean()), 2),
                    "step_ms": round(dt * 1e3, 2),
                    "device": str(jax.devices()[0]),
                },
            }
        )
    )


if __name__ == "__main__":
    main()
