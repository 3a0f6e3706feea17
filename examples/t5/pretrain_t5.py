"""T5 (encoder-decoder) pretraining example — span-corruption-style
seq2seq on synthetic data over a tp × pp × dp mesh.

The enc-dec counterpart of ``examples/gpt/pretrain_gpt.py``
(reference role: the Megatron T5 path,
``apex/transformer/pipeline_parallel/schedules/common.py:30-120``'s
``ModelType.encoder_and_decoder`` routing): the pipeline carries TWO
activation streams — encoder stages before the split rank, decoder
stages (+ the forwarded encoder output) at and after it — via the
dual-stream 1F1B tick schedule.

Synthetic task: the decoder must reproduce the source sequence
shifted by one (a copy task — loss visibly falls within a few steps,
so the example doubles as an end-to-end smoke check).

    # 8 virtual CPU devices:
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/t5/pretrain_t5.py --pp 4 --split 2 --steps 4
    # flags compose: --tp 2, --fp16 (dynamic loss scaling through the
    # dual-stream pipeline), --fused-ce (chunked fused LM-head+CE)
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from apex_tpu.models.t5 import (
    T5Config,
    init_params,
    make_pp_train_step,
    make_train_step,
    params_to_pp_layout,
)
from apex_tpu.optimizers import FusedAdam


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--split", type=int, default=None,
                   help="pipeline rank where encoder hands to decoder "
                        "(default pp//2)")
    p.add_argument("--micro-batches", type=int, default=2)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--enc-layers", type=int, default=2)
    p.add_argument("--dec-layers", type=int, default=2)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--fp16", action="store_true",
                   help="dynamic loss scaling through the pipeline")
    p.add_argument("--fused-ce", action="store_true",
                   help="chunked fused LM-head+CE (ops/fused_ce.py)")
    return p.parse_args()


def make_batch(rng, batch, seq, vocab):
    """Copy task: decoder input is <bos>+src[:-1], target is src."""
    src = rng.randint(2, vocab, size=(batch, seq))
    dec_in = np.concatenate([np.ones((batch, 1), src.dtype), src[:, :-1]], 1)
    return jnp.asarray(src), jnp.asarray(dec_in), jnp.asarray(src)


def main():
    args = parse_args()
    n_dev = len(jax.devices())
    dp = n_dev // (args.tp * args.pp)
    assert dp >= 1 and dp * args.tp * args.pp == n_dev, (
        f"tp({args.tp}) x pp({args.pp}) must divide device count {n_dev}")
    split = args.split if args.split is not None else max(args.pp // 2, 1)

    config = T5Config(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_encoder_layers=args.enc_layers, num_decoder_layers=args.dec_layers,
        num_attention_heads=args.heads,
        max_src_len=args.seq, max_tgt_len=args.seq,
        compute_dtype=jnp.float16 if args.fp16 else jnp.bfloat16,
        checkpoint_layers=True,
        fused_ce=args.fused_ce,
        fused_ce_chunk=next(c for c in range(min(128, args.seq), 0, -1)
                            if args.seq % c == 0),
    )
    params = init_params(config, jax.random.PRNGKey(0))
    opt = FusedAdam(lr=args.lr)

    scaler = sstate = None
    if args.fp16:
        from apex_tpu.amp import DynamicLossScaler

        scaler = DynamicLossScaler(init_scale=2.0 ** 15)
        sstate = scaler.init()

    if args.pp > 1:
        mesh = Mesh(np.array(jax.devices()).reshape(dp, args.pp, args.tp),
                    ("dp", "pp", "tp"))
        params = params_to_pp_layout(params, pp=args.pp, split=split)
        state = opt.init(params)
        step = make_pp_train_step(config, opt, mesh,
                                  num_microbatches=args.micro_batches,
                                  split=split, dp_axis="dp",
                                  loss_scaler=scaler, donate_state=True)
    else:
        mesh = Mesh(np.array(jax.devices()).reshape(dp, args.tp),
                    ("dp", "tp"))
        state = opt.init(params)
        step = make_train_step(config, opt, mesh, dp_axis="dp",
                               donate_state=True)
        assert scaler is None, "--fp16 demo path requires --pp > 1"

    # a small fixed pool of batches: a fresh random batch per step keeps
    # the copy task at uniform-entropy loss for tens of steps (nothing
    # generalizes that fast at this size); cycling a pool makes the
    # loss fall visibly within one epoch, which is what a smoke example
    # is for
    rng = np.random.RandomState(0)
    pool = [make_batch(rng, args.global_batch, args.seq, args.vocab)
            for _ in range(4)]
    # loss printing rides the async telemetry seam (the APX108-clean
    # spelling): the loop never blocks on a device array — completed
    # copies print a step or two later, the flush drains the rest
    from apex_tpu.observability.stepstats import AsyncFetcher

    fetcher = AsyncFetcher()

    def emit(harvested):
        for _, s, tree in harvested:
            print(f"step {s}: loss={float(tree['loss']):.4f}", flush=True)

    t0 = time.time()
    for i in range(args.steps):
        src, dec_in, tgt = pool[i % len(pool)]
        if scaler is not None:
            params, state, sstate, loss = step(params, state, sstate,
                                               src, dec_in, tgt)
        else:
            params, state, loss = step(params, state, src, dec_in, tgt)
        fetcher.put("loss", i, {"loss": loss})
        emit(fetcher.ready())
    emit(fetcher.flush())
    dt = time.time() - t0
    tok = args.steps * args.global_batch * args.seq
    print(f"{args.steps} steps in {dt:.1f}s ({tok / dt:.0f} tokens/s)")


if __name__ == "__main__":
    main()
