"""GPT-345M memory fit on one chip (VERDICT r4 task 7).

BASELINE config 4 is GPT-2 345M (L24 H1024 heads16) at batch 8, S1024;
whether that fits one chip's HBM with remat+flash has never been
answered — bench.py works around OOM by halving the batch blind.  This
harness answers it directly:

1. analytic budget: params / grads / Adam state / embedding+logits /
   per-layer activation checkpoints at the requested config
   (shape-only math via ``jax.eval_shape`` — no device allocation
   before the probes);
2. one real train step per candidate batch (descending from
   ``--batch``), each in a FRESH SUBPROCESS so ``memory_stats()``'s
   process-lifetime ``peak_bytes_in_use`` is the peak of THAT attempt,
   not of an earlier OOM'd one; the step donates params/state (the
   production setting — without donation XLA keeps old+new copies of
   ~5.5 GB of fp32 state live at 345M and the verdict is pessimistic);
3. one JSON line per attempt + a final fit verdict.

    python benchmarks/memfit_gpt.py                 # the 345M question
    python benchmarks/memfit_gpt.py --layers 12 --hidden 768  # 124M
"""

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp
import numpy as np


def analytic_budget(n_params, layers, hidden, seq, batch, vocab):
    """Rough HBM budget (bytes) by component — the denominator the
    measured peak is compared against.  Assumes a donated train step
    (no old+new double of params/state)."""
    f32, bf16 = 4, 2
    act_ckpt = layers * seq * batch * hidden * bf16  # one saved x per layer
    logits = seq * batch * vocab * f32               # fp32 logits (+CE)
    return {
        "params_fp32_mb": n_params * f32 / 2**20,
        "grads_fp32_mb": n_params * f32 / 2**20,
        "adam_state_mb": 2 * n_params * f32 / 2**20,
        "layer_checkpoints_mb": act_ckpt / 2**20,
        "logits_fp32_mb": logits / 2**20,
    }


def mem_stats():
    try:
        s = jax.local_devices()[0].memory_stats() or {}
        return {
            "bytes_in_use_mb": round(s.get("bytes_in_use", 0) / 2**20, 1),
            "peak_bytes_in_use_mb": round(
                s.get("peak_bytes_in_use", 0) / 2**20, 1),
            "bytes_limit_mb": round(s.get("bytes_limit", 0) / 2**20, 1),
        }
    except Exception as e:  # noqa: BLE001 — stats are optional telemetry
        return {"memory_stats_error": f"{type(e).__name__}: {e}"}


def _config(args):
    from apex_tpu.models.gpt import GPTConfig

    return GPTConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.layers, num_attention_heads=args.heads,
        max_seq_len=args.seq, compute_dtype=jnp.bfloat16,
        use_flash_attention=True, checkpoint_layers=True,
        fused_ce=args.fused_ce,
    )


def probe_one(args, batch, iters=3):
    """Run one attempt in THIS process (the per-batch child): one
    donated train step + timing, print the attempt record."""
    from apex_tpu.models.gpt import gpt_loss, init_params
    from apex_tpu.optimizers import FusedAdam

    cfg = _config(args)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = FusedAdam(lr=3e-4, weight_decay=0.1)
    state = opt.init(params)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, args.vocab, size=(batch, args.seq)))
    targets = jnp.roll(tokens, -1, axis=1)

    def _step(params, state):
        loss, grads = jax.value_and_grad(gpt_loss)(
            params, tokens, targets, cfg)
        params, state = opt.update(grads, state, params)
        return params, state, loss

    step = jax.jit(_step, donate_argnums=(0, 1))
    try:
        params, state, loss = step(params, state)
        float(loss)  # completion barrier
        t0 = time.perf_counter()
        for _ in range(iters):
            params, state, loss = step(params, state)
        float(loss)
        dt = (time.perf_counter() - t0) / iters
        print(json.dumps({
            "batch": batch, "fits": True,
            "ms_per_step": round(dt * 1e3, 2), **mem_stats(),
        }), flush=True)
        return 0
    except Exception as e:  # noqa: BLE001 — the OOM path is the point
        msg = str(e)
        oom = "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg
        print(json.dumps({
            "batch": batch, "fits": False, "oom": oom,
            "error": f"{type(e).__name__}: {msg[:300]}", **mem_stats(),
        }), flush=True)
        return 3 if oom else 4


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=50304)
    ap.add_argument("--fused-ce", action="store_true",
                    help="measure with the chunked fused LM-head+CE — "
                         "the A/B for its claimed ~3.3 GB/step peak-HBM "
                         "saving (the (S,B,V) fp32 logits + d_logits)")
    ap.add_argument("--probe-batch", type=int, default=None,
                    help=argparse.SUPPRESS)  # internal: child mode
    ap.add_argument("--probe-timeout", type=float, default=600.0)
    args = ap.parse_args()

    if args.probe_batch is not None:
        sys.exit(probe_one(args, args.probe_batch))

    from apex_tpu.models.gpt import init_params

    cfg = _config(args)
    # abstract key: the parent must NOT touch the (possibly wedged)
    # backend — a concrete PRNGKey would initialize it; eval_shape with
    # a ShapeDtypeStruct stays purely abstract
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), key)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(shapes))
    budget = analytic_budget(n_params, args.layers, args.hidden, args.seq,
                             args.batch, args.vocab)
    print(json.dumps({
        "params_m": round(n_params / 1e6, 1),
        "analytic_budget": {k: round(v, 1) for k, v in budget.items()},
    }), flush=True)

    base_cmd = [
        sys.executable, str(Path(__file__).resolve()),
        "--layers", str(args.layers), "--hidden", str(args.hidden),
        "--heads", str(args.heads), "--seq", str(args.seq),
        "--vocab", str(args.vocab),
    ] + (["--fused-ce"] if args.fused_ce else [])
    fit_batch = None
    b = args.batch
    while b >= 1:
        try:
            r = subprocess.run(
                base_cmd + ["--probe-batch", str(b)],
                timeout=args.probe_timeout, text=True, capture_output=True)
        except subprocess.TimeoutExpired:
            print(json.dumps({"batch": b, "fits": False,
                              "error": "probe subprocess timed out "
                                       "(device hung?)"}), flush=True)
            break
        sys.stdout.write(r.stdout)
        sys.stdout.flush()
        if r.returncode == 0:
            fit_batch = b
            break
        if r.returncode != 3:  # not an OOM: surface and stop
            tail = (r.stderr or "").strip().splitlines()[-1:] or ["no stderr"]
            print(json.dumps({"batch": b, "fits": False,
                              "rc": r.returncode, "stderr": tail[0]}),
                  flush=True)
            break
        b //= 2
    print(json.dumps({
        "verdict": {
            "config": f"L{args.layers} H{args.hidden} S{args.seq}",
            "requested_batch": args.batch,
            "max_fitting_batch": fit_batch,
            "fits_at_requested": fit_batch == args.batch,
        }
    }), flush=True)


if __name__ == "__main__":
    main()
