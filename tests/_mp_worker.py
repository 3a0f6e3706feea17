"""Worker for the real multi-process distributed tests.

Launched by ``tests/test_multiprocess.py`` as 2 OS processes × 4 virtual
CPU devices each (the reference's test shape:
``apex/transformer/testing/distributed_test_base.py:22-94`` spawns
``MultiProcessTestCase`` workers with file-store rendezvous; here the
rendezvous is ``jax.distributed.initialize``'s coordinator).

Phases:
1. **dp×tp train parity** — build the mesh across processes via
   ``parallel_state.initialize_model_parallel``, run 3 GPT train steps
   on global arrays, emit the loss trajectory (the pytest side compares
   it against a single-process oracle).
2. **ZeRO distributed checkpoint/resume** — train 2 steps with
   ``DistributedFusedAdam`` (state sharded over (tp, dp) across both
   processes), write a per-process checkpoint of exactly the shards
   each process addresses (``io.save_distributed_checkpoint``),
   "restart" by reassembling global arrays from the shard files, run
   one more step, and verify bit-identical params vs the uninterrupted
   run.
"""

import argparse
import json
import os
import sys
from pathlib import Path

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--num-processes", type=int, default=2)
    ap.add_argument("--coordinator", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    out = Path(args.out)

    import jax

    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.distributed.initialize(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    assert jax.process_count() == args.num_processes
    assert jax.local_device_count() == 4, jax.local_devices()
    assert jax.device_count() == 8, jax.devices()

    from apex_tpu import io
    from apex_tpu.models.gpt import (
        GPTConfig,
        init_params,
        make_train_step,
        param_specs,
    )
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.optimizers.fused_adam import AdamState
    from apex_tpu.transformer import parallel_state as ps
    from jax.sharding import PartitionSpec as P

    mesh = ps.initialize_model_parallel(
        tensor_model_parallel_size_=2, devices=jax.devices()
    )
    config = GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_attention_heads=4,
        max_seq_len=16, compute_dtype=jnp.float32, checkpoint_layers=True,
    )
    specs = param_specs(config)
    rng = np.random.RandomState(0)
    tokens_np = rng.randint(0, 64, size=(8, 16))
    targets_np = np.roll(tokens_np, -1, axis=1)

    def to_global(tree, spec_tree):
        return io.make_global_array_tree(tree, mesh, spec_tree)

    # ---------------------------------------------- phase 1: dp×tp parity
    params = to_global(init_params(config, jax.random.PRNGKey(0)), specs)
    opt = FusedAdam(lr=1e-2)
    sspec = AdamState(step=P(), exp_avg=specs, exp_avg_sq=specs, master=None)
    state = to_global(opt.init(jax.tree.map(np.asarray, params)), sspec)
    # ^ init on host values: every process builds the same zero state
    data_spec = P("dp", None)
    tokens = to_global(tokens_np, data_spec)
    targets = to_global(targets_np, data_spec)

    step = make_train_step(config, opt, mesh)
    losses = []
    for _ in range(3):
        params, state, loss = step(params, state, tokens, targets)
        losses.append(float(loss))
    if args.process_id == 0:
        (out / "losses.json").write_text(json.dumps(losses))
    print(f"[worker {args.process_id}] phase1 losses: {losses}", flush=True)

    # --- phase 1b: pp=2 × tp=4 (dp=1) — the pipeline ppermutes CROSS the
    # process boundary.  The mesh is dp-outermost, so with dp=1 stage 0
    # is devices 0-3 (all of process 0) and stage 1 is devices 4-7 (all
    # of process 1): every cross-stage send is a cross-process transfer.
    from apex_tpu.models.gpt import make_pp_train_step

    ps.destroy_model_parallel()
    pp_mesh = ps.initialize_model_parallel(
        tensor_model_parallel_size_=4, pipeline_model_parallel_size_=2,
        devices=jax.devices(),
    )
    assert pp_mesh.shape["dp"] == 1
    stage0 = {d.process_index for d in pp_mesh.devices[0, 0].ravel()}
    stage1 = {d.process_index for d in pp_mesh.devices[0, 1].ravel()}
    assert stage0 == {0} and stage1 == {1}, (
        f"stages must live on different processes (got {stage0} vs "
        f"{stage1}) for this test to exercise cross-process ppermutes")
    pp_cfg = GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=4, num_attention_heads=4,
        max_seq_len=16, compute_dtype=jnp.float32, checkpoint_layers=True,
    )
    pp_base = param_specs(pp_cfg)
    pp_specs = dict(pp_base)
    pp_specs["layers"] = {k: P("pp", *s[1:]) for k, s in pp_base["layers"].items()}
    pp_params_host = init_params(pp_cfg, jax.random.PRNGKey(2))
    pp_opt = FusedAdam(lr=1e-2)
    pp_state_host = pp_opt.init(pp_params_host)
    pp_sspec = AdamState(step=P(), exp_avg=pp_specs, exp_avg_sq=pp_specs,
                         master=None)
    pp_params = io.make_global_array_tree(pp_params_host, pp_mesh, pp_specs)
    pp_state = io.make_global_array_tree(pp_state_host, pp_mesh, pp_sspec)
    pp_tok = io.make_global_array_tree(tokens_np, pp_mesh, P("dp", None))
    pp_tgt = io.make_global_array_tree(targets_np, pp_mesh, P("dp", None))
    pp_step = make_pp_train_step(pp_cfg, pp_opt, pp_mesh, num_microbatches=2)
    pp_losses = []
    for _ in range(2):
        pp_params, pp_state, pp_loss = pp_step(pp_params, pp_state, pp_tok, pp_tgt)
        pp_losses.append(float(pp_loss))
    if args.process_id == 0:
        (out / "pp_losses.json").write_text(json.dumps(pp_losses))
    print(f"[worker {args.process_id}] phase1b pp losses: {pp_losses}", flush=True)
    ps.destroy_model_parallel()
    mesh = ps.initialize_model_parallel(
        tensor_model_parallel_size_=2, devices=jax.devices()
    )

    # ------------------------------- phase 2: ZeRO distributed ckpt/resume
    from apex_tpu.contrib.optimizers import DistributedFusedAdam

    zparams_host = init_params(config, jax.random.PRNGKey(1))
    zopt = DistributedFusedAdam(lr=1e-2, axis_name="dp")
    zstate_host = zopt.init(
        zparams_host, world_size=mesh.shape["dp"], param_specs=specs,
        axis_sizes={"tp": mesh.shape["tp"]},
    )
    zsspec = zopt.state_partition_spec()
    zparams = to_global(zparams_host, specs)
    zstate = to_global(zstate_host, zsspec)
    zstep = make_train_step(config, zopt, mesh)

    for _ in range(2):
        zparams, zstate, zloss = zstep(zparams, zstate, tokens, targets)

    ckpt_dir = out / "zero_ckpt"
    io.save_distributed_checkpoint(ckpt_dir, {"params": zparams, "state": zstate})
    multihost_utils.sync_global_devices("zero ckpt written")

    # uninterrupted continuation
    p3, s3, _ = zstep(zparams, zstate, tokens, targets)

    # restart: reassemble from the per-process shard files
    template = {
        "params": jax.tree.map(np.asarray, zparams_host),
        "state": jax.tree.map(
            lambda x: np.zeros(x.shape, x.dtype), zstate_host
        ),
    }
    # mesh-aware load: each process assembles only the slices its own
    # devices need, straight into global arrays
    restored = io.load_distributed_checkpoint(
        ckpt_dir, template, mesh=mesh,
        spec_tree={"params": specs, "state": zsspec},
    )
    rparams, rstate = restored["params"], restored["state"]
    p3r, s3r, _ = zstep(rparams, rstate, tokens, targets)

    # bit-identical resume, checked shard-by-shard on THIS process
    def assert_shards_equal(a, b, what):
        for leaf_a, leaf_b in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            for sa, sb in zip(leaf_a.addressable_shards, leaf_b.addressable_shards):
                assert sa.index == sb.index
                if not np.array_equal(np.asarray(sa.data), np.asarray(sb.data)):
                    raise AssertionError(
                        f"[worker {args.process_id}] {what} diverged after resume"
                    )

    assert_shards_equal(p3, p3r, "params")
    assert_shards_equal(s3, s3r, "optimizer state")
    (out / f"zero_ok_{args.process_id}").write_text("ok")
    print(f"[worker {args.process_id}] phase2 zero resume: bit-identical", flush=True)
    multihost_utils.sync_global_devices("done")


if __name__ == "__main__":
    main()
