"""Compile each cell's programs for a described ``v5e:2x2`` without a
chip, and print XLA's memory analysis: ``python -m cellbench.rehearse
[train:<config>:<mix>:<chips> | serve:<config>]...`` (with no argument, the
committed cells' programs; a configuration with ``"zero": true`` on four
chips compiles the ZeRO dp=4 step).

libtpu builds a compile-only v5e client from a topology name, so the
whole XLA:TPU + Mosaic compile runs in a sandbox with no accelerator.
This proves compilation and memory fit, never a time or a result.
Run with ``JAX_PLATFORMS=cpu``; it is a script to run by hand, not a
test (it loads the TPU library at its top level).
"""

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.update(TPU_ACCELERATOR_TYPE="v5litepod-4",
                  TPU_WORKER_HOSTNAMES="localhost", TPU_SKIP_MDS_QUERY="1")
ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def _report(name, compiled):
    from apex_tpu.analysis.lowered import pallas_kernels
    from cellbench.adapters.common import program_bytes

    m = compiled.memory_analysis()
    total = program_bytes(m)
    kernels = sorted(set(pallas_kernels(compiled)))
    print(json.dumps({
        "program": name,
        "arguments_GB": round(m.argument_size_in_bytes / 1e9, 3),
        "temporaries_GB": round(m.temp_size_in_bytes / 1e9, 3),
        "outputs_GB": round(m.output_size_in_bytes / 1e9, 3),
        "aliased_GB": round(m.alias_size_in_bytes / 1e9, 3),
        "total_GB": round(total / 1e9, 3), "kernels": kernels}), flush=True)


def _conf(name):
    with open(ROOT / "cellbench" / "configs" / f"{name}.json") as f:
        return json.load(f)


def _mix(name):
    with open(ROOT / "cellbench" / "traffic" / f"{name}.json") as f:
        return json.load(f)


def train(devices, conf_name, mix_name):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.gpt import GPTConfig, make_train_step, param_specs
    from cellbench import weights
    from cellbench.adapters import layout

    conf, mix = _conf(conf_name), _mix(mix_name)
    args = conf["cellbench"]["args"]
    s = weights.sizes(conf)
    seq, gb = int(args["seq"]), int(mix["global_batch"])
    mesh = Mesh(np.array(devices).reshape(len(devices), 1, 1),
                ("dp", "pp", "tp"))
    config = GPTConfig(
        vocab_size=s["V"], hidden_size=s["H"], num_layers=s["L"],
        num_attention_heads=s["heads"], max_seq_len=seq,
        compute_dtype=jnp.dtype(args["compute_dtype"]),
        checkpoint_layers=True, remat_policy=args["remat_policy"],
        use_flash_attention=True, fused_ce=True, fused_ce_chunk=128)
    pspecs = dict(param_specs(config))
    shard = lambda tree: jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), tree,
        is_leaf=lambda x: isinstance(x, P))
    shapes = jax.eval_shape(lambda k: layout.to_program_tree(
        weights.gpt2_weights(conf, k)), jax.random.PRNGKey(0))
    with_sh = lambda tree, sh: jax.tree.map(
        lambda a, b: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=b),
        tree, sh)
    params = with_sh(shapes, shard(pspecs))
    hyper = dict(lr=float(mix["lr"]), weight_decay=args["weight_decay"])
    if args["zero"]:
        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        opt = DistributedFusedAdam(axis_name="dp", **hyper)
        # the ZeRO init reads its arguments on the host, so it cannot be
        # traced for shapes: run it on zeros on this sandbox's CPU
        real = opt.init(jax.tree.map(
            lambda a: np.zeros(a.shape, a.dtype), shapes),
            world_size=len(devices), param_specs=pspecs,
            axis_sizes={"tp": 1})
        state_shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), real)
        del real
        sspec = opt.state_partition_spec()
    else:
        from apex_tpu.optimizers import FusedAdam

        opt = FusedAdam(**hyper)
        state_shapes = jax.eval_shape(opt.init, shapes)
        sspec = state_shapes._replace(step=P(), exp_avg=pspecs,
                                      exp_avg_sq=pspecs)
    state = with_sh(state_shapes, shard(sspec))
    step = make_train_step(config, opt, mesh, donate_state=True)
    tok = jax.ShapeDtypeStruct((gb, seq), jnp.int32,
                               sharding=NamedSharding(mesh, P("dp", None)))
    _report(f"{conf_name} step, {len(devices)} chip(s), batch {gb}",
            step.lower(params, state, tok, tok).compile())


def serve(device, conf_name, pages=(16, 64, 128)):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from apex_tpu.inference import DecodeConfig, KVCacheConfig
    from apex_tpu.inference.decode import make_decode_step, make_prefill
    from apex_tpu.inference.kv_cache import alloc_pools
    from apex_tpu.models.gpt import GPTConfig
    from cellbench import weights
    from cellbench.adapters import layout

    conf = _conf(conf_name)
    args = conf["cellbench"]["args"]
    s = weights.sizes(conf)
    one = SingleDeviceSharding(device)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    config = GPTConfig(
        vocab_size=s["V"], hidden_size=s["H"], num_layers=s["L"],
        num_attention_heads=s["heads"], max_seq_len=s["P"],
        position_embedding_type="learned", compute_dtype=jnp.bfloat16,
        checkpoint_layers=False)
    shapes = jax.eval_shape(lambda k: layout.to_program_tree(
        weights.gpt2_weights(conf, k)), jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), shapes)
    B = int(args["max_batch"])
    for page in pages:
        pps = -(-int(args["max_context"]) // page)
        dcfg = DecodeConfig(
            cache=KVCacheConfig(num_pages=1 + B * pps, page_size=page,
                                pages_per_seq=pps, dtype=jnp.bfloat16),
            max_batch=B, max_prompt_len=int(args["max_prompt_len"]),
            temperature=float(args["temperature"]),
            top_k=int(args["top_k"]), attn_impl="pallas",
            sample_impl="pallas")
        pools = jax.tree.map(
            lambda a: sds(a.shape, a.dtype),
            jax.eval_shape(lambda: alloc_pools(
                config.num_layers, config.kv_heads, config.head_dim,
                dcfg.cache)))
        i32 = lambda *shape: sds(shape, jnp.int32)
        dec = make_decode_step(config, dcfg).lower(
            params, pools, i32(B), i32(B), sds((B,), jnp.bool_),
            i32(B, pps), sds((B,), jnp.uint32))
        _report(f"{conf_name} decode step, page {page}", dec.compile())
        pre = make_prefill(config, dcfg).lower(
            params, pools, i32(1, dcfg.max_prompt_len), i32(), i32(),
            i32(pps), sds((), jnp.uint32))
        _report(f"{conf_name} prefill, page {page}", pre.compile())


def main(argv):
    """Each argument is ``train:<config>:<mix>:<chips>`` or
    ``serve:<config>``; with none, the committed cells' programs."""
    from jax.experimental import topologies

    import apex_tpu.utils.platform as platform

    # "auto" kernel impls ask on_tpu(), which sees this sandbox's CPU
    platform.on_tpu = lambda: True
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    for what in argv or ["train:gpt2-medium-train:steady-b8:1",
                         "serve:gpt2-large-serve"]:
        kind, *rest = what.split(":")
        if kind == "train":
            conf, mix, chips = rest
            train(devices[:int(chips)], conf, mix)
        elif kind == "serve":
            serve(devices[0], rest[0])
        else:
            raise SystemExit(f"cellbench.rehearse: cannot read {what!r}")


if __name__ == "__main__":
    main(sys.argv[1:])
