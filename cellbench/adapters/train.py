"""The training adapter: builds what ``examples/gpt/pretrain_gpt.main``
builds, in its order, and drives the step it would drive.

It cannot call ``main``: ``main`` runs a fixed ``--steps``, keeps the
step in closures and hands out no step times (listed in ``PERF.md`` for
the ``tracing`` issue).  So the construction below is main's, line for
line where it matters: ``GPTConfig`` as the CLI fills it, the mesh from
``parallel_state``, the optimizer, params and state placed as the step
shards them BEFORE the first call, ``make_train_step(..., donate_state=
True)`` wrapped in ``tracing.TracedStep``, batches through
``io.PrefetchIterator``.  Two things differ on purpose: the weights are
the benchmark's own, made on the device from ``--seed`` in one jitted
call (``cellbench/weights.py``), and the optimizer state is made under
one ``jit`` instead of op by op.
"""

import math
import threading
import time
from typing import Dict

import numpy as np

from cellbench import arith, loadgen, weights
from cellbench.adapters import common, layout
from cellbench.reference import gpt2 as reference

FOLLOWED_STEPS = 3      # the reference follows the first three steps
# Steps the host may run ahead of the device.  Two were enough to keep
# the chip busy (idle 0.07%) until the host was held up: in one set of six
# runs three lost 1 to 4 of 161 steps (chip runs of PR 23), on a machine
# whose CPU cores are shared.  Six steps are 1.9 s of queued work.
IN_FLIGHT = 6


#: a leaf whose reference first gradient is under this share of the
#: median leaf's has no gradient to speak of (the key bias: softmax
#: ignores a shift common to all keys).  Adam divides such a gradient by
#: its own size, so the leaf's UPDATE is rounding noise at full step
#: size in the program and nothing in float32: the parameter-change
#: comparison leaves it out (its gradient is still compared).
NO_GRADIENT = 1e-4


def _leaf_gap(prog: Dict[str, float], ref: Dict[str, float], skip=()):
    """Worst leaf by |program's norm - reference's norm| over the larger
    of that leaf's reference norm and the median leaf's (some gradients
    are all but zero).  Returns (gap, leaf, the three worst)."""
    med = float(np.median([ref[k] for k in ref]))
    gaps = sorted(((abs(prog[k] - r) / max(r, med), k)
                   for k, r in ref.items() if k not in skip), reverse=True)
    return gaps[0][0], gaps[0][1], gaps[:3]


def run(env) -> Dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from apex_tpu import io
    from apex_tpu.models.gpt import GPTConfig, make_train_step, param_specs
    from apex_tpu.observability import tracing
    from apex_tpu.observability.tracing import span
    from apex_tpu.transformer import parallel_state as ps

    cell, log = env["cell"], env["log"]
    conf, mix = cell["config_file"], cell["traffic_file"]
    args = conf["cellbench"]["args"]
    limits = conf["cellbench"]["correct"]
    s = weights.sizes(conf)
    seq, gb, chips = int(args["seq"]), int(mix["global_batch"]), cell["chips"]
    key = weights.seed_key(env["seed"])
    if env["trace"]:
        tracing.configure(capacity=1 << 16)
    phases = common.Phases(env["t_setup_start"])
    phases.mark("imports of the program")

    # ---- what pretrain_gpt.main builds, in its order
    mesh = ps.initialize_model_parallel(
        tensor_model_parallel_size_=1, pipeline_model_parallel_size_=1,
        devices=jax.devices()[:chips])
    dp = mesh.shape["dp"]
    config = GPTConfig(
        vocab_size=s["V"], hidden_size=s["H"], num_layers=s["L"],
        num_attention_heads=s["heads"], max_seq_len=seq,
        ffn_hidden_size=s["F"], layernorm_eps=conf["layer_norm_epsilon"],
        compute_dtype=jnp.dtype(args["compute_dtype"]),
        checkpoint_layers=True, remat_policy=args["remat_policy"],
        position_embedding_type="learned",
        use_flash_attention=bool(args["flash_attention"]),
        fused_ce=bool(args["fused_ce"]),
        fused_ce_impl=args.get("fused_ce_impl"),
        fused_ce_chunk=next(c for c in range(min(128, seq), 0, -1)
                            if seq % c == 0))
    shard = lambda spec_tree: jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
    pspecs = dict(param_specs(config))
    born = jax.jit(
        lambda k: layout.to_program_tree(weights.gpt2_weights(conf, k)),
        out_shardings=shard(pspecs))
    params = born(key)
    jax.block_until_ready(params)
    phases.mark("weights")

    hyper = dict(lr=float(mix["lr"]), weight_decay=args["weight_decay"],
                 betas=tuple(args["betas"]), eps=args["eps"])
    if args["zero"]:
        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        optimizer = DistributedFusedAdam(axis_name="dp", **hyper)
        state = optimizer.init(params, world_size=dp, param_specs=pspecs,
                               axis_sizes={"tp": 1})
        sspec = optimizer.state_partition_spec()
        state = jax.device_put(state, shard(sspec))
    else:
        from apex_tpu.optimizers import FusedAdam

        optimizer = FusedAdam(**hyper)
        sspec = jax.eval_shape(optimizer.init, params)._replace(
            step=P(), exp_avg=pspecs, exp_avg_sq=pspecs)
        state = jax.jit(optimizer.init, out_shardings=shard(sspec))(params)
    step = tracing.TracedStep(
        make_train_step(config, optimizer, mesh, loss_scaler=None,
                        donate_state=True, telemetry=None),
        name="train.step.dispatch")
    jax.block_until_ready(state)
    phases.mark("optimizer state")

    stop = threading.Event()

    def batches():
        for b in loadgen.generator(mix).batches(mix, s["V"], seq,
                                                 env["seed"]):
            if stop.is_set():
                return
            yield b

    prefetch = io.PrefetchIterator(batches(), size=int(mix["prefetch"]))
    compiles = common.CompileWatch()

    def one_step():
        """The window's own call and feed (main's loop body)."""
        nonlocal params, state
        with span("train.data_wait"):
            batch = next(prefetch)
        tokens = jnp.asarray(batch[:, :-1])
        targets = jnp.asarray(batch[:, 1:])
        out = step(params, state, tokens, targets)
        params, state = out[0], out[1]
        return out[-1]

    # ---- the first steps: warm-up, and what the reference follows
    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    first_grad = first_grad_norms = None
    if not args["zero"]:   # a ZeRO state holds flat shards: not read yet
        # the first gradient as the optimizer got it: Adam's exp_avg
        # after one step is (1 - beta1) times it
        grad_of = jax.jit(lambda st: jax.tree.map(
            lambda m: m / (1.0 - hyper["betas"][0]), st.exp_avg))
        norms_of = jax.jit(lambda g: jax.tree.map(norm, g))
    delta_norms_of = jax.jit(lambda p, k: jax.tree.map(
        lambda a, b: norm(a - b), p,
        layout.to_program_tree(weights.gpt2_weights(conf, k))))
    first_losses = []
    for i in range(FOLLOWED_STEPS):
        first_losses.append(one_step())
        if i == 0:
            first_losses[0].block_until_ready()
            phases.mark("first step (compile or cache read)")
        if i == 0 and not args["zero"]:
            g = grad_of(state)
            first_grad_norms = jax.device_get(norms_of(g))
            # kept on the host: the step leaves no room on the chip
            first_grad = jax.device_get(g)
            del g
            phases.mark("first gradient read back")
    delta_norms = jax.device_get(delta_norms_of(params, key))
    first_losses = [float(x) for x in jax.device_get(first_losses)]
    phases.mark("steps 2 and 3, parameter change")
    lowered = step.lower(
        params, state, jnp.zeros((gb, seq), jnp.int32),
        jnp.zeros((gb, seq), jnp.int32))
    mem = lowered.compile().memory_analysis()
    step_bytes = common.program_bytes(mem)
    jax.block_until_ready((params, state))
    phases.mark("step's memory analysis")
    log(phases.line())

    # ---- the window
    wt = env["window_trace"]
    seconds = env["seconds"]
    losses, pending = [], []
    traced_steps = 0
    compiles.start()
    t0 = time.monotonic()
    setup_s = time.time() - env["t_setup_start"]
    while True:
        age = time.monotonic() - t0
        if age >= seconds:
            break
        if wt.should_start(age):
            jax.block_until_ready(pending)
            wt.start()
        elif wt.should_stop(age):
            jax.block_until_ready(pending)
            wt.stop()
        loss = one_step()
        losses.append(loss)
        pending.append(loss)
        if wt.running:
            traced_steps += 1
        if len(pending) > IN_FLIGHT:
            pending.pop(0).block_until_ready()
    jax.block_until_ready((params, state, pending))
    t1 = time.monotonic()
    if wt.running:
        wt.stop()
    compiles.stop()
    stop.set()

    window_s = t1 - t0
    losses = [float(x) for x in jax.device_get(losses)]
    steps = len(losses)
    failed = sum(1 for x in losses if not math.isfinite(x))
    tokens_per_s = steps * gb * seq / window_s
    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    alloc_peak = max((st.get("peak_bytes_in_use", 0) for st in stats),
                     default=0)
    host_spans = (tracing.get_tracer().spans() if env["trace"] else [])
    log(f"train: {steps} steps in {window_s:.3f} s, first losses "
        f"{first_losses}, last loss {losses[-1] if losses else None}, "
        f"step memory {step_bytes / 1e9:.2f} GB "
        f"(arguments {mem.argument_size_in_bytes / 1e9:.2f}, temporaries "
        f"{mem.temp_size_in_bytes / 1e9:.2f}), allocator peak "
        f"{alloc_peak / 1e9:.2f} GB")

    # ---- free the program's state, then the reference follows
    del params, state, step, lowered, pending
    prog_grad = (None if first_grad_norms is None else {
        k: float(v) for k, v in
        layout.published_names(first_grad_norms).items()})
    prog_delta = {k: float(v) for k, v in
                  layout.published_names(delta_norms).items()}
    checks = follow(conf, mix, args, env["seed"], key, first_losses,
                    prog_grad, prog_delta, first_grad, limits,
                    quant=env.get("control"))
    ok = common.judge(checks, {
        "kernels tripped": common.tripped_kernels(),
        "compiles in the window": compiles.durations}, log)

    n_params = arith.gpt2_param_count(conf)
    return {
        "correct": ok, "attempted": steps, "failed": failed,
        "setup_s": setup_s,
        "e2e": {"train_tokens_per_s": tokens_per_s},
        "memory_peak_bytes": int(max(alloc_peak, step_bytes)),
        "host_spans": host_spans,
        "counters": {
            "traced_steps": traced_steps, "steps": steps,
            "step_hbm_GB": step_bytes / 1e9,
            "flops_per_token": arith.model_flops_per_token(
                n_params, s["L"], seq, s["H"]),
            "n_params": n_params, "window_s": window_s,
        },
        "checks": checks,
    }


def follow(conf, mix, args, seed, key, prog_losses, prog_grad, prog_delta,
           prog_first_grad, limits, quant=None):
    """Run the plain reference through the first steps and return the
    numbers compared as (name, value, limit).  ``prog_first_grad`` is the
    program's first gradient, a host tree in the program's layout.  With
    ``quant`` the reference is computed at that lower precision and
    takes the PROGRAM's place (the control): it is then compared with
    the plain reference, and the program's readings are ignored."""
    import jax
    import jax.numpy as jnp

    s = weights.sizes(conf)
    seq = int(args["seq"])
    rows = loadgen.generator(mix).first_batches(mix, s["V"], seq, seed,
                                                FOLLOWED_STEPS)
    batches = [(jnp.asarray(b[:, :-1]), jnp.asarray(b[:, 1:])) for b in rows]
    make = jax.jit(lambda k: weights.gpt2_weights(conf, k))
    norms = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))
    kw = dict(lr=float(mix["lr"]), beta1=args["betas"][0],
              beta2=args["betas"][1], eps_adam=args["eps"],
              weight_decay=args["weight_decay"],
              ln_eps=conf["layer_norm_epsilon"],
              rows_per_block=int(args.get("reference_rows_per_block", 2)))
    floats = lambda tree: {k: float(v) for k, v in layout.flatten_published(
        jax.device_get(tree)).items()}

    def readings(q, **more):
        out = reference.train_steps(make(key), batches, s["heads"],
                                    quant=q, **kw, **more)
        delta = norms(out.pop("params"), make(key))
        return ([float(x) for x in jax.device_get(out["losses"])],
                floats(out["first_grad_norms"]), floats(delta), out)

    other = None
    if quant is not None:
        prog_losses, prog_grad, prog_delta, low = readings(
            quant, keep_first_grad=True)
        other = low["first_grad"]
    elif prog_first_grad is not None:
        other = jax.device_put(layout.to_published_tree(prog_first_grad))
    ref_losses, ref_grad, ref_delta, ref = readings(
        None, other_first_grad=other)
    del other
    checks = [(f"loss gap, step {i + 1}", abs(p - r), limits["loss_abs"])
              for i, (p, r) in enumerate(zip(prog_losses, ref_losses))]
    med = float(np.median(list(ref_grad.values())))
    skip = {k for k, g in ref_grad.items() if g < NO_GRADIENT * med}
    if prog_grad is not None:
        gap, leaf, worst = _leaf_gap(prog_grad, ref_grad)
        checks.append((f"first-gradient norm gap, worst leaf ({leaf})",
                       gap, limits["grad_norm_gap"]))
        print(f"train: first-gradient gaps, worst three {worst}", flush=True)
        diff = floats(ref["first_grad_diff_norms"])
        med = float(np.median(list(ref_grad.values())))
        gap, leaf = max((d / max(ref_grad[k], med), k)
                        for k, d in diff.items() if k not in skip)
        checks.append((f"first-gradient difference, norm over the "
                       f"reference's norm, worst leaf ({leaf})", gap,
                       limits["grad_diff"]))
    gap, leaf, worst = _leaf_gap(prog_delta, ref_delta, skip)
    checks.append((f"parameter-change norm gap after "
                   f"{FOLLOWED_STEPS} steps, worst leaf ({leaf}; without "
                   f"{sorted(skip)}, whose reference gradient is nil)",
                   gap, limits["delta_norm_gap"]))
    print(f"train: parameter-change gaps, worst three {worst}", flush=True)
    return checks
