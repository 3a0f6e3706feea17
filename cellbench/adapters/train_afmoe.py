"""The training adapter of the ``afmoe`` family: builds what
``examples/gpt/pretrain_gpt.main --family afmoe`` builds, in its order,
and drives the step it would drive.

As ``adapters/train.py`` (and for its reason: ``main`` runs a fixed
``--steps`` and hands out no step times), the construction below is
main's: ``AFMoEConfig.from_published`` of the configuration file with
this chip's share of the experts, the mesh from ``parallel_state``, the
family's recipe in ``FusedAdam`` (per leaf; gains without decay) over
the family's TRAINABLE tree, ``make_train_step(config, ...,
donate_state=True)`` wrapped in ``tracing.TracedStep``, batches through
``io.PrefetchIterator``.  Two things differ on purpose: the weights are
the benchmark's own, made on the device from ``--seed`` in one jitted
call (``cellbench/weights_afmoe.py``), and the optimizer state is made
under one ``jit``.

``correct`` is decided after the window: the same compiled step runs
the first three steps and the window, and the plain reference
(``cellbench/reference/afmoe.py``) follows those three steps from the
same seed.  Compared: the loss of steps 1-3; the first gradient, by
leaf, as norm gap and as difference, dense leaves and expert leaves
(routed experts and routers: what a flipped choice of expert moves)
each under limits of their own; the parameter change after three steps;
the per-expert load of step 1 (share of assignments that differ); the
routers' biases after three steps; and the assignments computed here
over the three steps against the reference's count (none dropped).

``control``: ``"float8_e4m3fn"`` (any precision the reference rounds
to) puts the reference at that precision in the program's place;
``"no_balance_update"`` runs the program with the balance rule's step
at 0, a step that skips the bias update.
"""

import math
import threading
import time
from typing import Dict

import numpy as np

from cellbench import loadgen, weights, weights_afmoe
from cellbench.adapters import common
from cellbench.adapters.train import IN_FLIGHT, _leaf_gap
from cellbench.reference import afmoe as reference

FOLLOWED_STEPS = 3


def program_config(conf: Dict, args: Dict, **overrides):
    """The family's configuration as ``pretrain_gpt --family afmoe``
    builds it from the file."""
    import jax.numpy as jnp

    from apex_tpu.models.afmoe import AFMoEConfig

    seq = int(args["seq"])
    kw = dict(
        num_experts=weights_afmoe.router_width(conf),
        held_start=int(conf["cellbench"].get("held_start", 0)),
        held_count=conf["num_experts"],
        compute_dtype=jnp.dtype(args["compute_dtype"]),
        param_dtype=jnp.dtype(args["param_dtype"]),
        remat_policy=args["remat_policy"],
        use_flash_attention=bool(args["flash_attention"]),
        attn_impl=args.get("attn_impl", "auto"),
        fused_ce=bool(args["fused_ce"]),
        fused_ce_impl=args.get("fused_ce_impl"),
        fused_ce_chunk=next(c for c in range(min(128, seq), 0, -1)
                            if seq % c == 0),
        expert_impl=args.get("expert_impl", "auto"))
    kw.update(overrides)
    return AFMoEConfig.from_published(conf, **kw)


def run(env) -> Dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from apex_tpu import io
    from apex_tpu.models import afmoe
    from apex_tpu.models.gpt import make_train_step
    from apex_tpu.observability import tracing
    from apex_tpu.observability.tracing import span
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state as ps

    cell, log = env["cell"], env["log"]
    conf, mix = cell["config_file"], cell["traffic_file"]
    args = conf["cellbench"]["args"]
    limits = conf["cellbench"]["correct"]
    seq, gb, chips = int(args["seq"]), int(mix["global_batch"]), cell["chips"]
    key = weights.seed_key(env["seed"])
    control = env.get("control")
    if env["trace"]:
        tracing.configure(capacity=1 << 16)
    phases = common.Phases(env["t_setup_start"])
    phases.mark("imports of the program")

    # ---- what pretrain_gpt.main builds for the family, in its order
    mesh = ps.initialize_model_parallel(
        tensor_model_parallel_size_=1, pipeline_model_parallel_size_=1,
        devices=jax.devices()[:chips])
    config = program_config(conf, args, **(
        {"load_balance_coeff": 0.0} if control == "no_balance_update"
        else {}))
    family = config.train_family()
    shard = lambda spec_tree: jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
    pspecs = family.param_specs()

    def born(k):
        tree = weights_afmoe.to_program_tree(
            weights_afmoe.weights(conf, k), conf)
        return family.merge(tree, afmoe.init_state(config))

    params = jax.jit(born, out_shardings=shard(pspecs))(key)
    jax.block_until_ready(params)
    phases.mark("weights")

    hyper = dict(lr=float(mix["lr"]), weight_decay=args["weight_decay"],
                 betas=tuple(args["betas"]), eps=args["eps"])
    optimizer = FusedAdam(
        **hyper, param_group_fn=family.weight_decay_group,
        group_hypers={"gain": {"weight_decay": 0.0}},
        use_buckets=bool(args.get("use_buckets", False)))
    trainable_specs = family.split(pspecs)[0]
    sspec = jax.eval_shape(optimizer.init, family.split(params)[0])._replace(
        step=P(), exp_avg=trainable_specs, exp_avg_sq=trainable_specs)
    state = jax.jit(lambda p: optimizer.init(family.split(p)[0]),
                    out_shardings=shard(sspec))(params)
    step = tracing.TracedStep(
        make_train_step(config, optimizer, mesh, loss_scaler=None,
                        donate_state=True, telemetry=None),
        name="train.step.dispatch")
    jax.block_until_ready(state)
    phases.mark("optimizer state")

    stop = threading.Event()

    def batches():
        for b in loadgen.generator(mix).batches(mix, conf["vocab_size"], seq,
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
    # the first gradient as the optimizer got it: Adam's exp_avg after
    # one step is (1 - beta1) times it
    norms_of_grad = jax.jit(lambda st: jax.tree.map(
        lambda m: norm(m) / (1.0 - hyper["betas"][0]), st.exp_avg))
    delta_norms_of = jax.jit(lambda p, k: jax.tree.map(
        lambda a, b: norm(a - b), family.split(p)[0],
        weights_afmoe.to_program_tree(weights_afmoe.weights(conf, k), conf)))
    first_losses = []
    first_grad = first_grad_norms = first_load = None
    for i in range(FOLLOWED_STEPS):
        first_losses.append(one_step())
        if i == 0:
            first_losses[0].block_until_ready()
            phases.mark("first step (compile or cache read)")
            first_grad_norms = jax.device_get(norms_of_grad(state))
            # kept on the host: the step leaves no room on the chip
            scale = 1.0 / (1.0 - hyper["betas"][0])
            first_grad = jax.tree.map(lambda m: np.asarray(m) * scale,
                                      jax.device_get(state.exp_avg))
            first_load = np.asarray(params["state"]["last_load"])
            phases.mark("first gradient read back")
    delta_norms = jax.device_get(delta_norms_of(params, key))
    first_losses = [float(x) for x in jax.device_get(first_losses)]
    followed = jax.device_get(params["state"])
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
    # the device-side counters: read before the window and at its close
    closing = np.asarray(params["state"]["counters"])
    in_window = dict(zip(afmoe.COUNTER_NAMES,
                         (closing - followed["counters"]).tolist()))
    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    alloc_peak = max((st.get("peak_bytes_in_use", 0) for st in stats),
                     default=0)
    host_spans = (tracing.get_tracer().spans() if env["trace"] else [])
    log(f"train: {steps} steps in {window_s:.3f} s, first losses "
        f"{first_losses}, last loss {losses[-1] if losses else None}, "
        f"step memory {step_bytes / 1e9:.2f} GB "
        f"(arguments {mem.argument_size_in_bytes / 1e9:.2f}, temporaries "
        f"{mem.temp_size_in_bytes / 1e9:.2f}), allocator peak "
        f"{alloc_peak / 1e9:.2f} GB, window counters {in_window}")

    # ---- free the program's state, then the reference follows
    del params, state, step, lowered, pending
    numbers = lambda tree: {k: float(v) for k, v in weights_afmoe.to_published(
        tree, conf, transpose=False).items()}
    program = {
        "losses": first_losses, "grad_norms": numbers(first_grad_norms),
        "delta_norms": numbers(delta_norms),
        "first_grad": weights_afmoe.to_published(first_grad, conf),
        "first_load": first_load, "biases": followed["router_bias"],
        "held": int(followed["counters"][
            afmoe.COUNTER_NAMES.index("moe_assignments_held")])}
    del first_grad
    checks = follow(conf, mix, args, env["seed"], key, program, limits,
                    quant=(control if control != "no_balance_update"
                           else None))
    ok = common.judge(checks, {
        "kernels tripped": common.tripped_kernels(),
        "compiles in the window": compiles.durations}, log)

    return {
        "correct": ok, "attempted": steps, "failed": failed,
        "setup_s": setup_s,
        "e2e": {"train_tokens_per_s": tokens_per_s},
        "memory_peak_bytes": int(max(alloc_peak, step_bytes)),
        "host_spans": host_spans,
        "counters": {
            "traced_steps": traced_steps, "steps": steps,
            "step_hbm_GB": step_bytes / 1e9, "window_s": window_s,
            "tokens_per_step": gb * seq,
            "moe_layers": config.num_moe_layers,
            "experts_held": len(config.held),
            **{k: v for k, v in in_window.items() if k != "steps"},
        },
        "checks": checks,
    }


def follow(conf, mix, args, seed, key, program, limits, quant=None):
    """Run the plain reference through the first steps and return the
    numbers compared as (name, value, limit).  ``program``: the
    program's readings (losses, per-leaf norms under the published
    names, its first gradient as a host tree in the published layout,
    the load of step 1, the biases after the last step, the assignments
    it computed).  With ``quant`` the reference is computed at that
    lower precision and takes the PROGRAM's place (the control): it is
    then compared with the plain reference, and the program's readings
    are ignored."""
    import jax
    import jax.numpy as jnp

    seq = int(args["seq"])
    rows = loadgen.generator(mix).first_batches(
        mix, conf["vocab_size"], seq, seed, FOLLOWED_STEPS)
    batches = [(jnp.asarray(b[:, :-1]), jnp.asarray(b[:, 1:])) for b in rows]
    make = jax.jit(lambda k: weights_afmoe.weights(conf, k))
    norms = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))
    held_start = int(conf["cellbench"].get("held_start", 0))
    held = slice(held_start, held_start + conf["num_experts"])
    kw = dict(lr=float(mix["lr"]), beta1=args["betas"][0],
              beta2=args["betas"][1], eps_adam=args["eps"],
              weight_decay=args["weight_decay"], held_start=held_start)
    floats = lambda tree: {k: float(v) for k, v in
                           jax.device_get(tree).items()}

    def readings(q, **more):
        out = reference.train_steps(make(key), batches, conf, quant=q,
                                    **kw, **more)
        delta = floats(norms(out.pop("params"), make(key)))
        loads = np.asarray(jax.device_get(out["loads"]))
        return {"losses": [float(x) for x in jax.device_get(out["losses"])],
                "grad_norms": floats(out["first_grad_norms"]),
                "delta_norms": delta, "first_load": loads[0],
                "biases": np.asarray(out["biases"]),
                "held": int(loads[:, :, held].sum()),
                "first_grad": out["first_grad"],
                "diff": out["first_grad_diff_norms"]}

    if quant is not None:
        program = readings(quant, keep_first_grad=True)
    ref = readings(None, other_first_grad=program["first_grad"])
    program["first_grad"] = None

    checks = [(f"loss gap, step {i + 1}", abs(p - r), limits["loss_abs"])
              for i, (p, r) in enumerate(zip(program["losses"],
                                             ref["losses"]))]
    diff = floats(ref["diff"])
    med = float(np.median(list(ref["grad_norms"].values())))
    groups = (("dense leaves", "", lambda k: not weights_afmoe.is_expert(k)),
              ("expert leaves", "_experts", weights_afmoe.is_expert))
    for label, suffix, member in groups:
        mine = lambda d: {k: v for k, v in d.items() if member(k)}
        other = {k for k in ref["grad_norms"] if not member(k)}
        gap, leaf, worst = _leaf_gap(program["grad_norms"],
                                     ref["grad_norms"], skip=other)
        checks.append((f"first-gradient norm gap, {label}, worst leaf "
                       f"({leaf})", gap, limits["grad_norm_gap" + suffix]))
        print(f"train: first-gradient gaps, {label}, worst three {worst}",
              flush=True)
        gap, leaf = max((d / max(ref["grad_norms"][k], med), k)
                        for k, d in mine(diff).items())
        checks.append((f"first-gradient difference, norm over the "
                       f"reference's norm, {label}, worst leaf ({leaf})",
                       gap, limits["grad_diff" + suffix]))
        gap, leaf, worst = _leaf_gap(program["delta_norms"],
                                     ref["delta_norms"], skip=other)
        checks.append((f"parameter-change norm gap after {FOLLOWED_STEPS} "
                       f"steps, {label}, worst leaf ({leaf})", gap,
                       limits["delta_norm_gap" + suffix]))
        print(f"train: parameter-change gaps, {label}, worst three {worst}",
              flush=True)
    # the load of step 1 by layer and expert: an assignment that went
    # elsewhere is one too few here and one too many there
    moved = np.abs(program["first_load"].astype(np.int64)
                   - ref["first_load"]).sum(-1) / 2
    share = float((moved / ref["first_load"].sum(-1)).max())
    checks.append(("per-expert load of step 1, share of assignments that "
                   "differ, worst layer", share, limits["load_share"]))
    step = conf["load_balance_coeff"] * FOLLOWED_STEPS
    checks.append((f"router bias after {FOLLOWED_STEPS} steps, mean "
                   f"difference over {FOLLOWED_STEPS} steps of the rule",
                   float(np.abs(program["biases"] - ref["biases"]).mean()
                         / step), limits["bias_gap"]))
    checks.append((f"assignments computed here over {FOLLOWED_STEPS} steps "
                   f"({program['held']}) against the reference's count "
                   f"({ref['held']}), relative",
                   abs(program["held"] - ref["held"]) / ref["held"],
                   limits["held_count_gap"]))
    return checks
