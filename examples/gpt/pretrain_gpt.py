"""GPT pretraining — the flagship end-to-end composition.

The reference has no trainer of its own (SURVEY §1 L7: entry points are
users' scripts); this example is the script a Megatron/apex user would
write, re-based on apex_tpu: 3D parallelism (tp × pp × dp) over a
device mesh, optional fp16 dynamic loss scaling (the amp × parallel
flagship stack, reference ``apex/amp/handle.py:16`` +
``apex/transformer/amp/grad_scaler.py``), optional ZeRO-2 optimizer
state sharding (``DistributedFusedAdam``), Megatron batch sampling, and
async checkpoint/resume through ``apex_tpu.io``.

Runs out of the box on the virtual CPU mesh (synthetic data):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python examples/gpt/pretrain_gpt.py --tp 2 --pp 2 --steps 4
    ... --tp 2 --fp16                  # fp16 + dynamic loss scaling
    ... --tp 2 --zero                  # ZeRO-2 state sharding over dp
    ... --tp 2 --zero --grad-sync-dtype int8   # quantized grad sync
    #   (int8/fp8 wire + error-feedback residuals in the sharded state)
    ... --checkpoint /tmp/gpt_ck --steps 4   # then: --resume /tmp/gpt_ck
    ... --checkpoint /tmp/gpt_ck --auto-resume   # preemption-safe: SIGTERM
    #   saves+flushes and exits; rerunning the same line resumes from the
    #   newest valid checkpoint (torn files skipped) — apex_tpu.resilience
    ... --tp 2 --zero --checkpoint /tmp/gpt_ck --auto-resume   # ELASTIC:
    #   --zero checkpoints are per-dp-rank step_* dirs; the same command
    #   at a DIFFERENT device count (dp=4 -> dp=2) reshards the full
    #   sharded state on resume (resilience.elastic)
    ... --watchdog-secs 60   # wedged-step watchdog: drain + exit 75
    #   (EX_TEMPFAIL) for supervisor restart-with-backoff
    ... --chaos-kill-at-step 3   # pod chaos: die hard (exit 137, no save)
    ... --supervise --zero --checkpoint /tmp/gpt_ck --auto-resume   # SELF-
    #   HEALING: an outer supervisor relaunches this same command on
    #   75/137/crash with full-jitter backoff, quarantines a corrupt
    #   newest checkpoint (resume falls back one step), trips a circuit
    #   breaker (exit 76) after K no-progress failures, and prints the
    #   whole-job goodput report (apex_tpu.resilience.supervisor)
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax
import jax.numpy as jnp
import numpy as np


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--micro-batches", type=int, default=2)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--fp16", action="store_true",
                   help="float16 compute + dynamic loss scaling")
    p.add_argument("--rope", action="store_true",
                   help="rotary position embeddings (no learned table)")
    p.add_argument("--num-query-groups", type=int, default=None,
                   help="grouped-query attention: kv-head groups (1 = MQA)")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO-2: shard optimizer state over dp")
    p.add_argument("--grad-sync-dtype", default=None,
                   choices=["int8", "float8_e4m3fn", "float8_e5m2"],
                   help="quantized ZeRO gradient sync (needs --zero): the "
                        "per-bucket reduce-scatter carries int8/fp8 "
                        "payloads with per-block fp32 scales, and the "
                        "quantization error rides the optimizer state as "
                        "an error-feedback residual (checkpointed; resume "
                        "must pass the same flag)")
    p.add_argument("--sequence-parallel", action="store_true")
    p.add_argument("--remat-policy", default="full", choices=["full", "dots"],
                   help="layer remat: 'full' saves only layer inputs, "
                        "'dots' keeps matmul outputs (cheaper backward)")
    p.add_argument("--fused-ce", action="store_true",
                   help="chunked fused LM-head+CE: never materializes "
                        "the fp32 (S,B,V) logits (ops/fused_ce.py)")
    p.add_argument("--flash-attention", action="store_true",
                   help="flash attention core (ops/attention.py): the "
                        "Pallas kernels on TPU, the scan composite "
                        "elsewhere")
    p.add_argument("--family", default="gpt", choices=["gpt", "afmoe"],
                   help="the model family: 'gpt' (the flags above size "
                        "it), or 'afmoe' (models/afmoe.py: window and "
                        "full attention mixed, sandwich norms, gated "
                        "GQA, held sparse experts), sized by "
                        "--model-config; its recipe is AdamW betas "
                        "0.9/0.95, weight decay 0.1 on matrices and "
                        "none on gains, the routers' biases outside the "
                        "optimizer")
    p.add_argument("--model-config", default=None,
                   help="a published-style config.json of the family "
                        "(model_type afmoe).  A file that describes one "
                        "chip's share of an expert group gives the "
                        "router's width under 'published' and its own "
                        "num_experts is what this process holds, from "
                        "--held-start on")
    p.add_argument("--held-start", type=int, default=0,
                   help="first expert id this process holds")
    p.add_argument("--checkpoint", default=None, help="save dir (async)")
    p.add_argument("--save-every", type=int, default=4)
    p.add_argument("--keep", type=int, default=3,
                   help="multi-process: retain this many step_* dirs "
                        "(min 3 — younger dirs may still be writing)")
    p.add_argument("--data", default=None,
                   help="memmapped token file (flat binary of ids); "
                        "consumed as non-overlapping seq+1 windows. "
                        "Default: a synthetic random corpus")
    p.add_argument("--data-dtype", default="uint16",
                   choices=["uint16", "int32"],
                   help="token id dtype of --data")
    p.add_argument("--resume", default=None, help="checkpoint dir to resume")
    p.add_argument("--watchdog-secs", type=float, default=None,
                   help="step watchdog: a step exceeding this many "
                        "seconds (wedged collective, hung compile) is "
                        "declared dead — the async checkpoint queue is "
                        "drained and the process exits with the distinct "
                        "code 75 (EX_TEMPFAIL) so a supervisor restarts "
                        "with backoff; the run then resumes elastically")
    p.add_argument("--watchdog-compile-grace", type=float, default=600.0,
                   help="the FIRST step's watchdog allowance (jit "
                        "compile makes it legitimately slow)")
    p.add_argument("--chaos-kill-at-step", type=int, default=None,
                   help="chaos: die HARD (exit 137, no save, no drain) "
                        "at this loop step — the kill-one-host fault; "
                        "rerunning the same command resumes elastically")
    p.add_argument("--chaos-wedge-step", type=int, default=None,
                   help="chaos: wedge this loop step's dispatch for "
                        "--chaos-wedge-secs (pair with --watchdog-secs "
                        "to demonstrate the drain-and-exit path)")
    p.add_argument("--chaos-wedge-secs", type=float, default=120.0)
    p.add_argument("--metrics-dir", default=None,
                   help="observability sink dir (apex_tpu.observability): "
                        "device-side StepStats telemetry rides the jitted "
                        "step and is fetched ASYNCHRONOUSLY (no per-step "
                        "host sync), windows land in metrics.jsonl, a "
                        "final Prometheus snapshot in metrics.prom, and "
                        "goodput accounting (productive vs checkpoint/"
                        "restore/restart/wedge wall time, surviving "
                        "elastic restarts) in goodput_*.json + "
                        "goodput_report.json")
    p.add_argument("--telemetry-every", type=int, default=8,
                   help="StepStats fetch cadence (steps per window): the "
                        "accumulated window is handed to the async "
                        "fetcher and a fresh one swapped in — lower = "
                        "finer time series, higher = less host work")
    p.add_argument("--run-id", default="gpt",
                   help="correlation id stamped on structured logs, "
                        "metrics points, and xprof trace spans (join key "
                        "is (run_id, step))")
    p.add_argument("--trace-dir", default=None,
                   help="host-side distributed tracing + crash forensics "
                        "(apex_tpu.observability.tracing/flightrec): "
                        "spans wrap the loop's host phases (data wait, "
                        "step dispatch, telemetry fetch, checkpoint "
                        "save/restore) — never the compiled step itself "
                        "(tracing on/off is pinned to identical "
                        "lowerings and bitwise loss) — and export as "
                        "trace_<run-id>_<pid>.json (Perfetto/"
                        "chrome://tracing loadable) plus spans JSONL; a "
                        "flight recorder ring of recent spans/events/"
                        "telemetry windows dumps atomically here on "
                        "watchdog wedge, StepGuard abort, and preemption")
    p.add_argument("--trace-capacity", type=int, default=4096,
                   help="finished-span ring size (oldest dropped)")
    p.add_argument("--auto-resume", action="store_true",
                   help="preemption-safe mode (needs --checkpoint): resume "
                        "from the newest VALID checkpoint in the dir if one "
                        "exists (torn files from a killed writer are "
                        "skipped), install a SIGTERM hook that saves and "
                        "flushes before exiting, and degrade kernel compile "
                        "failures to the XLA fallback instead of dying — "
                        "the same command line works for the first launch "
                        "and every restart")
    from apex_tpu.resilience.supervisor import add_supervisor_args

    add_supervisor_args(p)
    return p.parse_args(argv)


def _afmoe_config(args):
    """``--family afmoe``: the configuration ``--model-config`` names,
    with this process's share of its experts and the trainer's flags."""
    from apex_tpu.models.afmoe import AFMoEConfig

    for flag, bad in (("--tp", args.tp > 1), ("--pp", args.pp > 1),
                      ("--zero", args.zero), ("--rope", args.rope),
                      ("--sequence-parallel", args.sequence_parallel),
                      ("--num-query-groups",
                       args.num_query_groups is not None)):
        if bad:
            raise SystemExit(f"--family afmoe does not take {flag}")
    if not args.model_config:
        raise SystemExit("--family afmoe needs --model-config")
    with open(args.model_config) as f:
        conf = json.load(f)
    return AFMoEConfig.from_published(
        conf,
        num_experts=conf.get("published", {}).get("num_experts",
                                                  conf["num_experts"]),
        held_start=args.held_start, held_count=conf["num_experts"],
        compute_dtype=jnp.float16 if args.fp16 else jnp.bfloat16,
        remat_policy=args.remat_policy,
        use_flash_attention=args.flash_attention, fused_ce=args.fused_ce,
        fused_ce_chunk=next(c for c in range(min(128, args.seq), 0, -1)
                            if args.seq % c == 0))


def main(argv=None):
    """Run the trainer; returns what the run is judged by — per-step
    losses, the device, per-device memory, the kernel-fallback registry,
    the first step's dispatch (trace + compile) seconds, the seconds of
    each backend compile of the step (one entry = compiled once), and
    ``lower`` (a thunk lowering the step the loop ran, for reading its
    compiled text)."""
    args = parse_args(argv)

    if args.supervise:
        # the self-healing outer loop: relaunch THIS command (minus the
        # supervisor flags) as a child and run the restart state
        # machine — exit-code table, crash-loop breaker, checkpoint
        # quarantine, goodput summary.  Runs before any jax backend
        # init: the parent must never hold the devices the child needs.
        from apex_tpu.resilience.supervisor import run_supervised_cli

        if not args.auto_resume and args.checkpoint:
            raise SystemExit("--supervise needs --auto-resume with "
                             "--checkpoint: a restarted child that does "
                             "not resume would retrain from step 0")
        raise SystemExit(run_supervised_cli(
            args, argv=(None if argv is None else [__file__, *argv])))

    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from apex_tpu import io, resilience
    from apex_tpu.amp import DynamicLossScaler
    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.models.gpt import (
        GPTConfig, init_params, make_pp_train_step, make_train_step,
        param_specs,
    )
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state as ps
    from apex_tpu.transformer._data import MegatronPretrainingSampler

    if args.auto_resume and not args.checkpoint:
        raise SystemExit("--auto-resume needs --checkpoint (the dir it "
                         "both resumes from and saves into)")
    if args.telemetry_every < 1:
        raise SystemExit("--telemetry-every must be >= 1 (steps per "
                         "StepStats fetch window)")

    mesh = ps.initialize_model_parallel(
        tensor_model_parallel_size_=args.tp,
        pipeline_model_parallel_size_=args.pp,
    )
    dp = mesh.shape["dp"]
    print(f"mesh: dp={dp} pp={args.pp} tp={args.tp} "
          f"({len(jax.devices())} devices)")

    family = None
    if args.family == "afmoe":
        config = _afmoe_config(args)
        family = config.train_family()
        args.vocab, args.layers = config.vocab_size, config.num_hidden_layers
        args.hidden = config.hidden_size
    else:
        config = GPTConfig(
            vocab_size=args.vocab, hidden_size=args.hidden,
            num_layers=args.layers, num_attention_heads=args.heads,
            max_seq_len=args.seq,
            compute_dtype=jnp.float16 if args.fp16 else jnp.bfloat16,
            checkpoint_layers=True,
            remat_policy=args.remat_policy,
            sequence_parallel=args.sequence_parallel,
            position_embedding_type="rope" if args.rope else "learned",
            num_query_groups=args.num_query_groups,
            use_flash_attention=args.flash_attention,
            fused_ce=args.fused_ce,
            # largest divisor of seq <= 128, so the flag always engages
            # (the gpt_loss guard silently falls back on indivisibility)
            fused_ce_chunk=next(c for c in range(min(128, args.seq), 0, -1)
                                if args.seq % c == 0),
        )
    params = (init_params(config, jax.random.PRNGKey(0)) if family is None
              else family.init_params(jax.random.PRNGKey(0)))

    def train_param_specs():
        """PartitionSpec tree for the params as the train step shards
        them — with pp, stacked layers shard over the pp mesh axis.
        The ONE place this rule lives: ZeRO init and checkpoint specs
        both consume it."""
        from jax.sharding import PartitionSpec as P

        if family is not None:
            return family.param_specs()
        specs = dict(param_specs(config))
        if args.pp > 1:
            specs["layers"] = {
                k: P("pp", *s[1:]) for k, s in specs["layers"].items()
            }
        return specs

    if args.grad_sync_dtype and not args.zero:
        raise SystemExit("--grad-sync-dtype needs --zero: the quantized "
                         "wire's error-feedback residual lives in the "
                         "ZeRO optimizer's sharded state")
    # the model layout an elastic checkpoint must match (only dp is
    # elastic: tp/pp reshape is a state-layout change)
    mesh_meta = {"tp": args.tp, "pp": args.pp}

    if args.zero:
        optimizer = DistributedFusedAdam(lr=args.lr, weight_decay=0.01,
                                         axis_name="dp",
                                         grad_sync_dtype=args.grad_sync_dtype)
        # the specs handed to init must include every model axis the
        # params shard over
        zspecs = train_param_specs()
        axis_sizes = {"tp": args.tp}
        if args.pp > 1:
            axis_sizes["pp"] = args.pp
        state = optimizer.init(params, world_size=dp, param_specs=zspecs,
                               axis_sizes=axis_sizes)
    elif family is not None:
        # the family's recipe; its state (the routers' biases, the
        # counters) is in no optimizer's tree
        optimizer = FusedAdam(
            lr=args.lr, betas=(0.9, 0.95), weight_decay=0.1,
            param_group_fn=family.weight_decay_group,
            group_hypers={"gain": {"weight_decay": 0.0}})
        state = optimizer.init(family.split(params)[0])
    else:
        optimizer = FusedAdam(lr=args.lr, weight_decay=0.01)
        state = optimizer.init(params)

    scaler = DynamicLossScaler(init_scale=2.0 ** 12) if args.fp16 else None
    scaler_state = scaler.init() if scaler else None

    # Observability (apex_tpu.observability): the StepStats window rides
    # the jitted step (device-side accumulation, donated buffers) and is
    # fetched asynchronously — the loop below has ZERO blocking device
    # reads (`float(loss)` per step is the spelling analyzer rule APX108
    # flags); even without --metrics-dir the loss print itself goes
    # through the async fetcher.  With --metrics-dir the windows feed
    # the metrics registry (JSONL time series + final Prometheus
    # snapshot) and a goodput accountant attributes checkpoint/restore/
    # restart/wedge wall time across elastic restarts.
    from apex_tpu import observability as obs
    from apex_tpu.observability import flightrec, stepstats, tracing
    from apex_tpu.observability.tracing import span

    obs.set_step_context(run_id=args.run_id, step=0)
    fetcher = stepstats.AsyncFetcher()
    # telemetry windows drive MORE than the metrics files: the harvest
    # cadence is also the flight recorder's rolling republish (the
    # hard-kill dump) and the step-time/throughput anomaly detectors —
    # so a --trace-dir-only run builds StepStats too
    telemetry = (stepstats.StepTelemetry()
                 if (args.metrics_dir or args.trace_dir) else None)
    registry = obs.get_metrics()
    # Tracing + crash forensics (--trace-dir): a host-side span per loop
    # phase, exported Perfetto-loadable at exit; the flight recorder
    # subscribes to the tracer and to every log_structured event, and
    # dumps on wedge/abort/preemption (the watchdog and StepGuard call
    # flightrec.dump_active themselves — installing the recorder is the
    # only wiring the driver owes).  The anomaly monitor watches step
    # time and window throughput whenever any observability sink is on.
    tracer = None
    if args.trace_dir:
        Path(args.trace_dir).mkdir(parents=True, exist_ok=True)
        tracer = tracing.configure(capacity=args.trace_capacity)
    flight_dir = flightrec.default_dir(metrics_dir=args.metrics_dir,
                                       trace_dir=args.trace_dir)
    recorder = None
    if flight_dir is not None:
        recorder = flightrec.install(
            flightrec.FlightRecorder(flight_dir, run_id=args.run_id))
        if tracer is not None:
            recorder.attach(tracer)
    anomaly = (obs.AnomalyMonitor()
               if (args.metrics_dir or args.trace_dir) else None)
    # multi-process: metrics files are per-rank (rank labels alone can't
    # save a last-writer-wins file clobber on a shared FS), and the
    # goodput accountant runs on process 0 ONLY — every rank shares one
    # wall clock, so folding N concurrent session records as if they
    # were sequential restarts would double-count attributed time and
    # break the fractions-sum-to-1 closure
    proc = jax.process_index()
    rank_sfx = f"_rank{proc}" if jax.process_count() > 1 else ""
    if args.metrics_dir:
        # every rank writes its own metrics files — the dir must exist
        # on every rank, not just the accountant-owning process 0
        Path(args.metrics_dir).mkdir(parents=True, exist_ok=True)
    acct = (obs.GoodputAccountant(args.metrics_dir, run_id=args.run_id)
            if args.metrics_dir and proc == 0 else None)
    metrics_jsonl = (Path(args.metrics_dir) / f"metrics{rank_sfx}.jsonl"
                     if args.metrics_dir else None)

    #: wall time of the previous StepStats harvest — the window-level
    #: step-time series the anomaly detector watches when tracing is
    #: off (the harvest follows the ASYNC fetch's completed copy, the
    #: allowed timing seam; per-dispatch host timing would be the
    #: APX112 lie)
    last_window_wall = [time.time(), 0]
    #: checkpoint-save seconds since the last drain — deducted from the
    #: window samples below (a 30s save is not step time: scoring it
    #: would fire a false step-time alert and double the supervisor's
    #: next backoff on a perfectly healthy run)
    excluded_wall = [0.0]

    def observe_window_span(at_step):
        """One anomaly sample per DRAIN batch, not per window: wall
        time since the previous drain over the steps it covered.  When
        the host runs ahead, two windows can materialize in a single
        ``fetcher.ready()`` batch sharing one arrival time — per-window
        dts would read 2x-actual for the first and ~0 for the second,
        firing false step-time/throughput alerts."""
        now, prev_step = time.time(), last_window_wall[1]
        dt = now - last_window_wall[0] - excluded_wall[0]
        excluded_wall[0] = 0.0
        if anomaly is not None and at_step > prev_step > 0 and dt > 0:
            w_steps = at_step - prev_step
            anomaly.observe("step_time", dt / w_steps)
            anomaly.observe(
                "tokens_per_sec",
                w_steps * args.global_batch * args.seq / max(dt, 1e-9))
        last_window_wall[0], last_window_wall[1] = now, at_step

    losses = []  # per step, in step order (the fetcher is FIFO)

    def emit_harvested(kind, at_step, tree):
        """Print/record one harvested async fetch (host numpy values —
        the loop never touches device scalars)."""
        if kind == "loss":
            extra = (f" scale={float(tree['scale']):.0f}"
                     if "scale" in tree else "")
            losses.append(float(tree["loss"]))
            print(f"step {at_step}: loss={losses[-1]:.4f}{extra}",
                  flush=True)
        else:  # a StepStats window
            s = stepstats.StepTelemetry.emit(registry, tree)
            if recorder is not None:
                recorder.record_stats(at_step, s)
                recorder.checkpoint()  # republish the rolling recording
            if metrics_jsonl is not None:
                registry.snapshot_jsonl(metrics_jsonl,
                                        window_end_step=at_step)
            if acct is not None:
                acct.heartbeat()
            print(f"telemetry[{at_step}]: loss_mean={s['loss_mean']:.4f} "
                  f"grad_norm={s['grad_norm_last']:.3g} "
                  f"bad={s['bad_steps']}", flush=True)

    def build_step():
        # donate_state: the loop rebinds params/state every step and the
        # async checkpointer host-snapshots at save() time, so donation
        # is safe — and saves ~3x param bytes of transient HBM.  A
        # builder (not a one-shot) so a kernel compile failure can
        # rebuild the step against the tripped fallback registry.
        if args.pp > 1:
            built = make_pp_train_step(config, optimizer, mesh,
                                       num_microbatches=args.micro_batches,
                                       loss_scaler=scaler,
                                       donate_state=True,
                                       telemetry=telemetry)
        else:
            built = make_train_step(config, optimizer, mesh,
                                    loss_scaler=scaler,
                                    donate_state=True, telemetry=telemetry)
        # dispatch-span wrapper: lives entirely OUTSIDE jit (delegates
        # lower/attrs), so the compiled program and loss/params are
        # byte/bitwise identical with tracing on or off — the
        # TestTracingTrainStep lowered pin + test_tracing parity band
        return tracing.TracedStep(built, name="train.step.dispatch")

    step = build_step()

    # Corpus: a memmapped token file (--data, the real-pretraining path:
    # the OS pages in only the rows each batch touches) or a synthetic
    # random corpus.  Either way batches assemble through the native
    # multithreaded gather_rows on a background prefetch thread.
    if args.data:
        raw = np.memmap(args.data, dtype=args.data_dtype, mode="r")
        n = len(raw) // (args.seq + 1)
        if n < args.global_batch:
            raise ValueError(
                f"--data holds {n} samples of seq+1={args.seq + 1} tokens; "
                f"need at least one global batch ({args.global_batch})")
        corpus = raw[: n * (args.seq + 1)].reshape(n, args.seq + 1)
    else:
        corpus = np.random.RandomState(0).randint(
            0, args.vocab, size=(4096, args.seq + 1))
    start_step = 0

    multiproc = jax.process_count() > 1

    def ckpt_tree(params, state, step, scaler_state):
        return {
            "params": params,
            "state": state,
            "step": np.int64(step),
            "scaler": scaler.state_dict(scaler_state) if scaler else None,
        }

    def ckpt_specs():
        """The training-time PartitionSpec tree for everything saved —
        the same specs the train step shards with, NOT inferred from
        array shardings (freshly-initialized params are unsharded, so
        introspection would silently restore everything replicated)."""
        from jax.sharding import PartitionSpec as P

        pspecs = train_param_specs()
        if args.zero:
            sspec = optimizer.state_partition_spec()
        else:
            # a family's optimizer holds its trainable part alone
            ospecs = pspecs if family is None else family.split(pspecs)[0]
            sspec = type(state)(
                step=P(), exp_avg=ospecs, exp_avg_sq=ospecs,
                master=ospecs if state.master is not None else None,
            )
        scaler_spec = (
            jax.tree.map(lambda _: P(), scaler.state_dict(scaler_state))
            if scaler else None
        )
        return {"params": pspecs, "state": sspec, "step": P(),
                "scaler": scaler_spec}

    ckpt = io.AsyncCheckpointer() if args.checkpoint else None
    # ONE run controller for every mode: it owns the per-step protocol
    # (watchdog heartbeat + chaos delivery — wired further down once
    # those are built) and, for --zero single-process runs, the elastic
    # checkpointing (save + bounded-disk prune, restore-or-fresh with
    # cross-world resharding).  Multiproc keeps the per-process
    # distributed save path below.
    run_ctl = resilience.ElasticRunController(
        args.checkpoint, optimizer, world_size=dp, mesh_axes=mesh_meta,
        checkpointer=ckpt, keep=args.keep)

    # --resume points at a dir and fails loudly if nothing valid is
    # there; --auto-resume resumes from --checkpoint when it holds a
    # valid checkpoint and silently starts fresh otherwise (first
    # launch and post-preemption restart share one command line).
    t_restore = time.time()
    resume_dir = args.resume or (args.checkpoint if args.auto_resume
                                 else None)
    ck = None
    if resume_dir:
        if multiproc:
            # pod-scale restore: every process reads only the pieces its
            # own devices need (lazy shard files, no host materializes
            # the full state).  Per-step directories: an interrupted
            # save can only leave an INCOMPLETE newest dir, never a torn
            # mix of steps.  Process 0 picks the newest complete dir and
            # broadcasts it so the whole pod resumes the same step even
            # if a shared FS shows processes different file listings;
            # load errors (template/shape mismatch) propagate loudly.
            from jax.experimental import multihost_utils

            def newest_complete():
                try:
                    return io.latest_distributed_step(resume_dir)
                except io.AllCheckpointsTornError:
                    # encode over the broadcast so every process raises
                    # together instead of peers hanging in the collective
                    return -2

            chosen = newest_complete() if jax.process_index() == 0 else 0
            chosen = int(multihost_utils.broadcast_one_to_all(
                np.int64(chosen)))
            if chosen == -2 or (chosen < 0 and args.resume):
                # -2: step_* dirs EXIST but none is fully published —
                # prior progress would be silently discarded, so loud
                # even under --auto-resume (the single-process
                # AllCheckpointsTornError invariant, pod-scale)
                raise FileNotFoundError(
                    f"no complete checkpoint under {resume_dir}" +
                    (": step_* dirs exist but none is fully published; "
                     "refusing to silently restart from step 0"
                     if chosen == -2 else ""))
            if chosen >= 0:
                ck = io.load_distributed_checkpoint(
                    Path(resume_dir) / f"step_{chosen:08d}",
                    ckpt_tree(params, state, 0, scaler_state),
                    mesh=mesh, spec_tree=ckpt_specs())
        elif args.zero:
            # ELASTIC resume (apex_tpu.resilience.elastic): --zero runs
            # checkpoint as per-dp-rank step_* dirs whose index.json
            # records the saved world layout.  A dp=4 checkpoint resumes
            # at dp=2 (or dp=8) in this same command line: the sharded
            # optimizer state — m/v, masters/remainders, error-feedback
            # residuals — reshards through the bucket plan's one
            # padded_total formula; params/scaler ride rank 0's shard.
            # AllCheckpointsTornError (dirs exist, none complete) stays
            # loud even under --auto-resume.
            restored = resilience.restore_elastic_checkpoint(
                resume_dir, optimizer=optimizer, world_size=dp,
                mesh_axes=mesh_meta)
            if restored is None and args.resume:
                raise FileNotFoundError(
                    f"no elastic checkpoint under {resume_dir}")
            if restored is not None:
                params = restored.params
                state = restored.opt_state
                start_step = restored.step
                if scaler is not None:
                    if restored.scaler is None:
                        raise ValueError(
                            f"checkpoint in {resume_dir} has no "
                            "loss-scaler state (saved by a run without "
                            "--fp16); resume without --fp16 or point at "
                            "a matching run's dir")
                    scaler_state = scaler.load_state_dict(restored.scaler)
                msg = f"resumed at step {start_step}"
                if restored.resharded:
                    msg += (f" (elastic reshard: dp={restored.saved_world}"
                            f" -> dp={dp})")
                print(msg, flush=True)
        else:
            # torn-file-safe discovery: a file the preempted writer was
            # killed inside (bad header, short blob) is skipped with a
            # warning; only a VALID checkpoint is ever loaded
            try:
                path = io.latest_checkpoint(resume_dir)
            except io.AllCheckpointsTornError:
                # candidates EXISTED but every one failed validation:
                # prior progress would be silently discarded by a fresh
                # start — loud even under --auto-resume
                raise
            except FileNotFoundError:
                if args.resume:
                    raise  # explicit --resume with nothing valid: loud
                if any(Path(resume_dir).glob("step_*/index.json")):
                    # the dir holds ELASTIC step dirs (a --zero run's
                    # layout): silently starting fresh would discard
                    # that progress — name the flag mismatch instead
                    raise ValueError(
                        f"{resume_dir} holds elastic step_* checkpoints "
                        "(saved by a --zero run); resume with --zero or "
                        "point at a matching run's dir")
                path = None  # --auto-resume first launch: fresh start
            if path is not None:
                ck = io.load_checkpoint(path)
                ck = jax.tree.map(jnp.asarray, ck)
    if ck is not None:
        params = ck["params"]
        # the checkpoint restores the saved pytree structure, so a
        # checkpoint from a different optimizer fails loudly in update()
        state = ck["state"]
        start_step = int(ck["step"])
        if scaler is not None:
            if ck.get("scaler") is None:
                # checkpoints from a non---fp16 run carry no scaler
                # state (one dir mixing runs with different precision
                # flags hits this); fail with the mismatch, not a
                # NoneType subscript deep inside load_state_dict
                raise ValueError(
                    f"checkpoint in {resume_dir} has no loss-scaler "
                    "state (saved by a run without --fp16); resume "
                    "without --fp16 or point at a matching run's dir")
            scaler_state = scaler.load_state_dict(ck["scaler"])
        print(f"resumed at step {start_step}")
    if acct is not None and start_step:
        # goodput: restore (incl. any elastic reshard) is attributable
        # downtime, not productive time
        acct.add_segment("restore", time.time() - t_restore)
    if tracer is not None and resume_dir and start_step:
        # retro-emit (both endpoints known): the restore/reshard phase
        # as its own track in the trace
        tracer.emit("train.checkpoint_restore", t_restore,
                    time.time() - t_restore, resumed_step=start_step)

    mb_size = args.global_batch  # sampler yields global batches here

    def epoch_cycling_batches(consumed):
        """Megatron sampling with epoch wrap: the sampler is
        single-epoch by design (reference _batchsampler.py), so restart
        it from zero each time the corpus is exhausted."""
        consumed %= (len(corpus) // mb_size) * mb_size
        while True:
            it = MegatronPretrainingSampler(
                total_samples=len(corpus), consumed_samples=consumed,
                micro_batch_size=mb_size,
                data_parallel_rank=0, data_parallel_size=1,
            )
            yield from it
            consumed = 0

    sampler = epoch_cycling_batches(start_step * args.global_batch)

    # batch assembly off the training thread: the native multithreaded
    # gather_rows pulls the sampled rows (reference's DataLoader-worker
    # role; on a memmap corpus only the touched rows page in), a
    # depth-2 prefetch queue keeps it a step ahead of the device.
    # Token ids validate per batch — exactly the rows about to train —
    # so a bad id anywhere in --data fails loudly instead of wrapping
    # through the embedding lookup (the prefetch worker's exception
    # re-raises on the training thread).
    def assemble(idx):
        batch = io.native.gather_rows(corpus, np.asarray(idx))
        if args.data:
            lo, hi = int(batch.min()), int(batch.max())
            if lo < 0 or hi >= args.vocab:
                raise ValueError(
                    f"--data batch has token id "
                    f"{lo if lo < 0 else hi} outside [0, vocab={args.vocab})")
        return batch.astype(np.int32)

    prefetch = io.PrefetchIterator(sampler, size=2, transform=assemble)

    # SIGTERM (Cloud TPU preemption notice) -> finish the current step,
    # save, flush the async queue, exit 0; the same command resumes.
    pre = resilience.PreemptionHandler().install() if args.auto_resume \
        else None

    # chaos faults armed from the CLI (the one-command reproduction of
    # the pod-scale scenarios: kill-one-host, wedged step)
    chaos_monkey = None
    if args.chaos_kill_at_step is not None or args.chaos_wedge_step is not None:
        chaos_monkey = resilience.ChaosMonkey(resilience.ChaosPlan.make(
            kill_at=({0: args.chaos_kill_at_step}
                     if args.chaos_kill_at_step is not None else None),
            wedge_step_at=args.chaos_wedge_step,
            wedge_step_seconds=args.chaos_wedge_secs,
        ))

    # step watchdog: a wedged step (hung collective, hung compile) gets
    # one structured log, a bounded drain of the async queue, and the
    # distinct exit 75 so a supervisor restarts with backoff
    def on_wedge(info):
        """Watchdog pre-exit hook (best-effort, each piece its own
        job): force the step-time anomaly alert (the wedged dispatch
        never returns, so no ordinary observation will ever see it),
        persist the anomaly record + a final metrics snapshot so the
        counter increment survives the os._exit, and stamp the goodput
        session wedged.  The watchdog itself dumps the flight recorder
        right AFTER this hook — so the alert is IN the dump."""
        for piece in (
            (lambda: (anomaly.wedge(info.get("elapsed_s"),
                                    step=info.get("step")),
                      anomaly.persist(args.metrics_dir or args.trace_dir)))
                if anomaly is not None else None,
            (lambda: registry.snapshot_jsonl(metrics_jsonl, wedged=True))
                if metrics_jsonl is not None else None,
            (lambda: tracing.export_run(args.trace_dir, args.run_id,
                                        tracer))
                if tracer is not None else None,
            # goodput: stamp the session wedged BEFORE os._exit so the
            # report can attribute the lost tail per cause
            (lambda: acct.finalize("wedge")) if acct is not None else None,
        ):
            if piece is None:
                continue
            try:
                piece()
            except Exception:  # noqa: BLE001 — one broken sink must not
                pass           # rob the others (the watchdog still exits)

    watchdog = None
    if args.watchdog_secs is not None:
        watchdog = resilience.StepWatchdog(
            args.watchdog_secs, checkpointer=ckpt, preemption=pre,
            first_deadline_sec=args.watchdog_compile_grace,
            on_wedge=on_wedge)
        watchdog.start()
    # the controller's on_step drives both from here on
    run_ctl.watchdog = watchdog
    run_ctl.chaos = chaos_monkey

    def preempt_agreed():
        """Every process must take the same break-or-continue decision:
        one host seeing SIGTERM while another enters the next step would
        deadlock that step's cross-host collectives (and produce a
        partial step_* dir only some processes wrote).  A host-side
        allgather of the local flag per step is cheap next to a train
        step; single-process runs skip it."""
        if not multiproc:
            return pre.preempted
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.int8(pre.preempted))
        return bool(np.max(flags))

    def save_at(tree, step_no):
        t0 = time.time()
        try:
            with span("train.checkpoint_save", save_step=step_no):
                if acct is None:
                    return _save_at(tree, step_no)
                with acct.attribute("checkpoint"):
                    return _save_at(tree, step_no)
        finally:
            excluded_wall[0] += time.time() - t0

    def _save_at(tree, step_no):
        if multiproc:
            # each process snapshots + writes only its addressable
            # shards (non-addressable global arrays never hit host);
            # one directory per step keeps every published
            # checkpoint internally consistent
            ckpt.save_distributed(
                Path(args.checkpoint) / f"step_{step_no:08d}", tree)
            if jax.process_index() == 0:
                # bounded disk: drop dirs older than the newest
                # --keep.  The async queue holds ≤2 pending saves
                # per process, so anything older than the 3 newest
                # is fully published on every process — with the
                # default keep=3 a prune can never race a write.
                import shutil

                old = sorted(Path(args.checkpoint).glob("step_*"))
                for d in old[:-max(args.keep, 3)]:
                    shutil.rmtree(d, ignore_errors=True)
        elif args.zero:
            # elastic per-dp-rank step dir via the run controller:
            # index first (an interrupted save leaves an incomplete dir
            # the resume side skips as torn), shard snapshots on the
            # async queue, bounded disk via the controller's prune;
            # resume at a DIFFERENT dp reshards
            run_ctl.save(step_no, tree["params"], tree["state"],
                         scaler_state=tree["scaler"])
        else:
            # step-named files (atomic publish) so a preempted restart
            # picks the newest VALID one; same bounded-disk pruning
            ckpt.save(Path(args.checkpoint) / f"step_{step_no:08d}.ckpt",
                      tree)
            old = sorted(Path(args.checkpoint).glob("step_*.ckpt"))
            for f in old[:-max(args.keep, 3)]:
                try:
                    f.unlink()
                except OSError:
                    pass

    stats = telemetry.init() if telemetry is not None else None
    window_steps = 0  # host-side: steps accumulated since the last fetch

    def run_step(tokens, targets):
        nonlocal step
        step_args = [params, state]
        if scaler is not None:
            step_args.append(scaler_state)
        if stats is not None:
            step_args.append(stats)
        step_args = (*step_args, tokens, targets)
        if not args.auto_resume or multiproc:
            # fail-fast: without --auto-resume, kernel compile errors
            # surface to the operator (the degrade-and-rebuild retry
            # below is part of the --auto-resume contract, see --help).
            # Multi-process ALWAYS fails fast: a kernel error on ONE
            # host (flaky chip) tripping only that host's registry would
            # rebuild it on the scan fallback — whose collective count
            # differs per chunk from the kernel's — deadlocking every
            # peer inside the step's collectives.  The peers are stuck
            # device-side, so no host-level agreement (the
            # preempt_agreed pattern) can run here; a clean job-level
            # crash + --auto-resume restart is the recoverable path.
            return step(*step_args)
        # one rebuild per registered kernel: each retry's fresh trace can
        # surface the NEXT kernel's deferred compile error (the kernels
        # have never been proven on real chips — several failing at once
        # is the expected first-contact mode, and each has a fallback)
        from apex_tpu.resilience.fallback import KERNELS

        for _ in range(len(KERNELS) + 1):
            try:
                return step(*step_args)
            except Exception as e:  # noqa: BLE001 — kernel failures only
                # a Mosaic/Pallas failure is DEFERRED to the first call
                # of the jitted step: attribute it, trip the fallback
                # registry, rebuild — the fresh trace lowers the XLA
                # reference impl
                tripped = resilience.trip_from_exception(e)
                if not tripped:
                    raise
                if any(getattr(x, "is_deleted", lambda: False)()
                       for tree in step_args
                       for x in jax.tree.leaves(tree)):
                    # the failure surfaced AFTER execution started: the
                    # donated params/state buffers are gone, so a retry
                    # would read deleted arrays — restart from the
                    # checkpoint instead of a confusing secondary crash
                    raise RuntimeError(
                        "kernel failure after the step consumed its "
                        "donated inputs; rerun to resume from the last "
                        "checkpoint (the fallback registry is tripped "
                        f"for: {', '.join(tripped)})") from e
                print(f"kernel failure ({', '.join(tripped)}); rebuilt "
                      f"the step on the XLA fallback impl", flush=True)
                step = build_step()
        return step(*step_args)

    if not multiproc:
        # lay params and optimizer state out as the step shards them
        # BEFORE the first call: fresh (or restored) arrays sit unsharded
        # on device 0, step 0's outputs come back sharded, and a step 1
        # fed those would compile the whole step a second time
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        def placed(tree, spec_tree):
            return jax.device_put(tree, jax.tree.map(
                lambda spec: NamedSharding(mesh, spec), spec_tree,
                is_leaf=lambda x: isinstance(x, P)))

        specs = ckpt_specs()
        params = placed(params, specs["params"])
        state = placed(state, specs["state"])
        # the small replicated carries ride the same rule
        if scaler_state is not None:
            scaler_state = jax.device_put(scaler_state,
                                          NamedSharding(mesh, P()))
        if stats is not None:
            stats = jax.device_put(stats, NamedSharding(mesh, P()))

    # every backend compile of the train step, in seconds: one entry is
    # the contract (a second means the step's inputs changed layout or
    # shape under the loop)
    step_compile_s = []

    def on_compile(event, duration, fun_name=None, **_):
        if event.endswith("backend_compile_duration") \
                and fun_name == f"jit({step.__name__})":
            step_compile_s.append(round(duration, 2))

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    t0 = time.time()
    last_saved = None
    done = 0
    first_step_s = None
    for i in range(start_step, start_step + args.steps):
        done = i - start_step + 1
        # heartbeat + chaos delivery (wedge: the watchdog fires
        # mid-sleep; kill: hard exit 137, no drain); the first
        # iteration's allowance covers the jit compile
        run_ctl.on_step(i, deadline=(args.watchdog_compile_grace
                                     if i == start_step else None))
        obs.set_step_context(step=i)
        with span("train.data_wait"):
            batch = next(prefetch)
        tokens = jnp.asarray(batch[:, :-1])
        targets = jnp.asarray(batch[:, 1:])
        t_dispatch = time.time()
        out = run_step(tokens, targets)
        if first_step_s is None:
            # jit traces and compiles inside the first call, before the
            # async dispatch returns: this is the step's set-up time
            first_step_s = time.time() - t_dispatch
        params, state = out[0], out[1]
        k = 2
        if scaler is not None:
            scaler_state = out[k]
            k += 1
        if stats is not None:
            stats = out[k]
            k += 1
            window_steps += 1
        loss = out[-1]
        # the ASYNC telemetry seam: hand the device scalars to the
        # fetcher (starts a non-blocking copy) and print whatever
        # earlier steps have materialized — zero blocking host reads in
        # this loop (analyzer rule APX108 pins the spelling)
        push = {"loss": loss}
        if scaler is not None:
            push["scale"] = scaler_state.loss_scale
        fetcher.put("loss", i, push)
        if acct is not None:
            acct.step_done(tokens=args.global_batch * args.seq)
        if telemetry is not None \
                and (i + 1 - start_step) % args.telemetry_every == 0:
            # fetch the accumulated window, swap in a fresh one placed
            # like the old (the stats buffers are donated AND the jit
            # cache keys on shardings)
            fetcher.put("stats", i + 1, stats._asdict())
            stats = telemetry.init_like(stats)
            window_steps = 0
        harvested = fetcher.ready()
        if harvested:
            with span("train.telemetry_fetch", harvested=len(harvested)):
                for kind, at_step, tree in harvested:
                    emit_harvested(kind, at_step, tree)
                batch_stats = [s for k, s, _ in harvested if k == "stats"]
                if batch_stats:
                    observe_window_span(batch_stats[-1])
        if ckpt and (i + 1) % args.save_every == 0:
            save_at(ckpt_tree(params, state, i + 1, scaler_state), i + 1)
            last_saved = i + 1
        if pre is not None and preempt_agreed():
            if ckpt and last_saved != i + 1:
                save_at(ckpt_tree(params, state, i + 1, scaler_state),
                        i + 1)
            if ckpt:
                pre.drain(ckpt)  # every accepted save is durable
            print(f"preempted ({pre.reason or 'peer process'}) after "
                  f"step {i}; rerun the same command to resume",
                  flush=True)
            break
    jax.monitoring.unregister_event_duration_listener(on_compile)
    if watchdog is not None:
        watchdog.stop()  # the loop is done; the queue flush below may
        # legitimately outlast a step deadline
    # final async harvest: the tail window plus any loss lines still in
    # flight (blocking is correct here — the run is over)
    if telemetry is not None and stats is not None and window_steps > 0:
        fetcher.put("stats", start_step + done, stats._asdict())
    flushed = fetcher.flush()
    for kind, at_step, tree in flushed:
        emit_harvested(kind, at_step, tree)
    tail_stats = [s for k, s, _ in flushed if k == "stats"]
    if tail_stats:
        observe_window_span(tail_stats[-1])
    if ckpt:
        t_close = time.time()
        ckpt.close()
        if acct is not None:
            acct.add_segment("checkpoint", time.time() - t_close)
        print(f"checkpoint: {args.checkpoint}")
    if args.metrics_dir:
        (Path(args.metrics_dir) / f"metrics{rank_sfx}.prom").write_text(
            registry.prometheus_text())
    if acct is not None:  # process 0 owns the goodput record
        from apex_tpu.observability import goodput as gp

        acct.finalize("preempted" if (pre is not None and pre.preempted)
                      else "clean")
        n_params = gp.param_count(params)
        report = gp.goodput_report(
            args.metrics_dir,
            flops_per_token=gp.model_flops_per_token(
                n_params, args.layers, args.seq, args.hidden))
        (Path(args.metrics_dir) / "goodput_report.json").write_text(
            json.dumps(report, indent=1))
        print("goodput: " + " ".join(
            f"{k}={v:.1%}" for k, v in sorted(report["fractions"].items())),
            flush=True)
    if anomaly is not None:
        anomaly.persist(args.metrics_dir or args.trace_dir)
        counts = anomaly.counts()
        if counts:
            print("anomalies: " + " ".join(
                f"{k}={v}" for k, v in sorted(counts.items())), flush=True)
    if tracer is not None:
        exp = tracing.export_run(args.trace_dir, args.run_id, tracer)
        print(f"trace: {args.trace_dir} ({exp['events']} events, "
              f"{exp['dropped']} dropped)", flush=True)
    dt = time.time() - t0
    print(f"{done} steps in {dt:.1f}s "
          f"({args.global_batch * args.seq * done / dt:.0f} tokens/s)")
    # where the run is judged: the device it ran on and whether any
    # kernel degraded to its reference along the way
    from apex_tpu.utils.platform import device_facts, device_memory

    result = {"steps": done, "losses": losses,
              "first_step_s": first_step_s,
              "step_compile_s": step_compile_s,
              "device": device_facts(),
              "memory": device_memory(params=params, opt_state=state),
              "kernel_fallback": resilience.get_registry().status()}
    for key in ("step_compile_s", "device", "memory", "kernel_fallback"):
        print(f"{key}: " + json.dumps(result[key]), flush=True)
    final_args = [params, state]
    if scaler is not None:
        final_args.append(scaler_state)
    if stats is not None:
        final_args.append(stats)
    result["lower"] = lambda: step.lower(*final_args, tokens, targets)
    return result


if __name__ == "__main__":
    main()  # the returned summary is for callers; it is printed above
