"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls,
at the full width of three models the repo supports (depth included;
weights random from a seed), on whatever TPU JAX finds:

- the trainer: ``examples/gpt/pretrain_gpt.main`` at GPT-345M (L24 H1024
  heads16 V50304 S1024, global batch 8, bf16, FusedAdam) with every
  training kernel on (flash attention, Pallas LayerNorm, Pallas fused
  LM-head+CE) for 8 steps; then one forward+backward at the same shapes
  with the kernels on against the same call on the reference paths;
- what the third family adds to the training path: the flash kernels
  under a sliding window (forward, dq, dkv; GQA 16:2, head 128) against
  the scan twin, and the trainable expert layer's chunk walk (megablox
  ``gmm``/``tgmm``, 16 of 128 experts held) against ``ragged_dot``'s own
  derivative;
- the server: GPT-124M (L12 H768 heads12 V50304, rope, bf16 KV, page 16)
  through ``ContinuousBatchingScheduler`` as ``serve_gpt.main`` builds
  it, kernels forced (``attn_impl="pallas"``, ``sample_impl="pallas"``),
  16 requests once greedy and once ``temperature=1, top_k=40``; then
  decode-step logits against the training forward;
- the second served family: latent attention and held experts
  (``models/mla_moe.py``) at GigaChat3.1-702B-A36B's published widths,
  1 dense + 1 expert layer, 16 of 256 experts held, through the same
  ``serve_gpt.build_scheduler``: 12 greedy requests, the one-pool
  ``apex_kv_write`` and ``apex_mla_decode_attention`` among the decode
  step's kernels, decode logits against the family's full forward.

It refuses to start without a TPU, never sets ``JAX_PLATFORMS``, and
runs everything in this one process (a chip belongs to one process).
Every check raises: a failed phase ends the run with a traceback, a
non-zero exit code and no result line.  A passing run ends with two
lines: ``summary: {...}`` (per-phase results, versions, compile-cache
directory and entry counts; it ends with ``"claim": null`` because
nothing here is a performance claim — the times are set-up seconds for
orientation), and last the result line, one JSON object with exactly
these keys::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

    python chip_smoke.py
"""

import dataclasses
import gc
import json
import math
import os
import sys
import time
from importlib import metadata
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "examples" / "gpt"))
sys.path.insert(0, str(ROOT))

VOCAB = 50304
TRAIN_ARGV = ["--layers", "24", "--hidden", "1024", "--heads", "16",
              "--vocab", str(VOCAB), "--seq", "1024", "--global-batch", "8",
              "--steps", "8", "--flash-attention", "--fused-ce"]
TRAIN_KERNELS = {"apex_flash_fwd", "apex_flash_dq", "apex_flash_dkv",
                 "apex_ln_fwd", "apex_ln_bwd", "apex_fused_ce_fwd",
                 "apex_fused_ce_dx", "apex_fused_ce_dembed"}
SERVE_ARGV = ["--streams", "8", "--requests", "16", "--prompt-len", "128",
              "--max-new", "64", "--attn-impl", "pallas",
              "--sample-impl", "pallas"]  # widths: serve_gpt's 124M defaults
SERVE_KERNELS = {"apex_decode_attention", "apex_kv_write", "apex_fused_sample",
                 "apex_ln_fwd"}
# the second served family (latent attention, held experts) at its
# published widths, cut to 1 dense + 1 expert layer: 3.3 GB of bf16
LATENT_CONFIG = ROOT / "cellbench" / "configs" \
    / "gigachat3.1-702b-a36b-serve-ep16.json"
LATENT_ARGV = ["--streams", "8", "--requests", "12", "--prompt-len", "256",
               "--max-new", "24", "--page-size", "128",
               "--prefill-buckets", "128", "--temperature", "0",
               "--attn-impl", "pallas", "--sample-impl", "pallas"]
LATENT_KERNELS = {"apex_mla_decode_attention", "apex_kv_write",
                  "apex_fused_sample"}

# Kernel-vs-reference bounds for one bf16 forward+backward of GPT-345M.
# Both sides round activations to bf16 at every op and differ only in
# reduction order (online softmax over key blocks vs one einsum; vocab
# tiles vs one dense head), so they agree to bf16 rounding (2^-8 = 0.4%
# per op), averaged down over 8,192 tokens for the loss and over 355M
# elements for the gradient.  Measured on a v5e (my chip run, PR 21):
# loss diff 4.8e-6, grad-norm rel diff 4.9e-4, whole-gradient rel L2
# error 1.4e-2, worst leaf (wq) 1.8e-2.  The inputs are seeded, so the
# bounds' 4-10x of room is for another compiler version, not for noise.
# A wrong kernel is off by O(1) in at least one leaf.
LOSS_ATOL = 1e-3
GRAD_NORM_RTOL = 5e-3
GRAD_REL_L2 = 5e-2          # whole gradient
GRAD_LEAF_REL_L2 = 0.1      # any one parameter leaf that carries gradient

# Decode-step logits vs the training forward at 124M in bf16 with a bf16
# cache: the kernel reads k/v rounded once more through the cache dtype
# and sums pages in a different order.  tests/test_inference.py allows
# rtol 0.05 + atol 0.1 at toy width; the logits here are O(1) and the
# measured max abs diff on a v5e is 0.025 (my chip run, PR 21).
LOGITS_ATOL = 0.15
# a drawn token may sit below the reference's eligible set (the argmax
# when greedy, the top-k otherwise) only by a logit gap the kernel's bf16
# head cannot resolve; measured 0.0
SAMPLE_LOGIT_EPS = 0.05


def check(ok, message):
    if not ok:
        raise RuntimeError(f"chip_smoke: {message}")


def no_trip(status, where):
    tripped = {k: v["error"] for k, v in status.items() if v["tripped"]}
    check(not tripped, f"{where}: kernels degraded to their reference: "
                       f"{tripped}")


def kernels_in(lowered, want, where):
    """The proof that the executable of ``lowered`` (the step a phase
    just ran: compiling it again is a cache hit) holds every kernel in
    ``want``, read from its compiled text."""
    from apex_tpu.analysis.lowered import pallas_kernels

    found = pallas_kernels(lowered.compile())
    check(want <= set(found), f"{where}: compiled step lacks "
                              f"{sorted(want - set(found))}; has {found}")
    return {"compiled_kernels": sorted(set(found)),
            "custom_calls": len(found)}


# ------------------------------------------------------------------ trainer
def trainer_phase(extra_argv=()):
    import pretrain_gpt

    t0 = time.time()
    res = pretrain_gpt.main([*TRAIN_ARGV, *extra_argv])
    losses = res["losses"]
    out = {"losses": [round(x, 4) for x in losses],
           "setup_s": round(res["first_step_s"], 1),
           "step_compile_s": res["step_compile_s"],
           "wall_s": round(time.time() - t0, 1),
           "memory": res["memory"]}
    print("trainer: " + json.dumps(out), flush=True)
    check(len(losses) == 8 and all(math.isfinite(x) for x in losses),
          f"trainer: want 8 finite losses, got {losses}")
    check(abs(losses[0] - math.log(VOCAB)) < 0.5,
          f"trainer: first loss {losses[0]} is not within 0.5 of "
          f"ln({VOCAB}) = {math.log(VOCAB):.2f}")
    check(len(res["step_compile_s"]) == 1,
          f"trainer: the step compiled {len(res['step_compile_s'])} times "
          f"({res['step_compile_s']} s)")
    no_trip(res["kernel_fallback"], "trainer")
    out.update(kernels_in(res["lower"](), TRAIN_KERNELS, "trainer"))
    out["ok"] = True
    return out


def parity_phase():
    """One forward+backward at the trainer's shapes, kernels on, against
    the same call on the reference paths (einsum attention, jnp
    LayerNorm, dense head)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.analysis.lowered import pallas_kernels
    from apex_tpu.models.gpt import GPTConfig, gpt_loss, init_params
    from apex_tpu.resilience.fallback import get_registry

    kernels = GPTConfig(
        vocab_size=VOCAB, hidden_size=1024, num_layers=24,
        num_attention_heads=16, max_seq_len=1024,
        compute_dtype=jnp.bfloat16, use_flash_attention=True,
        fused_ce=True)
    reference = dataclasses.replace(kernels, use_flash_attention=False,
                                    fused_ce=False)
    params = init_params(kernels, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, VOCAB, size=(8, 1024)), jnp.int32)
    targets = jnp.asarray(rng.randint(0, VOCAB, size=(8, 1024)), jnp.int32)

    def compiled(config):
        fn = jax.jit(jax.value_and_grad(
            lambda p: gpt_loss(p, tokens, targets, config)))
        return fn.lower(params).compile()

    t0 = time.time()
    kern = compiled(kernels)
    # the Pallas LayerNorm has no impl argument: its documented off
    # switch is read while tracing, so it is set around this one trace
    os.environ["APEX_TPU_PALLAS_NORM"] = "0"
    try:
        ref = compiled(reference)
    finally:
        del os.environ["APEX_TPU_PALLAS_NORM"]
    setup_s = time.time() - t0
    check(TRAIN_KERNELS <= set(pallas_kernels(kern)),
          "parity: the kernel side lacks a kernel")
    check(not pallas_kernels(ref),
          f"parity: the reference side holds kernels {pallas_kernels(ref)}")
    no_trip(get_registry().status(), "parity")

    loss_k, grad_k = kern(params)
    loss_r, grad_r = ref(params)

    @jax.jit
    def compare(gk, gr):
        f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
        norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x)))
        total = lambda t: jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                       for x in jax.tree.leaves(t)))
        gk, gr = f32(gk), f32(gr)
        diff = jax.tree.map(jnp.subtract, gk, gr)
        return (total(gk), total(gr), total(diff),
                jax.tree.map(norm, gr), jax.tree.map(norm, diff))

    norm_k, norm_r, dist, leaf_ref, leaf_diff = jax.device_get(
        compare(grad_k, grad_r))
    # per-leaf error, over the leaves that carry gradient: a leaf whose
    # true gradient is zero (the key bias: softmax ignores a shift
    # common to all keys) is rounding noise on both sides
    leaves = {
        jax.tree_util.keystr(path): (float(d), float(r))
        for (path, d), r in zip(
            jax.tree_util.tree_leaves_with_path(leaf_diff),
            jax.tree.leaves(leaf_ref))}
    print("parity leaves (|diff|, |ref|): " + json.dumps(
        {k: [round(d, 6), round(r, 6)] for k, (d, r) in leaves.items()}),
        flush=True)
    rel = {k: d / r for k, (d, r) in leaves.items() if r >= 1e-3 * norm_r}
    worst = max(rel, key=rel.get)
    out = {"loss_kernels": float(loss_k), "loss_reference": float(loss_r),
           "grad_norm_kernels": float(norm_k),
           "grad_norm_reference": float(norm_r),
           "grad_rel_l2": float(dist / norm_r),
           "worst_leaf": worst, "worst_leaf_rel_l2": rel[worst],
           "setup_s": round(setup_s, 1)}
    print("parity: " + json.dumps(out), flush=True)
    check(math.isfinite(out["loss_kernels"])
          and abs(out["loss_kernels"] - out["loss_reference"]) <= LOSS_ATOL,
          f"parity: loss {out['loss_kernels']} vs reference "
          f"{out['loss_reference']} (atol {LOSS_ATOL})")
    check(abs(norm_k - norm_r) <= GRAD_NORM_RTOL * norm_r,
          f"parity: grad norm {norm_k} vs reference {norm_r} "
          f"(rtol {GRAD_NORM_RTOL})")
    check(out["grad_rel_l2"] <= GRAD_REL_L2,
          f"parity: gradient rel L2 error {out['grad_rel_l2']} "
          f"> {GRAD_REL_L2}")
    check(rel[worst] <= GRAD_LEAF_REL_L2,
          f"parity: leaf {worst} rel L2 error {rel[worst]} "
          f"> {GRAD_LEAF_REL_L2}")
    out["ok"] = True
    return out


# ------------------------------------- window kernels, trainable experts
# The flash kernels under a sliding window against the scan twin, and the
# trainable expert layer's chunk walk (megablox gmm / tgmm under their
# custom_vjp) against ``ragged_dot``'s own derivative: bf16 inputs, the
# same arithmetic in another order on both sides.  Bounds as the parity
# phase's (a wrong mask or a wrong group is off by O(1) in a leaf);
# measured on a v5e (my chip run, PR 38): window 1.05e-3, experts 2.0e-5.
WINDOW_REL_L2 = 5e-2
EXPERTS_REL_L2 = 5e-2


def window_experts_phase():
    """Forward and all three gradients of (a) windowed GQA flash
    attention, kernels against the scan twin, at 2 x 16:2 heads of 128
    over 2,048 positions with a window of 512, and (b)
    ``held_experts_ffn(buffer_rows=...)`` over 2,048 tokens, 16 of 128
    experts held, the Pallas grouped matmuls against ``ragged_dot``."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.analysis.lowered import pallas_kernels
    from apex_tpu.ops.attention import flash_attention
    from apex_tpu.transformer.expert_parallel import (
        expert_buffer_rows, held_experts_ffn,
    )

    bf16 = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 12)
    t0 = time.time()

    def rel_l2(got, want):
        f32 = lambda x: np.asarray(x, np.float32)
        num = sum(float(np.sum((f32(a) - f32(b)) ** 2)) for a, b in zip(
            jax.tree.leaves(got), jax.tree.leaves(want)))
        den = sum(float(np.sum(f32(b) ** 2)) for b in jax.tree.leaves(want))
        return math.sqrt(num / den)

    # (a) the band in fwd, dq and dkv
    q = jax.random.normal(ks[0], (2, 16, 2048, 128), bf16)
    k = jax.random.normal(ks[1], (2, 2, 2048, 128), bf16)
    v = jax.random.normal(ks[2], (2, 2, 2048, 128), bf16)
    g = jax.random.normal(ks[3], (2, 16, 2048, 128), bf16)

    def attend(impl):
        fn = jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, window=512, impl=impl).astype(jnp.float32)
                * g.astype(jnp.float32)), (0, 1, 2)))
        return fn.lower(q, k, v).compile()

    kern, twin = attend("pallas"), attend("scan")
    check({"apex_flash_fwd", "apex_flash_dq", "apex_flash_dkv"}
          <= set(pallas_kernels(kern)), "window: a flash kernel is missing")
    check(not pallas_kernels(twin), "window: the twin holds kernels")
    window_err = rel_l2(kern(q, k, v), twin(q, k, v))
    check(window_err <= WINDOW_REL_L2,
          f"window: kernels against the scan twin, rel L2 {window_err} "
          f"> {WINDOW_REL_L2}")

    # (b) the trainable expert layer
    T, H, F, E, held = 2048, 1024, 512, 128, range(16, 32)
    x = jax.random.normal(ks[4], (T, H), bf16)
    gx = jax.random.normal(ks[5], (T, H), bf16)
    params = {
        "router": jax.random.normal(ks[6], (H, E), jnp.float32) * 0.05,
        "router_bias": jnp.zeros((E,), jnp.float32),
        "we_gate": jax.random.normal(ks[7], (16, H, F), bf16) * 0.05,
        "we_up": jax.random.normal(ks[8], (16, H, F), bf16) * 0.05,
        "we_down": jax.random.normal(ks[9], (16, F, H), bf16) * 0.05}
    rows = expert_buffer_rows(T, 8, 16, E)

    def experts(impl):
        def loss(x, params):
            out, counts = held_experts_ffn(
                x, params, held, top_k=8, n_group=1, topk_group=1,
                scale=2.826, impl=impl, buffer_rows=rows)
            return jnp.sum(out.astype(jnp.float32)
                           * gx.astype(jnp.float32)), counts

        return jax.jit(jax.value_and_grad(
            loss, (0, 1), has_aux=True)).lower(x, params).compile()

    kern, twin = experts("pallas"), experts("xla")
    check({"gmm", "tgmm"} <= set(pallas_kernels(kern)),
          "experts: a grouped matmul kernel is missing")
    (loss_k, counts), grads_k = kern(x, params)
    (loss_t, _), grads_t = twin(x, params)
    experts_err = rel_l2((loss_k, grads_k), (loss_t, grads_t))
    check(experts_err <= EXPERTS_REL_L2,
          f"experts: Pallas grouped matmuls against ragged_dot, rel L2 "
          f"{experts_err} > {EXPERTS_REL_L2}")
    check(int(counts["assignments_held"]) == int(
        counts["load"][held.start:held.stop].sum()),
        "experts: an assignment was dropped")
    out = {"window_rel_l2": window_err, "experts_rel_l2": experts_err,
           "assignments_held": int(counts["assignments_held"]),
           "buffer_rows": int(counts["buffer_rows"]),
           "setup_s": round(time.time() - t0, 1), "ok": True}
    print("window_experts: " + json.dumps(out), flush=True)
    return out


# ------------------------------------------------------------------- server
def server_phase(temperature, top_k):
    import jax
    import jax.numpy as jnp

    import serve_gpt
    from apex_tpu.inference import Request
    from apex_tpu.inference.decode import decode_logits_tokenwise
    from apex_tpu.models.gpt import gpt_forward
    from apex_tpu.ops.decode_sampling_pallas import fused_sample
    from apex_tpu.resilience.fallback import get_registry

    where = f"server(T={temperature}, top_k={top_k})"
    args = serve_gpt.build_args().parse_args(
        [*SERVE_ARGV, "--temperature", str(temperature),
         "--top-k", str(top_k)])
    t0 = time.time()
    sched, params, config = serve_gpt.build_scheduler(args)
    rng = np.random.RandomState(0)
    for rid in range(args.requests):
        plen = int(rng.randint(64, args.prompt_len + 1))
        sched.submit(Request(
            rid=rid, prompt=rng.randint(0, VOCAB, size=plen).tolist(),
            max_new_tokens=args.max_new))
    sched.step()  # admits 8 prompts: the prefill's compile
    sched.step()  # launches the first decode step: its compile
    setup_s = time.time() - t0
    while not sched.idle():
        sched.step()
    wall_s = time.time() - t0
    done = sched.completed
    out = {"requests": len(done),
           "generated_tokens": sum(len(c.tokens) for c in done),
           "decode_compiles": sched.decode_cache_size(),
           "step_rebuilds": sched.stats["step_rebuilds"],
           "decode_steps": sched.stats["decode_steps"],
           "setup_s": round(setup_s, 1), "wall_s": round(wall_s, 1)}
    print(f"{where}: " + json.dumps(out), flush=True)
    check(len(done) == args.requests
          and all(len(c.tokens) == args.max_new for c in done),
          f"{where}: want {args.requests} requests of {args.max_new} "
          f"tokens, got {[len(c.tokens) for c in done]}")
    check(all(0 <= t < VOCAB for c in done for t in c.tokens),
          f"{where}: a generated token is outside the vocabulary")
    check(out["decode_compiles"] == 1 and out["step_rebuilds"] == 0,
          f"{where}: decode_compiles={out['decode_compiles']} "
          f"step_rebuilds={out['step_rebuilds']} (want 1 and 0)")
    no_trip(get_registry().status(), where)
    out.update(kernels_in(sched.lower_decode_step(), SERVE_KERNELS, where))

    # decode-step logits (the paged-attention kernel reading a bf16
    # cache) against the training forward, 8 positions past a 64-token
    # prefill
    S, prefix = 72, 64
    tokens = jnp.asarray(rng.randint(0, VOCAB, size=(1, S)), jnp.int32)
    row = jnp.arange(1, sched.dcfg.cache.pages_per_seq + 1, dtype=jnp.int32)
    dec = decode_logits_tokenwise(params, config, sched.dcfg, tokens,
                                  prefix, row)
    # one program: XLA merges the two calls' common forward
    ref, hidden = jax.jit(lambda p, t: (
        gpt_forward(p, t, config),
        gpt_forward(p, t, config, return_hidden=True)))(params, tokens)
    err = float(jnp.max(jnp.abs(dec - ref[prefix:, 0])))
    out["logits_max_abs_err"] = err
    print(f"{where}: logits max abs err {err:.4f}", flush=True)
    check(err <= LOGITS_ATOL,
          f"{where}: decode logits differ from the training forward by "
          f"{err} (atol {LOGITS_ATOL})")

    # the sampling kernel against the reference head on one batch of
    # hidden states: every drawn token must lie in the reference's
    # eligible set (the argmax when greedy, the top-k otherwise)
    x2 = hidden[-8:, 0]
    drawn = fused_sample(x2, params["embed"], jnp.arange(8, dtype=jnp.uint32),
                         temperature=temperature, top_k=top_k, impl="pallas")
    logits = jnp.matmul(x2.astype(jnp.float32),
                        params["embed"].T.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    k = top_k if (top_k and temperature > 0) else 1
    floor = np.asarray(jax.lax.top_k(logits, k)[0][:, -1])
    # on the host: NumPy refuses an out-of-range token id where a device
    # gather would clamp it into a plausible-looking one
    picked = np.asarray(logits)[np.arange(8), np.asarray(drawn)]
    gap = float(np.max(floor - picked))
    out["sample_logit_gap"] = gap
    check(gap <= SAMPLE_LOGIT_EPS,
          f"{where}: a sampled token's logit is {gap} below the "
          f"reference's eligible set (eps {SAMPLE_LOGIT_EPS})")
    out["ok"] = True
    return out


def latent_server_phase():
    """The latent-attention, sparse-expert family through the same
    entry point and scheduler: greedy requests served, the one-pool
    ``apex_kv_write`` and ``apex_mla_decode_attention`` in the compiled
    decode step, and its logits (absorbed attention over the paged bf16
    latent cache) against the family's full forward (flash kernel,
    keys and values expanded per head)."""
    import tempfile

    import jax
    import jax.numpy as jnp

    import serve_gpt
    from apex_tpu.inference import Request
    from apex_tpu.inference.decode import decode_logits_tokenwise
    from apex_tpu.models import mla_moe
    from apex_tpu.resilience.fallback import get_registry

    where = "server(latent)"
    conf = json.loads(LATENT_CONFIG.read_text())
    conf.update(num_hidden_layers=2, first_k_dense_replace=1)
    t0 = time.time()
    with tempfile.NamedTemporaryFile("w", suffix=".json") as f:
        json.dump(conf, f)
        f.flush()
        args = serve_gpt.build_args().parse_args(
            [*LATENT_ARGV, "--model-config", f.name])
        sched, params, config = serve_gpt.build_scheduler(args)
    vocab = config.vocab_size
    rng = np.random.RandomState(0)
    for rid in range(args.requests):
        plen = int(rng.randint(32, args.prompt_len + 1))
        sched.submit(Request(
            rid=rid, prompt=rng.randint(0, vocab, size=plen).tolist(),
            max_new_tokens=args.max_new))
    sched.step()  # admission and the prefills' compiles
    sched.step()  # the first decode step's launch: its compile
    setup_s = time.time() - t0
    while not sched.idle():
        sched.step()
    done = sched.completed
    out = {"requests": len(done),
           "generated_tokens": sum(len(c.tokens) for c in done),
           "decode_compiles": sched.decode_cache_size(),
           "step_rebuilds": sched.stats["step_rebuilds"],
           "counters": sched.read_counters(),
           "setup_s": round(setup_s, 1),
           "wall_s": round(time.time() - t0, 1)}
    print(f"{where}: " + json.dumps(out), flush=True)
    check(len(done) == args.requests
          and all(len(c.tokens) == args.max_new for c in done)
          and all(0 <= t < vocab for c in done for t in c.tokens),
          f"{where}: want {args.requests} requests of {args.max_new} "
          f"tokens inside the vocabulary")
    check(out["decode_compiles"] == 1 and out["step_rebuilds"] == 0,
          f"{where}: decode_compiles={out['decode_compiles']} "
          f"step_rebuilds={out['step_rebuilds']} (want 1 and 0)")
    c = out["counters"]
    check(0 < c["moe_assignments_held"] < c["moe_assignments_all"],
          f"{where}: the held experts' counters read {c}")
    no_trip(get_registry().status(), where)
    out.update(kernels_in(sched.lower_decode_step(), LATENT_KERNELS, where))

    S, prefix = 136, 128
    tokens = jnp.asarray(rng.randint(0, vocab, size=(1, S)), jnp.int32)
    row = jnp.arange(1, sched.dcfg.cache.pages_per_seq + 1, dtype=jnp.int32)
    dec = decode_logits_tokenwise(params, config, sched.dcfg, tokens,
                                  prefix, row)
    ref = jax.jit(lambda p, t: mla_moe.forward(
        p, t, config, attn_impl="pallas"))(params, tokens)
    err = float(jnp.max(jnp.abs(dec - ref[0, prefix:])))
    out["logits_max_abs_err"] = err
    print(f"{where}: logits max abs err {err:.4f}", flush=True)
    check(err <= LOGITS_ATOL,
          f"{where}: decode logits differ from the full forward by "
          f"{err} (atol {LOGITS_ATOL})")
    out["ok"] = True
    return out


# --------------------------------------------------------------------- main
def main():
    import jax
    import jaxlib

    from apex_tpu.utils.compile_cache import enable_compile_cache
    from apex_tpu.utils.platform import device_facts

    cache_dir = enable_compile_cache()
    device = device_facts()
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": metadata.version("libtpu")}
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX reports {device} "
                 f"({versions}) — refusing to run")
    print(f"chip_smoke: device {json.dumps(device)} "
          f"versions {json.dumps(versions)}", flush=True)

    def cache_entries():
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    t0 = time.time()
    summary = {"ok": False, "device": device, "versions": versions,
               "cache_dir": cache_dir,
               "cache_entries_before": cache_entries()}
    phases = summary["phases"] = {}
    phases["trainer"] = trainer_phase()
    gc.collect()
    phases["parity"] = parity_phase()
    gc.collect()
    phases["window_experts"] = window_experts_phase()
    gc.collect()
    phases["server_greedy"] = server_phase(temperature=0.0, top_k=0)
    phases["server_sampled"] = server_phase(temperature=1.0, top_k=40)
    gc.collect()
    phases["server_latent"] = latent_server_phase()
    if device["count"] >= 4:
        gc.collect()
        phases["trainer_tp2_dp2"] = trainer_phase(
            ["--tp", "2", "--sequence-parallel"])
    summary["cache_entries_after"] = cache_entries()
    summary["setup_s"] = round(sum(p["setup_s"] for p in phases.values()), 1)
    summary["wall_s"] = round(time.time() - t0, 1)
    summary["ok"] = all(p["ok"] for p in phases.values())
    summary["claim"] = None
    print("summary: " + json.dumps(summary), flush=True)
    # the result line: exactly these keys, the device as JAX reports it
    print(json.dumps({"ok": summary["ok"], "device": device}), flush=True)


if __name__ == "__main__":
    main()
