"""The serving adapter for a latent-attention, sparse-expert model
(``apex_tpu.models.mla_moe``): ``MLAMoEConfig`` + ``DecodeConfig`` +
``ContinuousBatchingScheduler``, the scheduler the GPT-2 adapter
drives, under the same open-loop generator and the same loop
(``serve.drive``).

What differs from ``adapters/serve.py``: the weights are born bfloat16
in the program's layout from the published-layout generator
(``cellbench/weights_mla_moe.py``), leaf by leaf, so that 10 GB of them
never exist twice; every prefill bucket is warmed up; the expert
layer's device-side counters are read once before and once after the
window; and the plain reference (``cellbench/reference/mla_moe.py``)
checks the sample layer by layer, one layer's float32 weights alive at
a time.

Two numbers are compared.  The first is ``serve.compare``'s: the WIDEST
gap by which a served token's reference logit lies below the
reference's best.  Here it cannot tell bf16 from float8 alone: the
router's top-8 is a discrete choice, its 8th and 9th scores are often
closer than bf16 activations resolve, and one flipped choice of a held
expert moves a token's logits by tenths (PERF.md, section 2, has the
measurement).  So the second number is the MEAN gap over the served
tokens, which a rare flip hardly moves and a lower precision moves
everywhere.  Nothing is masked and the reference never sees the
program's choices.
"""

import gc
import time
from typing import Dict, List

import numpy as np

from cellbench import arith, loadgen
from cellbench import weights_mla_moe as weights
from cellbench.adapters import common
from cellbench.adapters.serve import WARMUP_RID, drive, pick_sample
from cellbench.reference import mla_moe as reference

#: reference sequences are padded to a multiple of this: at most eight
#: shapes of each kind of layer compile over all seeds
REFERENCE_PAD = 256
#: requests the reference checks: the longest finished and two more.
#: Float32 at ``highest`` runs at about 1 TFLOP/s on the chip and a
#: request costs 10 to 20 TFLOP (16 held experts on every token), so
#: ``serve.pick_sample``'s five took 97 s of every run (PERF.md, PR 26)
CHECKED = 3


class _CountersAtClose:
    """The scheduler as ``serve.drive`` sees it, with one addition: the
    model's device-side counters are read when the window closes, so
    that they cover the window and not the drain.  ``drive`` asks
    ``idle()`` for the first time once the window has closed (the drain
    loop's condition; the window's loop never asks), so the first
    ``idle()`` IS the close: one readback, outside every step."""

    def __init__(self, sched):
        self._sched = sched
        self.at_close = None

    def __getattr__(self, name):
        return getattr(self._sched, name)

    def idle(self):
        if self.at_close is None:
            self.at_close = dict(
                self._sched.read_counters(),
                decode_steps=self._sched.stats["decode_steps"],
                prefills=self._sched.stats["prefills"])
        return self._sched.idle()


def model_config(conf):
    """``MLAMoEConfig`` of a configuration file.  Exits, cleanly and at
    once, where the program has no such model (a commit older than the
    family)."""
    import jax.numpy as jnp

    try:
        from apex_tpu.models.mla_moe import MLAMoEConfig
    except ModuleNotFoundError as e:
        raise SystemExit(
            f"cellbench: this checkout's apex_tpu cannot serve the "
            f"configuration ({e}); no workload runs") from None
    args = conf["cellbench"]["args"]
    s = weights.sizes(conf)
    return MLAMoEConfig.from_published(
        conf, n_routed_experts=s["E"], held_start=s["held_start"],
        held_count=s["held"],
        param_dtype=jnp.dtype(args["param_dtype"]),
        compute_dtype=jnp.dtype(args["compute_dtype"]))


# program leaf -> (published leaf, how to turn one layer of it)
def _layout(s: Dict) -> Dict:
    heads, nope = s["heads"], s["nope"]
    t = lambda w: w.T
    e = lambda w: w.transpose(0, 2, 1)
    same = lambda w: w

    def kvb(part):
        def f(w):
            w = w.reshape(heads, nope + s["v"], s["kv_rank"])
            w = w[:, :nope] if part == "k" else w[:, nope:]
            return w.transpose(2, 0, 1)
        return f

    attn = {
        "attn_norm": ("input_layernorm.weight", same),
        "wq_a": ("self_attn.q_a_proj.weight", t),
        "q_norm": ("self_attn.q_a_layernorm.weight", same),
        "wq_b": ("self_attn.q_b_proj.weight",
                 lambda w: w.T.reshape(s["q_rank"], heads,
                                       nope + s["rope"])),
        "wkv_a": ("self_attn.kv_a_proj_with_mqa.weight", t),
        "kv_norm": ("self_attn.kv_a_layernorm.weight", same),
        "wkv_b_k": ("self_attn.kv_b_proj.weight", kvb("k")),
        "wkv_b_v": ("self_attn.kv_b_proj.weight", kvb("v")),
        "wo": ("self_attn.o_proj.weight", t),
        "ffn_norm": ("post_attention_layernorm.weight", same),
    }
    dense = dict(attn, w_gate=("mlp.gate_proj.weight", t),
                 w_up=("mlp.up_proj.weight", t),
                 w_down=("mlp.down_proj.weight", t))
    moe = dict(
        attn, router=("mlp.gate.weight", t),
        router_bias=("mlp.gate.e_score_correction_bias", same),
        we_gate=("mlp.experts.gate_proj.weight", e),
        we_up=("mlp.experts.up_proj.weight", e),
        we_down=("mlp.experts.down_proj.weight", e),
        ws_gate=("mlp.shared_experts.gate_proj.weight", t),
        ws_up=("mlp.shared_experts.up_proj.weight", t),
        ws_down=("mlp.shared_experts.down_proj.weight", t))
    return {"dense": dense, "moe": moe}


def program_params(conf, key, param_dtype):
    """The program's parameter tree, born on the device in its own
    layout and dtype: each stacked leaf is one jitted program that
    draws its layers in turn (``lax.map``), so the float32 draw of one
    layer of one leaf is the largest temporary."""
    import jax
    import jax.numpy as jnp

    s = weights.sizes(conf)
    first = weights.held(conf).start
    stacks = {"dense": range(0, s["dense"]),
              "moe": range(s["dense"], s["L"])}
    out = {}
    for stack, leaves in _layout(s).items():
        layers = stacks[stack]
        if not len(layers):
            continue
        shapes = weights.layer_leaves(conf, layers[0])
        out[stack] = {}
        for leaf, (pub, turn) in leaves.items():
            shape, kind = shapes[pub]
            dtype = jnp.float32 if leaf == "router_bias" else param_dtype

            # the key is an ARGUMENT: closed over, the seed would be a
            # constant of the program and every seed a new compile
            def stacked(k, ix, pub=pub, shape=shape, kind=kind, turn=turn,
                        dtype=dtype):
                return jax.lax.map(lambda i: turn(weights.draw_leaf(
                    weights.layer_key(k, i), pub, shape, kind,
                    first)).astype(dtype), ix)

            out[stack][leaf] = jax.jit(stacked)(
                key, jnp.arange(layers.start, layers.stop))
    top = jax.jit(lambda k: weights.top_weights(conf, k))(key)
    out["embed"] = top["model.embed_tokens.weight"].astype(param_dtype)
    out["head"] = top["lm_head.weight"].astype(param_dtype)
    out["final_norm"] = top["model.norm.weight"].astype(param_dtype)
    return out


def build(conf, key, seed):
    """The model, the latent pool and the scheduler, as
    ``examples/gpt/serve_gpt.py`` builds them for this family, from a
    configuration file.  Returns ``(scheduler, decode config)``."""
    import jax.numpy as jnp

    from apex_tpu.inference import (
        ContinuousBatchingScheduler, DecodeConfig, KVCacheConfig,
    )

    args = conf["cellbench"]["args"]
    config = model_config(conf)
    params = program_params(conf, key, config.param_dtype)
    page = int(args["page_size"])
    pages_per_seq = -(-int(args["max_context"]) // page)
    dcfg = DecodeConfig(
        cache=KVCacheConfig(
            num_pages=1 + int(args["max_batch"]) * pages_per_seq,
            page_size=page, pages_per_seq=pages_per_seq,
            dtype=jnp.dtype(args["kv_dtype"])),
        max_batch=int(args["max_batch"]),
        max_prompt_len=int(args["max_prompt_len"]),
        prefill_buckets=tuple(int(b) for b in args["prefill_buckets"]),
        temperature=float(args["temperature"]), top_k=int(args["top_k"]),
        attn_impl=args["attn_impl"], sample_impl=args["sample_impl"],
        sample_dot_dtype=(jnp.dtype(args["sample_dot_dtype"])
                          if args.get("sample_dot_dtype") else None),
        base_seed=seed & 0xFFFFFFFF)
    return ContinuousBatchingScheduler(params, config, dcfg), dcfg


def warm_up(sched, dcfg, vocab, seed):
    """One request a prefill bucket and a few decode steps: every shape
    the window will use."""
    from apex_tpu.inference import Request

    rng = np.random.RandomState(seed % (2 ** 32))
    lo = 1
    for i, bucket in enumerate(dcfg.prefill_lengths):
        plen = max(lo, min(bucket, lo + 7))
        sched.submit(Request(
            rid=WARMUP_RID + i, max_new_tokens=3,
            prompt=rng.randint(0, vocab, size=plen).tolist()))
        lo = bucket + 1
    while not sched.idle():
        sched.step()


def run(env) -> Dict:
    import jax

    cell, log = env["cell"], env["log"]
    conf, mix = cell["config_file"], cell["traffic_file"]
    limits = conf["cellbench"]["correct"]
    config = model_config(conf)     # exits here on a parent without it

    from apex_tpu.inference import Request
    from apex_tpu.observability import tracing

    s = weights.sizes(conf)
    key = weights.seed_key(env["seed"])
    seconds = env["seconds"]
    if env["trace"]:
        tracing.configure(capacity=1 << 18)

    phases = common.Phases(env["t_setup_start"])
    phases.mark("imports of the program")
    sched, dcfg = build(conf, key, env["seed"])
    jax.block_until_ready(sched.params)
    phases.mark("weights and scheduler")
    clock = time.monotonic
    gen = loadgen.generator(mix)
    requests = gen.requests(mix, s["V"], env["seed"], seconds)
    log(f"serve: mix {loadgen.describe(requests)}")

    warm_up(sched, dcfg, s["V"], env["seed"])
    phases.mark("warm-up of every prefill bucket and the decode step")
    step_bytes = common.program_bytes(
        sched.lower_decode_step().compile().memory_analysis())
    phases.mark("decode step's memory analysis")
    held = gen.in_flight_at_open(mix, s["V"], env["seed"])
    for r in held:
        sched.submit(Request(rid=WARMUP_RID + 100 + r.rid, prompt=r.prompt,
                             max_new_tokens=r.max_new_tokens))
    while sched.queue and sched.num_active < dcfg.max_batch:
        sched.step()
    compiles = common.CompileWatch()
    compiles.start()
    gc.collect()
    before = dict(sched.read_counters(),
                  decode_steps=sched.stats["decode_steps"],
                  prefills=sched.stats["prefills"])
    phases.mark("requests in flight at the open")
    log(phases.line())

    # ---- the window
    wt = env["window_trace"]
    setup_s = time.time() - env["t_setup_start"]
    watched = _CountersAtClose(sched)
    w = drive(watched, requests, seconds, wt, log)
    compiles.stop()
    t0, t_close, due_at = w["t0"], w["t_close"], w["due_at"]
    lateness, refused, occupancy = w["lateness"], w["refused"], w["occupancy"]
    # what the window (not the drain) added to the device-side counters
    moved = {k: watched.at_close[k] - before[k] for k in before}

    everything = list(sched.completed)
    done = {c.rid: c for c in everything if c.rid < WARMUP_RID}
    attempted = len(due_at)
    short = [rid for rid, c in done.items()
             if len(c.tokens) != requests[rid].max_new_tokens]
    failed = refused + (attempted - refused - len(done)) + len(short)
    shift = time.time() - clock()       # scheduler clock -> time.time()
    ttft = [1e3 * (c.token_times[0] - due_at[rid])
            for rid, c in done.items() if wt.undisturbed(due_at[rid] + shift)]
    gaps = [1e3 * float(g) for c in done.values()
            for g in np.diff(c.token_times)]
    window_s = t_close - t0
    in_win = sum(1 for c in everything for t in c.token_times
                 if t0 <= t < t_close)
    e2e = {"serve_tokens_per_s": in_win / window_s}
    if ttft:
        for q in (50, 90):
            e2e[f"ttft_p{q}_ms"] = arith.percentile(ttft, q)
    if gaps:
        e2e["gap_p95_ms"] = arith.percentile(gaps, 95)
        e2e["gap_p50_ms"] = arith.percentile(gaps, 50)
    kv_pool_pct = (100.0 * float(np.mean(w["live_pages"]))
                   / (dcfg.cache.num_pages - 1) if w["live_pages"] else None)
    stats = jax.devices()[0].memory_stats() or {}
    alloc_peak = stats.get("peak_bytes_in_use", 0)
    decode_compiles = sched.decode_cache_size()
    sched_stats = dict(sched.stats)
    host_spans = tracing.get_tracer().spans() if env["trace"] else []
    prefill_spans = [sp["attrs"] for sp in host_spans
                     if sp["name"] == "serve.prefill"
                     and "padded_tokens" in sp["attrs"]]
    log(f"serve: {attempted} due, {len(done)} finished, {refused} refused, "
        f"{w['late_at_close']} submitted late at the close, drain "
        f"{w['t_drained'] - t_close:.2f} s; generator lateness mean "
        f"{1e3 * float(np.mean(lateness)) if lateness else 0:.3f} ms max "
        f"{1e3 * max(lateness, default=0):.3f} ms; decode steps "
        f"{sched_stats['decode_steps']}, prefills {sched_stats['prefills']}; "
        f"{len(held)} in flight at the open; latent pool "
        f"{kv_pool_pct or 0:.1f}% held on average; the window moved "
        f"{moved}; e2e { {k: round(v, 2) for k, v in e2e.items()} }; "
        f"decode-step memory {step_bytes / 1e9:.2f} GB, allocator peak "
        f"{alloc_peak / 1e9:.2f} GB")

    counters = {
        "slot_occupancy_pct": (100.0 * float(np.mean(occupancy))
                               / dcfg.max_batch if occupancy else None),
        "kv_pool_used_pct": kv_pool_pct,
        "step_hbm_GB": step_bytes / 1e9,
        "lateness_mean_ms": (1e3 * float(np.mean(lateness))
                             if lateness else None),
        # the whole window (the readers scale them to the traced
        # stretch by its share of the decode steps)
        "decode_steps": moved["decode_steps"],
        "moe_layers": config.num_moe_layers,
        "experts_held": len(config.held),
        **{k: moved[k] for k in moved if k.startswith("moe_")},
    }
    if prefill_spans:
        counters["prefill_tokens"] = sum(a["tokens"] for a in prefill_spans)
        counters["prefill_padded_tokens"] = sum(
            a["padded_tokens"] for a in prefill_spans)
    if wt.t_start is not None and wt.t_stop is not None:
        a, b = wt.t_start - shift, wt.t_stop - shift
        every = [c for c in everything if c.rid < WARMUP_RID
                 or c.rid >= WARMUP_RID + 100]
        # requests still decoding at the close are not in `completed`
        # until drained: they are, after the drain
        steps = {t for c in every for t in c.token_times[1:] if a <= t <= b}
        counters["traced_steps"] = len(steps)
        counters["traced_decode_tokens"] = sum(
            1 for c in every for t in c.token_times[1:] if a <= t <= b)
        counters["traced_kv_positions"] = sum(
            len(c.prompt) + k for c in every
            for k, t in enumerate(c.token_times[1:], start=1)
            if a <= t <= b)

    # ---- free the program's state, then the reference checks a sample
    sample = pick_sample(done, env["seed"])[:CHECKED]
    served = [(list(done[rid].prompt), list(done[rid].tokens))
              for rid in sample]
    del sched, watched, done, everything
    gc.collect()
    t_ref = time.time()
    checks = []
    if served:
        checks = compare(conf, key, served, limits, quant=env.get("control"))
    log(f"serve: reference check of {len(served)} requests "
        f"{time.time() - t_ref:.2f} s")
    ok = common.judge(checks, {
        "no finished request to compare": not served,
        "requests failed": failed,
        "kernels tripped": common.tripped_kernels(),
        "compiles in the window": compiles.durations,
        "step rebuilds": sched_stats["step_rebuilds"],
        "decode step compiled more than once": decode_compiles - 1,
    }, log)
    return {
        "correct": ok, "attempted": attempted, "failed": failed,
        "setup_s": setup_s, "e2e": e2e,
        "memory_peak_bytes": int(max(alloc_peak, step_bytes)),
        "host_spans": host_spans, "counters": counters, "checks": checks,
    }


def compare(conf, key, served, limits, quant=None) -> List:
    """The plain reference over each sampled request's prompt and served
    tokens, layer by layer (one layer's float32 weights alive at a
    time).  The number compared is the widest gap by which a served
    token's reference logit lies below the reference's best at that
    position (valid because the traffic is greedy).  With ``quant`` the
    served tokens are ignored and the token the lower precision puts
    first takes their place (the control)."""
    import jax
    import jax.numpy as jnp

    held = weights.held(conf)
    top = jax.jit(lambda k: weights.top_weights(conf, k))(key)
    make = jax.jit(lambda k, i: weights.layer_weights(conf, k, i),
                   static_argnums=1)
    seqs, poss = [], []
    for prompt, tokens in served:
        full = np.asarray(prompt + tokens[:-1], np.int32)
        pad = -len(full) % REFERENCE_PAD
        seqs.append(jnp.asarray(np.concatenate(
            [full, np.zeros(pad, np.int32)])))
        poss.append(jnp.arange(len(prompt) - 1,
                               len(prompt) - 1 + len(tokens)))

    def logits(q):
        fn = jax.jit(lambda h, w: reference.layer(h, w, conf, held, q))
        return reference.logits_at_each(
            conf, top, lambda i: make(key, i), seqs, poss, held, q,
            layer_fn=fn)

    ref = logits(None)
    low = logits(quant) if quant is not None else None
    widest, total, n_tokens, n_top = 0.0, 0.0, 0, 0
    for r, (_, tokens) in enumerate(served):
        nxt = (jnp.argmax(low[r], axis=-1) if low is not None
               else jnp.asarray(tokens, jnp.int32))
        best = jnp.max(ref[r], axis=-1)
        picked = jnp.take_along_axis(ref[r], nxt[:, None], axis=-1)[:, 0]
        widest = max(widest, float(jnp.max(best - picked)))
        total += float(jnp.sum(best - picked))
        n_tokens += len(tokens)
        n_top += int(jnp.sum(jnp.argmax(ref[r], axis=-1) == nxt))
    detail = (f"{n_tokens} tokens of {len(served)} requests, {n_top} are "
              f"the reference's own first choice")
    return [(f"widest logit gap of a served token below the reference's "
             f"best ({detail})", widest, limits["logit_gap"]),
            (f"mean logit gap of the served tokens below the reference's "
             f"best ({detail})", total / n_tokens,
             limits["mean_logit_gap"])]
