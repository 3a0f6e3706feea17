"""The serving adapter for a model whose layers mix by KDA or by MLA
(``kimi_linear``; ``apex_tpu.models.mla_moe`` with ``layer_kinds``):
the scheduler, the loop (``serve.drive``), the warm-up and the
counters' readback of ``adapters/serve_mla_moe.py``, around another
family's weights (``cellbench/weights_kda_mla_moe.py``), layout and
plain reference (``cellbench/reference/kda_mla_moe.py``).

What differs from that adapter: a layer's program leaves may be made
of several published ones (``wqkv`` is the three projections side by
side, ``conv_w`` the three filters); the stacks are four, by mixer and
FFN, and a stack's layers are not consecutive; the cache has per-slot
state beside the paged pool; one more device-side counter
(``kda_state_updates``) and the prefill spans' ``tokens`` /
``padded_tokens`` are handed to the readers; and the reference checks
ONE long request (a prompt is up to 4,096 tokens).  The first two
numbers compared are that adapter's: the WIDEST and the MEAN gap by
which a served token's reference logit lies below the reference's best
(its module doc says why two).  Both compare chosen tokens under
bfloat16 activations, which move one choice in six by themselves: of
the one precision this family adds, the recurrent state's, the widest
gap sees nothing and the mean a tenth over its limit (PERF.md, section
2).  So a THIRD number reads the state itself (:func:`probe_state`):
once the window has drained, the checked request's prompt is served again,
alone, through the same compiled programs, and the state that the
FIRST KDA layer holds for it after :data:`PROBE_TOKENS` decode steps is
set against the float32 reference's recurrence over the same tokens
(``reference.first_kda_state``), as a distance relative to the
reference's norm.  That layer's input is the embedding alone, so no
router's choice stands between the two sides and the number is steady
from seed to seed.  Besides the float8 control (``cellbench.control``)
there is a second, ``control="kda_state_bfloat16"``: the reference with
its recurrent state rounded to bfloat16 after every token, in the
program's place; the third number rejects it (PERF.md, section 2, has
the readings).
"""

import gc
import time
from typing import Dict, List

import numpy as np

from cellbench import arith, loadgen
from cellbench import weights_kda_mla_moe as weights
from cellbench.adapters import common
from cellbench.adapters.serve import WARMUP_RID, drive, pick_sample
from cellbench.adapters.serve_mla_moe import _CountersAtClose, warm_up
from cellbench.reference import kda_mla_moe as reference

#: reference sequences are padded to a multiple of this: at most ten
#: lengths of each kind of layer compile over all seeds
REFERENCE_PAD = 512
#: requests the reference checks: the longest the window finished.
#: Float32 at ``highest`` runs at about 1 TFLOP/s on the chip and a
#: token costs 5.6 GFLOP (32 held experts on every token, 12 layers):
#: with two requests the check took 62 to 91 s of a run of 216 to 244 s
#: (my chip runs, PR 30); one keeps a warm run inside the latent
#: cell's 143 to 218 s
CHECKED = 1
STATE_CONTROL = "kda_state_bfloat16"
#: decode steps of the state probe: a bfloat16 state has drifted as far
#: as it will after some 200 (the decays forget), and 256 steps of one
#: slot take 4 s
PROBE_TOKENS = 256
PROBE_RID = WARMUP_RID + 50


def model_config(conf):
    """``MLAMoEConfig`` of a configuration file.  Exits, cleanly and at
    once, where the program cannot serve such a model (a commit older
    than the per-layer mixer pattern)."""
    import jax.numpy as jnp

    try:
        from apex_tpu.models.mla_moe import MLAMoEConfig
    except ModuleNotFoundError as e:
        raise SystemExit(
            f"cellbench: this checkout's apex_tpu cannot serve the "
            f"configuration ({e}); no workload runs") from None
    if not hasattr(MLAMoEConfig, "layer_kinds"):
        raise SystemExit(
            "cellbench: this checkout's apex_tpu.models.mla_moe has no "
            "per-layer mixer pattern (layer_kinds): it cannot serve the "
            "configuration; no workload runs")
    args = conf["cellbench"]["args"]
    s = weights.sizes(conf)
    return MLAMoEConfig.from_published(
        conf, n_routed_experts=s["E"], held_start=s["held_start"],
        held_count=s["held"], param_dtype=jnp.dtype(args["param_dtype"]),
        compute_dtype=jnp.dtype(args["compute_dtype"]))


# program leaf -> (published leaves, how to make one layer of it)
def _layout(s: Dict) -> Dict:
    import jax.numpy as jnp

    heads, nope = s["heads"], s["nope"]
    t = lambda w: w.T
    e = lambda w: w.transpose(0, 2, 1)
    same = lambda w: w
    attn = "self_attn."

    def kvb(part):
        def f(w):
            w = w.reshape(heads, nope + s["v"], s["kv_rank"])
            w = w[:, :nope] if part == "k" else w[:, nope:]
            return w.transpose(2, 0, 1)
        return f

    norms = {"attn_norm": (("input_layernorm.weight",), same),
             "ffn_norm": (("post_attention_layernorm.weight",), same)}
    mla = dict(norms, **{
        "wq": ((attn + "q_proj.weight",),
               lambda w: w.T.reshape(s["H"], heads, nope + s["rope"])),
        "wkv_a": ((attn + "kv_a_proj_with_mqa.weight",), t),
        "kv_norm": ((attn + "kv_a_layernorm.weight",), same),
        "wkv_b_k": ((attn + "kv_b_proj.weight",), kvb("k")),
        "wkv_b_v": ((attn + "kv_b_proj.weight",), kvb("v")),
        "wo": ((attn + "o_proj.weight",), t)})
    kda = dict(norms, **{
        "wqkv": (tuple(attn + f"{n}_proj.weight" for n in "qkv"),
                 lambda *w: jnp.concatenate([x.T for x in w], axis=1)),
        "conv_w": (tuple(attn + f"{n}_conv1d.weight" for n in "qkv"),
                   lambda *w: jnp.concatenate([x[:, 0].T for x in w],
                                              axis=1)),
        "a_log": ((attn + "A_log",), same),
        "dt_bias": ((attn + "dt_bias",), same),
        "wf_a": ((attn + "f_a_proj.weight",), t),
        "wf_b": ((attn + "f_b_proj.weight",), t),
        "wb": ((attn + "b_proj.weight",), t),
        "wg_a": ((attn + "g_a_proj.weight",), t),
        "wg_b": ((attn + "g_b_proj.weight",), t),
        "o_norm": ((attn + "o_norm.weight",), same),
        "wo": ((attn + "o_proj.weight",), t)})
    dense = {"w_gate": (("mlp.gate_proj.weight",), t),
             "w_up": (("mlp.up_proj.weight",), t),
             "w_down": (("mlp.down_proj.weight",), t)}
    m = "block_sparse_moe."
    moe = {"router": ((m + "gate.weight",), t),
           "router_bias": ((m + "gate.e_score_correction_bias",), same),
           "we_gate": ((m + "experts.w1.weight",), e),
           "we_up": ((m + "experts.w3.weight",), e),
           "we_down": ((m + "experts.w2.weight",), e),
           "ws_gate": ((m + "shared_experts.gate_proj.weight",), t),
           "ws_up": ((m + "shared_experts.up_proj.weight",), t),
           "ws_down": ((m + "shared_experts.down_proj.weight",), t)}
    return {"dense": dict(mla, **dense), "moe": dict(mla, **moe),
            "kda_dense": dict(kda, **dense), "kda_moe": dict(kda, **moe)}


def program_params(conf, key, param_dtype):
    """The program's parameter tree, born on the device in its own
    layout and dtype: each stacked leaf is one jitted program that
    draws its layers in turn (``lax.map``), so the float32 draw of one
    layer of one leaf is the largest temporary."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.models.mla_moe import FLOAT32_LEAVES

    s = weights.sizes(conf)
    first = weights.held(conf).start
    layout = _layout(s)
    out = {}
    config = model_config(conf)
    stacks: Dict[str, List[int]] = {}
    for i in range(config.num_layers):
        stacks.setdefault(config.stack_of(i), []).append(i)
    for stack, layers in stacks.items():
        shapes = weights.layer_leaves(conf, layers[0])
        out[stack] = {}
        for leaf, (pubs, turn) in layout[stack].items():
            dtype = jnp.float32 if leaf in FLOAT32_LEAVES else param_dtype

            # the key is an ARGUMENT: closed over, the seed would be a
            # constant of the program and every seed a new compile
            def stacked(k, ix, pubs=pubs, turn=turn, dtype=dtype):
                def one(i):
                    lk = weights.layer_key(k, i)
                    return turn(*[weights.draw_leaf(
                        lk, pub, *shapes[pub], first, s["kda_heads"])
                        for pub in pubs]).astype(dtype)
                return jax.lax.map(one, ix)

            out[stack][leaf] = jax.jit(stacked)(
                key, jnp.asarray(layers, jnp.int32))
    top = jax.jit(lambda k: weights.top_weights(conf, k))(key)
    out["embed"] = top["model.embed_tokens.weight"].astype(param_dtype)
    out["head"] = top["lm_head.weight"].astype(param_dtype)
    out["final_norm"] = top["model.norm.weight"].astype(param_dtype)
    return out


def build(conf, key, seed):
    """The model, its cache and the scheduler, as
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
        "mla_layers": config.count("mla"),
        "kda_layers": config.count("kda"),
        "experts_held": len(config.held),
        **{k: moved[k] for k in moved if k.startswith(("moe_", "kda_"))},
    }
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
    t_ref = time.time()
    probe = probe_state(sched, served[0][0]) if served else None
    log(f"serve: state probe {time.time() - t_ref:.2f} s")
    del sched, watched, done, everything
    gc.collect()
    t_ref = time.time()
    checks = []
    if served:
        checks = compare(conf, key, served, limits, probe,
                         quant=env.get("control"))
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


def probe_state(sched, prompt):
    """``prompt`` served once more, alone, on the drained scheduler
    (the window's compiled prefill and decode step), up to
    :data:`PROBE_TOKENS` emitted tokens (fewer where the slot's pages
    end sooner).  Returns ``(tokens, state)``:
    the tokens the recurrent state has taken in by then (the prompt and
    every emitted token but the last) and the FIRST KDA layer's state of
    the request's slot, ``(heads, d, d)`` on the host; None where the
    scheduler still holds a request it could not drain."""
    from apex_tpu.inference import Request

    if not sched.idle():
        return None
    cache = sched.dcfg.cache
    steps = min(PROBE_TOKENS, cache.pages_per_seq * cache.page_size
                - len(prompt) - 1)
    sched.submit(Request(rid=PROBE_RID, prompt=prompt,
                         max_new_tokens=steps + 1))
    emitted = []
    while len(emitted) < steps:
        sched.step()
        emitted = next((m.emitted for m in sched.drain_manifest()
                        if m.rid == PROBE_RID), None)
        if emitted is None:
            return None
    state = np.asarray(sched.slot_state(PROBE_RID)["kda_state"][0])
    return list(prompt) + list(emitted[:-1]), state


def compare(conf, key, served, limits, probe=None, quant=None) -> List:
    """The plain reference over each sampled request's prompt and served
    tokens, layer by layer (one layer's float32 weights alive at a
    time).  The first two numbers are the widest and the mean gap by
    which a served token's reference logit lies below the reference's
    best at that position (valid because the traffic is greedy); the
    third is the distance of ``probe``'s state (:func:`probe_state`)
    from the state the reference's recurrence holds after the same
    tokens, over that state's norm.  With ``quant`` the program's
    outputs are ignored and a lower precision of the reference takes
    their place (the token it puts first, the state it holds): the
    matmuls' inputs rounded to ``quant``, or (:data:`STATE_CONTROL`) the
    KDA state rounded to bfloat16 after every token."""
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
    lower = {} if quant is None else (
        {"state_dtype": jnp.bfloat16} if quant == STATE_CONTROL
        else {"quant": quant})

    def logits(quant=None, state_dtype=None):
        fn = jax.jit(lambda h, w: reference.layer(h, w, conf, held, quant,
                                                  state_dtype))
        return reference.logits_at_each(
            conf, top, lambda i: make(key, i), seqs, poss, held, quant,
            layer_fn=fn)

    ref = logits()
    low = logits(**lower) if lower else None
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
    checks = [
        (f"widest logit gap of a served token below the reference's "
         f"best ({detail})", widest, limits["logit_gap"]),
        (f"mean logit gap of the served tokens below the reference's "
         f"best ({detail})", total / n_tokens, limits["mean_logit_gap"])]
    if probe is None:
        # nothing to read is a failure of the check, not a pass
        return checks + [("no state probe (the scheduler did not drain)",
                          float("inf"), limits["kda_state_drift"])]
    tokens, state = probe
    first = jax.jit(
        lambda *a, **kw: reference.first_kda_state(conf, *a, **kw),
        static_argnames=("quant", "state_dtype"))
    args = (top, make(key, 0), jnp.asarray(tokens, jnp.int32))
    want = first(*args)
    got = first(*args, **lower) if lower else jnp.asarray(state)
    drift = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    return checks + [
        (f"distance of the first KDA layer's state from the reference's, "
         f"over its norm ({len(tokens)} tokens, the last "
         f"{len(tokens) - len(served[0][0])} by decode steps)", drift,
         limits["kda_state_drift"])]
