"""The serving adapter for a model whose every layer mixes by attention
AND by a Mamba-2 recurrence (``falcon_h1``;
``apex_tpu.models.falcon_h1``): the scheduler, the loop
(``serve.drive``), the warm-up and the counters' readback of
``adapters/serve_mla_moe.py``, around another family's weights
(``cellbench/weights_falcon_h1.py``), layout and plain reference
(``cellbench/reference/falcon_h1.py``).

:func:`run` is ``adapters/serve_kda_mla_moe.run`` again but for what the
family changes: the model's configuration and layout (one stack, every
layer alike; ``wqkv`` is the three attention projections side by side);
the K/V pool is sized by the file (``num_pages``), not as slots x
longest request; the counters handed to the readers (``ssm_layers``,
``ssm_state_updates``, and the window's prompt and decode tokens for
``mfu.h1chat``); the reference checks ONE request of up to 2,560
positions.  The numbers compared are that adapter's three: the WIDEST
and the MEAN gap by which a served token's reference logit lies below
the reference's best, and (:func:`probe_state`) the distance of the
FIRST layer's recurrent state, read out of the scheduler after
:data:`PROBE_TOKENS` decode steps of the checked prompt served again
alone, from the float32 reference's recurrence over the same tokens,
over that state's norm, taken over the :data:`PROBE_HEADS` heads of
LONGEST MEMORY (:func:`slowest_heads`).  Over all heads the number does
not tell a bfloat16 state from a sound program: the program's own error
(bfloat16 inputs to a float32 state, 0.4-0.7% in every head) is as
large as a bfloat16 state's in the heads that forget within tens of
tokens, which carry most of the state's norm; a rounded state's error
grows with the root of a head's memory, 1% at 150 tokens and 10% at
600 (PERF.md, section 2, has both readings by head).  Besides the float8
control (``cellbench.control``) there is a second,
``control="ssm_state_bfloat16"``: the reference with its state rounded
to bfloat16 after every token, in the program's place; the third number
rejects it (PERF.md, section 2, has the readings).
"""

import gc
import time
from typing import Dict, List

import numpy as np

from cellbench import arith, loadgen
from cellbench import weights_falcon_h1 as weights
from cellbench.adapters import common
from cellbench.adapters.serve import WARMUP_RID, drive, pick_sample
from cellbench.adapters.serve_mla_moe import _CountersAtClose, warm_up
from cellbench.reference import falcon_h1 as reference

#: reference sequences are padded to a multiple of this: at most five
#: lengths compile over all seeds
REFERENCE_PAD = 512
#: requests the reference checks: the longest the window finished
CHECKED = 1
STATE_CONTROL = "ssm_state_bfloat16"
#: decode steps of the state probe (as the KDA adapter's: a bfloat16
#: state has drifted as far as it will after some 200)
PROBE_TOKENS = 256
PROBE_RID = WARMUP_RID + 50
#: heads the state number is taken over: those of longest memory
PROBE_HEADS = 4


def model_config(conf):
    """``FalconH1Config`` of a configuration file.  Exits, cleanly and
    at once, where the program has no such family (a commit older than
    it)."""
    import jax.numpy as jnp

    try:
        from apex_tpu.models.falcon_h1 import FalconH1Config
    except ModuleNotFoundError as e:
        raise SystemExit(
            f"cellbench: this checkout's apex_tpu cannot serve the "
            f"configuration ({e}); no workload runs") from None
    args = conf["cellbench"]["args"]
    return FalconH1Config.from_published(
        conf, param_dtype=jnp.dtype(args["param_dtype"]),
        compute_dtype=jnp.dtype(args["compute_dtype"]))


# program leaf -> (published leaves, how to make one layer of it)
def _layout() -> Dict:
    import jax.numpy as jnp

    t = lambda w: w.T
    same = lambda w: w
    attn, ssm, mlp = "self_attn.", "mamba.", "feed_forward."
    return {
        "attn_norm": (("input_layernorm.weight",), same),
        "ffn_norm": (("pre_ff_layernorm.weight",), same),
        "wqkv": (tuple(attn + f"{n}_proj.weight" for n in "qkv"),
                 lambda *w: jnp.concatenate([x.T for x in w], axis=1)),
        "wo": ((attn + "o_proj.weight",), t),
        "w_in": ((ssm + "in_proj.weight",), t),
        "conv_w": ((ssm + "conv1d.weight",), lambda w: w[:, 0].T),
        "conv_b": ((ssm + "conv1d.bias",), same),
        "a_log": ((ssm + "A_log",), same),
        "dt_bias": ((ssm + "dt_bias",), same),
        "d_skip": ((ssm + "D",), same),
        "mamba_norm": ((ssm + "norm.weight",), same),
        "w_out": ((ssm + "out_proj.weight",), t),
        "w_gate": ((mlp + "gate_proj.weight",), t),
        "w_up": ((mlp + "up_proj.weight",), t),
        "w_down": ((mlp + "down_proj.weight",), t)}


def program_params(conf, key, param_dtype, layers=None, rows=None):
    """The program's parameter tree, born on the device in its own
    layout and dtype: each stacked leaf is one jitted program that draws
    its layers in turn (``lax.map``), so the float32 draw of one layer
    of one leaf is the largest temporary.  ``layers``: the first so many
    only; ``rows``: of the vocabulary (what a stage of a pipeline, a
    slice of the head holds)."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.models.falcon_h1 import FLOAT32_LEAVES

    shapes = weights.layer_leaves(conf)
    n = weights.sizes(conf)["L"] if layers is None else int(layers)
    out = {"layers": {}}
    for leaf, (pubs, turn) in _layout().items():
        dtype = jnp.float32 if leaf in FLOAT32_LEAVES else param_dtype

        # the key is an ARGUMENT: closed over, the seed would be a
        # constant of the program and every seed a new compile
        def stacked(k, ix, pubs=pubs, turn=turn, dtype=dtype):
            def one(i):
                lk = weights.layer_key(k, i)
                return turn(*[weights.draw_leaf(conf, lk, pub, *shapes[pub])
                              for pub in pubs]).astype(dtype)
            return jax.lax.map(one, ix)

        out["layers"][leaf] = jax.jit(stacked)(
            key, jnp.arange(n, dtype=jnp.int32))
    top = jax.jit(lambda k: weights.top_weights(conf, k, rows))(key)
    out["embed"] = top["model.embed_tokens.weight"].astype(param_dtype)
    out["head"] = top["lm_head.weight"].astype(param_dtype)
    out["final_norm"] = top["model.final_layernorm.weight"] \
        .astype(jnp.float32)
    return out


def decode_config(conf, seed):
    """The ``DecodeConfig`` of a configuration file's ``args``: the K/V
    pool is ``num_pages`` pages (the file's; not slots x the longest
    request), a sequence's table ``max_context`` positions."""
    import jax.numpy as jnp

    from apex_tpu.inference import DecodeConfig, KVCacheConfig

    args = conf["cellbench"]["args"]
    page = int(args["page_size"])
    return DecodeConfig(
        cache=KVCacheConfig(
            num_pages=int(args["num_pages"]), page_size=page,
            pages_per_seq=-(-int(args["max_context"]) // page),
            dtype=jnp.dtype(args["kv_dtype"])),
        max_batch=int(args["max_batch"]),
        max_prompt_len=int(args["max_prompt_len"]),
        prefill_buckets=tuple(int(b) for b in args["prefill_buckets"]),
        temperature=float(args["temperature"]), top_k=int(args["top_k"]),
        attn_impl=args["attn_impl"], sample_impl=args["sample_impl"],
        sample_dot_dtype=(jnp.dtype(args["sample_dot_dtype"])
                          if args.get("sample_dot_dtype") else None),
        base_seed=seed & 0xFFFFFFFF)


def build(conf, key, seed):
    """The model, its cache and the scheduler, as
    ``examples/gpt/serve_gpt.py`` builds them for this family, from a
    configuration file.  Returns ``(scheduler, decode config)``."""
    from apex_tpu.inference import ContinuousBatchingScheduler

    config, dcfg = model_config(conf), decode_config(conf, seed)
    params = program_params(conf, key, config.param_dtype)
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
    inside = lambda t: t0 <= t < t_close
    in_win = sum(1 for c in everything for t in c.token_times if inside(t))
    # a request's prompt was prefilled when its first token came
    prompt_tokens = sum(len(c.prompt) for c in everything
                        if inside(c.token_times[0]))
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
        f"{len(held)} in flight at the open; K/V pool "
        f"{kv_pool_pct or 0:.1f}% held on average; the window moved "
        f"{moved}, prefilled {prompt_tokens} prompt tokens; e2e "
        f"{ {k: round(v, 2) for k, v in e2e.items()} }; "
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
        "ssm_layers": config.num_hidden_layers,
        "window_s": window_s,
        "window_prompt_tokens": prompt_tokens,
        "window_tokens": in_win,
        **{k: moved[k] for k in moved if k.startswith("ssm_")},
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
    """``prompt`` served once more, alone, on the drained scheduler (the
    window's compiled prefill and decode step), up to
    :data:`PROBE_TOKENS` emitted tokens (fewer where the slot's pages
    end sooner).  Returns ``(tokens, state)``: the tokens the recurrent
    state has taken in by then (the prompt and every emitted token but
    the last) and the FIRST layer's state of the request's slot,
    ``(heads, P, N)`` on the host; None where the scheduler still holds
    a request it could not drain."""
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
    state = np.asarray(sched.slot_state(PROBE_RID)["ssm_state"][0])
    return list(prompt) + list(emitted[:-1]), state


def slowest_heads(w0) -> np.ndarray:
    """The :data:`PROBE_HEADS` state-space heads of layer 0 (``w0``: its
    weights in the published layout) that forget slowest: the smallest
    decay rate ``exp(A_log) softplus(dt_bias)`` a token at a zero
    input."""
    rate = np.exp(np.asarray(w0["mamba.A_log"], np.float64)) \
        * np.logaddexp(0.0, np.asarray(w0["mamba.dt_bias"], np.float64))
    return np.sort(np.argsort(rate, kind="stable")[:PROBE_HEADS])


def compare(conf, key, served, limits, probe=None, quant=None) -> List:
    """The plain reference over each sampled request's prompt and served
    tokens, layer by layer (one layer's float32 weights alive at a
    time).  The first two numbers are the widest and the mean gap by
    which a served token's reference logit lies below the reference's
    best at that position (valid because the traffic is greedy); the
    third is the distance of ``probe``'s state (:func:`probe_state`)
    from the state the reference's recurrence holds after the same
    tokens, over that state's norm, over the heads of longest memory
    (:func:`slowest_heads`).  With ``quant`` the program's
    outputs are ignored and a lower precision of the reference takes
    their place (the token it puts first, the state it holds): the
    matmuls' inputs rounded to ``quant``, or (:data:`STATE_CONTROL`) the
    recurrent state rounded to bfloat16 after every token."""
    import jax
    import jax.numpy as jnp

    top = jax.jit(lambda k: weights.top_weights(conf, k))(key)
    make = jax.jit(lambda k, i: weights.layer_weights(conf, k, i),
                   static_argnums=1)
    lower = {} if quant is None else (
        {"state_dtype": jnp.bfloat16} if quant == STATE_CONTROL
        else {"quant": quant})

    def logits(tokens, positions, quant=None, state_dtype=None):
        fn = jax.jit(lambda h, w: reference.layer(h, w, conf, quant,
                                                  state_dtype))
        return reference.logits_at(conf, top, lambda i: make(key, i), tokens,
                                   positions, quant, layer_fn=fn)

    widest, total, n_tokens, n_top = 0.0, 0.0, 0, 0
    for prompt, tokens in served:
        full = np.asarray(prompt + tokens[:-1], np.int32)
        seq = jnp.asarray(np.concatenate(
            [full, np.zeros(-len(full) % REFERENCE_PAD, np.int32)]))
        pos = jnp.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
        ref = logits(seq, pos)
        nxt = (jnp.argmax(logits(seq, pos, **lower), axis=-1) if lower
               else jnp.asarray(tokens, jnp.int32))
        best = jnp.max(ref, axis=-1)
        picked = jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]
        widest = max(widest, float(jnp.max(best - picked)))
        total += float(jnp.sum(best - picked))
        n_tokens += len(tokens)
        n_top += int(jnp.sum(jnp.argmax(ref, axis=-1) == nxt))
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
                          float("inf"), limits["ssm_state_drift"])]
    tokens, state = probe
    first = jax.jit(
        lambda *a, **kw: reference.first_ssm_state(conf, *a, **kw),
        static_argnames=("quant", "state_dtype"))
    w0 = make(key, 0)
    args = (top, w0, jnp.asarray(tokens, jnp.int32))
    heads = slowest_heads(w0)
    want = first(*args)[heads]
    got = (first(*args, **lower) if lower else jnp.asarray(state))[heads]
    drift = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    return checks + [
        (f"distance of the first layer's recurrent state from the "
         f"reference's, over its norm, in the {len(heads)} heads of longest "
         f"memory ({len(tokens)} tokens, the last "
         f"{len(tokens) - len(served[0][0])} by decode steps)", drift,
         limits["ssm_state_drift"])]
