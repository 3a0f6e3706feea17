"""The serving adapter: ``GPTConfig`` + ``DecodeConfig`` +
``ContinuousBatchingScheduler`` as ``examples/gpt/serve_gpt
.build_scheduler`` builds them, under an open-loop load generator in the
same process and thread, as ``serve_gpt.serve`` runs its own.

Differences from ``build_scheduler``, each on purpose: positions are
learned (published GPT-2; ``build_scheduler`` hard-codes rope, the
scheduler supports both), the context is the model's full 1,024
positions, and the weights are the benchmark's own seeded ones.  The
loop is ``serve_gpt.serve``'s (submit when due, ``step()``, sleep when
idle) with two changes: every latency is timed from the request's DUE
time on the scheduler's clock (``Completion.submit_time`` is the admit
time), and nothing is submitted once the window has closed.
"""

import gc
import time
from typing import Dict, List

import numpy as np

from cellbench import arith, loadgen, weights
from cellbench.adapters import common, layout
from cellbench.reference import gpt2 as reference

WARMUP_RID = 10 ** 9
DRAIN_LIMIT_S = 150.0
CHECKED_REQUESTS = 5     # the longest finished request and four more


def build(conf, key, seed, page_size=None):
    """The model, the KV pool and the scheduler as
    ``serve_gpt.build_scheduler`` builds them, from a configuration
    file.  ``page_size`` overrides the file's (the sweep times
    several).  Returns ``(scheduler, decode config)``."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.inference import (
        ContinuousBatchingScheduler, DecodeConfig, KVCacheConfig,
    )
    from apex_tpu.models.gpt import GPTConfig

    args = conf["cellbench"]["args"]
    s = weights.sizes(conf)
    config = GPTConfig(
        vocab_size=s["V"], hidden_size=s["H"], num_layers=s["L"],
        num_attention_heads=s["heads"], max_seq_len=s["P"],
        ffn_hidden_size=s["F"], layernorm_eps=conf["layer_norm_epsilon"],
        position_embedding_type="learned",
        compute_dtype=jnp.dtype(args["compute_dtype"]),
        checkpoint_layers=False)
    params = jax.jit(lambda k: layout.to_program_tree(
        weights.gpt2_weights(conf, k)))(key)
    page = int(page_size or args["page_size"])
    pages_per_seq = -(-int(args["max_context"]) // page)
    dcfg = DecodeConfig(
        cache=KVCacheConfig(
            num_pages=1 + int(args["max_batch"]) * pages_per_seq,
            page_size=page, pages_per_seq=pages_per_seq,
            dtype=jnp.dtype(args["kv_dtype"])),
        max_batch=int(args["max_batch"]),
        max_prompt_len=int(args["max_prompt_len"]),
        temperature=float(args["temperature"]), top_k=int(args["top_k"]),
        attn_impl=args["attn_impl"], sample_impl=args["sample_impl"],
        sample_dot_dtype=(jnp.dtype(args["sample_dot_dtype"])
                          if args.get("sample_dot_dtype") else None),
        base_seed=seed & 0xFFFFFFFF)
    sched = ContinuousBatchingScheduler(params, config, dcfg)
    return sched, dcfg


def drive(sched, requests, seconds, wt=None, log=print) -> Dict:
    """One window of open-loop load: ``serve_gpt.serve``'s loop on due
    times.  Submits each request when it is due, steps the scheduler,
    sleeps when idle; after ``seconds`` nothing new is submitted and
    what is queued or in flight gets a bounded drain.  Returns the raw
    record: window edges on the scheduler's clock, each request's due
    time, the generator's lateness, refusals, active slots after every
    step, pages of the KV pool held after every step, and requests in the
    system sampled after every step."""
    from apex_tpu.inference import Request

    clock = time.monotonic          # the scheduler's default clock
    pending = list(requests)
    due_at: Dict[int, float] = {}
    lateness: List[float] = []
    occupancy: List[int] = []
    live_pages: List[int] = []
    in_system: List[tuple] = []
    refused = 0
    before = len(sched.completed)
    t0 = clock()
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        if wt is not None:
            if wt.should_start(now):
                wt.start()
            elif wt.should_stop(now):
                wt.stop()
        while pending and pending[0].due <= now:
            r = pending.pop(0)
            due_at[r.rid] = t0 + r.due
            lateness.append(now - r.due)
            try:
                sched.submit(Request(rid=r.rid, prompt=r.prompt,
                                     max_new_tokens=r.max_new_tokens))
            except (ValueError, RuntimeError) as e:
                refused += 1
                log(f"serve: request {r.rid} refused: {e}")
        if sched.step():
            occupancy.append(sched.num_active)
            live_pages.append(sched.allocator.live_pages)
            in_system.append((now, len(due_at) - refused
                              - (len(sched.completed) - before)))
        elif pending:
            time.sleep(min(0.001, max(0.0, pending[0].due - now)))
    t_close = clock()
    if wt is not None and wt.running:
        wt.stop()
    # every request is due inside the window: one the loop could not
    # submit in time (a stall longer than its gap) goes in now, late
    for r in pending:
        due_at[r.rid] = t0 + r.due
        lateness.append(t_close - t0 - r.due)
        sched.submit(Request(rid=r.rid, prompt=r.prompt,
                             max_new_tokens=r.max_new_tokens))
    while not sched.idle() and clock() - t_close < DRAIN_LIMIT_S:
        sched.step()
    return {"t0": t0, "t_close": t_close, "t_drained": clock(),
            "due_at": due_at, "lateness": lateness, "refused": refused,
            "occupancy": occupancy, "live_pages": live_pages,
            "in_system": in_system,
            "late_at_close": len(pending)}


def run(env) -> Dict:
    import jax

    from apex_tpu.inference import Request
    from apex_tpu.observability import tracing

    cell, log = env["cell"], env["log"]
    conf, mix = cell["config_file"], cell["traffic_file"]
    args = conf["cellbench"]["args"]
    limits = conf["cellbench"]["correct"]
    s = weights.sizes(conf)
    key = weights.seed_key(env["seed"])
    seconds = env["seconds"]
    if env["trace"]:
        tracing.configure(capacity=1 << 17)

    phases = common.Phases(env["t_setup_start"])
    phases.mark("imports of the program")
    sched, dcfg = build(conf, key, env["seed"])
    jax.block_until_ready(sched.params)
    phases.mark("weights and scheduler")
    clock = time.monotonic
    gen = loadgen.generator(mix)
    requests = gen.requests(mix, s["V"], env["seed"], seconds)
    log(f"serve: mix {loadgen.describe(requests)}")

    # ---- warm-up: the one prefill shape and the one decode step
    rng = np.random.RandomState(env["seed"] % (2 ** 32))
    for i in range(2):
        sched.submit(Request(
            rid=WARMUP_RID + i, max_new_tokens=3,
            prompt=rng.randint(0, s["V"], size=24).tolist()))
    while not sched.idle():
        sched.step()
    phases.mark("warm-up of prefill and decode step (cache reads)")
    step_bytes = common.program_bytes(
        sched.lower_decode_step().compile().memory_analysis())
    phases.mark("decode step's memory analysis")
    # the window opens on a server in steady state, not an empty one
    held = gen.in_flight_at_open(mix, s["V"], env["seed"])
    for r in held:
        sched.submit(Request(rid=WARMUP_RID + 100 + r.rid, prompt=r.prompt,
                             max_new_tokens=r.max_new_tokens))
    while sched.queue and sched.num_active < dcfg.max_batch:
        sched.step()
    compiles = common.CompileWatch()
    compiles.start()
    gc.collect()
    phases.mark("requests in flight at the open")
    log(phases.line())

    # ---- the window
    wt = env["window_trace"]
    setup_s = time.time() - env["t_setup_start"]
    w = drive(sched, requests, seconds, wt, log)
    compiles.stop()
    t0, t_close, due_at = w["t0"], w["t_close"], w["due_at"]
    lateness, refused, occupancy = w["lateness"], w["refused"], w["occupancy"]

    everything = list(sched.completed)
    done = {c.rid: c for c in everything if c.rid < WARMUP_RID}
    attempted = len(due_at)
    short = [rid for rid, c in done.items()
             if len(c.tokens) != requests[rid].max_new_tokens]
    failed = refused + (attempted - refused - len(done)) + len(short)
    # a traced run's profiler holds the loop for seconds when it starts
    # and stops: first tokens of requests due then say nothing of the server
    shift = time.time() - clock()       # scheduler clock -> time.time()
    ttft = [1e3 * (c.token_times[0] - due_at[rid])
            for rid, c in done.items() if wt.undisturbed(due_at[rid] + shift)]
    gaps = [1e3 * float(g) for c in done.values()
            for g in np.diff(c.token_times)]
    # output tokens whose time falls inside the window, over the window
    window_s = t_close - t0
    in_win = sum(1 for c in everything for t in c.token_times
                 if t0 <= t < t_close)
    e2e = {"serve_tokens_per_s": in_win / window_s}
    if ttft:
        for q in (50, 80, 90):
            e2e[f"ttft_p{q}_ms"] = arith.percentile(ttft, q)
        e2e["ttft_mean_ms"] = float(np.mean(ttft))
    if gaps:
        e2e["gap_p95_ms"] = arith.percentile(gaps, 95)
        e2e["gap_p50_ms"] = arith.percentile(gaps, 50)
    # pages the admitted requests hold (a request reserves its prompt and
    # its whole answer at admission) over the pool's, mean over the steps
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
        f"{len(held)} in flight at the open; KV pool "
        f"{kv_pool_pct or 0:.1f}% held on average; "
        f"ttft n={len(ttft)} "
        f"{ {k: round(v, 2) for k, v in e2e.items() if 'ttft' in k} }, "
        f"gaps n={len(gaps)}; decode-step memory "
        f"{step_bytes / 1e9:.2f} GB, allocator peak {alloc_peak / 1e9:.2f} GB")

    # counts for the readers: decode tokens and the context each read,
    # inside the traced stretch (scheduler clock -> time.time())
    counters = {
        "slot_occupancy_pct": (100.0 * float(np.mean(occupancy))
                               / dcfg.max_batch if occupancy else None),
        "kv_pool_used_pct": kv_pool_pct,
        "step_hbm_GB": step_bytes / 1e9,
        "lateness_mean_ms": (1e3 * float(np.mean(lateness))
                             if lateness else None),
    }
    if wt.t_start is not None and wt.t_stop is not None:
        a, b = wt.t_start - shift, wt.t_stop - shift
        steps = {t for c in done.values() for t in c.token_times[1:]
                 if a <= t <= b}
        counters["traced_steps"] = len(steps)
        counters["traced_decode_tokens"] = sum(
            1 for c in done.values() for t in c.token_times[1:]
            if a <= t <= b)
        counters["traced_kv_positions"] = sum(
            len(c.prompt) + k for c in done.values()
            for k, t in enumerate(c.token_times[1:], start=1)
            if a <= t <= b)

    # ---- free the program's state, then the reference checks a sample
    sample = pick_sample(done, env["seed"])
    served = [(list(done[rid].prompt), list(done[rid].tokens))
              for rid in sample]
    del sched, done
    gc.collect()
    checks = []
    if served:
        checks = compare(conf, key, served, limits, quant=env.get("control"))
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


def pick_sample(done: Dict, seed: int) -> List[int]:
    """The finished request with the most tokens in all, and
    ``CHECKED_REQUESTS - 1`` more drawn from the seed."""
    if not done:
        return []
    rids = sorted(done)
    longest = max(rids, key=lambda r: (len(done[r].prompt)
                                       + len(done[r].tokens), -r))
    rest = [r for r in rids if r != longest]
    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    extra = rng.choice(rest, size=min(CHECKED_REQUESTS - 1, len(rest)),
                       replace=False).tolist() if rest else []
    return [longest] + sorted(int(r) for r in extra)


def compare(conf, key, served, limits, quant=None):
    """One full forward of the plain reference over each sampled
    request's prompt and served tokens.  The number compared is the
    widest gap by which a served token's reference logit lies below the
    reference's best at that position (valid because the traffic is
    greedy).  With ``quant`` the served tokens are ignored and the
    token the lower precision puts first takes their place (the
    control)."""
    import jax
    import jax.numpy as jnp

    s = weights.sizes(conf)
    params = jax.jit(lambda k: weights.gpt2_weights(conf, k))(key)

    # the weights are an ARGUMENT: closed over, 3 GB of them would be
    # baked into the program as constants, on the host
    def gaps_of(params, tokens, positions, nxt):
        with jax.default_matmul_precision("highest"):
            ref = reference.logits_at(params, tokens, positions, s["heads"],
                                      conf["layer_norm_epsilon"])
            if quant is not None:
                low = reference.logits_at(
                    params, tokens, positions, s["heads"],
                    conf["layer_norm_epsilon"], quant=quant)
                nxt = jnp.argmax(low, axis=-1)
        best = jnp.max(ref, axis=-1)
        picked = jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]
        return best - picked, jnp.argmax(ref, axis=-1) == nxt

    gaps_of = jax.jit(gaps_of)
    widest, n_tokens, n_top = 0.0, 0, 0
    for prompt, tokens in served:
        # pad to a multiple of 128 so a handful of shapes compile
        full = np.asarray(prompt + tokens[:-1], np.int32)
        n = len(tokens)
        pad = min(-len(full) % 128, s["P"] - len(full))
        padded = np.concatenate([full, np.zeros(pad, np.int32)])
        positions = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        ppos = np.concatenate([positions, np.full(-n % 64, positions[-1])])
        pnxt = np.concatenate([np.asarray(tokens, np.int32),
                               np.full(-n % 64, tokens[-1], np.int32)])
        gap, top = jax.device_get(gaps_of(
            params, jnp.asarray(padded), jnp.asarray(ppos),
            jnp.asarray(pnxt)))
        widest = max(widest, float(np.max(gap[:n])))
        n_tokens += n
        n_top += int(np.sum(top[:n]))
    return [(f"widest logit gap of a served token below the reference's "
             f"best ({n_tokens} tokens of {len(served)} requests, "
             f"{n_top} are the reference's own first choice)",
             widest, limits["logit_gap"])]
