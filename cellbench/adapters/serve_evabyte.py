"""The serving adapter for a byte-level model with EVA attention
(``evabyte``; ``apex_tpu.models.evabyte``): the scheduler, the loop
(``serve.drive``), the warm-up and the counters' readback of
``adapters/serve_mla_moe.py``, around another family's weights
(``cellbench/weights_evabyte.py``), layout and plain reference
(``cellbench/reference/evabyte.py``).

What differs from the other serving adapters: the cache is windowed (a
page of pooled columns a window, a window buffer a slot), so the page
table is as wide as the longest request has WINDOWS; the device-side
counters are the columns the decode attention read and the chunks and
windows that closed; and the reference checks ONE request of at most
:data:`CHECKED_POSITIONS` positions, chosen among the finished ones
whose SERVED bytes cross a window boundary, so that a window's close, a
chunk's close and the first read of a fresh page of pooled columns all
lie inside the compared stretch.

Three numbers are compared.  The first two are the latent cells': the
WIDEST and the MEAN gap by which a served token's reference logit lies
below the reference's best (valid because the traffic is greedy; next
byte: the head's first ``vocab_size`` rows).  They hold every
projection, rotation and product of the eight layers, and do not see
the precision of the pooling: chosen bytes under bfloat16 activations
move more by themselves.  The third, ``eva_summary_drift``, reads the
pooling alone (:func:`probe_summaries`): once the window has drained,
the head of the checked request is served again, alone, through the
same compiled programs, as a prompt and then :data:`PROBE_STEPS` decode
steps, and the pooled pairs that the FIRST layer's page then holds for
the chunks of its open window (the prompt's from the prefill, the last
few from ``apex_eva_summarise``) are set against the reference's
pooling (``reference.pooled_pairs``: float32 ``alpha``, the result
rounded to the cache's bfloat16) of the very columns the slot's window
buffer holds for those chunks, as a distance over the reference's norm.
Held against the reference's own keys from the embedding instead, the
number reads the bfloat16 of the projections and of the cache itself
(0.33%) and a bfloat16 ``alpha`` under it (0.16%): it could not tell
the two apart (PERF.md, section 2).  Besides the float8 control
(``cellbench.control``) there is a second,
``control="eva_alpha_bfloat16"``: the reference with the pooling
weights ``alpha`` rounded to bfloat16, in the program's place.
"""

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import numpy as np

from cellbench import arith, loadgen
from cellbench import weights_evabyte as weights
from cellbench.adapters import common
from cellbench.adapters.serve import WARMUP_RID, drive
from cellbench.adapters.serve_mla_moe import _CountersAtClose, warm_up
from cellbench.reference import evabyte as reference

#: the longest sequence the reference checks: float32 at ``highest``
#: runs at about 1 TFLOP/s on the chip and a position costs 3.2 GFLOP
#: (8 layers), so 10k positions are half a minute
CHECKED_POSITIONS = 10240
ALPHA_CONTROL = "eva_alpha_bfloat16"
#: decode steps of the probe: five chunks close by the decode step's own
#: kernel after the prompt's
PROBE_STEPS = 72
PROBE_RID = WARMUP_RID + 50
#: the draw of the requests in flight at the open (:func:`in_flight`):
#: of forty draws the one whose remaining bytes (12,943) and prompt
#: bytes (185,060) lie nearest the mean (13,390 and 184,872)
HELD_DRAW = 37


def model_config(conf):
    """``EvaByteConfig`` of a configuration file.  Exits, cleanly and at
    once, where the program cannot serve such a model (a commit older
    than the family)."""
    import jax.numpy as jnp

    try:
        from apex_tpu.models.evabyte import EvaByteConfig
    except ModuleNotFoundError as e:
        raise SystemExit(
            f"cellbench: this checkout's apex_tpu cannot serve the "
            f"configuration ({e}); no workload runs") from None
    args = conf["cellbench"]["args"]
    return EvaByteConfig.from_published(
        conf, param_dtype=jnp.dtype(args["param_dtype"]),
        compute_dtype=jnp.dtype(args["compute_dtype"]))


def _layout(s: Dict) -> Dict:
    """program leaf -> (published leaves, how to make one layer of it)."""
    import jax.numpy as jnp

    t = lambda w: w.T
    same = lambda w: w
    pool = lambda w: w.reshape(s["heads"], s["d"])
    attn = "self_attn."
    return {
        "attn_norm": (("input_layernorm.weight",), same),
        "ffn_norm": (("post_attention_layernorm.weight",), same),
        "wqkv": (tuple(attn + f"{n}_proj.weight" for n in "qkv"),
                 lambda *w: jnp.concatenate([x.T for x in w], axis=1)),
        "wo": ((attn + "o_proj.weight",), t),
        "phi": ((attn + "adaptive_phi",), pool),
        "mu": ((attn + "adaptive_mu_k",), pool),
        "w_gate": (("mlp.gate_proj.weight",), t),
        "w_up": (("mlp.up_proj.weight",), t),
        "w_down": (("mlp.down_proj.weight",), t),
    }


def program_params(conf, key, param_dtype):
    """The program's parameter tree, born on the device in its own
    layout and dtype: each stacked leaf is one jitted program that
    draws its layers in turn (``lax.map``)."""
    import jax
    import jax.numpy as jnp

    s = weights.sizes(conf)
    shapes = weights.layer_leaves(conf)
    layers = {}
    for leaf, (pubs, turn) in _layout(s).items():
        # the key is an ARGUMENT: closed over, the seed would be a
        # constant of the program and every seed a new compile
        def stacked(k, ix, pubs=pubs, turn=turn):
            def one(i):
                lk = weights.layer_key(k, i)
                return turn(*[weights.draw_leaf(conf, lk, pub, *shapes[pub])
                              for pub in pubs]).astype(param_dtype)
            return jax.lax.map(one, ix)

        layers[leaf] = jax.jit(stacked)(
            key, jnp.arange(s["L"], dtype=jnp.int32))
    top = jax.jit(lambda k: weights.top_weights(conf, k))(key)
    return {"layers": layers,
            "embed": top["model.embed_tokens.weight"].astype(param_dtype),
            "head": top["lm_head.weight"].astype(param_dtype),
            "final_norm": top["model.norm.weight"].astype(param_dtype)}


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
    # a page of pooled columns covers page x chunk positions: a window
    pages_per_seq = -(-int(args["max_context"]) // (page * config.chunk_size))
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


def in_flight(gen, mix, vocab: int, seed: int) -> List:
    """The requests in flight at the open: the SAME set of prompt
    lengths and remaining answers for every seed, as the window's
    requests are the same set of lengths for every seed; the seed draws
    the ids.  The generator draws this set afresh from every seed, and
    with 20 slots that draw alone moved a window's bytes a second by 2.9%
    (a standard deviation: twenty answers' remaining bytes, 13,390 +-
    2,320, all free of a prefill inside the window; PERF.md, section
    6), more than the order of the window's own requests does."""
    rng = np.random.RandomState((seed + 0x5EED) % (2 ** 32))
    return [dataclasses.replace(
        r, prompt=rng.randint(0, vocab, size=len(r.prompt)).tolist())
        for r in gen.in_flight_at_open(mix, vocab, HELD_DRAW)]


def pick_checked(done: Dict, seed: int, window: int) -> Optional[int]:
    """The request the reference checks: drawn from the seed among the
    finished ones of at most :data:`CHECKED_POSITIONS` positions whose
    served bytes cross a window boundary (the first served byte sits at
    position ``len(prompt)``); among all that fit where none crosses."""
    fits = [r for r in sorted(done)
            if len(done[r].prompt) + len(done[r].tokens) <= CHECKED_POSITIONS]
    crossing = [r for r in fits if len(done[r].prompt) // window
                < (len(done[r].prompt) + len(done[r].tokens) - 1) // window]
    pool = crossing or fits
    if not pool:
        return None
    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    return int(pool[rng.randint(len(pool))])


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
    held = in_flight(gen, mix, s["V"], env["seed"])
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
        f"{sched_stats['decode_steps']}, prefills {sched_stats['prefills']}, "
        f"window rollovers {sched_stats['window_rollovers']}; "
        f"{len(held)} in flight at the open; pages of pooled columns "
        f"{kv_pool_pct or 0:.1f}% held on average; the window moved "
        f"{moved}; e2e { {k: round(v, 2) for k, v in e2e.items()} }; "
        f"decode-step memory {step_bytes / 1e9:.2f} GB, allocator peak "
        f"{alloc_peak / 1e9:.2f} GB")

    every = [c for c in everything if c.rid < WARMUP_RID
             or c.rid >= WARMUP_RID + 100]
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
        "layers": config.num_hidden_layers,
        **{k: moved[k] for k in moved if k.startswith("eva_")},
        # what the traffic's lengths say the decode attention of the
        # window had to read, a layer: for every token decoded inside
        # the window, its position's live window columns and the pooled
        # columns of the windows closed before it
        **_columns_by_the_lengths(every, t0, t_close, config),
    }
    if wt.t_start is not None and wt.t_stop is not None:
        a, b = wt.t_start - shift, wt.t_stop - shift
        # requests still decoding at the close are not in `completed`
        # until drained: they are, after the drain
        steps = {t for c in every for t in c.token_times[1:] if a <= t <= b}
        counters["traced_steps"] = len(steps)
        counters["traced_decode_tokens"] = sum(
            1 for c in every for t in c.token_times[1:] if a <= t <= b)
        # the traced stretch's share of the columns: by the lengths, not
        # by its share of the steps (a stretch with a slot or two free,
        # or with shorter contexts, reads fewer columns a step)
        traced = _columns_by_the_lengths(every, a, b, config)
        counters["traced_window_cols"] = traced["expected_window_cols"]
        counters["traced_summary_cols"] = traced["expected_summary_cols"]

    log("serve: columns the decode attention read a layer in the window, "
        "counted on the device against reckoned from the lengths: own "
        f"{moved['eva_window_cols']} / {counters['expected_window_cols']}, "
        f"pooled {moved['eva_summary_cols']} / "
        f"{counters['expected_summary_cols']}")

    # ---- free the program's state, then the reference checks a request
    rid = pick_checked(done, env["seed"], config.window_size)
    served = None if rid is None else (list(done[rid].prompt),
                                       list(done[rid].tokens))
    t_ref = time.time()
    probe = probe_summaries(sched, served[0], len(served[0]), config) \
        if served else None
    log(f"serve: summary probe {time.time() - t_ref:.2f} s")
    del sched, watched, done, everything, every
    gc.collect()
    t_ref = time.time()
    checks = []
    if served:
        checks = compare(conf, key, served, limits, probe,
                         quant=env.get("control"))
    log(f"serve: reference check of request {rid} "
        f"{time.time() - t_ref:.2f} s")
    ok = common.judge(checks, {
        "no finished request to compare": served is None,
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


def _columns_by_the_lengths(completions, t0, t_close, config) -> Dict:
    """The decode attention's columns of the window, from the requests'
    lengths alone (a token emitted at ``token_times[k]``, ``k >= 1``,
    was decoded at position ``len(prompt) + k - 1``)."""
    W, per = config.window_size, config.window_size // config.chunk_size
    own = pooled = 0
    for c in completions:
        t = np.asarray(c.token_times[1:])
        pos = len(c.prompt) + np.flatnonzero((t0 <= t) & (t < t_close))
        own += int(np.sum(pos % W + 1))
        pooled += int(np.sum(pos // W)) * per
    return {"expected_window_cols": own, "expected_summary_cols": pooled}


def probe_steps(config) -> int:
    """Decode steps of the probe: :data:`PROBE_STEPS`, a quarter of a
    window at most."""
    return min(PROBE_STEPS, config.window_size // 4)


def probe_cut(plen: int, config) -> int:
    """Where the probe's prompt stops: half a chunk into a chunk, at
    most ``plen``, and so that the probe's decode steps stay inside the
    prompt's last window with at least a quarter of it behind them (a
    prompt that ends early in a window stops late in the one before)."""
    W, chunk = config.window_size, config.chunk_size
    if plen < 2 * chunk:
        return plen
    r = plen % W
    if r < W // 4 and plen >= W:        # late in the window before
        r, base = W, plen // W * W - W
    else:
        base = plen // W * W
    r = min(r, W - probe_steps(config) - chunk)
    return base + (r - chunk // 2) // chunk * chunk + chunk // 2


def probe_summaries(sched, tokens, plen, config):
    """The head of ``tokens`` (a checked request's prompt and served
    bytes; ``plen`` its prompt's length) served once more, alone, on the
    drained scheduler (the window's compiled prefill and decode step):
    a prompt of :func:`probe_cut` bytes, then :data:`PROBE_STEPS` decode
    steps.  Returns ``(positions, cut, (ktilde, vtilde), (k, v))``: how
    many positions the cache has taken in by then, how many of them
    were the probe's prompt, the FIRST layer's pooled pairs of the whole
    chunks of its open window ``(chunks, heads, d)``, and the own
    columns of those chunks as the slot's window buffer holds them
    ``(chunks * chunk, heads, d)``, all on the host in float32; None
    where the scheduler still holds a request it could not drain."""
    from apex_tpu.inference import Request

    if not sched.idle():
        return None
    W, chunk = config.window_size, config.chunk_size
    cut, steps = probe_cut(plen, config), probe_steps(config)
    sched.submit(Request(rid=PROBE_RID, prompt=list(tokens[:cut]),
                         max_new_tokens=steps + 1))
    emitted: List[int] = []
    while len(emitted) < steps:
        sched.step()
        emitted = next((m.emitted for m in sched.drain_manifest()
                        if m.rid == PROBE_RID), None)
        if emitted is None:
            return None
    state = sched.slot_state(PROBE_RID)
    taken = cut + len(emitted) - 1
    n = taken % W // chunk              # whole chunks of the open window

    def columns(pages, count):  # layer 0 of (layers, pages, heads, d, page)
        x = np.asarray(pages[0].astype(np.float32))
        return x.transpose(0, 3, 1, 2).reshape(-1, *x.shape[1:3])[:count]

    page = taken // W           # the open window's page of pooled columns
    pooled = tuple(columns(state[name][:, page:page + 1], n)
                   for name in ("k", "v"))
    own = tuple(columns(state[name + ".window"], n * chunk)
                for name in ("k", "v"))
    return taken, cut, pooled, own


def compare(conf, key, served, limits, probe=None, quant=None) -> List:
    """The plain reference over the checked request's prompt and served
    bytes, layer by layer (one layer's float32 weights alive at a time).
    The first two numbers are the widest and the mean gap by which a
    served byte's reference logit lies below the reference's best at
    that position, over the head's first ``vocab_size`` rows; the third
    is the distance of ``probe``'s pooled pairs (:func:`probe_summaries`)
    from the reference's pooling of the probe's own columns, over its
    norm.  With
    ``quant`` the program's outputs are ignored and a lower precision of
    the reference takes their place (the byte it puts first, the pooled
    pairs it makes): the matmuls' inputs rounded to ``quant``, or
    (:data:`ALPHA_CONTROL`) the pooling weights rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    V = weights.sizes(conf)["V"]
    pad_to = int(conf["window_size"])
    top = jax.jit(lambda k: weights.top_weights(conf, k))(key)
    make = jax.jit(lambda k, i: weights.layer_weights(conf, k, i),
                   static_argnums=1)
    prompt, tokens = served
    full = np.asarray(prompt + tokens[:-1], np.int32)
    seq = jnp.asarray(np.concatenate(
        [full, np.zeros(-len(full) % pad_to, np.int32)]))
    pos = jnp.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    lower = {} if quant is None else (
        {"alpha_dtype": jnp.bfloat16} if quant == ALPHA_CONTROL
        else {"quant": quant})

    def logits(quant=None, alpha_dtype=None):
        fn = jax.jit(lambda h, w: reference.layer(h, w, conf, quant,
                                                  alpha_dtype))
        return reference.logits_at(
            conf, top, lambda i: make(key, i), seq, pos, quant,
            layer_fn=fn)[:, :V]

    ref = logits()
    nxt = (jnp.argmax(logits(**lower), axis=-1) if lower
           else jnp.asarray(tokens, jnp.int32))
    best = jnp.max(ref, axis=-1)
    picked = jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]
    n_top = int(jnp.sum(jnp.argmax(ref, axis=-1) == nxt))
    detail = (f"{len(tokens)} bytes after a prompt of {len(prompt)}, "
              f"{n_top} are the reference's own first choice")
    checks = [
        (f"widest logit gap of a served byte below the reference's best "
         f"({detail})", float(jnp.max(best - picked)), limits["logit_gap"]),
        (f"mean logit gap of the served bytes below the reference's best "
         f"({detail})", float(jnp.mean(best - picked)),
         limits["mean_logit_gap"])]
    if probe is None:
        # nothing to read is a failure of the check, not a pass
        return checks + [("no summary probe (the scheduler did not drain)",
                          float("inf"), limits["eva_summary_drift"])]
    taken, cut, pooled, own = probe
    kv = jnp.dtype(conf["cellbench"]["args"]["kv_dtype"])
    pool = jax.jit(
        lambda *a, **kw: reference.pooled_pairs(
            conf, *a, cache_dtype=None if kv == jnp.float32 else kv, **kw),
        static_argnames=("quant", "alpha_dtype"))
    args = (make(key, 0), jnp.asarray(own[0]), jnp.asarray(own[1]))
    want = jnp.concatenate(pool(*args), axis=-1)
    got = jnp.concatenate(pool(*args, **lower) if lower
                          else [jnp.asarray(x) for x in pooled], axis=-1)
    drift = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    return checks + [
        (f"distance of the first layer's pooled keys and values from the "
         f"reference's pooling of the same cached columns, over its norm "
         f"({want.shape[0]} chunks of the window that position {taken} "
         f"lies in, the last {taken - cut} positions by decode steps)",
         drift, limits["eva_summary_drift"])]
