"""The serving adapter for a model whose layers mix by a gated short
convolution or by grouped-query attention, over a dense or a sparse
feed-forward (``lfm2_moe``; ``apex_tpu.models.lfm2_moe``): the
scheduler, the loop (``serve.drive``), the warm-up and the counters'
readback of ``adapters/serve_falcon_h1.py``, around another family's
weights (``cellbench/weights_lfm2_moe.py``), layout and plain reference
(``cellbench/reference/lfm2_moe.py``).

:func:`run` is ``adapters/serve_falcon_h1.run`` again but for what the
family changes: the model's configuration and layout (the parameter tree
follows the layer loop's plan: unrolled layers with leaves of their own,
a period's positions stacked over its repeats); the counters handed to
the readers (``conv_layers``, ``attn_layers``, ``moe_layers``,
``experts_held``, the device-side ``conv_state_updates`` and ``moe_*``,
the scheduler's ``admit_blocked_pages`` of the window); the reference
checks :data:`CHECKED` requests of up to 3,072 positions.  The numbers
compared are that adapter's three and one more: the WIDEST and the MEAN
gap by which a served token's reference logit lies below the
reference's best, and two of the convolution layers' TAILS
(:func:`probe_state`), read out of the scheduler after
:data:`PROBE_TOKENS` decode steps of the checked prompt served again
alone: each layer's distance from the last two rows of ``z = B * x``
that the float32 reference's forward over the same tokens gives that
layer, over their norm.

- ``conv_tail_drift`` is the distance of ONE layer, :func:`probe_layer`:
  the deepest convolution layer that NO ROUTER stands before (published
  layer 2 of the benchmark's stage).  Its tail is right only if every
  decode step shifted the tails of the layers before it rightly (its
  input at the last two positions is their convolutions over the tokens
  before), and it is a smooth function of the weights' and activations'
  precision: the float8 control fails it.  A layer under a router is
  not smooth: the four best of 32 sigmoid scores lie a hundredth apart,
  so a bfloat16 program and the float32 reference often choose another
  expert for a token, and the LAST convolution layer's tail read 0.19
  on a sound run (my chip run, PR 47): a number of the flips on two
  rows' paths.
- ``widest_tail_drift`` is the LARGEST distance over ALL the
  convolution layers, those inside the period's scan among them, where
  the layer loop computes a tail's index from the repeat.  A deep
  layer's two rows read anything from 0.3 to 0.9 on a sound run (ten of
  my chip runs, PR 47), so the distance is taken over
  :data:`PROBE_READS` readings of the tails, two decode steps apart (16
  rows a layer and not 2): the norm of all the differences over the
  norm of all the reference's rows.  Its limit lies above what the
  flips read and below what a tail that is not the sequence's own reads
  (unrelated rows: about 1.4).  It is held against two PLANTED FAULTS
  (:data:`FAULTS`, ``control=``): the program itself runs, with the
  convolution layers of the scan's last repeat leaving their tails as
  the prefill wrote them (``stale_conv_tail``) or reading and shifting
  the tails of the repeat before (``wrong_conv_tail``).

With the float8 control (``cellbench.control``) the reference's own
lower precision takes the program's place in all four.
"""

import contextlib
import gc
import time
from typing import Dict, List

import numpy as np

from cellbench import arith, loadgen
from cellbench import weights_lfm2_moe as weights
from cellbench.adapters import common
from cellbench.adapters.serve import WARMUP_RID, drive, pick_sample
from cellbench.adapters.serve_falcon_h1 import decode_config
from cellbench.adapters.serve_mla_moe import _CountersAtClose, warm_up
from cellbench.reference import lfm2_moe as reference

__all__ = ["FAULTS", "build", "compare", "decode_config", "model_config",
           "planted", "probe_layer", "probe_state", "program_params", "run",
           "warm_up"]

#: reference sequences are padded to a multiple of this: at most six
#: lengths compile over all seeds
REFERENCE_PAD = 512
#: requests the reference checks: the longest the window finished and
#: one drawn from the seed
CHECKED = 2
#: decode steps of the tail probe, and how many times it reads the
#: tails: after the last step and after every second one before it
PROBE_TOKENS = 64
PROBE_READS = 8
PROBE_RID = WARMUP_RID + 50
#: ``control=`` values that run the PROGRAM with a fault planted in its
#: decode step (:func:`planted`)
STALE_TAIL, WRONG_TAIL = "stale_conv_tail", "wrong_conv_tail"
FAULTS = (STALE_TAIL, WRONG_TAIL)


def model_config(conf):
    """``LFM2MoEConfig`` of a configuration file.  Exits, cleanly and at
    once, where the program has no such family (a commit older than
    it)."""
    import jax.numpy as jnp

    try:
        from apex_tpu.models.lfm2_moe import LFM2MoEConfig
    except ModuleNotFoundError as e:
        raise SystemExit(
            f"cellbench: this checkout's apex_tpu cannot serve the "
            f"configuration ({e}); no workload runs") from None
    args = conf["cellbench"]["args"]
    return LFM2MoEConfig.from_published(
        conf, param_dtype=jnp.dtype(args["param_dtype"]),
        compute_dtype=jnp.dtype(args["compute_dtype"]))


# program leaf -> (published leaves, how to make one layer of it)
def _layout() -> Dict:
    import jax.numpy as jnp

    t = lambda w: w.T
    same = lambda w: w
    each = lambda w: w.transpose(0, 2, 1)       # an expert a matrix
    conv, attn, ffn = "conv.", "self_attn.", "feed_forward."
    return {
        "operator_norm": (("operator_norm.weight",), same),
        "ffn_norm": (("ffn_norm.weight",), same),
        "w_in": ((conv + "in_proj.weight",), t),
        "conv_w": ((conv + "conv.weight",), lambda w: w[:, 0].T),
        "w_out": ((conv + "out_proj.weight",), t),
        "wqkv": (tuple(attn + f"{n}_proj.weight" for n in "qkv"),
                 lambda *w: jnp.concatenate([x.T for x in w], axis=1)),
        "q_norm": ((attn + "q_layernorm.weight",), same),
        "k_norm": ((attn + "k_layernorm.weight",), same),
        "wo": ((attn + "out_proj.weight",), t),
        "w1": ((ffn + "w1.weight",), t), "w3": ((ffn + "w3.weight",), t),
        "w2": ((ffn + "w2.weight",), t),
        "router": ((ffn + "gate.weight",), t),
        "router_bias": ((ffn + "expert_bias",), same),
        "we_gate": ((ffn + "experts.w1.weight",), each),
        "we_up": ((ffn + "experts.w3.weight",), each),
        "we_down": ((ffn + "experts.w2.weight",), each)}


def program_params(conf, key, config):
    """The program's parameter tree (``param_shapes(config)``'s), born on
    the device in its own layout and dtype: each leaf is one jitted
    program (a period's stacked leaf draws its repeats in turn,
    ``lax.map``), so the float32 draw of one expert of one leaf is the
    largest temporary."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.models.lfm2_moe import FLOAT32_LEAVES, param_shapes

    layout = _layout()
    prefix, period, n, suffix = config.plan
    start, p = len(prefix), len(period)
    drawn = {}

    def fn(leaf, like: int, stacked: bool):
        """The jitted draw of ``leaf`` for layers shaped like layer
        ``like``: ``(key, layer) -> leaf`` or ``(key, layers) ->
        stacked leaf``.  The key is an ARGUMENT: closed over, the seed
        would be a constant of the program and every seed a new
        compile."""
        shapes = weights.layer_leaves(conf, like)
        pubs, turn = layout[leaf]
        sig = (leaf, stacked, tuple(shapes[pub] for pub in pubs))
        if sig not in drawn:
            dtype = jnp.float32 if leaf in FLOAT32_LEAVES \
                else config.param_dtype

            def one(k, i):
                lk = weights.layer_key(k, i)
                return turn(*[weights.draw_leaf(lk, pub, *shapes[pub])
                              for pub in pubs]).astype(dtype)

            drawn[sig] = jax.jit(
                (lambda k, ix: jax.lax.map(lambda i: one(k, i), ix))
                if stacked else one)
        return drawn[sig]

    shapes = param_shapes(config)
    out = {"prefix": [], "period": [], "suffix": []}
    for part, first in (("prefix", 0), ("suffix", start + n * p)):
        for j, leaves in enumerate(shapes[part]):
            at = jnp.asarray(first + j, jnp.int32)
            out[part].append({leaf: fn(leaf, first + j, False)(key, at)
                              for leaf in leaves})
    for j, leaves in enumerate(shapes["period"]):
        ix = start + j + p * jnp.arange(n, dtype=jnp.int32)
        out["period"].append({leaf: fn(leaf, start + j, True)(key, ix)
                              for leaf in leaves})
    top = jax.jit(lambda k: weights.top_weights(conf, k))(key)
    out["embed"] = top["model.embed_tokens.weight"].astype(config.param_dtype)
    out["final_norm"] = top["model.embedding_norm.weight"] \
        .astype(jnp.float32)
    return out


def build(conf, key, seed):
    """The model, its cache and the scheduler, as
    ``examples/gpt/serve_gpt.py`` builds them for this family, from a
    configuration file.  Returns ``(scheduler, decode config)``."""
    from apex_tpu.inference import ContinuousBatchingScheduler

    config, dcfg = model_config(conf), decode_config(conf, seed)
    params = program_params(conf, key, config)
    return ContinuousBatchingScheduler(params, config, dcfg), dcfg


@contextlib.contextmanager
def planted(fault, config):
    """While open, every decode step traced holds ``fault`` (one of
    :data:`FAULTS`; None: nothing is touched) in the convolution layers
    of the scan's LAST repeat (the last convolution layer where the
    layers have no period): :data:`STALE_TAIL` leaves their tails as
    they were (the prefill's, for ever), :data:`WRONG_TAIL` has them
    read and shift the tails of the repeat before.  The prefill is
    sound either way."""
    if fault is None:
        yield
        return
    import jax.numpy as jnp
    from apex_tpu.ops import kda

    prefix, period, n, _ = config.plan
    each = sum(1 for k in period if k[0] == "conv") or 1
    first = sum(1 for k in prefix if k[0] == "conv") + (n - 1) * each \
        if n else config.count("conv") - 1
    sound = kda.conv_step

    def faulty(x, w, tails, active, layer, impl="auto"):
        deep = jnp.logical_and(layer >= first, layer < first + each)
        if fault == STALE_TAIL:
            active = jnp.logical_and(active, jnp.logical_not(deep))
        else:
            layer = jnp.where(deep, jnp.maximum(layer - each, 0), layer)
        return sound(x, w, tails, active, layer, impl=impl)

    kda.conv_step = faulty
    try:
        yield
    finally:
        kda.conv_step = sound


def run(env) -> Dict:
    # exits here on a parent without the family
    config = model_config(env["cell"]["config_file"])
    fault = env.get("control") if env.get("control") in FAULTS else None
    with planted(fault, config):
        return _run(env, config, None if fault else env.get("control"))


def _run(env, config, quant) -> Dict:
    import jax

    cell, log = env["cell"], env["log"]
    conf, mix = cell["config_file"], cell["traffic_file"]
    limits = conf["cellbench"]["correct"]

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
    blocked = "admit_blocked_pages"
    before = dict(sched.read_counters(),
                  decode_steps=sched.stats["decode_steps"],
                  prefills=sched.stats["prefills"])
    blocked_before = sched.stats[blocked]
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
        f"{kv_pool_pct or 0:.1f}% held on average, admission blocked on "
        f"pages {sched_stats[blocked] - blocked_before} times and on a "
        f"slot {sched_stats['admit_blocked_slot']}; the window moved "
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
        "admit_blocked_pages": sched_stats[blocked] - blocked_before,
        # the whole window (the readers scale them to the traced
        # stretch by its share of the decode steps)
        "decode_steps": moved["decode_steps"],
        "conv_layers": config.count("conv"),
        "attn_layers": config.count("attn"),
        "moe_layers": config.num_hidden_layers - config.num_dense_layers,
        "experts_held": config.num_experts,
        "window_s": window_s,
        "window_prompt_tokens": prompt_tokens,
        "window_tokens": in_win,
        **{k: v for k, v in moved.items()
           if k.startswith(("conv_", "moe_"))},
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
    log(f"serve: tail probe {time.time() - t_ref:.2f} s")
    del sched, watched, done, everything
    gc.collect()
    t_ref = time.time()
    checks = []
    if served:
        checks = compare(conf, key, served, limits, probe, quant=quant)
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
    end sooner).  Returns ``(tokens, ends, tails)``: the tokens the
    convolution layers have taken in by the end (the prompt and every
    emitted token but the last); ``ends``, how many of them they had
    taken in at each of the (up to :data:`PROBE_READS`) readings, the
    last one ``len(tokens)``; and the tails of the request's slot at
    each reading, ``(readings, convolution layers, conv_L_cache - 1,
    hidden)`` on the host, oldest row first.  None where the scheduler
    still holds a request it could not drain."""
    from apex_tpu.inference import Request

    if not sched.idle():
        return None
    cache = sched.dcfg.cache
    steps = min(PROBE_TOKENS, cache.pages_per_seq * cache.page_size
                - len(prompt) - 1)
    sched.submit(Request(rid=PROBE_RID, prompt=prompt,
                         max_new_tokens=steps + 1))
    hidden = sched.model.config.hidden_size
    emitted, ends, tails = [], [], []
    while len(emitted) < steps:
        sched.step()
        emitted = next((m.emitted for m in sched.drain_manifest()
                        if m.rid == PROBE_RID), None)
        if emitted is None:
            return None
        left = steps - len(emitted)
        if emitted and left % 2 == 0 and left < 2 * PROBE_READS:
            rows = np.asarray(sched.slot_state(PROBE_RID)["conv_tail"],
                              np.float32)
            ends.append(len(prompt) + len(emitted) - 1)
            tails.append(rows.reshape(len(rows), -1, hidden))
    return list(prompt) + list(emitted[:-1]), ends, np.stack(tails)


def probe_layer(layer_types, num_dense_layers: int) -> int:
    """The layer (0-based) whose tail ``conv_tail_drift`` reads: the
    deepest convolution layer before which no router stands, that is,
    among the dense layers and the first expert layer (whose mixer runs
    before its router)."""
    kinds = list(layer_types)[:num_dense_layers + 1]
    return max(i for i, t in enumerate(kinds) if t == "conv")


def _padded(tokens) -> np.ndarray:
    full = np.asarray(tokens, np.int32)
    return np.concatenate(
        [full, np.zeros(-len(full) % REFERENCE_PAD, np.int32)])


def compare(conf, key, served, limits, probe=None, quant=None) -> List:
    """The plain reference over each sampled request's prompt and served
    tokens, layer by layer (one layer's float32 weights alive at a
    time).  The first two numbers are the widest and the mean gap by
    which a served token's reference logit lies below the reference's
    best at that position (valid because the traffic is greedy); the
    other two are distances of ``probe``'s tails (:func:`probe_state`)
    from the last two rows of ``z`` that the reference's forward over
    the same tokens gives each convolution layer, over their norm: the
    probed layer's (:func:`probe_layer`) and the largest of all.  With
    ``quant`` the program's outputs are ignored and the reference with
    every matmul's inputs rounded to ``quant`` takes their place (the
    token it puts first, the tails it holds)."""
    import jax
    import jax.numpy as jnp

    top = jax.jit(lambda k: weights.top_weights(conf, k))(key)
    # a compiled draw a KIND of layer, the index traced
    draw = jax.jit(lambda k, i, at: weights.layer_weights(conf, k, i, at),
                   static_argnums=2)
    make = lambda k, i: draw(k, jnp.int32(i), weights.like(conf, i))
    fns = {q: jax.jit(lambda h, w, q=q: reference.layer(h, w, conf, q))
           for q in {None, quant}}

    def logits(tokens, positions, q=None):
        return reference.logits_at(conf, top, lambda i: make(key, i), tokens,
                                   positions, q, layer_fn=fns[q])

    widest, total, n_tokens, n_top = 0.0, 0.0, 0, 0
    for prompt, tokens in served:
        seq = jnp.asarray(_padded(prompt + tokens[:-1]))
        pos = jnp.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
        ref = logits(seq, pos)
        nxt = (jnp.argmax(logits(seq, pos, quant), axis=-1) if quant
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
        return checks + [("no tail probe (the scheduler did not drain)",
                          float("inf"), limits["conv_tail_drift"])]
    tokens, ends, tails = probe
    kinds = list(conf["layer_types"])
    convs = [i for i, t in enumerate(kinds) if t == "conv"]

    def tails_of(q):
        """Every convolution layer's tail at every reading,
        (convolution layers, readings, K - 1, H)."""
        out = []
        with jax.default_matmul_precision("highest"):
            h = reference.embed(top, jnp.asarray(_padded(tokens)))
            for i in range(convs[-1] + 1):
                w = make(key, i)
                if i in convs:
                    out.append(reference.conv_tail(conf, h, w, q, ends))
                h = fns[q](h, w)
        return jnp.stack(out)

    want = tails_of(None)
    got = tails_of(quant) if quant else jnp.asarray(tails).swapaxes(0, 1)
    far = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    at = convs.index(probe_layer(kinds, conf["num_dense_layers"]))
    drift = [far(g, w) for g, w in zip(got, want)]
    widest = int(np.argmax(drift))
    rows = f"the reference's last {want.shape[2]} rows of z, over their norm"
    return checks + [
        (f"distance of a convolution layer's tail (layer {convs[at] + 1}, "
         f"the deepest before any router) from {rows} ({len(tokens)} "
         f"tokens, the last {len(tokens) - len(served[0][0])} by decode "
         f"steps)", far(got[at, -1], want[at, -1]),
         limits["conv_tail_drift"]),
        (f"widest distance of the {len(convs)} convolution layers' tails "
         f"(layer {convs[widest] + 1}'s) from {rows}, over {len(ends)} "
         f"readings two decode steps apart", drift[widest],
         limits["widest_tail_drift"])]
