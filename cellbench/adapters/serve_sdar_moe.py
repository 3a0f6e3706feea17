"""The serving adapter for a model that GENERATES BY DIFFUSION OVER BLOCKS
(``sdar_moe``; ``apex_tpu.models.sdar_moe``): the scheduler, the loop
(``serve.drive``), the warm-up and the counters' readback of
``adapters/serve_falcon_h1.py``, around another family's weights
(``cellbench/weights_sdar_moe.py``), layout and plain reference
(``cellbench/reference/sdar_moe.py``).

What the family changes: a request carries ``denoising_steps`` (the
generator deals them by rid, :class:`_Submitting` hands them to the
scheduler); the K/V pool is sized by the file; the counters handed to
the readers are the block step's (``blk_*``) and the expert layer's
(``moe_*``); and ``correct`` is decided on WHAT EVERY PASS DID, not on a
stream of tokens.  Four of the window's requests are drawn from the seed
BEFORE it opens, :data:`CHECKED_PER_VALUE` of each ``denoising_steps``,
each of at most :data:`CHECKED_POSITIONS` positions (a request's mean
logit gap reads 0.017 to 0.14 under one set of weights: two requests a
run left a sound run at 0.111 and the float8 control at 0.171); for these, and for no other, the
scheduler is asked to keep every pass of the slot
(``Request.record_passes`` -> ``Completion.block_trace``: the block's
ids after the pass and its kind).  They are laid out as block diffusion
is trained
(``reference.diffusion_layout``): the clean sequence, then the state
every generated block had BEFORE denoising pass ``t``: one reference
forward a ``t`` gives the reference's logits for every block at that
pass, given the state the program had.  The numbers compared:

- ``logit_gap`` and ``mean_logit_gap``, over every token at the pass
  that unmasked it: how far the reference's logit of the served token
  lies under the reference's best (as the latent cells define them);
- ``confidence_gap``: over the denoising passes that left a masked
  position, the MEAN of the reference's confidence of the best masked
  position the program did NOT choose less that of the worst it chose
  (0 where they agree), AS A SHARE of what a sampler that takes no
  notice of the confidences (it unmasks the leftmost) would have read
  on the same passes: a head that ranks by something other than the
  softmax value reads near 1.  (Over 18,991 rows a seeded model's
  confidences are of the order of 0.002 and a bfloat16 program ranks
  two that are a tenth apart either way: the WIDEST gap of a few
  hundred passes reads 0.001 for it and 0.0015 for the blind sampler,
  and the mean's scale moves twofold with the seed's weights for both
  alike, 0.6e-4 to 1.4e-4 against 1.9e-4 to 3.3e-4; their ratio reads
  0.28 to 0.44.  The line gives all three.)

The commit pass's keys and values are held by the first two on every
LATER block (the reference attends the clean tokens), and by a second
control beside the float8 one (``cellbench.control``):
``control="stale_block_kv"`` puts the reference in the program's place
with every generated block's keys and values kept from its LAST
DENOISING pass (its state before that pass, masks and all) and never
recomputed from its clean tokens.  The third control is
``confidence_gap``'s own: ``control="leftmost_unmask"`` puts the
float32 reference in the program's place with a sampler that takes no
notice of the confidences and unmasks the leftmost masked positions;
its tokens are the reference's own, so the two logit numbers read 0 and
only the ranking fails.  Every sound run's line says what that sampler
would have read on the same passes.
"""

import gc
import time
from typing import Dict, List

import numpy as np

from cellbench import arith, loadgen
from cellbench import weights_sdar_moe as weights
from cellbench.adapters import common
from cellbench.adapters.serve import WARMUP_RID, drive
from cellbench.adapters.serve_falcon_h1 import decode_config
from cellbench.adapters.serve_mla_moe import _CountersAtClose
from cellbench.reference import sdar_moe as reference

#: a checked request's positions at most (prompt + answer)
CHECKED_POSITIONS = 768
#: requests checked of each ``denoising_steps`` value of the mix
CHECKED_PER_VALUE = 2
#: the clean part and the noisy part of a reference layout are padded
#: to multiples of these: a handful of shapes compile over all seeds
CLEAN_PAD, NOISY_PAD = 256, 128
STALE_CONTROL = "stale_block_kv"
LEFTMOST_CONTROL = "leftmost_unmask"
COMMIT = 1      # inference.decode.BLOCK_COMMIT, as the trace holds it


def model_config(conf):
    """``SDARMoEConfig`` of a configuration file.  Exits, cleanly and at
    once, where the program has no such family (a commit older than
    it)."""
    import jax.numpy as jnp

    try:
        from apex_tpu.models.sdar_moe import SDARMoEConfig
    except ModuleNotFoundError as e:
        raise SystemExit(
            f"cellbench: this checkout's apex_tpu cannot serve the "
            f"configuration ({e}); no workload runs") from None
    args, s = conf["cellbench"]["args"], weights.sizes(conf)
    return SDARMoEConfig.from_published(
        conf, num_experts=s["E"], held_start=s["held_start"],
        held_count=s["held"], block_length=int(args["block_length"]),
        denoising_steps=int(args["denoising_steps"]),
        remasking=args["remasking"],
        confidence_threshold=float(args["confidence_threshold"]),
        mask_token_id=int(args["mask_token_id"]),
        param_dtype=jnp.dtype(args["param_dtype"]),
        compute_dtype=jnp.dtype(args["compute_dtype"]))


# program leaf -> (published leaves, how to make one layer of it)
def _layout() -> Dict:
    import jax.numpy as jnp

    t = lambda w: w.T
    same = lambda w: w
    each = lambda w: w.transpose(0, 2, 1)       # an expert a matrix
    attn, moe = "self_attn.", "mlp."
    return {
        "attn_norm": (("input_layernorm.weight",), same),
        "ffn_norm": (("post_attention_layernorm.weight",), same),
        "q_norm": ((attn + "q_norm.weight",), same),
        "k_norm": ((attn + "k_norm.weight",), same),
        "wqkv": (tuple(attn + f"{n}_proj.weight" for n in "qkv"),
                 lambda *w: jnp.concatenate([x.T for x in w], axis=1)),
        "wo": ((attn + "o_proj.weight",), t),
        "router": ((moe + "gate.weight",), t),
        "we_gate": ((moe + "experts.gate_proj.weight",), each),
        "we_up": ((moe + "experts.up_proj.weight",), each),
        "we_down": ((moe + "experts.down_proj.weight",), each)}


def program_params(conf, key, param_dtype):
    """The program's parameter tree, born on the device in its own
    layout and dtype: each stacked leaf is one jitted program that draws
    its layers in turn (``lax.map``), so the float32 draw of one layer
    of one leaf is the largest temporary."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.models.sdar_moe import FLOAT32_LEAVES

    shapes = weights.layer_leaves(conf)
    s = weights.sizes(conf)
    first = weights.held(conf).start
    out = {"layers": {}}
    for leaf, (pubs, turn) in _layout().items():
        dtype = jnp.float32 if leaf in FLOAT32_LEAVES else param_dtype

        # the key is an ARGUMENT: closed over, the seed would be a
        # constant of the program and every seed a new compile
        def stacked(k, ix, pubs=pubs, turn=turn, dtype=dtype):
            def one(i):
                lk = weights.layer_key(k, i)
                return turn(*[weights.draw_leaf(lk, pub, *shapes[pub], first)
                              for pub in pubs]).astype(dtype)
            return jax.lax.map(one, ix)

        out["layers"][leaf] = jax.jit(stacked)(
            key, jnp.arange(s["L"], dtype=jnp.int32))
    top = jax.jit(lambda k: weights.top_weights(conf, k))(key)
    out["embed"] = top["model.embed_tokens.weight"].astype(param_dtype)
    out["head"] = top["lm_head.weight"].astype(param_dtype)
    out["final_norm"] = top["model.norm.weight"].astype(jnp.float32)
    return out


def build(conf, key, seed):
    """The model, its cache and the scheduler, as
    ``examples/gpt/serve_gpt.py`` builds them for this family, from a
    configuration file.  Returns ``(scheduler, decode config)``."""
    from apex_tpu.inference import ContinuousBatchingScheduler

    config, dcfg = model_config(conf), decode_config(conf, seed)
    params = program_params(conf, key, config.param_dtype)
    return ContinuousBatchingScheduler(params, config, dcfg), dcfg


def warm_up(sched, dcfg, vocab, seed, steps=(2, 4)):
    """One request a prefill bucket and denoising-steps value, a few
    blocks each: every shape the window will use."""
    from apex_tpu.inference import Request

    rng = np.random.RandomState(seed % (2 ** 32))
    lo = 4
    for i, bucket in enumerate(dcfg.prefill_lengths):
        plen = max(lo, min(bucket, lo + 7))
        sched.submit(Request(
            rid=WARMUP_RID + i, max_new_tokens=9,
            prompt=rng.randint(0, vocab - 1, size=plen).tolist(),
            denoising_steps=steps[i % len(steps)]))
        lo = bucket + 4
    while not sched.idle():
        sched.step()


class _Submitting(_CountersAtClose):
    """:class:`_CountersAtClose`, and a request submitted through it
    gets its ``denoising_steps`` (``serve.drive`` builds its requests
    without: a ``TimedRequest`` has no field for it) and, if it is one
    of ``checked``, has its passes kept."""

    def __init__(self, sched, steps_of: Dict[int, int], checked=()):
        super().__init__(sched)
        self._steps_of, self._checked = steps_of, set(checked)

    def submit(self, request):
        request.denoising_steps = self._steps_of[request.rid]
        request.record_passes = request.rid in self._checked
        return self._sched.submit(request)


def pick_checked(requests, steps_of: Dict[int, int], seed: int) -> List[int]:
    """The requests the reference checks, by their place in the window's
    ``requests``: :data:`CHECKED_PER_VALUE` of each ``denoising_steps``
    value, of at most :data:`CHECKED_POSITIONS` positions, drawn from
    the seed."""
    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    out = []
    for value in sorted(set(steps_of.values())):
        pool = [rid for rid, r in enumerate(requests)
                if steps_of[rid] == value
                and len(r.prompt) + r.max_new_tokens <= CHECKED_POSITIONS]
        out += [int(r) for r in rng.choice(
            pool, size=min(CHECKED_PER_VALUE, len(pool)), replace=False)]
    return out


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
    steps_of = dict(enumerate(gen.steps(mix, len(requests), env["seed"])))
    checked = pick_checked(requests, steps_of, env["seed"])
    log(f"serve: mix {loadgen.describe(requests)}")

    warm_up(sched, dcfg, s["V"], env["seed"],
            tuple(mix["lengths"]["denoising_steps"]))
    phases.mark("warm-up of every prefill bucket and the block step")
    step_bytes = common.program_bytes(
        sched.lower_decode_step().compile().memory_analysis())
    phases.mark("block step's memory analysis")
    held = gen.in_flight_at_open(mix, s["V"], env["seed"])
    for r, t in zip(held, gen.steps(mix, len(held), 0)):
        sched.submit(Request(rid=WARMUP_RID + 100 + r.rid, prompt=r.prompt,
                             max_new_tokens=r.max_new_tokens,
                             denoising_steps=t))
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
    watched = _Submitting(sched, steps_of, checked)
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
    # a block's tokens share a stamp: the gaps between BLOCKS
    gaps = [1e3 * float(g) for c in done.values()
            for g in np.diff(sorted(set(c.token_times)))]
    window_s = t_close - t0
    inside = lambda t: t0 <= t < t_close
    in_win = sum(1 for c in everything for t in c.token_times if inside(t))
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
        f"{1e3 * max(lateness, default=0):.3f} ms; block steps "
        f"{sched_stats['decode_steps']} ({sched_stats['block_passes']} "
        f"slot-passes, {sched_stats['block_commits']} commits, "
        f"{sched_stats['wasted_slot_steps']} wasted, "
        f"{sched_stats['decode_overlapped']} launched over the step "
        f"before), prefills {sched_stats['prefills']}; {len(held)} in "
        f"flight at the open; K/V pool {kv_pool_pct or 0:.1f}% held on "
        f"average; the window moved {moved}; e2e "
        f"{ {k: round(v, 2) for k, v in e2e.items()} }; "
        f"block-step memory {step_bytes / 1e9:.2f} GB, allocator peak "
        f"{alloc_peak / 1e9:.2f} GB")

    counters = {
        "slot_occupancy_pct": (100.0 * float(np.mean(occupancy))
                               / dcfg.max_batch if occupancy else None),
        "kv_pool_used_pct": kv_pool_pct,
        "step_hbm_GB": step_bytes / 1e9,
        "lateness_mean_ms": (1e3 * float(np.mean(lateness))
                             if lateness else None),
        # the whole window (the readers scale them to the traced
        # stretch by its share of the block steps)
        "decode_steps": moved["decode_steps"],
        "moe_layers": config.num_hidden_layers,
        "experts_held": len(config.held),
        "window_s": window_s,
        "window_tokens": in_win,
        **{k: v for k, v in moved.items()
           if k.startswith(("blk_", "moe_"))},
    }
    if wt.t_start is not None and wt.t_stop is not None:
        counters["traced_steps"] = sum(
            1 for sp in wt.spans_inside(host_spans)
            if sp["name"] == "serve.decode_step")

    # ---- free the program's state, then the reference checks a sample
    served = [(list(done[rid].prompt), list(done[rid].tokens),
               steps_of[rid], [(int(a), np.asarray(row).tolist())
                               for a, row in done[rid].block_trace])
              for rid in checked if rid in done]
    del sched, watched, done, everything
    gc.collect()
    t_ref = time.time()
    checks = []
    if served:
        checks = compare(conf, key, served, limits,
                         quant=env.get("control"))
    log(f"serve: reference check of {len(served)} requests "
        f"{time.time() - t_ref:.2f} s")
    ok = common.judge(checks, {
        "no finished request to compare": not served,
        "requests failed": failed,
        "kernels tripped": common.tripped_kernels(),
        "compiles in the window": compiles.durations,
        "step rebuilds": sched_stats["step_rebuilds"],
        "block step compiled more than once": decode_compiles - 1,
    }, log)
    return {
        "correct": ok, "attempted": attempted, "failed": failed,
        "setup_s": setup_s, "e2e": e2e,
        "memory_peak_bytes": int(max(alloc_peak, step_bytes)),
        "host_spans": host_spans, "counters": counters, "checks": checks,
    }


# ------------------------------------------------------------ the check
def passes_of(prompt, tokens, block: int, mask_id: int, trace) -> Dict:
    """A served request's passes, taken apart by block.  Returns
    ``{"clean": the sequence as committed (prompt, answer and the last
    block's surplus), "blocks": [(start, [(ids before, ids after) a
    denoising pass, in order])], "faults": what of the trace breaks the
    procedure}``: a block's first state is what is left of the prompt
    beside masks; every pass's state follows from the one before; a
    commit pass changes nothing and what it commits is what was
    served."""
    P, W = len(prompt), block
    clean, blocks, faults = list(prompt[:P // W * W]), {}, []
    for start, row in trace:
        ids, kind = list(row[:W]), row[W]
        if start not in blocks:
            first = list(prompt[start:start + W])
            blocks[start] = {"state": first + [mask_id] * (W - len(first)),
                             "passes": [], "done": False}
        b = blocks[start]
        if b["done"]:
            faults.append(f"a pass after the commit of block {start}")
        changed = [i for i in range(W) if ids[i] != b["state"][i]]
        if any(b["state"][i] != mask_id or ids[i] == mask_id
               for i in changed) or len(changed) != row[W + 1]:
            faults.append(f"block {start}: a pass rewrote a clean position "
                          f"or miscounted ({b['state']} -> {ids})")
        if kind == COMMIT:
            if changed or mask_id in ids:
                faults.append(f"block {start}: committed {ids} from "
                              f"{b['state']}")
            b["done"] = True
            clean += ids
        else:
            b["passes"].append((b["state"], ids))
        b["state"] = ids
    if clean[P:P + len(tokens)] != list(tokens) \
            or len(clean) != -(-(P + len(tokens)) // W) * W:
        faults.append("the committed blocks are not the served tokens")
    return {"clean": clean, "faults": faults,
            "blocks": [(a, b["passes"]) for a, b in sorted(blocks.items())
                       if a >= P // W * W]}


def _padded_layout(clean: int, starts, block: int):
    """``reference.diffusion_layout`` padded to a few shapes: clean rows
    to a multiple of :data:`CLEAN_PAD`, noisy rows to one of
    :data:`NOISY_PAD`; a padding row sees itself alone and nothing sees
    it.  Returns ``(positions, visible, row of each noisy block's first
    position)``."""
    pos, vis = reference.diffusion_layout(clean, starts, block)
    c_pad, n = -clean % CLEAN_PAD, len(starts) * block
    n_pad = -n % NOISY_PAD
    S = clean + c_pad + n + n_pad
    order = np.concatenate([np.arange(clean), np.full(c_pad, -1),
                            clean + np.arange(n), np.full(n_pad, -1)])
    positions = np.where(order >= 0, pos[np.maximum(order, 0)], 0)
    visible = np.eye(S, dtype=bool)
    real = np.flatnonzero(order >= 0)
    visible[np.ix_(real, real)] = vis
    return positions.astype(np.int32), visible, \
        clean + c_pad + block * np.arange(len(starts))


def _left_behind(c, masked, took) -> float:
    """Of one pass that took some masked positions and left some: the
    confidence of the best it left less that of the worst it took, 0
    where every one it took is at least as good as every one it left."""
    left = [i for i, m in enumerate(masked) if m and i not in took]
    return max(float(max(c[i] for i in left) - min(c[i] for i in took)), 0.)


def compare(conf, key, served, limits, quant=None) -> List:
    """The plain reference over each checked request's passes (module
    doc), layer by layer with one layer's float32 weights alive at a
    time and every layout of every request carried side by side.  With
    ``quant`` the program's choices are ignored and a lesser reference
    takes its place, given the same states: its tokens, and the
    positions ITS confidences rank first, as many as the program
    unmasked in that pass.  ``quant`` is a precision (the matmuls'
    inputs rounded to it), :data:`STALE_CONTROL` (float32, every
    generated block's keys and values those of its state before its
    last denoising pass) or :data:`LEFTMOST_CONTROL` (the reference
    itself, unmasking the leftmost masked positions whatever their
    confidence: no second forward)."""
    import jax
    import jax.numpy as jnp

    args = conf["cellbench"]["args"]
    W, mask_id = int(args["block_length"]), int(args["mask_token_id"])
    held = weights.held(conf)
    top = jax.jit(lambda k: weights.top_weights(conf, k))(key)
    make = jax.jit(lambda k, i: weights.layer_weights(conf, k, i),
                   static_argnums=1)

    forwards_again = quant not in (None, LEFTMOST_CONTROL)
    faults, layouts = [], []
    for n, (prompt, tokens, steps, trace) in enumerate(served):
        taken = passes_of(prompt, tokens, W, mask_id, trace)
        faults += taken["faults"]
        clean = taken["clean"]
        # a block's keys and values as a program that never recomputed
        # them would hold them: its state before its last denoising pass
        stale = list(clean)
        for start, passes in taken["blocks"]:
            if passes:
                stale[start:start + W] = passes[-1][0]
        for t in range(max(len(p) for _, p in taken["blocks"])):
            at = [(a, p[t]) for a, p in taken["blocks"] if len(p) > t]
            positions, visible, first = _padded_layout(
                len(clean), [a for a, _ in at], W)
            for lesser in ((False, True) if forwards_again else (False,)):
                ids = np.full(positions.shape, mask_id, np.int32)
                ids[:len(clean)] = stale if lesser \
                    and quant == STALE_CONTROL else clean
                for row, (_, (before, _)) in zip(first, at):
                    ids[row:row + W] = before
                layouts.append({
                    "lesser": lesser, "at": at, "first": first, "request": n,
                    "positions": jnp.asarray(positions),
                    "visible": jnp.asarray(visible),
                    "h": reference.embed(top, jnp.asarray(ids))})
    precision = quant if forwards_again and quant != STALE_CONTROL else None
    fns = {False: jax.jit(lambda h, w, p, v: reference.layer(
               h, w, conf, p, v, held)),
           True: jax.jit(lambda h, w, p, v: reference.layer(
               h, w, conf, p, v, held, precision))}
    with jax.default_matmul_precision("highest"):
        for i in range(int(conf["num_hidden_layers"])):
            w = make(key, i)
            for lay in layouts:
                lay["h"] = fns[lay["lesser"]](lay["h"], w, lay["positions"],
                                              lay["visible"])
            del w
        for lay in layouts:
            rows = (lay["first"][:, None] + np.arange(W)[None]).reshape(-1)
            lay["logits"] = np.asarray(reference.head_logits(
                conf, top, lay.pop("h")[rows],
                precision if lay["lesser"] else None)) \
                .reshape(len(lay["first"]), W, -1)

    widest, total, n_tokens, n_top, n_passes = 0., 0., 0, 0, 0
    # the ranking, over the passes that LEFT a masked position: [sum,
    # widest] of what the pass read, and of what a sampler blind to the
    # confidences (the leftmost) would have read in its place
    ranked, n_ranked = {"served": [0., 0.], "leftmost": [0., 0.]}, 0
    of_request = [[0., 0] for _ in served]      # sum of gaps, tokens
    sound = [lay for lay in layouts if not lay["lesser"]]
    lesser = [lay for lay in layouts if lay["lesser"]] or [None] * len(sound)
    for ref, low in zip(sound, lesser):
        for j, (_, (before, after)) in enumerate(ref["at"]):
            z = ref["logits"][j].copy()
            z[:, mask_id] = reference.NEG
            x_ref, c = reference.token_confidence(z, mask_id)
            masked = [x == mask_id for x in before]
            chosen = [i for i in range(W) if masked[i]
                      and after[i] != mask_id]
            tok = {i: after[i] for i in chosen}
            leftmost = reference.choose(masked, c, len(chosen), "sequential")
            if quant == LEFTMOST_CONTROL:
                chosen, tok = leftmost, {i: int(x_ref[i]) for i in leftmost}
            elif low is not None:   # the lesser reference's own choice
                x_low, c_low = reference.token_confidence(
                    low["logits"][j], mask_id)
                chosen = reference.choose(masked, c_low, len(chosen),
                                          "low_confidence_static")
                tok = {i: int(x_low[i]) for i in chosen}
            for i in chosen:
                gap = float(z[i].max() - z[i, tok[i]])
                widest, total = max(widest, gap), total + gap
                n_tokens += 1
                n_top += int(np.argmax(z[i]) == tok[i])
                of_request[ref["request"]][0] += gap
                of_request[ref["request"]][1] += 1
            n_passes += 1
            if chosen and sum(masked) > len(chosen) \
                    and args["remasking"] != "sequential":
                n_ranked += 1
                for who, took in (("served", chosen), ("leftmost", leftmost)):
                    gap = _left_behind(c, masked, took)
                    ranked[who][0] += gap
                    ranked[who][1] = max(ranked[who][1], gap)
    detail = (f"{n_tokens} tokens unmasked in {n_passes} passes of "
              f"{len(served)} requests, {n_top} are the reference's own "
              f"first choice; mean gap by request "
              + "/".join(f"{g / max(k, 1):.4f}" for g, k in of_request))
    mean_of = lambda who: ranked[who][0] / max(n_ranked, 1)
    blind = mean_of("leftmost")
    checks = [
        (f"widest logit gap of a served token below the reference's best "
         f"at the pass that unmasked it ({detail})", widest,
         limits["logit_gap"]),
        (f"mean logit gap of the served tokens below the reference's best "
         f"({detail})", total / max(n_tokens, 1), limits["mean_logit_gap"]),
        (f"confidence gap as a share of a blind sampler's: the reference's "
         f"confidence of the best masked position a pass left, less that "
         f"of the worst it chose, over what unmasking the leftmost would "
         f"have read ({n_ranked} passes that left one, of {n_passes}; "
         f"mean gap {mean_of('served'):.6g}, widest "
         f"{ranked['served'][1]:.6g}, the leftmost's widest "
         f"{ranked['leftmost'][1]:.6g}; unmasking the leftmost would read "
         f"{blind:.6g})", mean_of("served") / blind if blind else 0.,
         limits["confidence_gap"])]
    if faults:
        checks.append((f"passes that break the procedure: {faults[:3]}",
                       float(len(faults)), 0.0))
    return checks
