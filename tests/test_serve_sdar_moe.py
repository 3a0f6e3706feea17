"""The sixth served family (``apex_tpu/models/sdar_moe.py``: generation by
diffusion over blocks) through ``ContinuousBatchingScheduler`` against
the plain reference (``cellbench/reference/sdar_moe.py``) on seeded
weights, tiny sizes, the CPU: prefill then blocks through the paged
cache (logits, confidences, tokens; both remainders of the prompt;
``denoising_steps`` 1, 2, 4; the three strategies; slots at different
phases in one batch; greedy and seeded sampling), the shares of the
experts adding up to the uncut layer (four of two here, eight of sixteen
in the cell), the kernels of the block step against plain softmaxes, and
the scheduler's invariants; and the FUSED block step (a block's commit
rides the next block's first denoising pass) against the unfused
procedure written out here, a commit pass of its own a block."""

import json
from dataclasses import replace as dataclass_replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.inference import (
    ContinuousBatchingScheduler, DecodeConfig, KVCacheConfig, Request,
)
from apex_tpu.inference.decode import (
    BLOCK_COMMIT, BLOCK_DENOISE, block_passes, init_block_state,
    make_block_prefill, make_block_step, unmask_counts,
)
from apex_tpu.models import sdar_moe as M
from cellbench import weights_sdar_moe as weights
from cellbench.adapters import serve_sdar_moe as adapter
from cellbench.reference import sdar_moe as reference

#: the catalog's row (``model-configs`` guide, ``SDAR-30B-A3B-Chat``):
#: what ``tests/cellbench/test_cellbench_sdar_moe.py`` holds the
#: committed configuration to
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
MASK = 63
TINY = {
    "model_type": "sdar_moe", "vocab_size": 64, "hidden_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "moe_intermediate_size": 16,
    "num_experts": 2, "num_experts_per_tok": 2, "attention_bias": False,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "hidden_act": "silu",
    "norm_topk_prob": True, "rope_scaling": None, "rope_theta": 1000000,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 4096,
    "published": {"num_experts": 8},
    "cellbench": {
        "held_start": 2,
        "args": {"compute_dtype": "float32", "param_dtype": "float32",
                 "block_length": 4, "denoising_steps": 4,
                 "remasking": "low_confidence_static",
                 "confidence_threshold": 0.9, "mask_token_id": MASK},
        "correct": {"logit_gap": 1e-3, "mean_logit_gap": 1e-4,
                    "confidence_gap": 1e-5}}}
KEY_SEED = 11
#: (prompt length, answer length, denoising steps): both remainders of
#: the prompt, every step count, an answer that ends inside a block
REQUESTS = ((8, 8, 4), (5, 7, 2), (3, 6, 1), (10, 12, None), (13, 4, 3))


def _conf(**args):
    conf = json.loads(json.dumps(TINY))
    conf["cellbench"]["args"].update(args)
    return conf


def _dcfg(impl="xla", temperature=0.0, **kw):
    return DecodeConfig(
        cache=KVCacheConfig(num_pages=20, page_size=8, pages_per_seq=6,
                            dtype=jnp.float32),
        max_batch=3, max_prompt_len=16, prefill_buckets=(8,),
        temperature=temperature, attn_impl=impl, sample_impl=impl,
        sample_dot_dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def tiny():
    """The tiny model's configuration file, key, program tree and the
    reference's weights, once a module."""
    conf, key = _conf(), weights.seed_key(KEY_SEED)
    params = adapter.program_params(conf, key, jnp.float32)
    top, layers = jax.jit(lambda k: (
        weights.top_weights(conf, k),
        [weights.layer_weights(conf, k, i) for i in range(2)]))(key)
    return conf, key, params, top, layers


#: the longest sequence of a test: the reference forwards every prefix
#: padded to it (ONE compile; under the block-causal mask what follows a
#: whole block does not reach it)
LONGEST = 32


def _reference_forward(conf, top, layers, head_scale=1.0):
    """``ids -> logits`` of the plain reference under the dense
    block-causal mask; ``len(ids)`` a multiple of the block."""
    held = weights.held(conf)
    whole = jax.jit(lambda ids: reference.logits_of(
        conf, top, lambda i: layers[i], held, ids, jnp.arange(LONGEST),
        jnp.asarray(reference.block_causal(LONGEST, 4))))

    def forward(ids):
        assert len(ids) % 4 == 0 and len(ids) <= LONGEST
        padded = jnp.asarray(list(ids) + [0] * (LONGEST - len(ids)),
                             jnp.int32)
        return head_scale * whole(padded)[:len(ids)]
    return forward


def _serve(conf, params, dcfg, requests=REQUESTS, seed=0, lane="interactive"):
    sched = ContinuousBatchingScheduler(params, adapter.model_config(conf),
                                        dcfg)
    rng = np.random.RandomState(seed)
    reqs = [Request(rid=i, prompt=rng.randint(0, MASK, size=p).tolist(),
                    max_new_tokens=g, denoising_steps=t, lane=lane,
                    record_passes=True)
            for i, (p, g, t) in enumerate(requests)]
    for r in reqs:
        sched.submit(r)
    done = {c.rid: c for c in sched.run_until_drained()}
    return sched, reqs, done


# ------------------------------- (1) the scheduler against the reference
@pytest.mark.parametrize("strategy", M.REMASKING)
def test_served_blocks_are_the_references_procedure(tiny, strategy):
    """Five requests over three slots (so slots sit at different phases
    of different blocks in one batch): every request's tokens are those
    of ``reference.generate`` over the reference's dense-mask forward,
    pass by pass; and the adapter's own comparison (logits and
    confidences of every pass, given the state the program had) reads
    rounding.  Under ``low_confidence_dynamic`` the head is scaled so
    that confidences pass the threshold: some passes unmask more than
    the static count, none more than are masked."""
    conf, key, params, top, layers = tiny
    scale = 6.0 if strategy == "low_confidence_dynamic" else 1.0
    conf = _conf(remasking=strategy, confidence_threshold=0.5)
    params = dict(params, head=params["head"] * scale)
    sched, reqs, done = _serve(conf, params, _dcfg())
    forward = _reference_forward(conf, top, layers, scale)
    more = 0
    for r in reqs:
        steps = r.denoising_steps or 4
        want, passes = reference.generate(
            forward, r.prompt, r.max_new_tokens, 4, steps, MASK, strategy,
            0.5)
        got = done[r.rid]
        assert got.tokens == want and len(want) == r.max_new_tokens
        rows = [(start, list(row[:4])) for start, row in got.block_trace]
        assert rows == [(start, after) for start, _, _, after in passes]
        kinds = [int(row[4]) for _, row in got.block_trace]
        assert kinds == [BLOCK_DENOISE if chosen else BLOCK_COMMIT
                         for _, _, chosen, _ in passes]
        counts = unmask_counts(4, steps)
        more += sum(len(chosen) > counts[0] for _, _, chosen, _ in passes)
    assert (more > 0) == (strategy == "low_confidence_dynamic")
    if strategy == "low_confidence_static":
        # the adapter's check (it draws the weights itself), two requests
        served = [(r.prompt, done[r.rid].tokens, r.denoising_steps or 4,
                   [(a, np.asarray(row).tolist())
                    for a, row in done[r.rid].block_trace])
                  for r in reqs[:2]]
        checks = adapter.compare(conf, key, served,
                                 conf["cellbench"]["correct"])
        assert len(checks) == 3 and all(v <= lim for _, v, lim in checks)
    # the step in flight survives: all but the first launch of a stretch
    # found the step before it unread
    assert sched.stats["decode_overlapped"] > sched.stats["decode_steps"] // 2
    assert sched.stats["wasted_slot_steps"] == 0 \
        or strategy == "low_confidence_dynamic"
    assert sched.decode_cache_size() == 1


def test_a_block_step_through_the_cache_gives_the_references_logits(tiny):
    """Prefill (whole blocks of the prompt into the pages) then ONE
    block step with ``return_logits``: slot 0 at a block that holds
    two masks, slot 2 at another position with another prompt, slot 1
    idle.  The logits of the live slots' rows are the reference's
    dense-mask forward's over prompt and block state, and the fused
    head's token and confidence (kernel through the interpreter) are
    the float32 softmax's."""
    from apex_tpu.inference.kv_cache import alloc_named_pools
    from apex_tpu.ops.decode_sampling_pallas import fused_sample_confidence

    conf, key, params, top, layers = tiny
    cfg, dcfg = adapter.model_config(conf), _dcfg()
    model = cfg.served_model()
    pools = alloc_named_pools(model.cache_spec(), dcfg.cache, slots=3)
    prefill = make_block_prefill(model, dcfg)
    step = make_block_step(model, dcfg, return_logits=True)
    rng = np.random.RandomState(3)
    prompts = {0: rng.randint(0, MASK, size=8).tolist(),
               2: rng.randint(0, MASK, size=6).tolist()}
    tables = np.zeros((3, 6), np.int32)
    tables[0, :2], tables[2, :2] = (1, 2), (3, 4)
    blocks = init_block_state(3, 4, MASK)
    states = {0: [7, MASK, 9, MASK], 2: prompts[2][4:] + [MASK, MASK]}
    for slot, prompt in prompts.items():
        keep = len(prompt) // 4 * 4
        padded = np.zeros((1, 8), np.int32)
        padded[0, :len(prompt)] = prompt
        pools = prefill(params, pools, jnp.asarray(padded), jnp.int32(keep),
                        jnp.asarray(tables[slot]))
        blocks = dict(
            blocks, pos=blocks["pos"].at[slot].set(keep),
            ids=blocks["ids"].at[slot].set(jnp.asarray(states[slot])))
    active = jnp.asarray([True, False, True])
    _, _, logits = step(params, pools, blocks, jnp.full((3,), 4, jnp.int32),
                        jnp.full((3,), 48, jnp.int32), active,
                        jnp.asarray(tables), jnp.zeros((3,), jnp.uint32))
    forward = _reference_forward(conf, top, layers)
    for slot, prompt in prompts.items():
        keep = len(prompt) // 4 * 4
        want = forward(prompt[:keep] + states[slot])[keep:]
        np.testing.assert_allclose(logits[slot], want, atol=2e-5, rtol=0)
        x0, conf_ = reference.token_confidence(want, MASK)
        hidden = jnp.linalg.lstsq(      # rows whose head product is `want`
            params["head"].astype(jnp.float32), jnp.asarray(want).T)[0].T
        tok, c = fused_sample_confidence(
            hidden, params["head"], jnp.zeros((4,), jnp.uint32),
            temperature=0.0, exclude=MASK, impl="interpret",
            dot_dtype=jnp.float32)
        exact, c_exact = reference.token_confidence(
            hidden @ params["head"].T, MASK)
        assert tok.tolist() == exact.tolist()
        np.testing.assert_allclose(c, c_exact, rtol=1e-5)


def test_either_branch_moves_the_logits(tiny):
    """The seeded weights let attention AND the experts move the stream
    (``assumed.weights``): the reference with either left out of every
    layer gives logits far from the model's."""
    conf, key, params, top, layers = tiny
    held = weights.held(conf)
    ids = jnp.asarray(np.random.RandomState(5).randint(0, MASK, size=24))
    vis = jnp.asarray(reference.block_causal(24, 4))

    def logits(branches):
        fn = lambda h, w, p, v: reference.layer(h, w, conf, p, v, held,
                                                branches=branches)
        return reference.logits_of(conf, top, lambda i: layers[i], held, ids,
                                   jnp.arange(24), vis, layer_fn=fn)

    whole = logits(("attention", "experts"))
    for left in (("attention",), ("experts",)):
        assert float(jnp.max(jnp.abs(whole - logits(left)))) > 0.1
    # and the program's full forward is the reference's
    got = M.forward(params, ids[None], adapter.model_config(conf),
                    attn_impl="xla")[0]
    np.testing.assert_allclose(got, whole, atol=2e-5, rtol=0)


@pytest.mark.parametrize("impl", ["interpret"])
def test_the_kernels_serve_what_the_twins_serve(tiny, impl):
    """The whole path through the Pallas interpreter (block-causal flash
    prefill, the block's K/V write, the folded block attention, the head
    with its confidence) serves the tokens of the XLA twins, greedy and
    with seeded sampling (same seeds, same draws)."""
    conf, key, params, top, layers = tiny
    few = REQUESTS[:3]
    for temperature in (0.0, 0.8):
        _, reqs, kernel = _serve(conf, params, _dcfg(impl, temperature), few)
        _, _, twin = _serve(conf, params, _dcfg("xla", temperature), few)
        for r in reqs:
            assert kernel[r.rid].tokens == twin[r.rid].tokens
            assert len(twin[r.rid].tokens) == r.max_new_tokens
    # sampling is seeded: another base seed, other tokens
    _, _, other = _serve(conf, params,
                         _dcfg("xla", 0.8, base_seed=99), few)
    assert any(other[i].tokens != twin[i].tokens for i in range(len(few)))


# ----------------------------------------- (2) the shares sum to the whole
def test_the_shares_of_the_experts_sum_to_the_uncut_layer(tiny):
    """Four shares of two experts each (the cell: eight of sixteen): the
    parts of the routed result that the program's held-expert layer
    gives under the softmax router, one call a share, add up to what
    the reference's UNCUT expert layer (all eight held) gives; attention
    is what every chip computes alike and is counted once: the uncut
    reference layer is the stream, its attention and that sum."""
    from apex_tpu.transformer.expert_parallel import held_experts_ffn

    conf, key, _, _, _ = tiny
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(24, 32), jnp.float32)
    pos, vis = jnp.arange(24), jnp.asarray(reference.block_causal(24, 4))
    shares = []
    for start in range(0, 8, 2):
        share = _conf()
        share["cellbench"]["held_start"] = start
        shares.append({k: v.astype(jnp.float32) for k, v in jax.jit(
            lambda k, c=share: weights.layer_weights(c, k, 0))(key).items()})

    def part(x, w, start):
        each = lambda name: w[f"mlp.experts.{name}_proj.weight"] \
            .transpose(0, 2, 1)
        return held_experts_ffn(
            x, {"router": w["mlp.gate.weight"].T, "we_gate": each("gate"),
                "we_up": each("up"), "we_down": each("down")},
            range(start, start + 2), top_k=2, n_group=1, topk_group=1,
            scale=1.0, softmax=True, impl="xla")[0]

    whole_conf = _conf()
    whole_conf["num_experts"] = 8
    whole_conf["cellbench"]["held_start"] = 0
    whole = jax.jit(lambda k: weights.layer_weights(whole_conf, k, 0))(key)
    for name in ("mlp.experts.gate_proj.weight",
                 "mlp.experts.down_proj.weight"):    # expert e is expert e
        np.testing.assert_array_equal(
            np.asarray(whole[name].astype(jnp.float32))[2:4], shares[1][name])
    with jax.default_matmul_precision("highest"):
        attn_only = reference.layer(h, whole, whole_conf, pos, vis,
                                    range(0, 8), branches=("attention",))
        x = reference.rms_norm(
            attn_only, whole["post_attention_layernorm.weight"]
            .astype(jnp.float32), 1e-6)
        # the shares route over the SAME stream: the one after attention
        summed = sum(part(x, w, start)
                     for start, w in zip(range(0, 8, 2), shares))
        uncut = reference.layer(h, whole, whole_conf, pos, vis, range(0, 8))
    np.testing.assert_allclose(attn_only + summed, uncut, atol=2e-5, rtol=0)
    # every token's two chosen experts lie in some share: no part is nil
    assert float(jnp.max(jnp.abs(summed))) > 0.1


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_the_stacked_chunk_walk_gives_what_the_whole_buffer_gives(impl):
    """``held_experts_ffn(layer=, buffer_rows=)``, the form the block
    step and the prefill run: the held assignments compacted and walked
    in chunks over the STACKED experts (only this layer's groups get
    rows) give what the ``T * top_k``-row buffer gives, with one chunk
    (all held assignments fit), with spill chunks (a chunk of 8 rows
    for some 30 held assignments) and with dead tokens; the counts
    agree."""
    from apex_tpu.transformer.expert_parallel import held_experts_ffn

    rng = np.random.RandomState(14)
    T, H, F, E, layers = 32, 16, 8, 8, 3
    held = range(2, 6)
    x = jnp.asarray(rng.randn(T, H), jnp.float32)
    params = {"router": jnp.asarray(rng.randn(H, E), jnp.float32),
              "we_gate": jnp.asarray(rng.randn(layers, 4, H, F) * .3,
                                     jnp.float32),
              "we_up": jnp.asarray(rng.randn(layers, 4, H, F) * .3,
                                   jnp.float32),
              "we_down": jnp.asarray(rng.randn(layers, 4, F, H) * .3,
                                     jnp.float32)}
    mask = jnp.asarray(rng.rand(T) > 0.2)
    kw = dict(top_k=2, n_group=1, topk_group=1, scale=1.0, token_mask=mask,
              layer=jnp.int32(1), softmax=True, impl=impl)
    whole, counts = held_experts_ffn(x, params, held, **kw)
    assert int(counts["assignments_held"]) > 16
    for rows in (64, 8):
        got, chunked = held_experts_ffn(x, params, held, buffer_rows=rows,
                                        **kw)
        np.testing.assert_allclose(got, whole, atol=1e-5, rtol=0)
        for name in ("assignments_held", "assignments_all", "experts_hit"):
            assert int(chunked[name]) == int(counts[name])
        assert (int(chunked["spill_chunks"]) > 0) == (rows == 8)
    # and another layer's experts give another result
    other, _ = held_experts_ffn(x, params, held, buffer_rows=64,
                                **dict(kw, layer=jnp.int32(2)))
    assert float(jnp.max(jnp.abs(other - whole))) > 0.1


# ----------------------------------------------------- (3) the kernels
def _dense_block_attention(q, k_pool, v_pool, tables, lengths, W):
    """(B * W, H, D) against a paged pool, dense: each of a slot's rows
    over its first ``lengths[b]`` columns."""
    B, P = tables.shape
    _, h_kv, D, page = k_pool.shape
    H = q.shape[1]
    out = np.zeros(q.shape, np.float32)
    for b in range(B):
        n = int(lengths[b])
        if not n:
            continue
        k = np.concatenate([np.asarray(k_pool[tables[b, p]], np.float32)
                            for p in range(P)], axis=-1)[:, :, :n]
        v = np.concatenate([np.asarray(v_pool[tables[b, p]], np.float32)
                            for p in range(P)], axis=-1)[:, :, :n]
        for w in range(W):
            for h in range(H):
                g = h // (H // h_kv)
                s = np.asarray(q[b * W + w, h], np.float32) @ k[g] / np.sqrt(D)
                p = np.exp(s - s.max())
                out[b * W + w, h] = (p / p.sum()) @ v[g].T
    return out


@pytest.mark.parametrize("impl", ["interpret", "xla"])
def test_block_attention_reads_a_slots_columns_for_all_of_its_rows(impl):
    """``block_decode_attention`` (the kernel through the interpreter:
    the form that walks a slot's live pages itself; and its twin)
    against a dense softmax over ragged lengths, an idle slot among
    them, every page past a slot's length and every page no slot holds
    POISONED (NaN): a value there would reach the output."""
    from apex_tpu.ops.decode_attention_pallas import block_decode_attention

    rng = np.random.RandomState(1)
    B, W, H, h_kv, D, page, P = 4, 4, 4, 2, 16, 128, 3
    # block ends (multiples of 4): inside a page, none, two pages and a
    # bit, a page to its last column
    lengths = np.asarray([52, 0, 260, 128], np.int32)
    tables = np.zeros((B, P), np.int32)
    k_pool = np.full((1 + B * P, h_kv, D, page), np.nan, np.float32)
    v_pool = np.full_like(k_pool, np.nan)
    page_id = 1
    for b in range(B):
        for p in range(-(-int(lengths[b]) // page)):
            tables[b, p] = page_id
            k_pool[page_id] = rng.randn(h_kv, D, page)
            v_pool[page_id] = rng.randn(h_kv, D, page)
            live = int(lengths[b]) - p * page
            if live < page:     # the tail of the last live page: what a
                k_pool[page_id, :, :, live:] = 1e4     # longer tenant left
                v_pool[page_id, :, :, live:] = -1e4
            page_id += 1
    q = rng.randn(B * W, H, D).astype(np.float32)
    want = _dense_block_attention(q, k_pool, v_pool, tables, lengths, W)
    # the twin gathers whole pages before it masks: poison would be 0 * NaN
    # there, so it reads zeros where the kernel must read nothing
    pools = (k_pool, v_pool) if impl == "interpret" else (
        np.nan_to_num(k_pool), np.nan_to_num(v_pool))
    got = block_decode_attention(
        jnp.asarray(q), jnp.asarray(pools[0]), jnp.asarray(pools[1]),
        jnp.asarray(tables), jnp.asarray(lengths), W, impl=impl)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    assert not np.any(np.asarray(got[W:2 * W]))     # the idle slot: zeros


@pytest.mark.parametrize("impl", ["interpret", "xla"])
def test_a_blocks_columns_are_rewritten_in_place(impl):
    """``write_block_pools``: a block's W columns land at its position
    in its page, an inactive slot and a position outside the table
    write nothing, every other column of the pool stays as it was."""
    from apex_tpu.inference.kv_cache import write_block_pools

    rng = np.random.RandomState(2)
    B, W, h_kv, D, page, L = 3, 4, 2, 8, 8, 2
    pool = rng.randn(L, 7, h_kv, D, page).astype(np.float32)
    tables = np.asarray([[1, 2, 0], [3, 0, 0], [5, 6, 0]], np.int32)
    positions = np.asarray([12, 0, 4], np.int32)
    active = np.asarray([True, False, True])
    new = rng.randn(B * W, h_kv, D).astype(np.float32)
    (got,) = write_block_pools(
        (jnp.asarray(pool),), (jnp.asarray(new),), jnp.asarray(tables),
        jnp.asarray(positions), jnp.asarray(active), W, layer=jnp.int32(1),
        impl=impl)
    want = pool.copy()
    for b, (pg, lane) in {0: (2, 4), 2: (5, 4)}.items():
        for w in range(W):
            want[1, pg, :, :, lane + w] = new[b * W + w]
    got = np.asarray(got)
    np.testing.assert_array_equal(got[:, 1:], want[:, 1:])  # page 0: garbage


@pytest.mark.parametrize("page", [128, 16])
@pytest.mark.parametrize("impl", ["interpret", "xla"])
def test_block_attention_takes_a_length_a_half_of_the_rows(impl, page):
    """``block_decode_attention`` with ``lengths`` (B, 2), the block
    step's call: 2W = 8 rows a slot x 8 query heads a key/value head are
    ONE group of 64, the first 4 rows (the held block) under the first
    length and the last 4 (the open block) under the second, W more.
    Both kernel forms (a page of whole lane tiles: the walk; a smaller
    one: the grid) and the twin against a dense softmax a row: both
    halves live in one page and across two, a dead held half (a first
    block), a dead open half (a last block's commit), a dead slot; what
    lies past a half's length is poison the other half may read."""
    from apex_tpu.ops.decode_attention_pallas import block_decode_attention

    rng = np.random.RandomState(9)
    B, W, H, h_kv, D, P = 5, 4, 16, 2, 16, 3
    pos = np.asarray([page - 4, page, 8, 2 * page + 4, 0], np.int32)
    lengths = np.stack([pos, pos + W], axis=1)
    lengths[2, 0] = 0           # nothing held: a request's first block
    lengths[3, 1] = 0           # no open block: its last block's commit
    lengths[4] = 0              # an idle slot
    tables = np.zeros((B, P), np.int32)
    k_pool = np.full((1 + B * P, h_kv, D, page), np.nan, np.float32)
    v_pool = np.full_like(k_pool, np.nan)
    page_id = 1
    for b in range(B):
        longest = int(lengths[b].max())
        for p in range(-(-longest // page)):
            tables[b, p] = page_id
            k_pool[page_id] = rng.randn(h_kv, D, page)
            v_pool[page_id] = rng.randn(h_kv, D, page)
            live = longest - p * page
            if live < page:
                k_pool[page_id, :, :, live:] = 1e4
                v_pool[page_id, :, :, live:] = -1e4
            page_id += 1
    q = rng.randn(B * 2 * W, H, D).astype(np.float32)
    # dense, a half at a time: rows (b, half, w) against lengths[b, half]
    halves = q.reshape(B, 2, W, H, D)
    want = np.stack([_dense_block_attention(
        halves[:, i].reshape(B * W, H, D), k_pool, v_pool, tables,
        lengths[:, i], W).reshape(B, W, H, D) for i in range(2)], axis=1)
    pools = (k_pool, v_pool) if impl == "interpret" else (
        np.nan_to_num(k_pool), np.nan_to_num(v_pool))
    got = block_decode_attention(
        jnp.asarray(q), jnp.asarray(pools[0]), jnp.asarray(pools[1]),
        jnp.asarray(tables), jnp.asarray(lengths), 2 * W, impl=impl)
    got = np.asarray(got).reshape(B, 2, W, H, D)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    for b, half in ((2, 0), (3, 1), (4, 0), (4, 1)):    # dead: zeros, no NaN
        assert not np.any(got[b, half])
    # the two halves of a slot differ: the open rows saw W columns more
    assert np.max(np.abs(got[0, 0] - block_decode_attention(
        jnp.asarray(halves[:1, 0].reshape(W, H, D)), jnp.asarray(pools[0]),
        jnp.asarray(pools[1]), jnp.asarray(tables[:1]),
        jnp.asarray(lengths[:1, 1]), W, impl=impl))) > 1e-3
    with pytest.raises(ValueError, match="lengths"):
        block_decode_attention(
            jnp.asarray(q), jnp.asarray(pools[0]), jnp.asarray(pools[1]),
            jnp.asarray(tables), jnp.asarray(np.repeat(lengths, 2, 1)[:, :3]),
            2 * W, impl=impl)


@pytest.mark.parametrize("page", [16, 4])
@pytest.mark.parametrize("impl", ["interpret", "xla"])
def test_two_blocks_a_slot_are_written_tile_by_tile(impl, page):
    """``write_block_pools`` with ``active`` (B, 2), the block step's
    call: the held block at ``positions`` and the open one W after it.
    Where both lie in one page the kernel path writes them in ONE grid
    step (two steps on a tile would lose the first's columns: every
    column is checked), where the open block starts a page in two
    tiles (always, where a page is one block: ``page`` 4); a dead half,
    a dead slot, a first block (nothing before position 0) and a block
    outside the table write nothing; every other column of the pool
    stays as it was."""
    from apex_tpu.inference.kv_cache import write_block_pools

    rng = np.random.RandomState(12)
    B, W, h_kv, D, L, P = 6, 4, 2, 8, 2, 32 // page
    pool = rng.randn(L, 1 + B * P, h_kv, D, page).astype(np.float32)
    tables = 1 + np.arange(B * P, dtype=np.int32).reshape(B, P)
    # the HELD block's start: one page; held ends its page, open starts
    # the next; held dead; open dead and past the table; no block before
    # the first; idle
    positions = np.asarray([4, 12, 8, 28, -4, 4], np.int32)
    active = np.asarray([[1, 1], [1, 1], [0, 1], [1, 0], [0, 1], [0, 0]],
                        bool)
    new = rng.randn(B * 2 * W, h_kv, D).astype(np.float32)
    (got,) = write_block_pools(
        (jnp.asarray(pool),), (jnp.asarray(new),), jnp.asarray(tables),
        jnp.asarray(positions), jnp.asarray(active), W, layer=jnp.int32(1),
        impl=impl)
    want = pool.copy()
    for b in range(B):
        for c in range(2 * W):
            at = int(positions[b]) + c
            if active[b, c // W] and 0 <= at < P * page:
                want[1, tables[b, at // page], :, :, at % page] \
                    = new[b * 2 * W + c]
    got = np.asarray(got)
    np.testing.assert_array_equal(got[:, 1:], want[:, 1:])  # page 0: garbage
    for b, at in ((0, 4), (0, 8), (1, 12), (1, 16), (3, 28)):   # written
        assert np.any(got[1, tables[b, at // page]]
                      != pool[1, tables[b, at // page]])


def test_the_block_write_kernel_routes_its_tiles():
    """``pool_write_block_pallas`` alone (the interpreter): a source of
    2W columns whose column 0 takes a lane that may lie LEFT of the
    tile (the run crosses a page: the same source serves two tiles, each
    the columns that fall inside it), a column mask, dead tiles at the
    garbage page."""
    from apex_tpu.ops.kv_write_pallas import pool_write_block_pallas

    rng = np.random.RandomState(13)
    h_kv, D, page, C = 2, 8, 16, 8
    pool = rng.randn(1, 5, h_kv, D, page).astype(np.float32)
    src = rng.randn(1, 4, C, h_kv, D).astype(np.float32)
    dest = np.asarray([1, 2, 3, 0], np.int32)
    first = np.asarray([6, 12, -4, 0], np.int32)
    live = np.ones((4, C), bool)
    live[0, :4] = False             # tile 0: its first block dead
    live[3] = False                 # tile 3: dead, at the garbage page
    (got,) = pool_write_block_pallas(
        (jnp.asarray(pool),), (jnp.asarray(src),), jnp.asarray(dest),
        jnp.asarray(first), jnp.asarray(live), jnp.int32(0), interpret=True)
    want = pool.copy()
    for t in range(4):
        for c in range(C):
            lane = int(first[t]) + c
            if live[t, c] and 0 <= lane < page:
                want[0, dest[t], :, :, lane] = src[0, t, c]
    np.testing.assert_array_equal(np.asarray(got), want)
    # tile 1 took columns 0..3 (lanes 12..15), tile 2 columns 4..7 (0..3)
    assert np.all(np.asarray(got)[0, 2, :, :, 12:] == np.moveaxis(
        src[0, 1, :4], 0, -1))
    assert np.all(np.asarray(got)[0, 3, :, :, :4] == np.moveaxis(
        src[0, 2, 4:], 0, -1))
    assert np.all(np.asarray(got)[0, 3, :, :, 4:] == pool[0, 3, :, :, 4:])


def test_the_block_causal_flash_forward_at_a_size_with_many_subtiles():
    """``apex_flash_fwd(block=4)`` through the interpreter against the
    dense block-causal softmax at 512 positions in sub-tiles of 128 (the
    diagonal crosses four), grouped queries; its walk is the causal one
    (no sub-tile above the diagonal becomes live) and its code no larger
    than the causal call's; what the mask cannot take is refused."""
    from apex_tpu.ops.attention import block_causal_attention
    from apex_tpu.ops.flash_attention_pallas import (
        _fwd_call, flash_fwd_pallas,
    )

    rng = np.random.RandomState(4)
    S, H, h_kv, D = 512, 4, 2, 32
    q = jnp.asarray(rng.randn(1, H, S, D), jnp.float32)
    k = jnp.asarray(rng.randn(1, h_kv, S, D), jnp.float32)
    v = jnp.asarray(rng.randn(1, h_kv, S, D), jnp.float32)
    want = block_causal_attention(q, k, v, 4, impl="xla")
    flat = lambda t: t.reshape(-1, S, D)
    got, _ = flash_fwd_pallas(flat(q), flat(k), flat(v), D ** -0.5, True, 0,
                              0, block_q=256, block_k=256, interpret=True,
                              heads=H, kv_heads=h_kv, block=4)
    np.testing.assert_allclose(got.reshape(1, H, S, D), want, atol=2e-5,
                               rtol=0)
    # a block sees further than the causal triangle: the two differ
    causal, _ = flash_fwd_pallas(flat(q), flat(k), flat(v), D ** -0.5, True,
                                 0, 0, block_q=256, block_k=256,
                                 interpret=True, heads=H, kv_heads=h_kv)
    assert float(jnp.max(jnp.abs(causal - got))) > 0.1
    # the code: the same walk, so a module no more than 2% larger
    size = lambda **kw: len(jax.export.export(
        jax.jit(lambda a, b, c: flash_fwd_pallas(
            a, b, c, 0.1, True, 0, 0, heads=32, kv_heads=4, **kw)[0]),
        platforms=["tpu"])(
            jax.ShapeDtypeStruct((32, 768, 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((4, 768, 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((4, 768, 128), jnp.bfloat16))
        .mlir_module_serialized)
    assert size(block=4) <= 1.02 * size()
    for bad in (dict(block=3), dict(block=4, window=8),
                dict(block=4, q_offset=2)):
        with pytest.raises(ValueError, match="block"):
            flash_fwd_pallas(flat(q), flat(k), flat(v), 1.0, True,
                             bad.pop("q_offset", 0), 0, interpret=True,
                             heads=H, kv_heads=h_kv, **bad)
    assert _fwd_call.cache_info().currsize > 0


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_the_heads_confidence_is_the_softmax_value(temperature):
    """``fused_sample_confidence``: kernel (interpreter) and twin give
    the token of the plain draw and, beside it, the float32 softmax's
    value at that token, over vocabulary tiles with a ragged edge, the
    excluded row out of both."""
    from apex_tpu.ops.decode_sampling_pallas import (
        fused_sample_confidence, gumbel_from_seed,
    )

    rng = np.random.RandomState(6)
    N, Hd, V, out = 12, 32, 300, 299
    x = jnp.asarray(rng.randn(N, Hd), jnp.float32)
    table = jnp.asarray(rng.randn(V, Hd) * 0.5, jnp.float32)
    table = table.at[out].set(table[out] * 20)      # would win every row
    seeds = jnp.arange(N, dtype=jnp.uint32) + 5
    z = (x @ table.T).at[:, out].set(-jnp.inf)
    if temperature:
        z = z / temperature
        want = jnp.argmax(z + gumbel_from_seed(
            seeds[:, None], jnp.arange(V, dtype=jnp.int32)[None]), axis=-1)
    else:
        want = jnp.argmax(z, axis=-1)
    conf = jnp.take_along_axis(jax.nn.softmax(z, axis=-1), want[:, None],
                               axis=-1)[:, 0]
    for impl in ("interpret", "xla"):
        tok, c = fused_sample_confidence(
            x, table, seeds, temperature=temperature, exclude=out, impl=impl,
            dot_dtype=jnp.float32)
        assert tok.tolist() == want.tolist() and out not in tok.tolist()
        np.testing.assert_allclose(c, conf, rtol=2e-5)


def test_the_softmax_router_is_softmax_then_top_k():
    from apex_tpu.transformer.expert_parallel import route_softmax

    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(40, 16), jnp.bfloat16)
    w = jnp.asarray(rng.randn(16, 12), jnp.float32)
    ids, weights_ = route_softmax(x, w, top_k=3)
    p = jax.nn.softmax(x.astype(jnp.float32) @ w, axis=-1)
    top, want = jax.lax.top_k(p, 3)
    assert ids.tolist() == want.tolist() and ids.dtype == jnp.int32
    np.testing.assert_allclose(weights_, top / top.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(weights_.sum(-1), 1.0, rtol=1e-6)


# --------------------------------------------- (4) scheduler invariants
def test_a_request_emits_its_answer_by_blocks(tiny):
    """Exactly ``max_new_tokens`` tokens; ``W - r`` at the first commit
    (r = prompt mod W) and W after, but for the last block's surplus;
    none at a denoising pass; a block's tokens share one stamp; passes
    are what the schedule says; pages reserved by prompt + answer
    rounded up to a block."""
    conf, key, params, _, _ = tiny
    sched, reqs, done = _serve(conf, params, _dcfg())
    for r in reqs:
        c, P, G = done[r.rid], len(r.prompt), r.max_new_tokens
        steps = r.denoising_steps or 4
        assert len(c.tokens) == len(c.token_times) == G
        stamps = sorted(set(c.token_times))
        sizes = [c.token_times.count(t) for t in stamps]
        first = min(4 - P % 4, G)
        assert sizes[0] == first and all(n == 4 for n in sizes[1:-1])
        assert sum(sizes) == G
        commits = [row for _, row in c.block_trace if row[4] == BLOCK_COMMIT]
        blocks = -(-(P + G) // 4) - P // 4
        assert len(commits) == blocks == len(stamps)
        assert len(c.block_trace) == block_passes(4, steps, 4 - P % 4) \
            + (blocks - 1) * block_passes(4, steps, 4)
        assert MASK not in c.tokens
    assert sched.stats["block_commits"] == sum(
        -(-(len(r.prompt) + r.max_new_tokens) // 4) - len(r.prompt) // 4
        for r in reqs)
    assert sched.allocator.live_pages == 0
    counters = sched.read_counters()
    assert counters["blk_commit_passes"] == sched.stats["block_commits"]
    assert counters["blk_denoise_passes"] + counters["blk_commit_passes"] \
        == sched.stats["block_passes"]
    assert counters["blk_rows_forwarded"] == 4 * sched.stats["block_passes"]
    assert counters["moe_assignments_all"] \
        == 2 * 2 * counters["blk_rows_forwarded"]      # top-2, two layers
    assert 0 < counters["moe_assignments_held"] \
        < counters["moe_assignments_all"]


def test_passes_are_kept_only_for_a_request_that_asks(tiny):
    """``Request.record_passes`` off (the default): the same tokens, no
    ``block_trace``, and ``serve.request`` still counts the request's
    blocks."""
    from apex_tpu.observability import tracing

    conf, key, params, _, _ = tiny
    _, reqs, kept = _serve(conf, params, _dcfg())
    tracing.configure(capacity=1 << 12)
    try:
        sched = ContinuousBatchingScheduler(
            params, adapter.model_config(conf), _dcfg())
        for r in reqs:
            sched.submit(Request(**{**r.__dict__, "record_passes": False,
                                    "trace_id": None}))
        done = {c.rid: c for c in sched.run_until_drained()}
        spans = {s["attrs"]["rid"]: s["attrs"]
                 for s in tracing.get_tracer().spans()
                 if s["name"] == "serve.request"}
        steps = [s["attrs"] for s in tracing.get_tracer().spans()
                 if s["name"] == "serve.decode_step"]
    finally:
        tracing.disable()
    # the block step's span says how many commits it held and how many of
    # them rode a denoising pass of their slot: all but a request's last
    read = [a for a in steps if "commits" in a]
    assert sum(a["commits"] for a in read) == sched.stats["block_commits"]
    assert sum(a["fused_commits"] for a in read) \
        == sched.read_counters()["blk_commits_fused"] \
        == sched.stats["block_commits"] - len(reqs)
    assert sum(a["emitted"] for a in read) \
        == sum(r.max_new_tokens for r in reqs)
    assert max(a["block_rows"] for a in steps) == 2 * 4 * 3
    for r in reqs:
        assert done[r.rid].block_trace is None
        assert done[r.rid].tokens == kept[r.rid].tokens
        P, G = len(r.prompt), r.max_new_tokens
        assert spans[r.rid]["blocks"] == -(-(P + G) // 4) - P // 4
        assert spans[r.rid]["denoising_steps"] == (r.denoising_steps or 4)


def test_preemption_mid_block_loses_no_committed_block(tiny):
    """A best-effort request preempted while a block is being denoised
    continues from its committed blocks: the block in progress is
    started again, and the tokens served in all are those of an
    unpreempted run."""
    conf, key, params, _, _ = tiny
    dcfg = DecodeConfig(
        cache=KVCacheConfig(num_pages=7, page_size=8, pages_per_seq=6,
                            dtype=jnp.float32),
        max_batch=2, max_prompt_len=32, prefill_buckets=(8, 16),
        temperature=0.0, attn_impl="xla", sample_impl="xla",
        sample_dot_dtype=jnp.float32)
    rng = np.random.RandomState(8)
    slow = Request(rid=0, prompt=rng.randint(0, MASK, size=6).tolist(),
                   max_new_tokens=18, lane="best_effort", denoising_steps=4,
                   record_passes=True)
    fast = Request(rid=1, prompt=rng.randint(0, MASK, size=20).tolist(),
                   max_new_tokens=8, denoising_steps=2)
    cfg = adapter.model_config(conf)
    alone = ContinuousBatchingScheduler(params, cfg, dcfg)
    alone.submit(Request(**{**slow.__dict__, "trace_id": None}))
    (want,) = alone.run_until_drained()
    sched = ContinuousBatchingScheduler(params, cfg, dcfg)
    sched.submit(slow)
    for _ in range(8):      # two blocks committed, the third in progress
        sched.step()
    kept = sched.drain_manifest()[0]
    assert kept.denoising_steps == 4 and 0 < len(kept.emitted) < 18
    sched.submit(fast)      # 4 pages of 6: the resident must yield
    done = {c.rid: c for c in sched.run_until_drained()}
    assert sched.stats["preemptions"] == 1 and done[0].preemptions == 1
    assert done[0].tokens[:len(kept.emitted)] == kept.emitted
    assert done[0].tokens == want.tokens and len(done[1].tokens) == 8
    assert done[0].prompt == slow.prompt
    commits = [a for a, row in done[0].block_trace if row[4] == BLOCK_COMMIT]
    assert commits == sorted(set(commits)) == list(range(4, 24, 4))


def test_what_a_block_generating_model_cannot_be_served_with(tiny):
    """``denoising_steps`` is refused by a model that yields a token a
    step; a block-generating one refuses ``draft_len``,
    ``prefill_chunk``, ``prefix_sharing`` and ``top_k`` with a reason,
    a ``denoising_steps`` outside [1, W], ``eos_id``, and a page that a
    block would straddle."""
    from apex_tpu.models.gpt import GPTConfig, init_params

    conf, key, params, _, _ = tiny
    cfg = adapter.model_config(conf)
    for kw, reason in ((dict(draft_len=2), "draft_len"),
                       (dict(prefill_chunk=8), "prefill_chunk"),
                       (dict(prefix_sharing=True), "prefix_sharing"),
                       (dict(top_k=5), "top_k")):
        with pytest.raises(NotImplementedError, match=reason):
            ContinuousBatchingScheduler(params, cfg, _dcfg(**kw))
    with pytest.raises(ValueError, match="multiples of the block"):
        ContinuousBatchingScheduler(params, cfg, DecodeConfig(
            cache=KVCacheConfig(num_pages=8, page_size=6, pages_per_seq=4,
                                dtype=jnp.float32),
            max_batch=2, max_prompt_len=12))
    sched = ContinuousBatchingScheduler(params, cfg, _dcfg())
    for bad, reason in ((dict(denoising_steps=5), "denoising_steps"),
                        (dict(denoising_steps=0), "denoising_steps"),
                        (dict(eos_id=3), "eos_id")):
        with pytest.raises(ValueError, match=reason):
            sched.submit(Request(rid=9, prompt=[1, 2, 3], max_new_tokens=4,
                                 **bad))
    gpt = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_attention_heads=2, max_seq_len=32,
                    compute_dtype=jnp.float32, checkpoint_layers=False)
    plain = ContinuousBatchingScheduler(
        init_params(gpt, jax.random.PRNGKey(0)), gpt,
        DecodeConfig(cache=KVCacheConfig(num_pages=8, page_size=8,
                                         pages_per_seq=4, dtype=jnp.float32),
                     max_batch=2, max_prompt_len=16, attn_impl="xla",
                     sample_impl="xla"))
    with pytest.raises(ValueError, match="denoising_steps"):
        plain.submit(Request(rid=1, prompt=[1, 2], max_new_tokens=2,
                             denoising_steps=2))
    with pytest.raises(ValueError, match="block_length"):
        M.SDARMoEConfig(block_length=4, denoising_steps=5)


# ------------------- (5) the fused step against the unfused procedure
def _unfused(params, cfg, dcfg, prompt, answer, steps, row):
    """Generation by blocks as it ran before a commit rode a pass, one
    request in slot 0: every forward is ONE block through the cache
    (``decode_block`` with the held half dead), a denoising pass while
    the block holds a mask (plain float32 logits, the mask's row out,
    the reference's choice), then a COMMIT pass of its own over the
    clean block.  Returns ``(tokens, trace rows, forwards, pools)``."""
    from apex_tpu.inference.kv_cache import alloc_named_pools

    model, W = cfg.served_model(), 4
    B, P = dcfg.max_batch, len(prompt)
    tables = np.zeros((B, dcfg.cache.pages_per_seq), np.int32)
    tables[0] = row
    pools = alloc_named_pools(model.cache_spec(), dcfg.cache, slots=B)
    keep = P // W * W
    if keep:
        padded = np.zeros((1, next(b for b in dcfg.prefill_lengths
                                   if b >= keep)), np.int32)
        padded[0, :min(P, padded.shape[1])] = prompt[:padded.shape[1]]
        pools = make_block_prefill(model, dcfg)(
            params, pools, jnp.asarray(padded), jnp.int32(keep),
            jnp.asarray(row))

    @jax.jit
    def forward(pools, ids, pos):
        tokens = jnp.zeros((B, 2, W), jnp.int32).at[0, 1].set(ids)
        hidden, pools = model.decode_block(
            params, tokens.reshape(-1),
            jnp.zeros((B,), jnp.int32).at[0].set(pos),
            jnp.zeros((B, 2), bool).at[0, 1].set(True), pools,
            jnp.asarray(tables), "xla")
        return hidden[:W].astype(jnp.float32) \
            @ params["head"].T.astype(jnp.float32), pools

    seq, trace, forwards = list(prompt[:keep]), [], 0
    for start in range(keep, -(-(P + answer) // W) * W, W):
        ids = list(prompt[start:start + W])
        ids += [MASK] * (W - len(ids))
        for n_t in unmask_counts(W, steps) + [0]:
            logits, pools = forward(pools, jnp.asarray(ids), start)
            forwards += 1
            if MASK not in ids:     # the commit: the clean block's columns
                trace.append((start, ids + [BLOCK_COMMIT, 0]))
                break
            x0, conf = reference.token_confidence(logits, MASK)
            chosen = reference.choose([i == MASK for i in ids], conf, n_t,
                                      "low_confidence_static")
            ids = [int(x0[i]) if i in chosen else t
                   for i, t in enumerate(ids)]
            trace.append((start, ids + [BLOCK_DENOISE, len(chosen)]))
        seq += ids
    return seq[P:P + answer], trace, forwards, pools


def _columns(pools, row, page_size, upto):
    """A sequence's cached columns ``[0, upto)`` of every layer, keys
    and values: (2, L, upto, kv heads, d)."""
    at = np.arange(upto)
    return np.stack([np.asarray(pools[n])[:, row[at // page_size], :, :,
                                          at % page_size]
                     for n in ("k", "v")]).swapaxes(1, 2)


#: name: (page size, slots, impl, [(prompt, answer, denoising steps)])
FUSED_CASES = {
    "steps_1": (8, 3, "xla", [(8, 12, 1)]),
    "steps_2": (8, 3, "xla", [(8, 12, 2)]),
    "steps_4": (8, 3, "xla", [(8, 12, 4)]),
    # the first block opens on two prompt tokens and takes fewer passes
    "prompt_remainder": (8, 3, "xla", [(6, 9, 4)]),
    "one_block": (8, 3, "xla", [(8, 3, 2)]),
    # blocks at 8 and 12 of a page of 16: the held block and the open
    # one share their page, ONE grid step writes both
    "held_and_open_share_a_page": (16, 2, "interpret", [(8, 8, 2)]),
    # blocks at 12 and 16: the open block starts a page, two tiles
    "open_block_starts_a_page": (16, 2, "interpret", [(12, 8, 2)]),
    # one slot: the second request takes it when the first has left
    "slot_taken_again": (8, 1, "xla", [(8, 8, 1), (5, 6, 4), (4, 4, 2)]),
}


@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_the_fused_step_is_the_unfused_procedure_in_fewer_steps(tiny, case):
    """A block's commit rides the next block's first denoising pass:
    against the unfused procedure (:func:`_unfused`, a commit pass of
    its own) the scheduler serves the same tokens and the same trace
    block by block (a ``BLOCK_COMMIT`` row a block, its ids unchanged),
    leaves the same columns in the pool after every commit (a committed
    block's columns are never written again: the pool at the end holds
    them all), and takes a step fewer a block but the last: the
    denoising passes and ONE more, not one more a block."""
    conf, key, params, _, _ = tiny
    page, slots, impl, requests = FUSED_CASES[case]
    dcfg = DecodeConfig(
        cache=KVCacheConfig(num_pages=12, page_size=page, pages_per_seq=4,
                            dtype=jnp.float32),
        max_batch=slots, max_prompt_len=16, prefill_buckets=(8,),
        temperature=0.0, attn_impl=impl, sample_impl=impl,
        sample_dot_dtype=jnp.float32)
    cfg = adapter.model_config(conf)
    sched = ContinuousBatchingScheduler(params, cfg, dcfg)
    rng = np.random.RandomState(21)
    reqs = [Request(rid=i, prompt=rng.randint(0, MASK, size=p).tolist(),
                    max_new_tokens=g, denoising_steps=t, record_passes=True)
            for i, (p, g, t) in enumerate(requests)]
    rows = {}
    for r in reqs:
        sched.submit(r)
    while not sched.idle():
        sched.step()
        for i, s in enumerate(sched._slots):
            if s is not None:
                rows.setdefault(s.request.rid, sched._page_tables[i].copy())
    done = {c.rid: c for c in sched.completed}
    steps = blocks = 0
    twin = dataclass_replace(dcfg, attn_impl="xla", sample_impl="xla")
    for r in reqs:
        tokens, trace, forwards, pools = _unfused(
            params, cfg, twin, r.prompt, r.max_new_tokens,
            r.denoising_steps, rows[r.rid])
        got = done[r.rid]
        assert got.tokens == tokens and len(tokens) == r.max_new_tokens
        assert [(a, list(map(int, row))) for a, row in got.block_trace] \
            == trace
        n = sum(row[4] == BLOCK_COMMIT for _, row in trace)
        assert n == -(-(len(r.prompt) + len(tokens)) // 4) \
            - len(r.prompt) // 4
        blocks, steps = blocks + n, steps + forwards - (n - 1)
        if r is reqs[-1]:   # its pages were not taken again
            upto = -(-(len(r.prompt) + len(tokens)) // 4) * 4
            np.testing.assert_allclose(
                _columns(sched.pools, rows[r.rid], page, upto),
                _columns(pools, rows[r.rid], page, upto), atol=1e-5, rtol=0)
    # one slot or one request: the steps are the requests' own, added up
    assert sched.stats["decode_steps"] == steps
    assert sched.stats["block_commits"] == blocks
    counters = sched.read_counters()
    assert counters["blk_commit_passes"] == blocks
    assert counters["blk_commits_fused"] == blocks - len(reqs)
    assert counters["blk_denoise_passes"] + blocks \
        == sched.stats["block_passes"] == steps + blocks - len(reqs)
    assert counters["blk_rows_forwarded"] == 4 * sched.stats["block_passes"]
    assert sched.stats["wasted_slot_steps"] == 0
    assert sched.decode_cache_size() == 1


def test_a_held_block_does_not_outlive_its_tenant(tiny):
    """One slot.  A best-effort request of one denoising step a block
    (every pass leaves a clean block HELD for the next step) is
    preempted between two steps; the interactive request that takes the
    slot starts with no held block (``_set_block`` clears the flag: a
    leaked one would be committed at ``pos - W``, over the last block of
    the newcomer's prompt) and is served what it is served alone; the
    preempted request loses the block it held (its tokens had not been
    emitted: a token is emitted at its block's commit) and serves in
    all what an unpreempted run serves."""
    conf, key, params, _, _ = tiny
    dcfg = DecodeConfig(
        cache=KVCacheConfig(num_pages=12, page_size=8, pages_per_seq=5,
                            dtype=jnp.float32),
        max_batch=1, max_prompt_len=32, prefill_buckets=(8, 16, 32),
        temperature=0.0, attn_impl="xla", sample_impl="xla",
        sample_dot_dtype=jnp.float32)
    cfg = adapter.model_config(conf)
    rng = np.random.RandomState(22)
    slow = Request(rid=0, prompt=rng.randint(0, MASK, size=8).tolist(),
                   max_new_tokens=20, lane="best_effort", denoising_steps=1,
                   record_passes=True)
    fast = Request(rid=1, prompt=rng.randint(0, MASK, size=8).tolist(),
                   max_new_tokens=8, denoising_steps=2, record_passes=True)
    alone = {}
    for r in (slow, fast):
        one = ContinuousBatchingScheduler(params, cfg, dcfg)
        one.submit(Request(**{**r.__dict__, "trace_id": None}))
        (alone[r.rid],) = one.run_until_drained()
    sched = ContinuousBatchingScheduler(params, cfg, dcfg)
    sched.submit(slow)
    for _ in range(4):
        sched.step()
    sched.drain_manifest()      # settles the step in flight
    assert bool(sched._blocks["held_live"][0])      # a clean block waits
    emitted = len(sched._slots[0].generated)
    assert 0 < emitted < 20 and emitted % 4 == 0
    sched.submit(fast)
    sched.step()                # preempts, admits: the flag is cleared
    assert sched._slots[0].request.rid == 1
    assert not bool(sched._blocks["held_live"][0])
    done = {c.rid: c for c in sched.run_until_drained()}
    assert sched.stats["preemptions"] == 1
    for rid in (0, 1):
        assert done[rid].tokens == alone[rid].tokens
    assert [(a, list(map(int, row))) for a, row in done[1].block_trace] \
        == [(a, list(map(int, row))) for a, row in alone[1].block_trace]
    commits = [a for a, row in done[0].block_trace if row[4] == BLOCK_COMMIT]
    assert commits == list(range(8, 28, 4))
