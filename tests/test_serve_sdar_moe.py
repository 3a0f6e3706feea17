"""The sixth served family (``apex_tpu/models/sdar_moe.py``: generation by
diffusion over blocks) through ``ContinuousBatchingScheduler`` against
the plain reference (``cellbench/reference/sdar_moe.py``) on seeded
weights, tiny sizes, the CPU: prefill then blocks through the paged
cache (logits, confidences, tokens; both remainders of the prompt;
``denoising_steps`` 1, 2, 4; the three strategies; slots at different
phases in one batch; greedy and seeded sampling), the shares of the
experts adding up to the uncut layer (four of two here, eight of sixteen
in the cell), the kernels of the block step against plain softmaxes, and
the scheduler's invariants."""

import json

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
        blocks = {"ids": blocks["ids"].at[slot].set(jnp.asarray(states[slot])),
                  "passes": blocks["passes"],
                  "pos": blocks["pos"].at[slot].set(keep)}
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
    finally:
        tracing.disable()
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
