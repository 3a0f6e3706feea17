"""The KDA ops (``ops/kda.py``) and a model whose layers mix by KDA or
by positionless MLA (``models/mla_moe.py`` with ``layer_kinds``) against
the plain reference (``cellbench/reference/kda_mla_moe.py``), at a small
size on the CPU: 5 layers (KDA + dense; KDA, KDA, MLA, KDA with
experts), hidden 64, 4 heads of 16, conv 4, ranks 16 + 8, 32 experts,
top-4, 8 held.  Seeded weights in the published layout
(``cellbench/weights_kda_mla_moe.py``), float32 on both sides, so every
comparison is to reduction-order rounding: the chunked form sums a
chunk's 64 rank-one updates in another order than the recurrence, and
the decays enter as differences of running sums."""

import copy
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from apex_tpu.inference import (  # noqa: E402
    ContinuousBatchingScheduler, DecodeConfig, KVCacheConfig, Request,
)
from apex_tpu.inference.decode import (  # noqa: E402
    decode_logits_tokenwise, make_decode_step, make_prefill,
)
from apex_tpu.inference.kv_cache import (  # noqa: E402
    COUNTERS, PerSlot, alloc_named_pools,
)
from apex_tpu.models import mla_moe  # noqa: E402
from apex_tpu.ops import kda  # noqa: E402
from cellbench import weights_kda_mla_moe as weights  # noqa: E402
from cellbench.adapters import serve_kda_mla_moe as adapter  # noqa: E402
from cellbench.reference import kda_mla_moe as reference  # noqa: E402

TINY = {
    "model_type": "kimi_linear", "vocab_size": 256,
    "model_max_length": 4096, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_shared_experts": 1, "num_experts": 8,
    "routed_scaling_factor": 2.446, "kv_lora_rank": 16,
    "q_lora_rank": None, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "qk_nope_head_dim": 16, "num_expert_group": 1, "topk_group": 1,
    "num_experts_per_token": 4, "first_k_dense_replace": 1,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "rope_scaling": None,
    "mla_use_nope": True,
    "linear_attn_config": {"full_attn_layers": [4],
                           "kda_layers": [1, 2, 3, 5], "num_heads": 4,
                           "head_dim": 16, "short_conv_kernel_size": 4},
    "published": {"num_experts": 32},
    "cellbench": {"adapter": "serve_kda_mla_moe", "held_start": 8,
                  "args": {"compute_dtype": "float32",
                           "param_dtype": "float32"}},
}
SEED = 2 ** 31 + 4321       # a large seed, as the driver's are
#: float32 on both sides: the widest difference read is 1.5e-6 on logits
#: of 0.6 (reduction order); ten times that
TOL = 2e-5


def _conf(held_start=8, held=8):
    conf = copy.deepcopy(TINY)
    conf["num_experts"] = held
    conf["cellbench"]["held_start"] = held_start
    return conf


@pytest.fixture(scope="module")
def model():
    conf = _conf()
    key = weights.seed_key(SEED)
    return (conf, key, adapter.model_config(conf),
            adapter.program_params(conf, key, jnp.float32))


def _reference_logits(conf, key, tokens):
    return reference.logits_at(
        conf, weights.top_weights(conf, key),
        lambda i: weights.layer_weights(conf, key, i),
        jnp.asarray(tokens), jnp.arange(len(tokens)), weights.held(conf))


def _kda_inputs(T, H, d, seed, strong=True):
    """Normalised queries and keys, decays from nearly none to (with
    ``strong``) e^-4.5 a token, write strengths across (0, 1)."""
    rng = np.random.RandomState(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.randn(T, H, d)) * d ** -0.5
    k = unit(rng.randn(T, H, d))
    v = rng.randn(T, H, d)
    g = -np.exp(rng.uniform(-6, 1.5 if strong else -2, size=(T, H, d)))
    beta = 1 / (1 + np.exp(-rng.randn(T, H)))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


def test_the_reference_imports_nothing_of_the_program_and_sets_highest():
    text = (REPO / "cellbench" / "reference" / "kda_mla_moe.py").read_text()
    assert "apex_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text


# ------------------------------------------------------------------ the ops
@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("T", [64, 100, 192, 7])
def test_chunked_kda_is_the_recurrence(T, impl):
    """Several lengths (one a whole chunk, one not a multiple of the
    chunk, one of three chunks, one shorter than a sub-block), from a
    non-zero state, decays up to e^-4.5 a token: the naive ``exp(-G)``
    would overflow within a chunk.  Outputs and final state agree to
    float32 rounding (measured 2e-7 and 2e-6 on values of 0.2 and 1)."""
    H, d = 3, 32
    q, k, v, g, beta = _kda_inputs(T, H, d, seed=T)
    S0 = jnp.asarray(np.random.RandomState(1).randn(H, d, d) * 0.1,
                     jnp.float32)
    want_o, want_s = kda.kda_recurrent(q, k, v, g, beta, S0)
    got_o, got_s = kda.kda_chunked(q, k, v, g, beta, S0, impl=impl)
    assert float(jnp.max(jnp.abs(got_o - want_o))) < 2e-6
    assert float(jnp.max(jnp.abs(got_s - want_s))) < 2e-5
    assert float(jnp.max(jnp.abs(want_o))) > 0.05


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_a_padded_tail_changes_no_bit_of_the_state(impl):
    """Positions with ``beta = 0`` and ``g = 0`` (how padding is
    marked) after the sequence: the same final state, bit for bit,
    whether they fill up the last chunk or add whole chunks."""
    H, d, T = 2, 16, 100
    q, k, v, g, beta = _kda_inputs(T, H, d, seed=3)
    S0 = jnp.zeros((H, d, d), jnp.float32)
    _, want = kda.kda_chunked(q, k, v, g, beta, S0, impl=impl)
    for extra in (28, 92):
        rng = np.random.RandomState(extra)
        pad = lambda x, fill: jnp.concatenate(
            [x, jnp.asarray(fill, jnp.float32)])
        junk = rng.randn(extra, H, d)
        _, got = kda.kda_chunked(
            pad(q, junk), pad(k, junk), pad(v, junk),
            pad(g, np.zeros((extra, H, d))), pad(beta, np.zeros((extra, H))),
            S0, impl=impl)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_kda_decode_is_one_step_of_the_recurrence(impl):
    """On the stacked state at one layer: active slots advance by one
    token of the recurrence; an inactive slot's state, POISONED with
    NaN, stays poisoned, and no active slot's result differs by a bit
    from a run in which nothing was poisoned; other layers and the
    garbage row's neighbours are untouched."""
    B, H, d, L = 5, 4, 32, 3
    q, k, v, g, beta = _kda_inputs(B, H, d, seed=5)
    rng = np.random.RandomState(6)
    clean = jnp.asarray(rng.randn(L, B + 1, H, d, d), jnp.float32)
    active = jnp.asarray([True, False, True, True, False])
    poisoned = clean.at[:, 1].set(jnp.nan).at[:, 4].set(jnp.nan)
    o_clean, s_clean = kda.kda_decode(q, k, v, g, beta, clean, active, 1,
                                      impl=impl)
    o, s = kda.kda_decode(q, k, v, g, beta, poisoned, active, 1, impl=impl)
    for b in (0, 2, 3):
        want_o, want_s = kda.kda_recurrent(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], g[b:b + 1],
            beta[b:b + 1], clean[1, b])
        assert float(jnp.max(jnp.abs(o[b] - want_o[0]))) < 1e-5
        assert float(jnp.max(jnp.abs(s[1, b] - want_s))) < 1e-5
        np.testing.assert_array_equal(np.asarray(o[b]),
                                      np.asarray(o_clean[b]))
        np.testing.assert_array_equal(np.asarray(s[1, b]),
                                      np.asarray(s_clean[1, b]))
    assert bool(jnp.all(jnp.isnan(s[:, 1]))) \
        and bool(jnp.all(jnp.isnan(s[:, 4])))
    assert float(jnp.max(jnp.abs(o[jnp.asarray([1, 4])]))) == 0.0
    for layer in (0, 2):
        np.testing.assert_array_equal(np.asarray(s[layer]),
                                      np.asarray(poisoned[layer]))


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_conv_step_shifts_an_active_slots_tail_only(impl):
    B, C, K, L = 5, 24, 4, 2
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(B, C), jnp.float32)
    w = jnp.asarray(rng.randn(K, C), jnp.float32)
    tails = jnp.asarray(rng.randn(L, B + 1, (K - 1) * C), jnp.float32)
    active = jnp.asarray([True, True, False, True, False])
    y, new = kda.conv_step(x, w, tails, active, 1, impl=impl)
    window = np.concatenate([np.asarray(tails[1, :B]).reshape(B, K - 1, C),
                             np.asarray(x)[:, None]], axis=1)
    np.testing.assert_allclose(y, (np.asarray(w)[None] * window).sum(1),
                               rtol=1e-5, atol=1e-6)
    for b in range(B):
        want = window[b, 1:].reshape(-1) if bool(active[b]) \
            else np.asarray(tails[1, b])
        np.testing.assert_array_equal(np.asarray(new[1, b]), want)
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(tails[0]))
    np.testing.assert_array_equal(np.asarray(new[1, B]),
                                  np.asarray(tails[1, B]))


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("shape", [(4, 8, 8), (40,)])
def test_install_rows_writes_one_slots_rows_of_every_layer(shape, impl):
    L, slots = 3, 20
    rng = np.random.RandomState(8)
    rows = jnp.asarray(rng.randn(L, slots + 1, *shape), jnp.float32)
    new = jnp.asarray(rng.randn(L, *shape), jnp.float32)
    got = kda.install_rows(rows, new, jnp.int32(17), impl=impl)
    np.testing.assert_array_equal(np.asarray(got[:, 17]), np.asarray(new))
    keep = np.arange(slots + 1) != 17
    np.testing.assert_array_equal(np.asarray(got)[:, keep],
                                  np.asarray(rows)[:, keep])


# ---------------------------------------------------------------- the model
def test_the_pattern_becomes_segments_of_whole_stacks(model):
    _, _, cfg, params = model
    assert cfg.kinds == ("kda", "kda", "kda", "mla", "kda")
    assert cfg.segments == (
        ("kda_dense", "kda", 0, 1, 0), ("kda_moe", "kda", 0, 2, 1),
        ("moe", "mla", 0, 1, 0), ("kda_moe", "kda", 2, 1, 3))
    assert params["kda_moe"]["wqkv"].shape == (3, 64, 3 * 64)
    assert "wq" in params["moe"] and "wq_a" not in params["moe"]
    spec = cfg.served_model().cache_spec()
    assert spec["latent"] == (1, 1, 24)
    assert spec["kda_state"] == PerSlot(4, (4, 16, 16), jnp.float32)
    assert spec["kda_conv"] == PerSlot(4, (3 * 3 * 64,), jnp.float32)
    assert cfg.served_model().counter_names[-1] == "kda_state_updates"
    # the all-MLA family is what it was: two stacks, three counters
    plain = mla_moe.MLAMoEConfig(num_dense_layers=1, num_moe_layers=2)
    assert plain.segments == (("dense", "mla", 0, 1, 0),
                              ("moe", "mla", 0, 2, 1))
    assert set(plain.served_model().cache_spec()) == {"latent"}
    assert len(plain.served_model().counter_names) == 3


def test_from_published_reads_null_ranks_and_scaling_without_help():
    conf = _conf()
    cfg = mla_moe.MLAMoEConfig.from_published(conf)
    assert cfg.q_lora_rank is None and cfg.rope_factor == 1.0
    assert not cfg.use_rope and cfg.n_group == 1
    assert cfg.softmax_scale == pytest.approx(24 ** -0.5)
    assert cfg.num_experts_per_tok == 4
    # the model layer knows no benchmark file's keys: every expert the
    # config counts is held; a chip's share of a wider router is the
    # caller's to say (the adapter and the entry point resolve the
    # file's "published" width)
    assert cfg.n_routed_experts == 8 and cfg.held == range(8)
    share = adapter.model_config(conf)
    assert share.n_routed_experts == 32 and share.held == range(8, 16)


def test_full_forward_logits_match_the_reference(model):
    conf, key, cfg, params = model
    tokens = np.random.RandomState(0).randint(0, 256, size=100)
    got = mla_moe.forward(params, jnp.asarray(tokens)[None], cfg,
                          attn_impl="xla")[0]
    want = _reference_logits(conf, key, tokens)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    assert float(jnp.max(jnp.abs(want))) > 0.1


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_prefill_then_decode_through_both_caches(model, impl):
    """Prefill 70 tokens (a chunk and a bit), decode 30 more one at a
    time through the paged latent pool (pages of 8) and the per-slot
    state: position by position the logits are the reference's full
    forward."""
    conf, key, cfg, params = model
    tokens = np.random.RandomState(1).randint(0, 256, size=100)
    dcfg = DecodeConfig(
        cache=KVCacheConfig(num_pages=20, page_size=8, pages_per_seq=16,
                            dtype=jnp.float32),
        max_batch=3, max_prompt_len=128, temperature=0.0, attn_impl=impl,
        sample_impl="xla")
    got = decode_logits_tokenwise(
        params, cfg, dcfg, jnp.asarray(tokens)[None], 70,
        jnp.arange(1, 17, dtype=jnp.int32))
    want = _reference_logits(conf, key, tokens)[70:]
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_a_reused_slot_sees_nothing_of_its_last_tenant(model):
    """The serving programs themselves: request A is prefilled into
    slot 1 and decoded a few steps; then request B, padded to a BUCKET
    (37 tokens in 64), is prefilled into the same slot and decoded:
    B's logits are the reference's full forward of B alone, and the
    neighbouring slot's state has not moved."""
    conf, key, cfg, params = model
    dcfg = DecodeConfig(
        cache=KVCacheConfig(num_pages=40, page_size=8, pages_per_seq=12,
                            dtype=jnp.float32),
        max_batch=3, max_prompt_len=64, temperature=0.0, attn_impl="xla",
        sample_impl="xla", sample_dot_dtype=jnp.float32)
    m = cfg.served_model()
    pools = alloc_named_pools(m.cache_spec(), dcfg.cache, slots=3)
    pools[COUNTERS] = jnp.zeros((len(m.counter_names),), jnp.int32)
    pools["kda_state"] = pools["kda_state"].at[:, 0].set(7.0)
    prefill = make_prefill(cfg, dcfg)
    step = make_decode_step(cfg, dcfg, return_logits=True)
    rng = np.random.RandomState(2)
    slot, active = 1, jnp.asarray([False, True, False])

    def serve(tokens, plen, table):
        nonlocal pools
        prompt = np.zeros((1, 64), np.int32)
        prompt[0, :plen] = tokens[:plen]
        pools, _ = prefill(params, pools, jnp.asarray(prompt),
                           jnp.int32(plen), jnp.int32(0),
                           jnp.asarray(table, jnp.int32), jnp.uint32(0),
                           jnp.int32(slot))
        tables = jnp.zeros((3, 12), jnp.int32).at[slot].set(
            jnp.asarray(table, jnp.int32))
        out = []
        for pos in range(plen, len(tokens)):
            tok = jnp.zeros((3,), jnp.int32).at[slot].set(int(tokens[pos]))
            pools, logits = step(
                params, pools, tok, jnp.full((3,), pos, jnp.int32), active,
                tables, jnp.zeros((3,), jnp.uint32))
            out.append(logits[slot])
        return jnp.stack(out)

    a = rng.randint(0, 256, size=60)
    serve(a, 50, np.arange(1, 13))
    b = rng.randint(0, 256, size=49)
    got = serve(b, 37, np.arange(13, 25))
    want = _reference_logits(conf, key, b)[37:]
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    assert float(jnp.min(pools["kda_state"][:, 0])) == 7.0
    # 4 KDA layers x (10 + 12) decode steps of one active slot
    assert int(pools[COUNTERS][3]) == 4 * 22


def test_scheduler_serves_the_family_greedy_as_the_reference(model):
    """More requests than slots, through ``ContinuousBatchingScheduler``
    with buckets: slots are reused, every served token is the
    reference's first choice (or within rounding of it), the counters
    count the state updates."""
    conf, key, cfg, params = model
    dcfg = DecodeConfig(
        cache=KVCacheConfig(num_pages=25, page_size=8, pages_per_seq=8,
                            dtype=jnp.float32),
        max_batch=2, max_prompt_len=32, prefill_buckets=(16,),
        temperature=0.0, attn_impl="xla", sample_impl="xla",
        sample_dot_dtype=jnp.float32)
    sched = ContinuousBatchingScheduler(params, cfg, dcfg)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 256, size=n).tolist() for n in (9, 21, 14, 5)]
    for i, p in enumerate(prompts):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    while not sched.idle():
        sched.step()
    assert len(sched.completed) == 4
    for c in sched.completed:
        seq = c.prompt + c.tokens[:-1]
        ref = _reference_logits(conf, key, seq)[len(c.prompt) - 1:]
        picked = jnp.take_along_axis(
            ref, jnp.asarray(c.tokens)[:, None], axis=1)[:, 0]
        assert float(jnp.max(jnp.max(ref, axis=-1) - picked)) < TOL
    counters = sched.read_counters()
    # every decode step of every request, in each of the 4 KDA layers
    assert counters["kda_state_updates"] == 4 * 4 * 5
    assert counters["moe_assignments_all"] == 4 * 4 * 4 * 5


def test_slot_state_is_the_residents_recurrence(model):
    """``scheduler.slot_state``: between two steps a resident request's
    first KDA layer holds the state of the reference's token-by-token
    recurrence over the prompt and every emitted token but the last
    (float32 both sides: rounding apart, 1e-5 of the state's norm); a
    bfloat16 state is 100 times as far; a request not resident has
    none."""
    conf, key, cfg, params = model
    dcfg = DecodeConfig(
        cache=KVCacheConfig(num_pages=25, page_size=8, pages_per_seq=12,
                            dtype=jnp.float32),
        max_batch=2, max_prompt_len=32, prefill_buckets=(16,),
        temperature=0.0, attn_impl="xla", sample_impl="xla",
        sample_dot_dtype=jnp.float32)
    sched = ContinuousBatchingScheduler(params, cfg, dcfg)
    rng = np.random.RandomState(4)
    other, prompt = (rng.randint(0, 256, size=n).tolist() for n in (7, 19))
    sched.submit(Request(rid=0, prompt=other, max_new_tokens=40))
    sched.submit(Request(rid=1, prompt=prompt, max_new_tokens=40))
    assert sched.slot_state(1) is None          # queued
    for _ in range(25):
        sched.step()
    emitted = next(m.emitted for m in sched.drain_manifest() if m.rid == 1)
    got = sched.slot_state(1)
    assert set(got) == {"kda_state", "kda_conv"}
    assert got["kda_state"].shape == (4, 4, 16, 16)
    tokens = jnp.asarray(prompt + emitted[:-1], jnp.int32)
    first = lambda **kw: reference.first_kda_state(
        conf, weights.top_weights(conf, key),
        weights.layer_weights(conf, key, 0), tokens, **kw)
    far = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    want = first()
    assert len(emitted) > 20 and far(got["kda_state"][0], want) < 1e-5
    assert far(first(state_dtype=jnp.bfloat16), want) > 1e-3
    while not sched.idle():
        sched.step()
    assert sched.slot_state(1) is None          # finished


def test_probe_state_reads_the_prompt_and_all_emitted_but_the_last(model):
    """The benchmark's probe (``adapter.probe_state``: ``step``, then
    ``drain_manifest``, then ``slot_state``, which settle the step in
    flight) holds a state that has taken in the prompt and every emitted
    token but the last — one token more, the step in flight's, reads
    thirty times the rounding."""
    conf, key, cfg, params = model
    dcfg = DecodeConfig(
        cache=KVCacheConfig(num_pages=13, page_size=8, pages_per_seq=6,
                            dtype=jnp.float32),
        max_batch=2, max_prompt_len=32, prefill_buckets=(16,),
        temperature=0.0, attn_impl="xla", sample_impl="xla",
        sample_dot_dtype=jnp.float32)
    sched = ContinuousBatchingScheduler(params, cfg, dcfg)
    prompt = np.random.RandomState(6).randint(0, 256, size=11).tolist()
    tokens, state = adapter.probe_state(sched, prompt)
    # the slot's pages end before PROBE_TOKENS: 6 x 8 - 11 - 1 = 36
    # emitted tokens, the first by the prefill, each other by a step
    # that the probe's manifest read back
    assert tokens[:11] == prompt and len(tokens) == 11 + 35
    assert sched.stats["decode_settles"] == 35
    first = lambda toks: reference.first_kda_state(
        conf, weights.top_weights(conf, key),
        weights.layer_weights(conf, key, 0), jnp.asarray(toks, jnp.int32))
    far = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    want = first(tokens)
    assert far(state, want) < 1e-5
    (m,) = sched.drain_manifest()
    assert m.emitted[:-1] == tokens[11:]
    assert far(first(tokens + m.emitted[-1:]), want) > 3e-4


def test_what_a_recurrent_state_cannot_serve_is_refused(model):
    _, _, cfg, params = model
    cache = KVCacheConfig(num_pages=9, page_size=8, pages_per_seq=4,
                          dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="per-slot recurrent"):
        ContinuousBatchingScheduler(params, cfg, DecodeConfig(
            cache=cache, max_batch=2, max_prompt_len=16,
            prefix_sharing=True))
    for knob in ({"draft_len": 2}, {"prefill_chunk": 8}):
        with pytest.raises(NotImplementedError, match="one position"):
            ContinuousBatchingScheduler(params, cfg, DecodeConfig(
                cache=cache, max_batch=2, max_prompt_len=16, **knob))
    with pytest.raises(ValueError, match="needs slots"):
        alloc_named_pools(cfg.served_model().cache_spec(), cache)
