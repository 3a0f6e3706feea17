"""The latent-attention, sparse-expert family (``models/mla_moe.py``)
against its plain reference (``cellbench/reference/mla_moe.py``), at a
small size on the CPU: 2 dense + 2 expert layers, hidden 64, 4 heads,
ranks 24/16, 32 experts in 4 groups, top-4, 8 held.  Seeded weights in
the published layout (``cellbench/weights_mla_moe.py``), float32 on
both sides, so every comparison is to reduction-order rounding."""

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
    ContinuousBatchingScheduler, DecodeConfig, KVCacheConfig,
    PageAllocator, Request,
)
from apex_tpu.inference.decode import decode_logits_tokenwise  # noqa: E402
from apex_tpu.inference.kv_cache import (  # noqa: E402
    COUNTERS, GARBAGE_PAGE, alloc_named_pools, copy_page,
    write_decode_pools, write_prompt_pools,
)
from apex_tpu.models import mla_moe  # noqa: E402
from apex_tpu.ops import mla_decode_pallas as mdp  # noqa: E402
from apex_tpu.ops.mla_decode_pallas import (  # noqa: E402
    mla_decode_attention, mla_decode_attention_xla, mla_decode_pallas,
)
from apex_tpu.transformer.expert_parallel import (  # noqa: E402
    grouped_gated_ffn, held_experts_ffn, route_group_limited,
)
from cellbench import weights_mla_moe as weights  # noqa: E402
from cellbench.adapters import serve_mla_moe as adapter  # noqa: E402
from cellbench.reference import mla_moe as reference  # noqa: E402

TINY = {
    "model_type": "deepseek_v3", "vocab_size": 256,
    "max_position_embeddings": 4096, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 4, "num_nextn_predict_layers": 0,
    "num_attention_heads": 4, "n_shared_experts": 1,
    "n_routed_experts": 8, "routed_scaling_factor": 2.5,
    "kv_lora_rank": 16, "q_lora_rank": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 24, "qk_nope_head_dim": 16, "n_group": 4,
    "topk_group": 2, "num_experts_per_tok": 4, "first_k_dense_replace": 2,
    "rms_norm_eps": 1e-6, "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32,
                     "rope_type": "yarn"},
    "published": {"n_routed_experts": 32},
    "cellbench": {"adapter": "serve_mla_moe", "held_start": 8,
                  "args": {"compute_dtype": "float32",
                           "param_dtype": "float32"}},
}
SEED = 2 ** 31 + 12345      # a large seed, as the driver's are
TOL = 2e-5


def _conf(held_start=8, held=8):
    conf = copy.deepcopy(TINY)
    conf["n_routed_experts"] = held
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


def test_the_reference_imports_nothing_of_the_program_and_sets_highest():
    text = (REPO / "cellbench" / "reference" / "mla_moe.py").read_text()
    assert "apex_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text


def test_full_forward_logits_match_the_reference(model):
    conf, key, cfg, params = model
    tokens = np.random.RandomState(0).randint(0, 256, size=40)
    got = mla_moe.forward(params, jnp.asarray(tokens)[None], cfg,
                          attn_impl="xla")[0]
    want = _reference_logits(conf, key, tokens)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    assert float(jnp.max(jnp.abs(want))) > 0.1


def test_yarn_frequencies_blend_interpolated_and_extrapolated(model):
    conf, _, cfg, _ = model
    got = mla_moe.yarn_inv_freq(cfg)
    base = 1.0 / (cfg.rope_theta ** (np.arange(0, 8, 2) / 8))
    np.testing.assert_allclose(got, reference.yarn_inv_freq(conf), rtol=1e-6)
    # the fastest pair keeps its frequency, the slowest is divided by 64
    assert got[0] == pytest.approx(base[0], rel=1e-6)
    assert got[-1] == pytest.approx(base[-1] / 64.0, rel=1e-5)
    assert cfg.softmax_scale == pytest.approx(
        24 ** -0.5 * (0.1 * np.log(64.0) + 1.0) ** 2)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_prefill_then_decode_through_the_latent_cache(model, impl):
    """Prefill 9 tokens, decode 23 more one at a time through the paged
    one-pool cache (pages of 8: positions cross three page edges):
    position by position the logits are the reference's full forward."""
    conf, key, cfg, params = model
    tokens = np.random.RandomState(1).randint(0, 256, size=32)
    dcfg = DecodeConfig(
        cache=KVCacheConfig(num_pages=9, page_size=8, pages_per_seq=4,
                            dtype=jnp.float32),
        max_batch=3, max_prompt_len=32, temperature=0.0, attn_impl=impl,
        sample_impl="xla")
    got = decode_logits_tokenwise(
        params, cfg, dcfg, jnp.asarray(tokens)[None], 9,
        jnp.asarray([5, 2, 7, 3], jnp.int32))
    want = _reference_logits(conf, key, tokens)[9:]
    assert float(jnp.max(jnp.abs(got - want))) < TOL


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_absorbed_decode_is_the_non_absorbed_attention(impl):
    """q_lat . c_kv + q_rope . k_r over the cached latent, then W_v on
    the way out, against keys and values materialised per head."""
    rng = np.random.RandomState(2)
    B, H, nope, dr, dv, rank, page, P = 3, 4, 16, 8, 24, 16, 8, 4
    lengths = jnp.asarray([27, 9, 0], jnp.int32)
    latent = rng.randn(B, P * page, rank + dr).astype(np.float32)
    q_nope = rng.randn(B, H, nope).astype(np.float32)
    q_rope = rng.randn(B, H, dr).astype(np.float32)
    w_k = rng.randn(rank, H, nope).astype(np.float32) * 0.3
    w_v = rng.randn(rank, H, dv).astype(np.float32) * 0.3
    tables = np.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [7, 8, 0, 0]])
    pool = np.zeros((2, 9, 1, rank + dr, page), np.float32)
    for b in range(B):
        for p in range(P):
            if tables[b, p]:
                pool[1, tables[b, p], 0] = \
                    latent[b, p * page:(p + 1) * page].T
    scale = 0.37
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope, w_k)
    o_lat = mla_decode_attention(
        jnp.concatenate([q_lat, jnp.asarray(q_rope)], -1),
        jnp.asarray(pool), jnp.asarray(tables, jnp.int32), lengths, rank,
        scale, impl=impl, layer=1)
    got = jnp.einsum("bhc,chd->bhd", o_lat, w_v)
    for b in range(2):
        n = int(lengths[b])
        c, k_r = latent[b, :n, :rank], latent[b, :n, rank:]
        k = np.concatenate(
            [np.einsum("tc,chd->thd", c, w_k),
             np.broadcast_to(k_r[:, None], (n, H, dr))], -1)
        v = np.einsum("tc,chd->thd", c, w_v)
        q = np.concatenate([q_nope[b], q_rope[b]], -1)
        s = np.einsum("hd,thd->ht", q, k) * scale
        p_ = np.exp(s - s.max(-1, keepdims=True))
        p_ /= p_.sum(-1, keepdims=True)
        want = np.einsum("ht,thd->hd", p_, v)
        np.testing.assert_allclose(got[b], want, atol=2e-5)
    assert float(jnp.max(jnp.abs(got[2]))) == 0.0     # the empty slot


# ------------------------------------------- the walk over live tiles
# A page of whole lane tiles (the cells' 128): the kernel copies a
# sequence's live tiles itself, the copies running on across sequences
# (PERF.md, PR 33).  The published column: 512 latent values + 64 rotary.
WALK_PAGE, WALK_P, WALK_DC, WALK_DL = 128, 3, 576, 512
#: one call's ragged lengths: inactive slots first, BETWEEN live ones
#: and last, one position, a page less one, a page, a page and one, a
#: full table
WALK_LENGTHS = [0, 1, 127, 0, 128, 129, WALK_P * WALK_PAGE, 0, 0, 5, 0]
WALK_CASES = [(64, jnp.bfloat16), (64, jnp.float32), (32, jnp.bfloat16),
              (32, jnp.float32)]
WALK_IDS = ["h64_bf16", "h64_f32", "h32_bf16", "h32_f32"]


def _walk_case(heads, dtype, seed=0, layers=2):
    """(q, pool, page table, lengths): every sequence's pages scattered
    through a stacked pool, page 0 the garbage page."""
    rng = np.random.RandomState(seed + heads)
    B = len(WALK_LENGTHS)
    pool = jnp.asarray(rng.randn(layers, 1 + B * WALK_P, 1, WALK_DC,
                                 WALK_PAGE), dtype)
    q = jnp.asarray(rng.randn(B, heads, WALK_DC) * 0.2, dtype)
    pt = jnp.asarray(1 + rng.permutation(B * WALK_P).reshape(B, WALK_P),
                     jnp.int32)
    return q, pool, pt, jnp.asarray(WALK_LENGTHS, jnp.int32)


def _walk(q, pool, pt, lengths):
    return mla_decode_pallas(q, pool, pt, lengths, WALK_DL, 0.0722,
                             interpret=True, layer=1)


def _live_pages(pt, lengths):
    """The pool pages that hold a live position, sequence by sequence
    in the order of their positions."""
    return [int(p) for row, n in zip(np.asarray(pt), np.asarray(lengths))
            for p in row[:-(-int(n) // WALK_PAGE)]]


@pytest.mark.parametrize("heads,dtype", WALK_CASES, ids=WALK_IDS)
def test_walk_matches_the_reference_over_ragged_lengths(heads, dtype):
    q, pool, pt, lengths = _walk_case(heads, dtype)
    assert mdp._plan(len(WALK_LENGTHS), WALK_P, WALK_PAGE)[0] == \
        (len(WALK_LENGTHS),)
    out = _walk(q, pool, pt, lengths)
    ref = mla_decode_attention_xla(q, pool, pt, lengths, WALK_DL, 0.0722,
                                   layer=1)
    assert out.shape == ref.shape and out.dtype == ref.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=0,
        atol=2e-5 if dtype == jnp.float32 else 0.03)
    dead = np.asarray(lengths) == 0
    assert float(np.abs(np.asarray(out, np.float32)[dead]).sum()) == 0.0
    assert np.abs(np.asarray(out, np.float32)[~dead]).max(axis=(1, 2)).all()


def test_walk_widens_a_narrower_cache_to_the_query():
    q, pool, pt, lengths = _walk_case(8, jnp.bfloat16)
    q = q.astype(jnp.float32)
    out = _walk(q, pool, pt, lengths)
    ref = mla_decode_attention_xla(q, pool, pt, lengths, WALK_DL, 0.0722,
                                   layer=1)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=0,
                               atol=0.03)


@pytest.mark.parametrize("heads,dtype", WALK_CASES, ids=WALK_IDS)
def test_walk_never_reads_a_dead_page(heads, dtype):
    """Every pool page that holds no live position — the garbage page,
    a table's slots past its length, every page of an inactive slot,
    the other layer — is poisoned: no bit of the output changes."""
    q, pool, pt, lengths = _walk_case(heads, dtype)
    live = np.zeros(pool.shape[1], bool)
    live[_live_pages(pt, lengths)] = True
    assert not live[GARBAGE_PAGE] and 0 < live.sum() < len(live) - 1
    poison = np.where(np.arange(pool.size).reshape(pool.shape) % 2,
                      np.nan, np.inf)
    mask = np.ones(pool.shape, bool)
    mask[1, live] = False                # layer 1's live pages stay
    clean = np.asarray(_walk(q, pool, pt, lengths), np.float32)
    dirty = np.asarray(_walk(q, jnp.where(mask, poison, pool)
                             .astype(pool.dtype), pt, lengths), np.float32)
    assert np.isfinite(clean).all()
    np.testing.assert_array_equal(dirty, clean)


@pytest.mark.parametrize("heads,dtype", WALK_CASES, ids=WALK_IDS)
def test_walk_clamps_the_page_table_before_it_is_an_address(heads, dtype):
    """Entries outside the pool, under live positions and past them:
    what the kernel reads is the table clipped into the pool, as the
    XLA twin gathers it."""
    q, pool, pt, lengths = _walk_case(heads, dtype)
    wild = np.asarray(pt).copy()
    wild[1, 0], wild[4, 0], wild[6, 1], wild[6, 2] = -7, 10 ** 6, -1, 2 ** 30
    wild[0, :], wild[9, 1:] = -3, 10 ** 7        # never read
    clipped = np.clip(wild, 0, pool.shape[1] - 1)
    out = _walk(q, pool, jnp.asarray(wild, jnp.int32), lengths)
    np.testing.assert_array_equal(
        np.asarray(out, np.float32),
        np.asarray(_walk(q, pool, jnp.asarray(clipped, jnp.int32), lengths),
                   np.float32))
    ref = mla_decode_attention_xla(q, pool, jnp.asarray(wild, jnp.int32),
                                   lengths, WALK_DL, 0.0722, layer=1)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=0,
        atol=2e-5 if dtype == jnp.float32 else 0.03)


@pytest.mark.parametrize("slots", [2, 3, 4])
def test_walk_asks_for_each_live_tile_once_and_for_no_other(slots,
                                                            monkeypatch):
    """What the kernel COPIES, from the copies it starts: the live
    tiles of the live sequences, each once, round robin over the VMEM
    slots in the order of the walk — nothing for an inactive slot,
    whether it lies before, between or after live ones — however many
    slots the walk has: with two no copy runs ahead of the arithmetic,
    with more some do (the launcher's constant is a measured choice,
    not what makes the cursor right)."""
    q, pool, pt, lengths = _walk_case(4, jnp.float32)
    started = []
    real = mdp._tile_copy

    class Spy:
        def __init__(self, dma, page, slot):
            self.dma, self.page, self.slot = dma, page, slot

        def start(self):
            jax.debug.callback(
                lambda p, k: started.append((int(p), int(k))), self.page,
                self.slot)
            self.dma.start()

        def wait(self):
            self.dma.wait()

    monkeypatch.setattr(mdp, "WALK_SLOTS", slots)
    monkeypatch.setattr(
        mdp, "_tile_copy", lambda pool_hbm, buf, sem, layer, page, slot:
        Spy(real(pool_hbm, buf, sem, layer, page, slot), page, slot))
    out = jax.block_until_ready(_walk(q, pool, pt, lengths))
    jax.effects_barrier()
    want = _live_pages(pt, lengths)
    # the callbacks are unordered effects: compare what was asked for,
    # and the slot of each, not the order they were delivered in
    assert sorted(started) == sorted(
        (page, k % slots) for k, page in enumerate(want))
    ref = mla_decode_attention_xla(q, pool, pt, lengths, WALK_DL, 0.0722,
                                   layer=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=0,
                               atol=2e-5)


def test_a_small_page_keeps_the_grid_of_page_slots():
    """Which form runs is a matter of the page's shape alone."""
    assert mdp._plan(128, 16, 128) == ((128,), mdp.WALK_SLOTS)
    assert mdp._plan(128, 48, 256) == ((128,), mdp.WALK_SLOTS)
    assert mdp._plan(3, 4, 8) == ((3, 1), 4)
    assert mdp._plan(8, 12, 16) == ((8, 2), 6)
    assert mdp._plan(8, 12, 64) == ((8, 2), 6)


# ------------------------------------------------------------- the router
def _route_args(conf):
    return dict(top_k=conf["num_experts_per_tok"], n_group=conf["n_group"],
                topk_group=conf["topk_group"],
                scale=conf["routed_scaling_factor"])


def test_router_ids_and_weights_match_the_reference():
    conf = _conf()
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(50, 64), jnp.float32)
    w = jnp.asarray(rng.randn(32, 64) * 0.3, jnp.float32)   # (E, H)
    b = jnp.asarray(rng.randn(32) * 0.05, jnp.float32)
    ids, wts = route_group_limited(x, w.T, b, **_route_args(conf))
    ref_ids, ref_wts = reference.route(x, w, b, conf)
    order, ref_order = np.argsort(ids, -1), np.argsort(ref_ids, -1)
    np.testing.assert_array_equal(np.take_along_axis(ids, order, -1),
                                  np.take_along_axis(ref_ids, ref_order, -1))
    np.testing.assert_allclose(np.take_along_axis(wts, order, -1),
                               np.take_along_axis(ref_wts, ref_order, -1),
                               rtol=1e-5)
    np.testing.assert_allclose(wts.sum(-1), 2.5, rtol=1e-5)


def _one_token_router(scores, bias):
    """A router whose sigmoid scores for one token are ``scores``."""
    logit = np.log(scores / (1.0 - scores)).astype(np.float32)
    x = np.zeros((1, 32), np.float32)
    x[0, 0] = 1.0
    w = np.zeros((32, 32), np.float32)       # (H, E)
    w[0] = logit
    return jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias, jnp.float32)


def test_the_group_limit_changes_the_choice():
    """Expert 17 has the third best score of all, but its group's two
    best add up to less than three other groups': with 2 groups kept of
    4 it is not chosen, with all groups kept it is."""
    conf = _conf()
    s = np.full(32, 0.05)
    s[[0, 1]] = [0.9, 0.8]        # group 0: 1.7
    s[[8, 9]] = [0.6, 0.6]        # group 1: 1.2
    s[[16, 17]] = [0.06, 0.7]     # group 2: 0.76 — out
    s[[24, 25]] = [0.5, 0.4]      # group 3: 0.9 — out
    x, w, b = _one_token_router(s, np.zeros(32))
    ids, wts = route_group_limited(x, w, b, **_route_args(conf))
    assert sorted(np.asarray(ids[0])) == [0, 1, 8, 9]
    ref_ids, _ = reference.route(x, w.T, b, conf)
    assert sorted(np.asarray(ref_ids[0])) == [0, 1, 8, 9]
    free = dict(_route_args(conf), topk_group=4)
    ids_free, _ = route_group_limited(x, w, b, **free)
    assert sorted(np.asarray(ids_free[0])) == [0, 1, 8, 17]
    np.testing.assert_allclose(
        np.sort(np.asarray(wts[0])),
        np.sort(2.5 * np.array([0.9, 0.8, 0.6, 0.6]) / 2.9), rtol=1e-5)


def test_the_bias_changes_the_choice_but_not_the_weight():
    """Expert 9's bias lifts it over expert 8 in the choice; its weight
    is still its own score over the sum of the chosen scores."""
    conf = _conf()
    s = np.full(32, 0.05)
    s[[0, 1, 2]] = [0.9, 0.8, 0.7]
    s[[8, 9]] = [0.6, 0.5]
    bias = np.zeros(32)
    bias[9] = 0.2
    x, w, b = _one_token_router(s, bias)
    ids, wts = route_group_limited(x, w, b, **_route_args(conf))
    assert sorted(np.asarray(ids[0])) == [0, 1, 2, 9]
    got = dict(zip(np.asarray(ids[0]).tolist(), np.asarray(wts[0])))
    assert got[9] == pytest.approx(2.5 * 0.5 / (0.9 + 0.8 + 0.7 + 0.5),
                                   rel=1e-5)
    plain, _ = route_group_limited(x, w, jnp.zeros(32), **_route_args(conf))
    assert sorted(np.asarray(plain[0])) == [0, 1, 2, 8]
    ref_ids, ref_wts = reference.route(x, w.T, b, conf)
    assert sorted(np.asarray(ref_ids[0])) == [0, 1, 2, 9]


# ------------------------------------------------------ the grouped matmul
@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("sizes", [[0, 5, 0, 7], [12, 0, 0, 0],
                                   [0, 0, 0, 0], [3, 3, 3, 3]])
def test_grouped_ffn_with_empty_and_full_experts(sizes, impl):
    """An expert that gets no row, one that gets all, none at all: rows
    of expert e go through expert e's weights (``ragged_dot`` and the
    Pallas grouped GEMM alike); rows past the live ones are the
    caller's to mask."""
    rng = np.random.RandomState(4)
    G, H, F, M = 4, 16, 8, 16
    rows = jnp.asarray(rng.randn(M, H), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(G, H, F) * 0.3, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(G, F, H) * 0.3, jnp.float32)
    got = grouped_gated_ffn(rows, wg, wu, wd, jnp.asarray(sizes, jnp.int32),
                            impl=impl)
    lo = 0
    for e, n in enumerate(sizes):
        x = rows[lo:lo + n]
        want = (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]
        np.testing.assert_allclose(got[lo:lo + n], want, atol=1e-5)
        lo += n


def test_stacked_expert_weights_are_indexed_not_sliced(model):
    """A layer loop hands the expert weights stacked over layers with
    the layer's index: the same result as that layer's weights alone."""
    _, _, cfg, params = model
    x = jnp.asarray(np.random.RandomState(11).randn(12, 64), jnp.float32)
    args = dict(top_k=4, n_group=4, topk_group=2, scale=2.5)
    for li in range(cfg.num_moe_layers):
        one = jax.tree.map(lambda a: a[li], params["moe"])
        stacked = dict(one, **{k: params["moe"][k]
                               for k in mla_moe.EXPERT_LEAVES})
        want, c1 = held_experts_ffn(x, one, cfg.held, **args)
        got, c2 = held_experts_ffn(x, stacked, cfg.held,
                                   layer=jnp.int32(li), **args)
        np.testing.assert_allclose(got, want, atol=1e-6)
        assert int(c1["assignments_held"]) == int(c2["assignments_held"])


# ------------------------------------------------------------ the share
def _kimi_family():
    """The KDA/MLA family's tiny configuration, weights, adapter and
    reference (``tests/test_serve_kda_mla_moe.py``)."""
    from cellbench import weights_kda_mla_moe as w
    from cellbench.adapters import serve_kda_mla_moe as a
    from cellbench.reference import kda_mla_moe as r
    import test_serve_kda_mla_moe as tiny

    return tiny._conf, w, a, r


#: family -> (its tiny configuration, weights, adapter and reference;
#: the shares; the first expert layer, its stack and the published
#: name of its experts' gate matrices; the router's arguments)
SHARES = {
    # 32 experts as 4 shares of 8, 4 groups of which 2 stay
    "deepseek_v3-4x8": (
        lambda: (_conf, weights, adapter, reference), (0, 8, 16, 24), 8,
        2, "moe", "mlp.experts.gate_proj.weight",
        dict(top_k=4, n_group=4, topk_group=2, scale=2.5)),
    # 32 experts as 8 shares of 4, one group: the cut of one chip of
    # eight (kimi-linear-48b-a3b-serve-ep8 holds 32 of 256)
    "kimi_linear-8x4": (
        _kimi_family, tuple(range(0, 32, 4)), 4, 1, "kda_moe",
        "block_sparse_moe.experts.w1.weight",
        dict(top_k=4, n_group=1, topk_group=1, scale=2.446)),
}


@pytest.mark.parametrize("family", sorted(SHARES))
def test_the_shares_add_up_to_the_uncut_layer(family):
    """The routed parts that all the shares give (the program's layer,
    told which experts it holds), plus the shared expert counted once,
    are what the uncut reference gives for the layer."""
    parts, starts, each, index, stack, gate, route = SHARES[family]
    conf_of, weights_, adapter_, reference_ = parts()
    key = weights_.seed_key(SEED)
    whole = conf_of(held_start=0, held=32)
    w_all = {k: v.astype(jnp.float32)
             for k, v in weights_.layer_weights(whole, key, index).items()}
    x = jnp.asarray(np.random.RandomState(5).randn(40, 64), jnp.float32)
    ident = lambda a: a
    want = reference_.routed_experts(x, w_all, whole, range(32), ident) \
        + reference_.shared_expert(x, w_all, ident)
    routed = jnp.zeros_like(x)
    held_total = hit = 0
    for start in starts:
        conf = conf_of(held_start=start, held=each)
        cfg = adapter_.model_config(conf)
        assert cfg.held == range(start, start + each)
        p = jax.tree.map(lambda a: a[0], adapter_.program_params(
            conf, key, jnp.float32)[stack])  # the stack's first layer
        # expert e has the same weights whichever share holds it
        np.testing.assert_array_equal(p["we_gate"][3].T,
                                      w_all[gate][start + 3])
        part, counts = held_experts_ffn(x, p, cfg.held, **route)
        routed = routed + part
        held_total += int(counts["assignments_held"])
        hit += int(counts["experts_hit"])
        assert int(counts["assignments_all"]) == 40 * 4
        shared = mla_moe._gated_ffn(x, p["ws_gate"], p["ws_up"],
                                    p["ws_down"])
    assert held_total == 40 * 4            # no assignment dropped or doubled
    assert 4 <= hit <= 32
    assert float(jnp.max(jnp.abs(routed + shared - want))) < TOL
    assert float(jnp.max(jnp.abs(want))) > 1e-3


def test_masked_tokens_route_nowhere(model):
    _, _, cfg, params = model
    p = jax.tree.map(lambda a: a[0], params["moe"])
    x = jnp.asarray(np.random.RandomState(6).randn(10, 64), jnp.float32)
    mask = jnp.arange(10) < 6
    args = dict(top_k=4, n_group=4, topk_group=2, scale=2.5)
    out, counts = held_experts_ffn(x, p, cfg.held, token_mask=mask, **args)
    alone, _ = held_experts_ffn(x[:6], p, cfg.held, **args)
    np.testing.assert_allclose(out[:6], alone, atol=1e-6)
    assert float(jnp.max(jnp.abs(out[6:]))) == 0.0
    assert int(counts["assignments_all"]) == 24


# ------------------------------------------------------ the one-pool cache
def _latent_pools(dtype=jnp.float32):
    cache = KVCacheConfig(num_pages=6, page_size=4, pages_per_seq=3,
                          dtype=dtype)
    pools = alloc_named_pools({"latent": (2, 1, 24)}, cache)
    assert pools["latent"].shape == (2, 6, 1, 24, 4)
    return pools


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_one_pool_masked_writes_go_to_the_garbage_page(impl):
    pool = _latent_pools()["latent"]
    new = jnp.asarray(np.random.RandomState(7).randn(3, 1, 24), jnp.float32)
    tables = jnp.asarray([[1, 2, 0], [3, 0, 0], [4, 5, 0]], jnp.int32)
    (out,) = write_decode_pools(
        (pool,), (new,), tables, jnp.asarray([5, 2, 1], jnp.int32),
        jnp.asarray([True, False, True]), layer=1, impl=impl)
    np.testing.assert_array_equal(out[1, 2, 0, :, 1], new[0, 0])   # pos 5
    np.testing.assert_array_equal(out[1, 4, 0, :, 1], new[2, 0])   # pos 1
    live = np.asarray(out).copy()
    live[:, GARBAGE_PAGE] = 0
    live[1, 2, 0, :, 1] = 0
    live[1, 4, 0, :, 1] = 0
    assert not live.any()          # the inactive row wrote no live page


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_one_pool_prompt_write_keeps_the_shared_window(impl):
    pool = _latent_pools()["latent"]
    stack = jnp.asarray(np.random.RandomState(8).randn(2, 8, 1, 24),
                        jnp.float32)
    (out,) = write_prompt_pools(
        (pool,), (stack,), jnp.asarray([2, 4, 0], jnp.int32),
        jnp.int32(6), start=jnp.int32(4), impl=impl)
    assert not np.asarray(out[:, 2]).any()       # positions 0-3: shared
    np.testing.assert_array_equal(out[:, 4, 0, :, 0], stack[:, 4, 0])
    np.testing.assert_array_equal(out[:, 4, 0, :, 1], stack[:, 5, 0])
    assert not np.asarray(out[:, 4, 0, :, 2:]).any()   # the pad tail


def test_copy_page_copies_every_named_pool_and_spares_the_counters():
    pools = _latent_pools()
    pools["latent"] = pools["latent"].at[:, 3].set(7.0)
    pools[COUNTERS] = jnp.asarray([1, 2, 3], jnp.int32)
    out = copy_page(pools, 3, 5)
    assert float(out["latent"][:, 5].min()) == 7.0
    assert not np.asarray(out["latent"][:, 4]).any()
    np.testing.assert_array_equal(out[COUNTERS], [1, 2, 3])
    with pytest.raises(ValueError):
        copy_page(pools, GARBAGE_PAGE, 5)
    alloc = PageAllocator(6)
    assert GARBAGE_PAGE not in alloc.allocate(5)


# ---------------------------------------------------------- the scheduler
def _scheduler(model, impl="xla", **kw):
    _, _, cfg, params = model
    dcfg = DecodeConfig(
        cache=KVCacheConfig(num_pages=25, page_size=8, pages_per_seq=6,
                            dtype=jnp.float32),
        max_batch=4, max_prompt_len=32, prefill_buckets=(8, 16),
        temperature=0.0, attn_impl=impl, sample_impl="xla",
        sample_dot_dtype=jnp.float32, **kw)
    return ContinuousBatchingScheduler(params, cfg, dcfg), dcfg


def test_scheduler_serves_the_family_greedy_as_the_reference(model):
    """Seven requests through four slots (pages recycle): every served
    token is the reference's first choice or within rounding of it;
    the decode step compiled once, each prefill bucket at most once."""
    conf, key, cfg, _ = model
    sched, dcfg = _scheduler(model)
    assert sorted(sched.pools) == [COUNTERS, "latent"]
    rng = np.random.RandomState(9)
    lens = [3, 8, 9, 16, 20, 31, 5]
    for rid, n in enumerate(lens):
        sched.submit(Request(rid=rid, max_new_tokens=6,
                             prompt=rng.randint(0, 256, size=n).tolist()))
    done = {c.rid: c for c in sched.run_until_drained()}
    assert len(done) == 7 and sched.stats["evicted"] == 7
    assert sched.decode_cache_size() == 1
    assert sched._prefill._cache_size() == len(dcfg.prefill_lengths) == 3
    for rid in (0, 3, 5):
        c = done[rid]
        seq = list(c.prompt) + list(c.tokens[:-1])
        ref = _reference_logits(conf, key, seq)[len(c.prompt) - 1:]
        picked = jnp.take_along_axis(
            ref, jnp.asarray(c.tokens)[:, None], axis=-1)[:, 0]
        assert float(jnp.max(jnp.max(ref, -1) - picked)) < TOL
    counts = sched.read_counters()
    steps = sched.stats["decode_steps"]
    assert counts["moe_assignments_all"] == 4 * cfg.num_moe_layers * sum(
        len(c.tokens) - 1 for c in done.values())
    assert 0 < counts["moe_assignments_held"] < counts["moe_assignments_all"]
    assert 0 < counts["moe_experts_hit"] <= 8 * cfg.num_moe_layers * steps


def test_prefill_span_says_what_was_padded(model):
    from apex_tpu.observability import tracing

    with tracing.TracingScope() as tracer:
        sched, _ = _scheduler(model)
        sched.submit(Request(rid=0, prompt=list(range(11)),
                             max_new_tokens=2))
        sched.run_until_drained()
        spans = [s for s in tracer.spans() if s["name"] == "serve.prefill"]
    assert [(s["attrs"]["tokens"], s["attrs"]["padded_tokens"])
            for s in spans] == [(11, 16)]


@pytest.mark.parametrize("param_dtype,cast", [("bfloat16", 0),
                                              ("float32", 18)])
def test_scheduler_casts_the_adapters_tree_once_or_not_at_all(param_dtype,
                                                              cast):
    """The cell's tree is born in bf16: the scheduler holds it as it is
    and launches nothing.  The same weights in float32 under bf16
    compute: the projections are cast once (6 attention + 3 FFN leaves
    a stack); the router, its bias, the norms, the held experts and
    both vocabulary tables keep float32."""
    from apex_tpu.observability import tracing

    conf = _conf()
    conf["cellbench"]["args"] = {"compute_dtype": "bfloat16",
                                 "param_dtype": param_dtype}
    cfg = adapter.model_config(conf)
    params = adapter.program_params(conf, weights.seed_key(SEED),
                                    jnp.dtype(param_dtype))
    with tracing.TracingScope() as tracer:
        sched, _ = _scheduler((conf, None, cfg, params))
        (span,) = [s for s in tracer.spans()
                   if s["name"] == "serve.prepare_params"]
    held = sched.params
    moved = [(stack, leaf) for stack in ("dense", "moe")
             for leaf in held[stack]
             if held[stack][leaf] is not params[stack][leaf]]
    assert len(moved) == span["attrs"]["cast_leaves"] == cast
    assert span["attrs"]["cast_bytes"] == sum(
        params[s][leaf].size * 4 for s, leaf in moved)
    assert (held is params) == (cast == 0)
    assert all(held[s][leaf].dtype == jnp.bfloat16 for s, leaf in moved)
    assert held["moe"]["router_bias"].dtype == jnp.float32
    for leaf in ("router", "attn_norm", "q_norm", "kv_norm", "ffn_norm",
                 "we_gate", "we_up", "we_down"):
        assert held["moe"][leaf] is params["moe"][leaf]
    assert held["embed"] is params["embed"]
    assert held["head"] is params["head"]
    sched.submit(Request(rid=0, prompt=list(range(9)), max_new_tokens=3))
    assert len(sched.run_until_drained()[0].tokens) == 3


def test_what_the_latent_family_does_not_serve_yet_is_refused(model):
    with pytest.raises(NotImplementedError, match="one position"):
        _scheduler(model, draft_len=2)
    with pytest.raises(NotImplementedError, match="one position"):
        _scheduler(model, prefill_chunk=8)
    with pytest.raises(ValueError, match="prefill_buckets"):
        DecodeConfig(max_prompt_len=16, prefill_buckets=(32,))


def test_inference_imports_no_model():
    for name in ("scheduler", "decode", "kv_cache"):
        text = (REPO / "apex_tpu" / "inference" / f"{name}.py").read_text()
        assert "import GPTConfig" not in text
        assert "from apex_tpu.models" not in text
