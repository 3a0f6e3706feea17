"""A model whose layers mix by a gated short convolution OR by grouped
attention, over a dense or a sparse feed-forward
(``models/lfm2_moe.py``), against the plain reference
(``cellbench/reference/lfm2_moe.py``), at a small size on the CPU: 10
layers (the catalog row's first ten ``layer_types``: a dense convolution
layer unrolled, two periods of ``conv, attention, conv, conv`` under one
scan, a convolution layer unrolled after them), hidden 64, 4 query heads
over 2 key/value heads of 16, 8 experts of 32 with 2 a token, 3 taps.
Seeded weights in the published layout
(``cellbench/weights_lfm2_moe.py``), float32 on both sides, so every
comparison is on LOGITS and to reduction-order rounding."""

import copy
import json
import signal
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
    COUNTERS, alloc_named_pools,
)
from apex_tpu.models import lfm2_moe  # noqa: E402
from apex_tpu.ops.kda import conv_step  # noqa: E402
from apex_tpu.transformer.expert_parallel import (  # noqa: E402
    held_experts_ffn, route_group_limited,
)
from cellbench import weights_lfm2_moe as weights  # noqa: E402
from cellbench.adapters import serve_lfm2_moe as adapter  # noqa: E402
from cellbench.reference import lfm2_moe as reference  # noqa: E402

#: the catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: LFM2-8B-A1B), as it stands
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv", "full_attention", "conv",
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}
TINY = dict(CATALOG, **{
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 10,
    "num_dense_layers": 1, "layer_types": CATALOG["layer_types"][:10],
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "max_position_embeddings": 4096,
    "cellbench": {"adapter": "serve_lfm2_moe",
                  "args": {"compute_dtype": "float32",
                           "param_dtype": "float32"}}})
SEED = 2 ** 31 + 4321       # a large seed, as the driver's are
#: float32 on both sides: the widest difference read is 8e-6 on logits
#: of 4 (reduction order); five times that
TOL = 4e-5
#: seconds a test of this file may take (the slowest takes 25 here)
TIME_LIMIT = 240


@pytest.fixture(autouse=True)
def _time_limit():
    def stop(*_):
        raise TimeoutError(f"over this file's limit of {TIME_LIMIT} s")

    old = signal.signal(signal.SIGALRM, stop)
    signal.alarm(TIME_LIMIT)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="module")
def model():
    conf = copy.deepcopy(TINY)
    key = weights.seed_key(SEED)
    cfg = adapter.model_config(conf)
    return conf, key, cfg, adapter.program_params(conf, key, cfg)


def _layer_weights(conf, key):
    return lambda i: weights.layer_weights(conf, key, i)


#: the reference's layer, jitted once a precision and branch set (a new
#: closure a call would compile its scans again every time)
_LAYER_FNS = {}
#: reference sequences are padded to this (causal: what follows a row
#: moves nothing in it), so that one length compiles
PAD = 80


def _layer_fn(conf, quant=None, branches=("mixer", "ffn")):
    at = (json.dumps(conf, sort_keys=True), quant, branches)
    if at not in _LAYER_FNS:
        _LAYER_FNS[at] = jax.jit(lambda h, w: reference.layer(
            h, w, conf, quant, branches=branches))
    return _LAYER_FNS[at]


_forward = jax.jit(lfm2_moe.forward,
                   static_argnames=("config", "attn_impl"))


def _padded(tokens):
    tokens = np.asarray(tokens, np.int32)
    assert len(tokens) <= PAD
    return jnp.asarray(np.concatenate(
        [tokens, np.zeros(PAD - len(tokens), np.int32)]))


def _reference_logits(conf, key, tokens, quant=None,
                      branches=("mixer", "ffn")):
    return reference.logits_at(
        conf, weights.top_weights(conf, key), _layer_weights(conf, key),
        _padded(tokens), jnp.arange(len(tokens)), quant,
        layer_fn=_layer_fn(conf, quant, branches))


def _reference_tails(conf, key, tokens, quant=None, only=None):
    """The convolution layers' tails after ``tokens`` (all of them, or
    layer ``only``'s), (layers, K - 1, H), by ONE forward of the
    reference."""
    h, out = reference.embed(weights.top_weights(conf, key),
                             _padded(tokens)), []
    fn = _layer_fn(conf, quant)
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(conf["layer_types"]):
            w = weights.layer_weights(conf, key, i)
            if kind == "conv" and only in (None, i):
                out.append(reference.conv_tail(conf, h[:len(tokens)], w,
                                               quant))
            h = fn(h, w)
    return jnp.stack(out)


def test_the_reference_imports_nothing_of_the_program_and_sets_highest():
    text = (REPO / "cellbench" / "reference" / "lfm2_moe.py").read_text()
    assert "apex_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text


def test_from_published_takes_the_catalog_row_as_it_stands():
    cfg = lfm2_moe.LFM2MoEConfig.from_published(CATALOG)
    assert cfg == lfm2_moe.LFM2MoEConfig()
    assert (cfg.head_dim, cfg.count("conv"), cfg.count("attn")) == (64, 18, 6)
    assert cfg.num_experts == 32 and cfg.tail_shape == (2 * 2048,)
    # two dense layers unrolled, four periods under one scan, six after
    prefix, period, n, suffix = cfg.plan
    assert prefix == (("conv", "dense"),) * 2 and n == 4 and len(suffix) == 6
    assert period == (("attn", "moe"),) + (("conv", "moe"),) * 3
    # the benchmark's stage: published layers 1-13, one of them dense
    cut = lfm2_moe.LFM2MoEConfig.from_published(dict(
        CATALOG, num_hidden_layers=13, num_dense_layers=1,
        layer_types=CATALOG["layer_types"][:13]))
    assert cut.plan == ((("conv", "dense"),),
                        (("conv", "moe"), ("attn", "moe"), ("conv", "moe"),
                         ("conv", "moe")), 3, ())
    # a pattern that never repeats is all prefix
    odd = lfm2_moe.LFM2MoEConfig.from_published(dict(
        CATALOG, num_hidden_layers=3, num_dense_layers=1,
        layer_types=["conv", "full_attention", "conv"]))
    assert odd.plan[1:3] == ((), 0) and len(odd.plan[0]) == 3
    for key, value in (("conv_bias", True), ("norm_topk_prob", False),
                       ("use_expert_bias", False),
                       ("tie_word_embeddings", False)):
        with pytest.raises(ValueError, match=key):
            lfm2_moe.LFM2MoEConfig.from_published(dict(CATALOG,
                                                       **{key: value}))
    with pytest.raises(ValueError, match="layer_types"):
        lfm2_moe.LFM2MoEConfig.from_published(dict(CATALOG,
                                                   num_hidden_layers=23))


def test_full_forward_logits_match_the_reference(model):
    """Two sequences of 40 through prefix, period and suffix: the
    logits are the reference's, one sequence at a time."""
    conf, key, cfg, params = model
    tokens = np.random.RandomState(0).randint(0, 256, size=(2, 40))
    got = _forward(params, jnp.asarray(tokens), config=cfg, attn_impl="xla")
    for b in range(2):
        want = _reference_logits(conf, key, tokens[b])
        assert float(jnp.max(jnp.abs(got[b] - want))) < TOL
        assert float(jnp.max(jnp.abs(want))) > 2.0


@pytest.mark.parametrize("branch", ["mixer", "ffn"])
def test_leaving_out_either_branch_moves_the_logits(model, branch):
    """Mixers and feed-forwards each move the stream: the reference
    without one is far from the program (so the comparison sees both)."""
    conf, key, cfg, params = model
    tokens = np.random.RandomState(5).randint(0, 256, size=24)
    got = _forward(params, jnp.asarray(tokens)[None], config=cfg,
                   attn_impl="xla")[0]
    kept = tuple(b for b in ("mixer", "ffn") if b != branch)
    without = _reference_logits(conf, key, tokens, branches=kept)
    assert float(jnp.max(jnp.abs(got - without))) > 100 * TOL


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_conv_step_at_three_taps_is_the_convolution(impl):
    """20 steps of ``conv_step`` over 5 slots (one of them idle
    throughout, one joining at step 7) of 128 channels in a 2-layer
    stack: an active slot's sums are the causal convolution over its
    own sequence, its tail the last two inputs, an idle slot's tail and
    the other layer are as they were."""
    rng = np.random.RandomState(7)
    B, C, K, T = 5, 128, 3, 20
    z = jnp.asarray(rng.randn(T, B, C), jnp.float32)
    filt = jnp.asarray(rng.randn(C, 1, K), jnp.float32)
    tails = jnp.full((2, B + 1, (K - 1) * C), 3.0, jnp.float32) \
        .at[1, :B].set(0.0)
    joins = np.array([0, 0, 7, T, 0])       # slot 3 never runs
    outs = []
    for t in range(T):
        active = jnp.asarray(joins <= t)
        y, tails = conv_step(z[t], filt[:, 0].T, tails, active, 1, impl=impl)
        outs.append(y)
    outs = jnp.stack(outs)
    for b in (0, 1, 2, 4):
        want = reference.short_conv(z[joins[b]:, b], filt)
        assert float(jnp.max(jnp.abs(outs[joins[b]:, b] - want))) < 1e-5
        assert jnp.array_equal(tails[1, b].reshape(K - 1, C), z[-2:, b])
    assert float(jnp.max(jnp.abs(tails[1, 3]))) == 0.0
    assert float(jnp.min(tails[0])) == 3.0 and float(tails[1, B, 0]) == 3.0


# ---------------------------------------------------------------- the router
def test_the_bias_moves_the_choice_and_not_the_weights():
    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(64, 32), jnp.float32)
    w = jnp.asarray(rng.randn(32, 8) * 32 ** -0.5, jnp.float32)
    route = lambda b, **kw: route_group_limited(
        x, w, b, top_k=2, n_group=1, topk_group=1, scale=1.0, **kw)
    ids0, w0 = route(jnp.zeros((8,)), eps=1e-6)
    bias = jnp.zeros((8,)).at[5].set(10.0)      # expert 5 always chosen
    ids1, w1 = route(bias, eps=1e-6)
    assert bool(jnp.all(jnp.any(ids1 == 5, axis=1)))
    assert not bool(jnp.all(jnp.any(ids0 == 5, axis=1)))
    s = jax.nn.sigmoid(x @ w)
    picked = jnp.take_along_axis(s, ids1, axis=1)
    # the ORIGINAL scores over their sum plus 1e-6: no trace of the 10
    want = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    assert float(jnp.max(jnp.abs(w1 - want))) < 1e-7
    # the reference's matrix of weights is the same choice and weights
    dense = reference.route(x, w.T, bias, 2)
    got = jnp.zeros_like(dense).at[jnp.arange(64)[:, None], ids1].set(w1)
    assert float(jnp.max(jnp.abs(got - dense))) < 1e-7
    # the constant is the argument's: 1e-20 (every other family's) gives
    # weights that sum to one to rounding, 1e-6 a sum that is short by
    # 1e-6 over the chosen scores' sum
    _, w20 = route(bias)
    short = 1.0 - w1.sum(-1)
    assert float(jnp.max(jnp.abs(1.0 - w20.sum(-1)))) < 2e-7
    assert float(jnp.max(jnp.abs(short - 1e-6 / picked.sum(-1)))) < 2e-7


def test_ties_go_to_the_lowest_id():
    """Equal scores (a zero router): the chosen are experts 0 and 1, in
    the program's router and in the reference's."""
    x = jnp.ones((3, 16), jnp.float32)
    ids, w = route_group_limited(
        x, jnp.zeros((16, 8)), jnp.zeros((8,)), top_k=2, n_group=1,
        topk_group=1, scale=1.0, eps=1e-6)
    assert ids.tolist() == [[0, 1]] * 3
    dense = reference.route(x, jnp.zeros((8, 16)), jnp.zeros((8,)), 2)
    assert bool(jnp.all((dense > 0) == (jnp.arange(8) < 2)[None]))
    assert float(jnp.max(jnp.abs(dense[:, :2] - w))) < 1e-7


# ------------------------------------------------------------ the share test
@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_the_halves_of_the_experts_add_up_to_the_whole_layer(model, impl):
    """The guide's share test: ``held_experts_ffn`` with ``held =
    range(E)`` is the reference's whole expert layer, and the parts that
    ``range(0, E / 2)`` and ``range(E / 2, E)`` give add up to it, with
    and without idle rows."""
    conf, key, cfg, _ = model
    w = weights.layer_weights(conf, key, 3)         # an expert layer
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    f = jnp.asarray(np.random.RandomState(13).randn(24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = reference.experts(f, w, conf, range(8), lambda x: x)
    E = 8
    layout = adapter._layout()
    p = {leaf: layout[leaf][1](*[w[pub] for pub in layout[leaf][0]])
         for leaf in ("router", "router_bias", "we_gate", "we_up", "we_down")}

    def part(held, mask=None):
        cut = {k: (v[held.start:held.stop] if k.startswith("we_") else v)
               for k, v in p.items()}
        out, counts = held_experts_ffn(
            f, cut, held, top_k=2, n_group=1, topk_group=1, scale=1.0,
            token_mask=mask, impl=impl, eps=1e-6)
        return out, counts

    whole, counts = part(range(E))
    assert float(jnp.max(jnp.abs(whole - want))) < TOL
    assert float(jnp.max(jnp.abs(want))) > 0.5
    # every assignment is computed here: nothing for the sort to drop
    assert int(counts["assignments_held"]) == 24 * 2 \
        == int(counts["assignments_all"])
    low, c_low = part(range(0, E // 2))
    high, c_high = part(range(E // 2, E))
    assert float(jnp.max(jnp.abs(low + high - want))) < TOL
    assert float(jnp.max(jnp.abs(low))) > 0.1 < float(jnp.max(jnp.abs(high)))
    assert int(c_low["assignments_held"]) + int(c_high["assignments_held"]) \
        == 48
    mask = jnp.arange(24) % 3 != 0
    masked, c_masked = part(range(E), mask)
    assert float(jnp.max(jnp.abs(
        masked - jnp.where(mask[:, None], want, 0.0)))) < TOL
    assert int(c_masked["assignments_all"]) == 16 * 2
    # the reference's halves add up too
    with jax.default_matmul_precision("highest"):
        halves = [reference.experts(
            f, {k: (v[h.start:h.stop] if "experts." in k else v)
                for k, v in w.items()}, conf, h, lambda x: x)
            for h in (range(0, 4), range(4, 8))]
    assert float(jnp.max(jnp.abs(halves[0] + halves[1] - want))) < TOL


def test_no_expert_takes_most_of_the_tokens(model):
    """The seeded router and its bias spread the choice: over 512 tokens
    through the first expert layer no expert gets more than twice its
    even share, none gets nothing, and the bias changes some token's
    choice."""
    conf, key, _, _ = model
    top = weights.top_weights(conf, key)
    tokens = jnp.asarray(np.random.RandomState(17).randint(0, 256, size=512))
    h = reference.hidden_after(conf, top, _layer_weights(conf, key),
                               tokens, 1, layer_fn=_layer_fn(conf))
    w = {k: v.astype(jnp.float32)
         for k, v in weights.layer_weights(conf, key, 1).items()}
    f = reference.rms_norm(h, w["ffn_norm.weight"], 1e-5)
    chosen = reference.route(f, w["feed_forward.gate.weight"],
                             w["feed_forward.expert_bias"], 2) > 0
    load = np.asarray(chosen.sum(0))
    assert load.min() > 0 and load.max() < 2 * 512 * 2 / 8
    blind = reference.route(f, w["feed_forward.gate.weight"],
                            jnp.zeros((8,)), 2) > 0
    assert 0 < int(jnp.sum(jnp.any(blind != chosen, axis=1))) < 256


# ------------------------------------------------------- prefill and decode
_PREFILLS = {}


def _prefill(cfg):
    """The served model's prefill, jitted once (one bucket of 16)."""
    if cfg not in _PREFILLS:
        m = cfg.served_model()
        _PREFILLS[cfg] = jax.jit(lambda p, t, n: m.prefill(p, t, n, "xla"))
    return _PREFILLS[cfg]


@pytest.mark.parametrize("plen", [16, 15, 14, 1, 9])
def test_a_padded_prompt_hands_back_the_tail_at_prompt_len(model, plen):
    """A prompt that ends 0, 1 and 2 positions into its bucket's padding
    (16, 15, 14 of 16), one shorter than the tail (1: the row before the
    sequence's start is zero) and one mid-bucket: every convolution
    layer's tail is the reference's last two rows of ``z`` at the
    prompt's TRUE end, and the logits there are the reference's."""
    conf, key, cfg, params = model
    tokens = np.random.RandomState(plen).randint(0, 256, size=16)
    padded = np.where(np.arange(16) < plen, tokens, 0)
    hidden, cache = _prefill(cfg)(params, jnp.asarray(padded)[None],
                                  jnp.int32(plen))
    want = _reference_tails(conf, key, tokens[:plen])
    got = cache["conv_tail"].reshape(want.shape)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    assert float(jnp.max(jnp.abs(want[:, -1]))) > 0.5
    if plen == 1:
        assert float(jnp.max(jnp.abs(got[:, 0]))) == 0.0
    assert cache["k"].shape == (2, 16, 2, 16) and hidden.shape == (16, 1, 64)
    logits = hidden[plen - 1, 0] @ params["embed"].T
    assert float(jnp.max(jnp.abs(
        logits - _reference_logits(conf, key, tokens[:plen])[-1]))) < TOL


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_prefill_then_decode_through_both_caches(model, impl):
    """Prefill 41 tokens, decode 30 more one at a time through the paged
    K/V pools (pages of 8) of the attention layers AND the per-slot
    tails of the convolution layers: position by position the logits
    are the reference's full forward."""
    conf, key, cfg, params = model
    tokens = np.random.RandomState(1).randint(0, 256, size=71)
    dcfg = DecodeConfig(
        cache=KVCacheConfig(num_pages=20, page_size=8, pages_per_seq=16,
                            dtype=jnp.float32),
        max_batch=3, max_prompt_len=128, temperature=0.0, attn_impl=impl,
        sample_impl="xla")
    got = decode_logits_tokenwise(
        params, cfg, dcfg, jnp.asarray(tokens)[None], 41,
        jnp.arange(1, 17, dtype=jnp.int32))
    want = _reference_logits(conf, key, tokens)[41:]
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_a_reused_slot_sees_nothing_of_its_last_tenant(model):
    """The serving programs themselves: request A is prefilled into
    slot 1 and decoded a few steps; then request B, padded to a BUCKET
    (37 tokens in 64), is prefilled into the same slot and decoded:
    B's logits are the reference's full forward of B alone, and the
    neighbouring slot's tails have not moved."""
    conf, key, cfg, params = model
    dcfg = DecodeConfig(
        cache=KVCacheConfig(num_pages=40, page_size=8, pages_per_seq=12,
                            dtype=jnp.float32),
        max_batch=3, max_prompt_len=64, temperature=0.0, attn_impl="xla",
        sample_impl="xla", sample_dot_dtype=jnp.float32)
    m = cfg.served_model()
    pools = alloc_named_pools(m.cache_spec(), dcfg.cache, slots=3)
    assert pools["k"].shape[0] == 2 and pools["conv_tail"].shape == (8, 4, 128)
    pools[COUNTERS] = jnp.zeros((len(m.counter_names),), jnp.int32)
    pools["conv_tail"] = pools["conv_tail"].at[:, 0].set(7.0)
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
    assert float(jnp.min(pools["conv_tail"][:, 0])) == 7.0
    # 22 decode steps of one active slot: 8 convolution layers' tails,
    # 9 expert layers of 2 assignments each, every one computed here
    counted = dict(zip(m.counter_names, np.asarray(pools[COUNTERS])))
    assert counted["conv_state_updates"] == 8 * 22
    assert counted["moe_assignments_held"] == 9 * 2 * 22 \
        == counted["moe_assignments_all"]
    assert 9 * 22 <= counted["moe_experts_hit"] <= 9 * 2 * 22


def test_scheduler_serves_the_family_greedy_as_the_reference(model,
                                                             monkeypatch):
    """More requests than slots, through ``ContinuousBatchingScheduler``
    with buckets: requests join as others leave mid-way, slots are
    reused, every served token is the reference's first choice (or
    within rounding of it), the counters count, and the benchmark's
    probe reads every convolution layer's tail."""
    conf, key, cfg, params = model
    monkeypatch.setattr(adapter, "REFERENCE_PAD", PAD)
    dcfg = DecodeConfig(
        cache=KVCacheConfig(num_pages=25, page_size=8, pages_per_seq=8,
                            dtype=jnp.float32),
        max_batch=2, max_prompt_len=32, prefill_buckets=(16,),
        temperature=0.0, attn_impl="xla", sample_impl="xla",
        sample_dot_dtype=jnp.float32)
    sched = ContinuousBatchingScheduler(params, cfg, dcfg)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 256, size=n).tolist() for n in (9, 21, 14, 1)]
    answers = (6, 11, 4, 8)             # so that slots free at odd times
    for i, (p, n) in enumerate(zip(prompts, answers)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=n))
    while not sched.idle():
        sched.step()
    assert len(sched.completed) == 4
    for c in sched.completed:
        seq = c.prompt + c.tokens[:-1]
        ref = _reference_logits(conf, key, seq)[len(c.prompt) - 1:]
        picked = jnp.take_along_axis(
            ref, jnp.asarray(c.tokens)[:, None], axis=1)[:, 0]
        assert float(jnp.max(jnp.max(ref, axis=-1) - picked)) < TOL
    # every decode step of every request (an answer's first token is the
    # prefill's): 8 convolution layers, 9 expert layers of 2 a token
    steps = sum(answers) - 4
    counted = sched.read_counters()
    assert counted["conv_state_updates"] == 8 * steps
    assert counted["moe_assignments_held"] == 18 * steps \
        == counted["moe_assignments_all"]
    # the benchmark's probe on the drained scheduler: every convolution
    # layer's tail is the reference's last two rows of z over the prompt
    # and every emitted token but the last; the float8 control's tail of
    # the deepest layer before any router (layer 2) is a thousand times
    # as far
    tokens, ends, tails = adapter.probe_state(sched, prompts[1])
    assert tokens[:21] == prompts[1] and len(tokens) == 21 + 8 * 8 - 21 - 2
    assert ends == list(range(len(tokens) - 14, len(tokens) + 1, 2))
    assert adapter.probe_layer(cfg.layer_types, cfg.num_dense_layers) == 1
    assert adapter.probe_layer(["full_attention", "conv", "conv"], 2) == 2
    want = lambda quant=None, n=None: _reference_tails(
        conf, key, tokens[:n], quant)
    far = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    assert tails.shape == (8, 8, 2, 64)
    # ... at every reading: the first and the last
    assert max(far(t, w) for t, w in zip(tails[-1], want())) < 1e-5
    assert max(far(t, w) for t, w in zip(tails[0], want(n=ends[0]))) < 1e-5
    assert far(want("float8_e4m3fn")[1], want()[1]) > 1e-2
    # ... and the adapter's four numbers pass on what was served, and
    # fail under the float8 control
    served = [(c.prompt, c.tokens) for c in sched.completed[:2]]
    limits = {"logit_gap": TOL, "mean_logit_gap": TOL,
              "conv_tail_drift": 1e-5, "widest_tail_drift": 1e-5}
    probe = (tokens, ends, tails)
    sound = adapter.compare(conf, key, served, limits, probe)
    assert len(sound) == 4 and all(v <= lim for _, v, lim in sound)
    assert "(layer 2, " in sound[2][0] and "8 convolution" in sound[3][0]
    assert "over 8 readings" in sound[3][0]
    control = adapter.compare(conf, key, served, limits, probe,
                              quant="float8_e4m3fn")
    assert control[1][1] > 100 * TOL and control[2][1] > 1e-2 \
        and control[3][1] > 1e-2
    # a tail that is another layer's reads in the widest number alone
    swapped = tails.copy()
    swapped[:, [5, 6]] = tails[:, [6, 5]]
    wrong = adapter.compare(conf, key, served, limits,
                            (tokens, ends, swapped))
    assert wrong[2][1] <= 1e-5 and wrong[3][1] > 0.5


def test_what_a_convolution_tail_cannot_serve_is_refused(model):
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
    pools = alloc_named_pools(cfg.served_model().cache_spec(), cache, slots=2)
    with pytest.raises(NotImplementedError, match="cannot be rolled back"):
        lfm2_moe.forward_decode(
            params, jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
            jnp.ones((4,), bool), pools, jnp.zeros((2, 4), jnp.int32), cfg,
            verify_width=2)
    with pytest.raises(ValueError, match="needs slots"):
        alloc_named_pools(cfg.served_model().cache_spec(), cache)


def test_init_params_builds_the_tree_the_forward_walks():
    """``init_params`` (the example's weights): the shapes are
    ``param_shapes``'s, norms, router and filter float32 and all else
    the parameter dtype, and the forward runs on them."""
    cfg = lfm2_moe.LFM2MoEConfig.from_published(
        {k: v for k, v in TINY.items() if k != "cellbench"})
    params = lfm2_moe.init_params(cfg, jax.random.PRNGKey(0))
    shapes = lfm2_moe.param_shapes(cfg)
    assert jax.tree.map(lambda x: x.shape, params) == jax.tree.map(
        lambda s: s, shapes, is_leaf=lambda x: isinstance(x, tuple))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in flat:
        want = jnp.float32 if path[-1].key in lfm2_moe.FLOAT32_LEAVES \
            else jnp.bfloat16
        assert leaf.dtype == want, path
    assert "head" not in params
    assert cfg.served_model().head(params) is params["embed"]
    logits = _forward(params, jnp.zeros((1, 8), jnp.int32), config=cfg,
                      attn_impl="xla")
    assert logits.shape == (1, 8, 256) and bool(jnp.all(jnp.isfinite(logits)))


def test_the_example_serves_the_family_from_a_config_file(tmp_path):
    """``examples/gpt/serve_gpt.py --model-config`` picks the family by
    ``model_type``: the smoke run serves it through the scheduler and
    holds every token to the full forward's greedy continuation."""
    sys.path.insert(0, str(REPO / "examples" / "gpt"))
    import serve_gpt

    conf = {k: v for k, v in TINY.items() if k != "cellbench"}
    path = tmp_path / "config.json"
    path.write_text(json.dumps(conf))
    serve_gpt.main(["--smoke", "--model-config", str(path),
                    "--attn-impl", "xla", "--sample-impl", "xla"])
