"""The Mamba-2 ops (``ops/ssd.py``) and a model whose every layer mixes
by attention AND by the recurrence (``models/falcon_h1.py``) against the
plain reference (``cellbench/reference/falcon_h1.py``), at a small size
on the CPU: 3 layers, hidden 64, 4 query heads over 2 key/value heads of
16, 4 state-space heads of 16 with a state of 32 in 2 groups, conv 4,
chunks of 16, the published multipliers.  Seeded weights in the
published layout (``cellbench/weights_falcon_h1.py``), float32 on both
sides, so every comparison is to reduction-order rounding."""

import copy
import json
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
from apex_tpu.models import falcon_h1  # noqa: E402
from apex_tpu.ops import ssd  # noqa: E402
from cellbench import weights_falcon_h1 as weights  # noqa: E402
from cellbench.adapters import serve_falcon_h1 as adapter  # noqa: E402
from cellbench.reference import falcon_h1 as reference  # noqa: E402

#: the catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: Falcon-H1-34B-Instruct), as it stands
CATALOG = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False, "vocab_size": 261120}
TINY = dict(CATALOG, **{
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mamba_d_ssm": 64,
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 32,
    "mamba_chunk_size": 16, "max_position_embeddings": 4096,
    "cellbench": {"adapter": "serve_falcon_h1",
                  "args": {"compute_dtype": "float32",
                           "param_dtype": "float32"}}})
SEED = 2 ** 31 + 4321       # a large seed, as the driver's are
#: float32 on both sides: the widest difference read is 4e-6 on logits
#: of 3 (reduction order); ten times that
TOL = 4e-5


@pytest.fixture(scope="module")
def model():
    conf = copy.deepcopy(TINY)
    key = weights.seed_key(SEED)
    return (conf, key, adapter.model_config(conf),
            adapter.program_params(conf, key, jnp.float32))


def _reference_logits(conf, key, tokens, **kw):
    return reference.logits_at(
        conf, weights.top_weights(conf, key),
        lambda i: weights.layer_weights(conf, key, i),
        jnp.asarray(tokens), jnp.arange(len(tokens)), **kw)


def _ssd_inputs(T, H, P, G, N, seed):
    """Steps from a thousandth to a third, ``A`` in [1, 16]: a head's
    memory from one token to a thousand."""
    rng = np.random.RandomState(seed)
    x = rng.randn(T, H, P)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), size=(T, H)))
    A = -np.exp(rng.uniform(0, np.log(16), size=H))
    B, C, D = rng.randn(T, G, N), rng.randn(T, G, N), rng.randn(H)
    return [jnp.asarray(a, jnp.float32) for a in (x, dt, A, B, C, D)]


def test_the_reference_imports_nothing_of_the_program_and_sets_highest():
    text = (REPO / "cellbench" / "reference" / "falcon_h1.py").read_text()
    assert "apex_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text


# ------------------------------------------------------------------ the ops
@pytest.mark.parametrize("chunk", [128, 32])
@pytest.mark.parametrize("T", [128, 200, 384, 7])
def test_chunked_ssd_is_the_recurrence(T, chunk):
    """Four lengths (one a whole chunk of 128, one not a multiple of
    it, one of three chunks, one shorter than any) at two chunk sizes,
    from a non-zero state: outputs and final state agree to float32
    rounding (measured 2e-5 on outputs of 10, 2e-6 on a state of 1)."""
    H, P, G, N = 4, 16, 2, 32
    a = _ssd_inputs(T, H, P, G, N, seed=T)
    S0 = jnp.asarray(np.random.RandomState(1).randn(H, P, N) * 0.1,
                     jnp.float32)
    want_y, want_s = ssd.ssd_recurrent(*a, S0)
    got_y, got_s = ssd.ssd_chunked(*a, S0, chunk=chunk)
    assert float(jnp.max(jnp.abs(got_y - want_y))) < 1e-4
    assert float(jnp.max(jnp.abs(got_s - want_s))) < 1e-5
    assert float(jnp.max(jnp.abs(want_y))) > 1.0


def test_a_padded_tail_changes_no_bit_of_the_state():
    """Positions with ``dt = 0`` (how padding is marked) after the
    sequence: the same final state, bit for bit, whether they fill up
    the last chunk or add whole chunks; whatever their other inputs."""
    H, P, G, N, T = 4, 16, 2, 32, 200
    x, dt, A, B, C, D = _ssd_inputs(T, H, P, G, N, seed=3)
    S0 = jnp.zeros((H, P, N), jnp.float32)
    _, want = ssd.ssd_chunked(x, dt, A, B, C, D, S0)
    for extra in (56, 184):
        rng = np.random.RandomState(extra)
        pad = lambda t, fill: jnp.concatenate(
            [t, jnp.asarray(fill, jnp.float32)])
        _, got = ssd.ssd_chunked(
            pad(x, rng.randn(extra, H, P)), pad(dt, np.zeros((extra, H))),
            A, pad(B, rng.randn(extra, G, N)), pad(C, rng.randn(extra, G, N)),
            D, S0)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_ssd_decode_is_one_step_of_the_recurrence(impl):
    """On the stacked state at one layer: active slots advance by one
    token of the recurrence; an inactive slot's state, POISONED with
    NaN, stays poisoned, and no active slot's result differs by a bit
    from a run in which nothing was poisoned; other layers are
    untouched."""
    n, H, P, G, N, L = 5, 4, 16, 2, 32, 3
    x, dt, A, B, C, D = _ssd_inputs(n, H, P, G, N, seed=5)
    clean = jnp.asarray(np.random.RandomState(6).randn(L, n + 1, H, P, N),
                        jnp.float32)
    active = jnp.asarray([True, False, True, True, False])
    poisoned = clean.at[:, 1].set(jnp.nan).at[:, 4].set(jnp.nan)
    y_clean, s_clean = ssd.ssd_decode(x, dt, A, B, C, D, clean, active, 1,
                                      impl=impl)
    y, s = ssd.ssd_decode(x, dt, A, B, C, D, poisoned, active, 1, impl=impl)
    for b in (0, 2, 3):
        want_y, want_s = ssd.ssd_recurrent(
            x[b:b + 1], dt[b:b + 1], A, B[b:b + 1], C[b:b + 1], D,
            clean[1, b])
        assert float(jnp.max(jnp.abs(y[b] - want_y[0]))) < 1e-5
        assert float(jnp.max(jnp.abs(s[1, b] - want_s))) < 1e-6
        np.testing.assert_array_equal(np.asarray(y[b]),
                                      np.asarray(y_clean[b]))
        np.testing.assert_array_equal(np.asarray(s[1, b]),
                                      np.asarray(s_clean[1, b]))
    assert bool(jnp.all(jnp.isnan(s[:, 1]))) \
        and bool(jnp.all(jnp.isnan(s[:, 4])))
    assert float(jnp.max(jnp.abs(y[jnp.asarray([1, 4])]))) == 0.0
    for layer in (0, 2):
        np.testing.assert_array_equal(np.asarray(s[layer]),
                                      np.asarray(poisoned[layer]))


# ---------------------------------------------------------------- the model
def test_from_published_takes_the_catalog_row_as_it_stands():
    cfg = falcon_h1.FalconH1Config.from_published(CATALOG)
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim) == (5120, 20, 4, 128)
    assert cfg.state_shape == (32, 128, 256) and cfg.in_width == 9248
    assert cfg.conv_channels == 5120 and cfg.conv_shape == (3 * 5120,)
    assert cfg.rope_theta == 1e11 and cfg.mamba_chunk_size == 128
    assert cfg.mlp_multipliers == (0.1767766952966369, 0.011160714285714284)
    vec = np.asarray(cfg.mup_vector)
    assert vec.shape == (9248,)
    assert [float(vec[i]) for i in (0, 4096, 8192, 8704, 9216)] \
        == pytest.approx(CATALOG["ssm_multipliers"])
    spec = cfg.served_model().cache_spec()
    assert spec["k"] == spec["v"] == (72, 4, 128)
    assert spec["ssm_state"] == PerSlot(72, (32, 128, 256), jnp.float32)
    assert spec["ssm_conv"] == PerSlot(72, (15360,), jnp.bfloat16)
    assert cfg.served_model().counter_names == ("ssm_state_updates",)
    # a layer is 430.1 M parameters, the embedding and the head 2.67 B
    shapes = falcon_h1.param_shapes(cfg)
    count = lambda tree: sum(int(np.prod(s)) for s in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))
    assert 430.0e6 < count(shapes["layers"]) / 72 < 430.2e6
    assert count(shapes) - count(shapes["layers"]) == 2 * 261120 * 5120 + 5120
    for key, value in (("mamba_norm_before_gate", True),
                       ("attn_layer_indices", [0, 4]),
                       ("mamba_conv_bias", False)):
        with pytest.raises(ValueError, match=key):
            falcon_h1.FalconH1Config.from_published(
                dict(CATALOG, **{key: value}))


def test_full_forward_logits_match_the_reference(model):
    conf, key, cfg, params = model
    tokens = np.random.RandomState(0).randint(0, 256, size=100)
    got = falcon_h1.forward(params, jnp.asarray(tokens)[None], cfg,
                            attn_impl="xla")[0]
    want = _reference_logits(conf, key, tokens)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    assert float(jnp.max(jnp.abs(want))) > 1.0


@pytest.mark.parametrize("branch", ["attention", "mamba"])
def test_zeroing_either_mixer_moves_the_logits(model, branch):
    """The seeded scales let the comparison see both branches: without
    the attention branch, and without the state-space branch, the
    logits move by more than 100 times the tolerance (and the program
    with that branch's output matrix zeroed is the reference without
    the branch)."""
    conf, key, cfg, params = model
    tokens = np.random.RandomState(7).randint(0, 256, size=60)
    whole = _reference_logits(conf, key, tokens)
    other = ("mamba",) if branch == "attention" else ("attention",)
    fn = lambda h, w: reference.layer(h, w, conf, branches=other)
    without = _reference_logits(conf, key, tokens, layer_fn=fn)
    assert float(jnp.max(jnp.abs(whole - without))) > 100 * TOL
    assert float(jnp.mean(jnp.abs(whole - without))) > 100 * TOL
    leaf = "wo" if branch == "attention" else "w_out"
    cut = dict(params, layers=dict(
        params["layers"], **{leaf: jnp.zeros_like(params["layers"][leaf])}))
    got = falcon_h1.forward(cut, jnp.asarray(tokens)[None], cfg,
                            attn_impl="xla")[0]
    assert float(jnp.max(jnp.abs(got - without))) < TOL


def test_the_stage_is_the_uncut_models_first_layers_and_vocabulary(model):
    """The cut slices depth and vocabulary only: a stage that holds
    layers 1-2 of 3 and the first quarter of the vocabulary gives the
    uncut reference's stream after layer 2 (the final norm aside), and
    its head's logits are the first quarter of the whole head's over
    the same stream."""
    conf, key, _, _ = model
    stage = dict(copy.deepcopy(conf), num_hidden_layers=2, vocab_size=64)
    cfg = adapter.model_config(stage)
    params = adapter.program_params(stage, key, jnp.float32)
    assert params["head"].shape == (64, 64)
    assert params["layers"]["w_in"].shape[0] == 2
    tokens = np.random.RandomState(8).randint(0, 64, size=50)
    top = weights.top_weights(conf, key)
    make = lambda i: weights.layer_weights(conf, key, i)
    h2 = reference.hidden_after(conf, top, make, jnp.asarray(tokens), 2)
    gain = top["model.final_layernorm.weight"].astype(jnp.float32)
    want_hidden = reference.rms_norm(h2, gain, conf["rms_norm_eps"]) \
        * conf["lm_head_multiplier"]
    got_hidden = falcon_h1.forward(params, jnp.asarray(tokens)[None], cfg,
                                   attn_impl="xla", return_hidden=True)[0]
    assert float(jnp.max(jnp.abs(got_hidden - want_hidden))) < TOL
    whole_head = top["lm_head.weight"].astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(params["head"]),
                                  np.asarray(whole_head[:64]))
    got = falcon_h1.forward(params, jnp.asarray(tokens)[None], cfg,
                            attn_impl="xla")[0]
    with jax.default_matmul_precision("highest"):
        want = jnp.matmul(want_hidden, whole_head.T)[:, :64]
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_a_padded_prompt_hands_back_state_and_tail_at_prompt_len(model):
    """``FalconH1Served.prefill`` of 37 tokens padded to a bucket of 64:
    whatever fills the padded tail, the state and the convolution's tail
    come out bit for bit the same (padded positions take ``dt = 0``),
    and they are the unpadded run's (to rounding: its matmuls have
    another shape), as are the cached keys and values of the real
    positions."""
    _, _, cfg, params = model
    m = cfg.served_model()
    rng = np.random.RandomState(9)
    tokens = rng.randint(0, 256, size=37)
    pad = lambda: jnp.asarray(np.concatenate(
        [tokens, rng.randint(0, 256, size=27)]))[None]
    _, want = m.prefill(params, jnp.asarray(tokens)[None], jnp.int32(37),
                        "xla")
    _, got = m.prefill(params, pad(), jnp.int32(37), "xla")
    _, again = m.prefill(params, pad(), jnp.int32(37), "xla")
    assert got["ssm_state"].shape == (3, 4, 16, 32)
    assert got["ssm_conv"].shape == (3, 3 * 192)
    for name in ("ssm_state", "ssm_conv"):
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(again[name]))
        np.testing.assert_allclose(got[name], want[name], rtol=1e-4,
                                   atol=1e-6)
    assert float(jnp.max(jnp.abs(want["ssm_state"]))) > 0.01
    for name in ("k", "v"):
        assert got[name].shape == (3, 64, 2, 16)
        np.testing.assert_allclose(got[name][:, :37], want[name],
                                   rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_prefill_then_decode_through_both_caches(model, impl):
    """Prefill 70 tokens (four chunks and a bit), decode 30 more one at
    a time through the paged K/V pools (pages of 8) AND the per-slot
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
    pools["ssm_state"] = pools["ssm_state"].at[:, 0].set(7.0)
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
    assert float(jnp.min(pools["ssm_state"][:, 0])) == 7.0
    # 3 layers x (10 + 12) decode steps of one active slot
    assert int(pools[COUNTERS][0]) == 3 * 22


def test_scheduler_serves_the_family_greedy_as_the_reference(model):
    """More requests than slots, through ``ContinuousBatchingScheduler``
    with buckets: slots are reused, every served token is the
    reference's first choice (or within rounding of it), the counter
    counts the state updates, and ``slot_state`` hands out the
    resident's recurrence."""
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
    # every decode step of every request, in each of the 3 layers
    assert sched.read_counters() == {"ssm_state_updates": 3 * 4 * 5}
    # the benchmark's probe on the drained scheduler: the first layer's
    # state is the reference's recurrence over the prompt and every
    # emitted token but the last; a bfloat16 state is 100 times as far
    tokens, state = adapter.probe_state(sched, prompts[1])
    assert tokens[:21] == prompts[1] and len(tokens) == 21 + 8 * 8 - 21 - 2
    first = lambda **kw: reference.first_ssm_state(
        conf, weights.top_weights(conf, key),
        weights.layer_weights(conf, key, 0), jnp.asarray(tokens, jnp.int32),
        **kw)
    far = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    want = first()
    assert state.shape == (4, 16, 32) and far(state, want) < 1e-5
    assert far(first(state_dtype=jnp.bfloat16), want) > 1e-3


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
    pools = alloc_named_pools(cfg.served_model().cache_spec(), cache, slots=2)
    with pytest.raises(NotImplementedError, match="cannot be rolled back"):
        falcon_h1.forward_decode(
            params, jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
            jnp.ones((4,), bool), pools, jnp.zeros((2, 4), jnp.int32), cfg,
            verify_width=2)
    with pytest.raises(ValueError, match="needs slots"):
        alloc_named_pools(cfg.served_model().cache_spec(), cache)


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
