"""The third served family, EvaByte (``apex_tpu.models.evabyte``): EVA
attention over a WINDOWED cache (a page of pooled columns a window, a
window buffer a decode slot), against the plain reference
(``cellbench/reference/evabyte.py``, which imports nothing of
``apex_tpu``) on the CPU at a tiny size: hidden 64, 4 heads of 16,
window 32, chunk 4, 3 layers, sequences of three windows and more."""

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
    decode_logits_tokenwise, make_decode_step,
)
from apex_tpu.inference.kv_cache import (  # noqa: E402
    PerSlot, Windowed, alloc_named_pools, page_positions, windowed_entry,
    windowed_view,
)
from apex_tpu.models.evabyte import (  # noqa: E402
    COUNTER_NAMES, EvaByteConfig, forward,
)
from apex_tpu.ops import eva  # noqa: E402
from apex_tpu.ops.decode_sampling_pallas import fused_sample  # noqa: E402
from cellbench import weights_evabyte as weights  # noqa: E402
from cellbench.adapters import serve_evabyte as adapter  # noqa: E402
from cellbench.reference import evabyte as reference  # noqa: E402

W, CHUNK, PAGE, V = 32, 4, 8, 320
TINY = {
    "model_type": "evabyte", "attention_class": "eva", "vocab_size": V,
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "num_pred_heads": 8,
    "window_size": W, "chunk_size": CHUNK, "num_chunks": None,
    "rms_norm_eps": 1e-5, "rope_theta": 100000, "rope_scaling": None,
    "norm_add_unit_offset": True, "fp32_skip_add": True,
    "fp32_logits": True, "fp32_ln": False, "mixedp_attn": True,
    "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": False, "init_std": 0.08,
    "max_position_embeddings": 4096,
    "cellbench": {"args": {"param_dtype": "float32",
                           "compute_dtype": "float32"}},
}
PPS = 5                     # pages a sequence: five windows, 160 positions


@pytest.fixture(scope="module")
def model():
    key = weights.seed_key(11)
    config = adapter.model_config(TINY)
    params = adapter.program_params(TINY, key, config.param_dtype)
    top = weights.top_weights(TINY, key)
    layer = lambda i: weights.layer_weights(TINY, key, i)
    return config, params, top, layer


def dcfg(impl="xla", slots=2, **kw):
    kw.setdefault("max_prompt_len", 4 * W)
    kw.setdefault("prefill_buckets", (W, 2 * W))
    return DecodeConfig(
        cache=KVCacheConfig(num_pages=1 + slots * PPS, page_size=PAGE,
                            pages_per_seq=PPS, dtype=jnp.float32),
        max_batch=slots, temperature=0.0, attn_impl=impl, sample_impl="xla",
        sample_dot_dtype=jnp.float32, **kw)


def ref_logits(model, tokens, positions=None):
    _, _, top, layer = model
    tokens = jnp.asarray(tokens, jnp.int32)
    pos = jnp.arange(len(tokens)) if positions is None else positions
    return reference.logits_at(TINY, top, layer, tokens, pos)


def tokens_of(seed, n):
    return np.random.RandomState(seed).randint(0, V, size=n).tolist()


# ------------------------------------------------------------ full forward
@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_full_forward_is_the_references_every_head(model, impl):
    """Three windows and a part of a fourth, a partial last chunk: all
    eight heads' logits at every position."""
    config, params, _, _ = model
    toks = tokens_of(0, 3 * W + 7)
    got = forward(params, jnp.asarray(toks, jnp.int32), config,
                  attn_impl=impl)
    want = ref_logits(model, toks)
    assert got.shape == want.shape == (len(toks), 8 * V)
    np.testing.assert_allclose(got, want, atol=2e-5)


# ------------------------------------------------- prefill, then decode
@pytest.mark.parametrize("impl,prefix", [
    ("xla", 45), ("xla", 64), ("xla", 70), ("interpret", 45),
    ("interpret", 62)])
def test_prefill_then_decode_is_the_full_forward(model, impl, prefix):
    """Token by token through the compiled decode step over the
    windowed cache, after a prefill that ends inside a chunk (45, 70,
    62), or exactly on a window's edge (64): every chunk close, the
    closes of windows 1, 2 and 3 and the first reads of their fresh
    pages of pooled columns lie in the decoded stretch."""
    config, params, _, _ = model
    toks = tokens_of(1, 4 * W)
    got = decode_logits_tokenwise(
        params, config, dcfg(impl), jnp.asarray([toks], jnp.int32), prefix,
        jnp.arange(1, 1 + PPS, dtype=jnp.int32))
    want = ref_logits(model, toks, jnp.arange(prefix, len(toks)))[:, :V]
    np.testing.assert_allclose(got, want, atol=3e-5)


def serve(config, params, requests, d, steps=4000):
    sched = ContinuousBatchingScheduler(params, config, d)
    for rid, (prompt, n) in enumerate(requests):
        sched.submit(Request(rid=rid, prompt=prompt, max_new_tokens=n))
    while not sched.idle() and steps:
        sched.step()
        steps -= 1
    return sched, {c.rid: c.tokens for c in sched.completed}


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_the_scheduler_serves_the_references_greedy_bytes(model, impl):
    """Five requests over two slots (so three of them move into a slot
    that another has used): prompts padded to each of the three
    buckets, answers that cross chunk and window boundaries.  Every
    served byte is the reference's own first choice given the bytes
    before it (greedy, float32 on both sides)."""
    config, params, _, _ = model
    requests = [(tokens_of(10, 21), 30), (tokens_of(11, 60), 40),
                (tokens_of(12, 100), 50), (tokens_of(13, 33), 9),
                (tokens_of(14, 5), 70)]
    sched, served = serve(config, params, requests, dcfg(impl))
    assert sorted(served) == [0, 1, 2, 3, 4]
    assert sched.decode_cache_size() == 1
    for rid, (prompt, n) in enumerate(requests):
        full = prompt + served[rid][:-1]
        want = ref_logits(model, full, jnp.arange(len(prompt) - 1,
                                                  len(full)))[:, :V]
        assert served[rid] == np.argmax(want, axis=-1).tolist(), rid
    # what the device counted is what the lengths say
    counts = sched.read_counters()
    assert tuple(counts) == COUNTER_NAMES
    own = pooled = chunks = windows = 0
    for prompt, n in requests:
        for pos in range(len(prompt), len(prompt) + n - 1):
            own += pos % W + 1
            pooled += pos // W * (W // CHUNK)
            chunks += pos % CHUNK == CHUNK - 1
            windows += pos % W == W - 1
    assert counts == {"eva_window_cols": own, "eva_summary_cols": pooled,
                      "eva_chunks_closed": chunks,
                      "eva_windows_closed": windows}
    assert sched.stats["window_rollovers"] == windows > 0


def test_a_reused_slot_sees_nothing_of_its_last_tenant(model):
    """One slot: B after A is B alone."""
    config, params, _, _ = model
    a, b = (tokens_of(20, 75), 40), (tokens_of(21, 37), 45)
    _, both = serve(config, params, [a, b], dcfg(slots=1))
    _, alone = serve(config, params, [b], dcfg(slots=1))
    assert both[1] == alone[0]


# ------------------------------------------------------------ the cache
def test_the_pooled_pages_hold_the_references_summaries(model):
    """After a prompt of two windows and a half and 14 decode steps, the
    request's pages hold, layer 0, the reference's ``ktilde``,
    ``vtilde`` of every closed chunk (the prompt's from the prefill, the
    last three from the decode step), and the window buffer the own
    columns of the open window."""
    config, params, top, layer = model
    prompt = tokens_of(30, 2 * W + 17)
    sched = ContinuousBatchingScheduler(params, config, dcfg("interpret"))
    sched.submit(Request(rid=7, prompt=prompt, max_new_tokens=40))
    emitted = []
    while len(emitted) < 15:
        sched.step()
        emitted = sched.drain_manifest()[0].emitted
    taken = prompt + list(emitted[:-1])
    state = sched.slot_state(7)
    assert set(state) == {"k", "v", "k.window", "v.window"}
    assert state["k"].shape == (3, 4, 4, 16, PAGE)     # 4 pages reserved
    assert state["k.window"].shape == (3, W // PAGE, 4, 16, PAGE)

    w0 = {n: x.astype(jnp.float32) for n, x in layer(0).items()}
    h = top["model.embed_tokens.weight"].astype(jnp.float32)[
        jnp.asarray(taken)]
    x = reference.rms_norm(h, w0["input_layernorm.weight"], 1e-5)
    with jax.default_matmul_precision("highest"):
        _, k, v = reference.projections(x, w0, TINY, lambda t: t)
        kt, vt = reference.summaries(k, v, w0, TINY, lambda t: t)
    n = len(taken) // CHUNK
    cols = lambda pages: np.asarray(pages[0]).transpose(0, 3, 1, 2) \
        .reshape(-1, 4, 16)
    np.testing.assert_allclose(cols(state["k"])[:n], kt, atol=2e-5)
    np.testing.assert_allclose(cols(state["v"])[:n], vt, atol=2e-5)
    live = len(taken) % W
    np.testing.assert_allclose(cols(state["k.window"])[:live],
                               k[len(taken) - live:], atol=2e-5)
    np.testing.assert_allclose(cols(state["v.window"])[:live],
                               v[len(taken) - live:], atol=2e-5)
    assert sched.slot_state(8) is None


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_dead_columns_and_an_open_windows_summaries_change_no_bit(model,
                                                                  impl):
    """Poison everything a decode step must not read: the window
    buffer's columns past the slot's live length (its last tenant's),
    the other slot's buffer, the page of pooled columns of the window
    that is still open, every unallocated page.  The step's logits are
    bit for bit those over the clean cache."""
    config, params, _, _ = model
    d = dcfg(impl)
    prompt = tokens_of(40, 2 * W + 10)
    sched = ContinuousBatchingScheduler(params, config, d)
    sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=30))
    for _ in range(6):
        sched.step()
    sched.drain_manifest()                  # settles the step in flight
    pos = int(sched._positions[0])
    assert pos // W == 2 and 12 < pos % W < W - 1
    pages = sched._slots[0].pages
    num_pages, wp = d.cache.num_pages, W // PAGE
    keep = np.zeros((sched.pools["k"].shape[1], PAGE), bool)
    keep[pages[0]] = keep[pages[1]] = True          # the closed windows
    own = num_pages + 0 * wp + np.arange(wp)
    live = pos % W                                  # columns 0 .. live - 1
    for j, page in enumerate(own):
        keep[page] = np.arange(PAGE) + j * PAGE < live
    poison = lambda x: jnp.where(keep[None, :, None, None, :], x, 1e30)
    step = make_decode_step(config, d, return_logits=True)
    args = (jnp.asarray([prompt[0], 0], jnp.int32),
            jnp.asarray([pos, 0], jnp.int32), jnp.asarray([True, False]),
            jnp.asarray(sched._page_tables), jnp.zeros((2,), jnp.uint32))
    # the step donates its pools: a fresh copy a call
    clean = lambda: {n: jnp.array(x) for n, x in sched.pools.items()}
    dirty = dict(clean(), k=poison(sched.pools["k"]),
                 v=poison(sched.pools["v"]))
    _, want = step(sched.params, clean(), *args)
    _, got = step(sched.params, dirty, *args)
    assert np.isfinite(np.asarray(want[0])).all()
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


def test_the_view_of_a_windowed_cache():
    """ONE page list a row: the closed windows' pages, then the slot's
    own; the current token's column; the length."""
    entry = Windowed(3, 4, 16, CHUNK, W)
    tables = jnp.asarray([[5, 6, 7, 0, 0], [9, 0, 0, 0, 0]], jnp.int32)
    got, cols, lengths = windowed_view(
        tables, jnp.asarray([2 * W + 9, 3]), jnp.asarray([True, False]),
        jnp.arange(2), entry, PAGE, 11)
    wp = W // PAGE
    assert got.shape == (2, PPS + wp)
    assert got[0].tolist() == [5, 6, 11, 12, 13, 14, 0, 0, 0]
    assert got[1].tolist()[:wp] == [11 + wp + j for j in range(wp)]
    assert cols.tolist() == [2 * PAGE + 9, 3]
    assert lengths.tolist() == [2 * PAGE + 10, 0]


def test_admission_counts_pages_at_the_stride(model):
    config, params, _, _ = model
    spec = config.served_model().cache_spec()
    d = dcfg(slots=2)
    assert page_positions(spec, d.cache) == W == PAGE * CHUNK
    assert windowed_entry(spec, d.cache) == Windowed(3, 4, 16, CHUNK, W)
    pools = alloc_named_pools(spec, d.cache, slots=2)
    assert pools["k"].shape == (3, 1 + 2 * PPS + 2 * (W // PAGE), 4, 16,
                                PAGE)
    sched = ContinuousBatchingScheduler(params, config, d)
    sched.submit(Request(rid=0, prompt=tokens_of(50, 40), max_new_tokens=30))
    sched.step()
    assert sched.allocator.live_pages == 3          # ceil(70 / 32)
    sched.submit(Request(rid=1, prompt=tokens_of(51, 20), max_new_tokens=9))
    sched.step()
    assert sched.allocator.live_pages == 3 + 1
    with pytest.raises(ValueError, match="pages"):  # six windows, five fit
        sched.submit(Request(rid=2, prompt=tokens_of(52, 100),
                             max_new_tokens=70))
    # a page of pooled columns must be one window; every paged pool of
    # a spec is windowed alike
    with pytest.raises(ValueError, match="page_size x stride == window"):
        windowed_entry(spec, KVCacheConfig(num_pages=4, page_size=16,
                                           pages_per_seq=2))
    with pytest.raises(ValueError, match="windowed alike"):
        windowed_entry(dict(spec, other=(3, 4, 16)))
    assert windowed_entry({"k": (3, 4, 16),
                           "s": PerSlot(1, (2,), jnp.float32)}) is None


def test_what_the_scheduler_refuses(model):
    config, params, _, _ = model
    served = config.served_model()
    assert served.multi_position is False
    for kw in (dict(draft_len=2), dict(prefill_chunk=16)):
        with pytest.raises(NotImplementedError, match="one position"):
            ContinuousBatchingScheduler(params, config, dcfg(**kw))
    with pytest.raises(NotImplementedError,
                       match="window buffer cannot be shared"):
        ContinuousBatchingScheduler(params, config,
                                    dcfg(prefix_sharing=True))
    with pytest.raises(ValueError, match="whole windows"):
        serve(config, params, [(tokens_of(60, 9), 3)],
              dcfg(prefill_buckets=(W // 2,)))
    with pytest.raises(NotImplementedError, match="one position"):
        served.decode(params, None, None, None, {}, None, "xla",
                      verify_width=3)


# ------------------------------------------------------------ the kernels
def test_the_summarise_kernel_is_its_twin():
    """Slots that close a chunk at different columns of different
    pages, one that closes none: the kernel (through the interpreter)
    against the gather."""
    rng = np.random.RandomState(0)
    k_pool, v_pool = (jnp.asarray(rng.randn(2, 9, 4, 16, PAGE), jnp.float32)
                      for _ in range(2))
    phi, mu = (jnp.asarray(rng.randn(4, 16) * 0.25, jnp.float32)
               for _ in range(2))
    args = (k_pool, v_pool, phi, mu, jnp.asarray([3, 8, 5, 1]),
            jnp.asarray([0, 4, 4, 0]),
            jnp.asarray([True, True, False, True]), jnp.int32(1), CHUNK)
    want = eva.eva_summarise(*args, impl="xla")
    got = eva.eva_summarise(*args, impl="interpret")
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(g, w_, atol=1e-6)
        assert not np.asarray(g[2]).any()           # closes none: zeros
    # and both are the pooling written down
    kc = k_pool[1, 8][:, :, 4:8]                    # (heads, d, chunk)
    alpha = jax.nn.softmax(jnp.einsum("hdj,hd->hj", kc, phi), axis=-1)
    np.testing.assert_allclose(
        want[0][1], jnp.einsum("hj,hdj->hd", alpha, kc) + mu, atol=1e-6)
    with pytest.raises(ValueError, match="whole chunks"):
        eva.eva_summarise(*args[:-1], 3, impl="xla")


@pytest.mark.parametrize("seen", [0, 8, 16])
def test_the_window_attention_kernel_is_its_twin(seen):
    rng = np.random.RandomState(seen)
    room = eva.pooled_capacity(3, W // CHUNK, W)
    assert room == 32 and eva.pooled_capacity(1, W // CHUNK, W) == 0
    q, k, v = (jnp.asarray(rng.randn(W, 4, 16), jnp.float32)
               for _ in range(3))
    kt, vt = (jnp.asarray(rng.randn(room, 4, 16), jnp.float32)
              for _ in range(2))
    want = eva.eva_window_attention(q, k, v, kt, vt, seen, impl="xla")
    got = eva.eva_window_attention(q, k, v, kt, vt, seen, impl="interpret")
    np.testing.assert_allclose(got, want, atol=2e-5)
    # rows of the buffer from ``seen`` on are not read
    np.testing.assert_array_equal(
        eva.eva_window_attention(q, k, v, kt.at[seen:].set(1e30),
                                 vt.at[seen:].set(1e30), seen, impl="xla"),
        want)


def test_a_first_window_has_no_pooled_buffer():
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(W, 4, 16), jnp.float32)
               for _ in range(3))
    none = jnp.zeros((0, 4, 16), jnp.float32)
    np.testing.assert_allclose(
        eva.eva_window_attention(q, k, v, none, none, 0, impl="interpret"),
        eva.eva_window_attention(q, k, v, none, none, 0, impl="xla"),
        atol=2e-5)


@pytest.mark.parametrize("temperature,top_k", [(0.0, 0), (1.0, 0),
                                               (0.7, 40)])
def test_the_sampling_head_at_320_rows(temperature, top_k):
    """A vocabulary of 2.5 lane tiles: the kernel pads its last tile
    and masks the rows it padded; the configuration keeps 320."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(20, 64), jnp.float32)
    head = jnp.asarray(rng.randn(V, 64) * 0.3, jnp.float32)
    seeds = jnp.arange(20, dtype=jnp.uint32) * 7 + 1
    args = dict(temperature=temperature, top_k=top_k, dot_dtype=jnp.float32)
    want = fused_sample(x, head, seeds, impl="xla", **args)
    got = fused_sample(x, head, seeds, impl="interpret", **args)
    np.testing.assert_array_equal(got, want)
    assert int(jnp.max(got)) < V
    if temperature == 0.0:
        # a row whose best byte is in the padded tile's real part
        head = head.at[V - 1].set(x[3] * 10)
        assert int(fused_sample(x, head, seeds, impl="interpret",
                                **args)[3]) == V - 1


# ---------------------------------------------------------- configuration
def test_from_published_reads_the_catalogs_row_as_committed():
    conf = json.loads((REPO / "cellbench" / "configs"
                       / "evabyte-6.5b-serve-pp4.json").read_text())
    c = EvaByteConfig.from_published(conf)
    assert (c.vocab_size, c.hidden_size, c.intermediate_size,
            c.num_attention_heads, c.head_dim) == (320, 4096, 11008, 32, 128)
    assert (c.num_hidden_layers, c.num_pred_heads, c.window_size,
            c.chunk_size) == (8, 8, 2048, 16)
    assert c.rope_theta == 100000.0 and c.rms_norm_eps == 1e-5
    assert c.max_position_embeddings == 32768
    assert EvaByteConfig.from_published(
        dict(conf, **conf["published"])).num_hidden_layers == 32
    entry = c.cache_entry
    assert entry == Windowed(8, 32, 128, 16, 2048)
    for key, value in (("attention_class", "full"), ("num_chunks", 4),
                       ("norm_add_unit_offset", False),
                       ("fp32_skip_add", False), ("model_type", "llama"),
                       ("num_key_value_heads", 8)):
        with pytest.raises(ValueError, match=key):
            EvaByteConfig.from_published(dict(conf, **{key: value}))
    with pytest.raises(ValueError, match="whole chunks"):
        EvaByteConfig(window_size=100, chunk_size=16)
