"""The plain decode loop keeps ONE step in flight
(``inference/scheduler.py``): ``step()`` launches step n+1, then reads
step n back and emits it, then admits.  What these tests hold, at a tiny
size on the CPU and for each of the three served families (GPT-2's
paged k/v, the latent cache with held experts, KDA's per-slot recurrent
state beside it):

- every request's served stream is bitwise what token-by-token greedy
  decoding gives (one decode step a token from an empty cache; tied to
  ``decode_logits_tokenwise`` once a family), through admissions
  mid-flight, an ``eos_id`` found a step late, prefix sharing with a
  copy-on-write, preemption and a drain — each with a step in flight;
- readers see a settled scheduler, ``idle()`` and ``step()`` count the
  step in flight, the decode step compiles once, the overlap engages on
  every step but the first of a busy stretch under ``serve.drive``'s
  calls, and the watchdog's hook waits for nothing.
"""

import sys
from contextlib import nullcontext
from pathlib import Path
from types import SimpleNamespace

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
    decode_logits_tokenwise, make_decode_step, served,
)
from apex_tpu.inference.kv_cache import (  # noqa: E402
    COUNTERS, alloc_named_pools,
)
from apex_tpu.models import mla_moe  # noqa: E402
from apex_tpu.models.gpt import GPTConfig, init_params  # noqa: E402
from apex_tpu.observability import tracing  # noqa: E402

VOCAB = 61
PAGE = 4
FAMILIES = ("gpt", "latent", "kda")


def _build(name):
    if name == "gpt":
        cfg = GPTConfig(
            vocab_size=VOCAB, hidden_size=32, num_layers=2,
            num_attention_heads=4, max_seq_len=64,
            position_embedding_type="rope", compute_dtype=jnp.float32,
            checkpoint_layers=False)
        params = init_params(cfg, jax.random.PRNGKey(0))
        # at the initialiser's scale a tiny tied-embedding GPT repeats
        # its last token for ever: matrices 16 times as large make the
        # greedy stream depend on everything the cache holds
        params["layers"] = {
            k: v * 16.0 if k in ("wq", "wk", "wv", "wo", "fc1", "fc2")
            else v for k, v in params["layers"].items()}
        return cfg, params
    kda = name == "kda"
    cfg = mla_moe.MLAMoEConfig(
        vocab_size=VOCAB, hidden_size=32, num_dense_layers=1,
        num_moe_layers=3 if kda else 2, num_attention_heads=2,
        q_lora_rank=12, kv_lora_rank=8, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=48,
        moe_intermediate_size=16, n_routed_experts=8, held_start=2,
        held_count=4, n_group=2, topk_group=1, num_experts_per_tok=2,
        rope_original_max_position=16,
        layer_kinds=("kda", "kda", "mla", "kda") if kda else None,
        kda_num_heads=2, kda_head_dim=16, param_dtype=jnp.float32,
        compute_dtype=jnp.float32)
    return cfg, mla_moe.init_params(cfg, jax.random.PRNGKey(4))


class _Family:
    """A tiny model of one family and its token-by-token greedy walk:
    ONE compiled decode step (logits out), one slot, an empty cache."""

    def __init__(self, name):
        self.name = name
        self.cfg, self.params = _build(name)
        self.pages = 16
        self.dcfg1 = DecodeConfig(
            cache=KVCacheConfig(num_pages=1 + self.pages, page_size=PAGE,
                                pages_per_seq=self.pages,
                                dtype=jnp.float32),
            max_batch=1, temperature=0.0, attn_impl="xla",
            sample_impl="xla", sample_dot_dtype=jnp.float32)
        self._step = make_decode_step(self.cfg, self.dcfg1,
                                      return_logits=True)
        self._walks = {}

    def _fresh_pools(self):
        m = served(self.cfg)
        pools = alloc_named_pools(m.cache_spec(), self.dcfg1.cache, slots=1)
        if m.counter_names:
            pools[COUNTERS] = jnp.zeros((len(m.counter_names),), jnp.int32)
        return m.serving_params(self.params), pools

    def walk(self, prompt, n):
        """The first ``n`` greedy tokens after ``prompt``, one decode
        step a token."""
        key = (tuple(prompt), n)
        if key in self._walks:
            return self._walks[key]
        tree, pools = self._fresh_pools()
        table = jnp.arange(1, 1 + self.pages, dtype=jnp.int32)[None]
        seq, out = list(prompt), []
        for pos in range(len(prompt) + n - 1):
            pools, logits = self._step(
                tree, pools, jnp.asarray([seq[pos]], jnp.int32),
                jnp.asarray([pos], jnp.int32), jnp.asarray([True]), table,
                jnp.zeros((1,), jnp.uint32))
            if pos >= len(prompt) - 1:
                out.append(int(jnp.argmax(logits[0])))
                seq.append(out[-1])
        self._walks[key] = out
        return out

    def sched(self, *, max_batch=3, num_pages=40, pages_per_seq=10,
              max_prompt=16, **knobs):
        dcfg = DecodeConfig(
            cache=KVCacheConfig(num_pages=num_pages, page_size=PAGE,
                                pages_per_seq=pages_per_seq,
                                dtype=jnp.float32),
            max_batch=max_batch, max_prompt_len=max_prompt,
            prefill_buckets=(8,), temperature=0.0, attn_impl="xla",
            sample_impl="xla", sample_dot_dtype=jnp.float32, **knobs)
        return ContinuousBatchingScheduler(self.params, self.cfg, dcfg)

    def check(self, completions):
        """Every completion is the walk over its prompt, as far as it
        was served."""
        for c in completions:
            want = self.walk(c.prompt, len(c.tokens))
            assert c.tokens == want, (
                f"{self.name} rid {c.rid}: served {c.tokens}, "
                f"token-by-token decoding gives {want}")


@pytest.fixture(scope="module", params=FAMILIES)
def fam(request):
    return _Family(request.param)


def _prompts(seed, lens):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, size=n).tolist() for n in lens]


def _in_flight(sched):
    return sched._inflight is not None


# ------------------------------------------------ the reference's anchor
def test_the_walk_is_decode_logits_tokenwise(fam):
    """The tests' token-by-token walk picks what
    ``decode_logits_tokenwise`` (prefill, then the compiled decode step
    a token) puts first at every position."""
    (prompt,) = _prompts(1, [7])
    tokens = fam.walk(prompt, 9)
    seq = prompt + tokens[:-1]
    logits = decode_logits_tokenwise(
        fam.params, fam.cfg, fam.dcfg1, jnp.asarray([seq], jnp.int32),
        len(prompt) - 1,
        jnp.arange(1, 1 + fam.pages, dtype=jnp.int32))
    assert [int(t) for t in jnp.argmax(logits, axis=-1)] == tokens


# --------------------------------------------------- the streams, bitwise
def test_mixed_lengths_with_admissions_mid_flight(fam):
    """Seven requests of mixed lengths through three slots, four of them
    submitted while steps are in flight: slots and pages recycle, every
    stream is the walk; no reader settled, the step compiled once."""
    sched = fam.sched()
    prompts = _prompts(2, [3, 8, 5, 12, 2, 9, 6])
    news = [6, 3, 9, 2, 7, 1, 5]
    for rid in range(3):
        sched.submit(Request(rid, prompts[rid], news[rid]))
    for rid in range(3, 7):
        sched.step()
        sched.step()
        assert _in_flight(sched)
        sched.submit(Request(rid, prompts[rid], news[rid]))
    done = sched.run_until_drained()
    assert sorted(c.rid for c in done) == list(range(7))
    assert [len(c.tokens) for c in sorted(done, key=lambda c: c.rid)] \
        == news
    fam.check(done)
    assert sched.decode_cache_size() == 1
    assert sched.stats["decode_settles"] == 0
    assert sched.stats["wasted_slot_steps"] == 0
    assert sched.stats["decode_overlapped"] > 0
    # every token but a request's first came out of a decode step, each
    # spending one draw of its slot: none wasted, none handed back twice
    assert int(sched._draws.sum()) == sum(news)
    assert not _in_flight(sched) and sched.idle()


def test_eos_found_a_step_late_wastes_one_slot_step(fam):
    """A sequence that ends on ``eos_id`` mid-stream is found when its
    token is read, one launch late: that slot-step is dropped and
    counted, its draw handed back, and the next tenant of the slot (and
    the neighbour decoding beside it) serve the walk, untouched."""
    neighbour, tenant = _prompts(3, [5, 7])
    # a prompt whose greedy stream first shows some token mid-stream
    ender, full, cut = next(
        (p, full, k + 1) for k in range(9, 0, -1) for seed in range(12)
        for p in _prompts(100 + seed, [6])
        for full in [fam.walk(p, 12)] if full.index(full[k]) == k)
    eos = full[cut - 1]
    sched = fam.sched(max_batch=2)
    sched.submit(Request(0, ender, 12, eos_id=eos))
    sched.submit(Request(1, neighbour, 14))
    sched.submit(Request(2, tenant, 8))         # queued: both slots taken
    done = {c.rid: c for c in sched.run_until_drained()}
    assert done[0].tokens == full[:cut]
    assert sched.stats["wasted_slot_steps"] == 1
    fam.check(done.values())
    assert len(done[1].tokens) == 14 and len(done[2].tokens) == 8
    # the wasted step's draw went back: one draw a served token
    assert int(sched._draws.sum()) == cut + 14 + 8
    assert sched.stats["decode_settles"] == 0


def test_prefix_sharing_cow_and_tail_page_under_a_step_in_flight(fam):
    """A finished request's tail page enters the trie while a step is in
    flight for its neighbour; a later request shares the full page and
    that tail, and copies the tail on its first divergent write, queued
    behind the step in flight.  All streams are the walk.  (A per-slot
    recurrent state refuses prefix sharing: no state is kept at a
    prefix's end.)"""
    if fam.name == "kda":
        with pytest.raises(NotImplementedError, match="per-slot recurrent"):
            fam.sched(prefix_sharing=True)
        return
    (system,) = _prompts(4, [6])                # one full page + a tail
    (longrun,) = _prompts(5, [5])
    sched = fam.sched(prefix_sharing=True)
    sched.submit(Request(0, longrun, 24))
    sched.submit(Request(1, system, 3))
    while not any(c.rid == 1 for c in sched.completed):
        sched.step()
    assert _in_flight(sched) and sched.num_active == 1
    sched.submit(Request(2, system, 9))         # the same prompt: its tail
    sched.submit(Request(3, system + [7], 6))   # the full page only
    done = {c.rid: c for c in sched.run_until_drained()}
    assert sched.stats["shared_tail_pages"] >= 1
    assert sched.stats["shared_full_pages"] >= 2
    assert sched.stats["cow_copies"] >= 1
    fam.check(done.values())
    assert [len(done[r].tokens) for r in range(4)] == [24, 3, 9, 6]
    assert sched.stats["decode_settles"] == 0


def test_preemption_settles_the_step_in_flight_and_continues(fam):
    """An interactive request that does not fit evicts the youngest
    best-effort resident: the step in flight is settled first (the
    victim keeps its token, its continuation starts from caches that
    hold it), and every stream, the preempted one included, is the
    walk."""
    a, b, c = _prompts(6, [6, 6, 6])
    sched = fam.sched(max_batch=2, num_pages=9, pages_per_seq=8)
    sched.submit(Request(0, a, 8, lane="best_effort"))
    sched.submit(Request(1, b, 8, lane="best_effort"))
    for _ in range(3):
        sched.step()
    assert _in_flight(sched) and sched.allocator.free_pages == 0
    sched.submit(Request(2, c, 8, lane="interactive"))
    done = {c.rid: c for c in sched.run_until_drained()}
    assert sched.stats["preemptions"] >= 1
    assert sched.stats["decode_settles"] >= 1
    assert sorted(done) == [0, 1, 2]
    assert all(len(c.tokens) == 8 for c in done.values())
    assert any(c.preemptions for c in done.values())
    fam.check(done.values())


def test_begin_drain_with_a_step_in_flight(fam):
    """``begin_drain`` settles the step in flight, hands the queue back
    and lets the residents finish: their streams are the walk, nothing
    of the step in flight is lost."""
    prompts = _prompts(7, [5, 9, 4, 6])
    sched = fam.sched(max_batch=2)
    for rid, p in enumerate(prompts):
        sched.submit(Request(rid, p, 7))
    for _ in range(3):
        sched.step()
    assert _in_flight(sched)
    emitted_before = sum(len(s.generated) for s in sched._slots if s)
    handed_back = sched.begin_drain()
    assert not _in_flight(sched) and sched.stats["decode_settles"] == 1
    assert sum(len(s.generated) for s in sched._slots if s) \
        == emitted_before + 2
    assert sorted(m.rid for m in handed_back) == [2, 3]
    assert not sched.drained()
    for _ in range(40):
        if sched.drained():
            break
        sched.step()
    assert sched.drained() and sched.idle()
    assert sorted(c.rid for c in sched.completed) == [0, 1]
    fam.check(sched.completed)


# ----------------------------------------------- settled readers, counters
def test_readers_see_every_launched_token_emitted(fam):
    """Between two calls a caller sees what a lockstep loop showed:
    after ``step()`` k the manifest holds k tokens of the resident, the
    device-side counters the steps that emitted them, and each of the
    settling readers leaves nothing in flight."""
    (prompt,) = _prompts(8, [6])
    sched = fam.sched()
    sched.submit(Request(0, prompt, 12))
    sched.step()                                # admit + prefill
    for k in range(1, 5):
        sched.step()                            # launches decode step k
        assert _in_flight(sched)
        assert len(sched._slots[0].generated) == k      # k - 1 steps read
        (m,) = sched.drain_manifest()
        assert not _in_flight(sched)
        assert m.emitted == fam.walk(prompt, 12)[:k + 1]
        assert m.remaining == 12 - (k + 1)
    assert sched.stats["decode_settles"] == 4
    assert sched.stats["decode_overlapped"] == 0        # lockstep by polling
    sched.step()
    assert _in_flight(sched)
    counters = sched.read_counters()
    assert not _in_flight(sched)
    if "kda_state_updates" in counters:
        kda_layers = sum(k == "kda" for k in fam.cfg.layer_kinds)
        assert counters["kda_state_updates"] \
            == kda_layers * sched.stats["decode_steps"]
    sched.step()
    assert sched.cancel(0) is None and not _in_flight(sched)
    sched.step()
    state = sched.slot_state(0)
    assert not _in_flight(sched)
    assert (state is not None) == (fam.name == "kda")
    assert sched.stats["decode_settles"] == 7
    fam.check(sched.run_until_drained())


def test_idle_and_step_count_the_unread_step(fam):
    """With the last step launched and unread, nothing is left to launch
    (the sequence's length is known): ``idle()`` is False, ``step()``
    reads it back and returns True, and only then is the server idle."""
    (prompt,) = _prompts(9, [5])
    sched = fam.sched()
    sched.submit(Request(0, prompt, 3))
    assert sched.step()                         # admit + prefill: token 1
    assert sched.step()                         # launch step 1
    assert sched.step()                         # launch step 2, read step 1
    assert _in_flight(sched) and len(sched._slots[0].generated) == 2
    assert not sched.idle()
    assert not sched._next_writers().any()      # the budget is in flight
    assert sched.step()                         # reads step 2: evicts
    assert not _in_flight(sched) and sched.idle()
    assert not sched.step()
    assert sched.completed[0].tokens == fam.walk(prompt, 3)
    assert sched.stats["decode_steps"] == 2
    assert sched.stats["decode_overlapped"] == 1


def test_token_times_are_readback_times(fam):
    """A token's time is the moment it is on the host: the clock read
    at its emit, after the launch of the step behind it."""
    ticks = iter(range(10_000))
    (prompt,) = _prompts(10, [4])
    dcfg = fam.sched().dcfg
    sched = ContinuousBatchingScheduler(
        fam.params, fam.cfg, dcfg, time_fn=lambda: float(next(ticks)))
    launches = []
    decode = sched._decode

    def spy(*args):
        launches.append(next(ticks))
        return decode(*args)

    sched._decode = spy
    sched.submit(Request(0, prompt, 4))
    (c,) = sched.run_until_drained()
    # token k + 1 comes out of launch k, and is stamped after launch k + 1
    assert len(launches) == 3 and len(c.token_times) == 4
    assert c.token_times[1] > launches[1] > launches[0]
    assert c.token_times[2] > launches[2] > c.token_times[1]
    assert c.token_times[3] > c.token_times[2]


def test_overlap_engages_under_the_benchmarks_driver(fam):
    """``cellbench.adapters.serve.drive`` (``step``, ``num_active``,
    ``allocator.live_pages``, ``completed``, ``submit``) settles
    nothing: ``in_flight`` is 1 on every ``serve.decode_step`` span but
    the first of a busy stretch (and a stretch's last, which only
    reads), and the spans count launches and reads as the stats do."""
    from cellbench.adapters import serve

    prompts = _prompts(11, [5, 9, 3, 7, 6, 4])
    requests = [SimpleNamespace(rid=i, due=0.002 * i, prompt=p,
                                max_new_tokens=6 + i)
                for i, p in enumerate(prompts)]
    sched = fam.sched()
    sched.submit(Request(10 ** 9, prompts[0], 2))       # the warm-up
    sched.run_until_drained()
    before = dict(sched.stats)
    with tracing.TracingScope() as tr:
        w = serve.drive(sched, requests, 0.25, log=lambda *_: None)
    assert sched.idle() and w["refused"] == 0
    done = [c for c in sched.completed if c.rid < 10 ** 9]
    assert len(done) == 6
    fam.check(done)
    assert sched.stats["decode_settles"] == 0
    assert sched.decode_cache_size() == 1
    steps = sorted((s for s in tr.spans()
                    if s["name"] == "serve.decode_step"),
                   key=lambda s: s["ts"])
    emits = [s for s in tr.spans() if s["name"] == "serve.emit"]
    n = sched.stats["decode_steps"] - before["decode_steps"]
    over = sched.stats["decode_overlapped"] - before["decode_overlapped"]
    assert len(emits) == n
    # a span launches, reads, or does both (the overlap)
    assert len(steps) == 2 * n - over
    assert sum(s["attrs"]["in_flight"] for s in steps) == over
    flags = [s["attrs"]["in_flight"] for s in steps]
    assert flags[0] == 0 and over >= n - 3
    # in_flight is 0 only where a stretch starts (a launch that found
    # nothing unread) or ends (a read with nothing left to launch)
    stretches = len(steps) - over
    assert stretches % 2 == 0 and flags.count(0) == stretches


def test_the_watchdogs_hook_waits_for_nothing(fam):
    """``_on_wedge`` runs on the watchdog's thread with the device hung
    in the step in flight: it reads the host's state and must not touch
    that step (here: reading it back would raise)."""

    class Hung:
        def __array__(self, *a, **k):
            raise AssertionError("the hook read the step in flight back")

    (prompt,) = _prompts(12, [5])
    sched = fam.sched()
    sched.submit(Request(0, prompt, 9))
    for _ in range(3):
        sched.step()
    tokens, sched._inflight.tokens = sched._inflight.tokens, Hung()
    sched._on_wedge({"elapsed_s": 1.0})
    (m,) = sched.drain_manifest(settle=False)
    assert m.emitted == fam.walk(prompt, 9)[:2]
    assert _in_flight(sched) and sched.stats["decode_settles"] == 0
    sched._inflight.tokens = tokens
    fam.check(sched.run_until_drained())


# ------------------------------------- the iteration taken apart (PR 35)
LAUNCH_ATTRS = ("upload_us", "enqueue_us", "dispatch_us", "wait_us")


def _drive_traced(fam, traced=True):
    """The benchmark's driver over six requests, after a warm-up:
    (scheduler, the window's completions by rid, spans)."""
    from cellbench.adapters import serve

    prompts = _prompts(13, [5, 9, 3, 7, 6, 4])
    requests = [SimpleNamespace(rid=i, due=0.002 * i, prompt=p,
                                max_new_tokens=6 + i)
                for i, p in enumerate(prompts)]
    sched = fam.sched()
    sched.submit(Request(10 ** 9, prompts[0], 2))       # the warm-up
    sched.run_until_drained()
    tr = tracing.Tracer(capacity=1 << 12)
    with tracing.TracingScope(tr) if traced else nullcontext():
        serve.drive(sched, requests, 0.25, log=lambda *_: None)
    done = {c.rid: c for c in sched.completed if c.rid < 10 ** 9}
    return sched, done, tr.spans()


@pytest.fixture(scope="module")
def traced_drive(fam):
    return _drive_traced(fam)


def _end(span):
    return span["ts"] + span["dur_us"] / 1e6


def test_every_decode_step_says_where_the_hosts_time_went(traced_drive):
    """``prep_us`` (before the span opened), then ``upload_us`` and
    ``enqueue_us``, whose sum is ``dispatch_us`` to the rounding, then
    ``wait_us``: on every span of a busy stretch, and they fit into it."""
    _, _, spans = traced_drive
    steps = [s for s in spans if s["name"] == "serve.decode_step"]
    assert len(steps) >= 11            # the longest answer alone
    for s in steps:
        at = s["attrs"]
        assert all(at[k] >= 0 for k in LAUNCH_ATTRS + ("prep_us",))
        assert abs(at["upload_us"] + at["enqueue_us"]
                   - at["dispatch_us"]) <= 2
        assert at["dispatch_us"] + at["wait_us"] <= s["dur_us"] + 2
    launched = [s["attrs"] for s in steps if s["attrs"]["in_flight"]]
    assert all(at["upload_us"] > 0 and at["enqueue_us"] > 0
               for at in launched)
    # a span that only reads launches nothing
    last = max(steps, key=lambda s: s["ts"])["attrs"]
    assert last["in_flight"] == 0 and last["dispatch_us"] <= 2


def test_the_spans_tile_a_period_up_to_the_callers_loop(traced_drive):
    """From one iteration's start to the next: the step's span, its
    ``serve.emit``, the ``serve.admit`` pass and the next step's
    ``prep_us``.  They never overlap, so they never exceed the period,
    and what they leave is the caller's loop."""
    _, _, spans = traced_drive
    steps = sorted((s for s in spans if s["name"] == "serve.decode_step"),
                   key=lambda s: s["ts"])
    others = [s for s in spans if s["name"] in ("serve.emit", "serve.admit")]
    periods = parts = 0.0
    for a, b in zip(steps, steps[1:]):
        if not (a["attrs"]["in_flight"] and b["attrs"]["in_flight"]):
            continue
        period = (b["ts"] - a["ts"]) * 1e6
        covered = a["dur_us"] + b["attrs"]["prep_us"] + sum(
            s["dur_us"] for s in others if a["ts"] <= s["ts"] < b["ts"])
        assert covered <= period + 200      # clocks differ by microseconds
        periods += period
        parts += covered
    assert periods > 0 and parts >= 0.5 * periods


def test_every_prefill_says_where_the_hosts_time_went(traced_drive):
    sched, _, spans = traced_drive
    prefills = sorted((s for s in spans if s["name"] == "serve.prefill"),
                      key=lambda s: s["ts"])
    assert len(prefills) == 6
    for s in prefills:
        at = s["attrs"]
        assert all(at[k] >= 0 for k in LAUNCH_ATTRS)
        assert abs(at["upload_us"] + at["enqueue_us"]
                   - at["dispatch_us"]) <= 2
        assert at["dispatch_us"] + at["wait_us"] <= s["dur_us"] + 2
        assert at["behind_step"] in (0, 1)
        assert at["tokens"] <= at["padded_tokens"] in (8, 16)
    # the first finds an empty server; one admitted mid-flight queues
    # on the device behind the step that was launched before it
    assert prefills[0]["attrs"]["behind_step"] == 0
    assert any(s["attrs"]["behind_step"] for s in prefills)


def test_the_stats_say_how_long_the_loop_waited(fam):
    """``device_wait_s`` and ``loop_host_s`` are kept with no tracer
    installed, and together they are time spent inside the loop."""
    import time

    prompts = _prompts(14, [5, 8, 3])
    sched = fam.sched()
    t0 = time.perf_counter()
    for rid, p in enumerate(prompts):
        sched.submit(Request(rid, p, 7))
    sched.run_until_drained()
    wall = time.perf_counter() - t0
    wait, host = sched.stats["device_wait_s"], sched.stats["loop_host_s"]
    assert isinstance(wait, float) and isinstance(host, float)
    assert wait > 0 and host > 0 and wait + host <= wall
    for _ in range(5):                  # an empty server waits for nothing
        assert not sched.step()
    assert sched.stats["device_wait_s"] == wait
    assert sched.stats["loop_host_s"] > host


def test_an_empty_server_is_one_span_a_stretch(fam):
    """``serve.idle``: one span a stretch of ``step()`` calls that found
    nothing resident, queued or in flight, recorded when work arrives,
    with the calls counted; none a call, and none while a request is in
    the server."""
    (prompt,) = _prompts(15, [5])
    sched = fam.sched()
    with tracing.TracingScope() as tr:
        for _ in range(10):
            assert not sched.step()
        after_10 = len(tr.spans())
        for _ in range(10_000):
            sched.step()
        assert len(tr.spans()) == after_10 == 0
        sched.submit(Request(0, prompt, 4))
        assert sched.step()
        (idle,) = [s for s in tr.spans() if s["name"] == "serve.idle"]
        assert idle["attrs"]["polls"] == 10_010 and idle["parent"] is None
        sched.run_until_drained()
        for _ in range(5):
            assert not sched.step()
        sched.submit(Request(1, prompt, 3))
        sched.run_until_drained()
    spans = tr.spans()
    idles = [s for s in spans if s["name"] == "serve.idle"]
    assert [s["attrs"]["polls"] for s in idles] == [10_010, 5]
    # nothing the server did lies inside a stretch it called empty
    work = [s for s in spans if s["name"] in (
        "serve.admit", "serve.prefill", "serve.decode_step", "serve.emit")]
    assert work and not any(i["ts"] <= w["ts"] < _end(i)
                            for i in idles for w in work)
    fam.check(sched.completed)


def test_streams_are_the_same_with_the_tracer_on_and_off(fam, traced_drive):
    _, on, _ = traced_drive
    sched, off, spans = _drive_traced(fam, traced=False)
    assert not spans and sorted(on) == sorted(off) == list(range(6))
    assert all(on[rid].tokens == off[rid].tokens for rid in on)
    fam.check(off.values())
    assert sched.stats["device_wait_s"] > 0
