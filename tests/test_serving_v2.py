"""Serving v2 tests: speculative decode, prefix sharing + COW, chunked
prefill, and SLO lanes.

The load-bearing contracts:

- **Speculative parity**: the spec engine's token streams are BITWISE
  the non-speculative engine's (greedy and sampled — every emission
  spends the same (slot, draw) seed), with accepted-tokens/step > 1 on
  repetitive text, and the verify step compiles once across draft
  hit/miss/occupancy mixes.
- **Prefix sharing accounting**: N sequences sharing a system prompt
  hold exactly ONE refcounted copy of its full pages (pool accounting
  pinned), COW on the first divergent write preserves per-sequence
  tokens bitwise vs unshared, and shared-prefix oversubscription
  admits strictly more concurrent sequences than worst-case
  reservation.
- **Chunked prefill**: prompts beyond the padded prefill limit admit
  as fixed-size chunks, produce the same greedy stream as a one-shot
  prefill engine, and interleave with resident decode streams.
- **Lanes**: best-effort residents are preempted through the
  evict→recycle path to admit the interactive head, survivors are
  uncorrupted, preempted generations complete via continuation, and
  the serve histograms split by lane.
- **Refcounted allocator**: property-band — random
  allocate/share/free sequences never leak, never double-free, and the
  garbage page's refcount never moves.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.analysis import lowered as lw
from apex_tpu.inference import (
    ContinuousBatchingScheduler, DecodeConfig, GARBAGE_PAGE, KVCacheConfig,
    NGramProposer, PageAllocator, PrefixCache, Request, accepted_tokens,
)
from apex_tpu.models.gpt import GPTConfig, gpt_forward, init_params
from apex_tpu.observability import MetricsScope
from apex_tpu.ops.decode_attention_pallas import (
    decode_attention_xla, paged_decode_attention_pallas,
)


def tiny_cfg(**kw):
    base = dict(
        vocab_size=61, hidden_size=32, num_layers=2,
        num_attention_heads=4, max_seq_len=128,
        position_embedding_type="rope", compute_dtype=jnp.float32,
        checkpoint_layers=False,
    )
    base.update(kw)
    return GPTConfig(**base)


def _sched(params, cfg, *, num_pages=40, page_size=4, pages_per_seq=16,
           max_batch=3, temperature=0.0, top_k=0, max_prompt=16, seed=0,
           **dk):
    dcfg = DecodeConfig(
        cache=KVCacheConfig(num_pages=num_pages, page_size=page_size,
                            pages_per_seq=pages_per_seq,
                            dtype=jnp.float32),
        max_batch=max_batch, max_prompt_len=max_prompt,
        temperature=temperature, top_k=top_k,
        attn_impl="xla", sample_impl="xla",
        sample_dot_dtype=jnp.float32, base_seed=seed, **dk)
    return ContinuousBatchingScheduler(params, cfg, dcfg)


def _repetitive_prompt(rng, vocab, period=4, length=14):
    pat = rng.randint(0, vocab, size=period).tolist()
    return (pat * (length // period + 1))[:length]


def _tokens_by_rid(completions):
    return {c.rid: tuple(c.tokens) for c in completions}


# ------------------------------------------------- verify-width attention
class TestVerifyWidthAttention:
    def _case(self, rng, B=2, W=3, H=4, KVH=2, D=16, num_pages=9, page=8,
              P=4):
        q = jnp.asarray(rng.randn(B * W, H, D), jnp.float32)
        kp = jnp.asarray(rng.randn(num_pages, KVH, D, page), jnp.float32)
        vp = jnp.asarray(rng.randn(num_pages, KVH, D, page), jnp.float32)
        pt = jnp.asarray(rng.randint(1, num_pages, size=(B, P)), jnp.int32)
        lengths = jnp.asarray(
            rng.randint(0, page * P, size=(B * W,)), jnp.int32)
        return q, kp, vp, pt, lengths

    def test_width_matches_repeated_tables(self):
        """The width layout is pure bookkeeping: scoring W consecutive
        rows against one shared table row must equal width=1 with the
        table explicitly repeated."""
        rng = np.random.RandomState(0)
        q, kp, vp, pt, lengths = self._case(rng)
        wide = decode_attention_xla(q, kp, vp, pt, lengths, width=3)
        flat = decode_attention_xla(q, kp, vp, jnp.repeat(pt, 3, axis=0),
                                    lengths, width=1)
        np.testing.assert_allclose(np.asarray(wide), np.asarray(flat),
                                   rtol=0, atol=1e-6)

    def test_kernel_width_matches_reference(self):
        rng = np.random.RandomState(1)
        q, kp, vp, pt, lengths = self._case(rng)
        ref = decode_attention_xla(q, kp, vp, pt, lengths, width=3)
        out = paged_decode_attention_pallas(q, kp, vp, pt, lengths,
                                            width=3, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0, atol=1e-5)

    def test_width_shape_mismatch_refuses(self):
        rng = np.random.RandomState(2)
        q, kp, vp, pt, lengths = self._case(rng)
        with pytest.raises(ValueError, match="width"):
            decode_attention_xla(q, kp, vp, pt, lengths, width=2)


# ----------------------------------------------------------- speculation
class TestSpeculative:
    @pytest.fixture(scope="class")
    def model(self):
        cfg = tiny_cfg()
        return cfg, init_params(cfg, jax.random.PRNGKey(0))

    def _requests(self, rng, n, vocab, max_new=8):
        return [Request(i, _repetitive_prompt(rng, vocab), max_new)
                for i in range(n)]

    @pytest.mark.parametrize("pet,gqa", [
        ("rope", None), ("learned", None), ("rope", 2)])
    def test_greedy_spec_stream_bitwise_vs_plain(self, pet, gqa):
        """The acceptance pin, across the gpt config zoo: greedy
        speculative serving emits BITWISE the non-speculative engine's
        token streams, and beats one token/step on repetitive text."""
        cfg = tiny_cfg(position_embedding_type=pet, num_query_groups=gqa,
                       max_seq_len=64)
        params = init_params(cfg, jax.random.PRNGKey(1))
        rng = np.random.RandomState(3)
        reqs = self._requests(rng, 4, cfg.vocab_size)

        plain = _sched(params, cfg)
        for r in reqs:
            plain.submit(Request(r.rid, list(r.prompt), r.max_new_tokens))
        want = _tokens_by_rid(plain.run_until_drained())

        spec = _sched(params, cfg, draft_len=3)
        for r in reqs:
            spec.submit(Request(r.rid, list(r.prompt), r.max_new_tokens))
        got = _tokens_by_rid(spec.run_until_drained())
        assert got == want, (
            "speculative greedy streams diverged from the plain engine")
        rate = spec.stats["spec_emitted"] / max(spec.stats["spec_steps"], 1)
        assert rate > 1.0, (
            f"accepted-tokens/step {rate:.2f} <= 1 on repetitive text — "
            f"drafts never land")
        assert spec.stats["decode_steps"] < plain.stats["decode_steps"], (
            "speculation saved no decode steps")

    def test_sampled_spec_stream_bitwise_vs_plain(self, model):
        """Temperature sampling too: each emission spends the same
        (slot, draw) seed the plain engine would, so even the SAMPLED
        stream is reproduced exactly.  (Requests <= max_batch: with a
        queue, speculation finishes residents at different STEPS, so a
        queued request can land in a different slot — a different seed
        lineage.  Greedy parity, which ignores seeds, holds regardless
        — the zoo test above queues 4 into 3 slots.)"""
        cfg, params = model
        rng = np.random.RandomState(4)
        reqs = self._requests(rng, 3, cfg.vocab_size)

        def run(draft):
            s = _sched(params, cfg, temperature=0.8, top_k=7, seed=5,
                       draft_len=draft)
            for r in reqs:
                s.submit(Request(r.rid, list(r.prompt), r.max_new_tokens))
            return _tokens_by_rid(s.run_until_drained())

        assert run(0) == run(4)

    def test_eos_respected_mid_acceptance(self, model):
        """An accepted burst that crosses eos truncates exactly where
        the plain engine stops."""
        cfg, params = model
        rng = np.random.RandomState(5)
        prompt = _repetitive_prompt(rng, cfg.vocab_size)
        plain = _sched(params, cfg)
        plain.submit(Request(0, list(prompt), 10))
        toks = plain.run_until_drained()[0].tokens
        eos = toks[len(toks) // 2]
        cut = toks.index(eos) + 1

        spec = _sched(params, cfg, draft_len=3)
        spec.submit(Request(0, list(prompt), 10, eos_id=eos))
        assert spec.run_until_drained()[0].tokens == toks[:cut]

    def test_verify_step_compiles_once_across_mixes(self, model):
        """assert_no_recompile on the verify step across occupancy x
        draft-hit/miss mixes (repetitive AND incompressible prompts,
        admissions and evictions in flight)."""
        cfg, params = model
        sched = _sched(params, cfg, draft_len=3)
        rng = np.random.RandomState(6)
        for i in range(5):
            prompt = (_repetitive_prompt(rng, cfg.vocab_size) if i % 2
                      else rng.randint(0, 61, size=7).tolist())
            sched.submit(Request(i, prompt, int(rng.randint(2, 9))))
        sched.run_until_drained()
        assert sched.stats["spec_steps"] > 0
        lw.assert_no_recompile(sched._verify, label="verify_step")

    def test_ngram_proposer_prompt_lookup(self):
        p = NGramProposer(draft_len=3, ngram_max=2, ngram_min=1)
        p.extend([5, 1, 2, 3, 9, 1, 2])
        # trailing bigram (1, 2) last occurred at positions 1..2 —
        # the continuation there is [3, 9, 1]
        assert p.propose() == [3, 9, 1]
        q = NGramProposer(draft_len=2)
        q.extend([1, 2, 3, 4])
        assert q.propose() == []  # nothing repeats

    def test_accepted_tokens_rule(self):
        # drafts all hit -> every emission consumed (incl. the bonus)
        assert accepted_tokens([7, 4, 5], [4, 5, 6]) == [4, 5, 6]
        # first draft misses -> only the standard-path token
        assert accepted_tokens([7, 9, 5], [4, 5, 6]) == [4]
        # partial
        assert accepted_tokens([7, 4, 9], [4, 5, 6]) == [4, 5]


# --------------------------------------------------- refcounted allocator
class TestRefcountAllocator:
    def test_share_and_deferred_recycle(self):
        a = PageAllocator(num_pages=6)
        pages = a.allocate(2)
        a.share(pages)  # second reference
        a.free(pages)   # drops to 1 — still live
        assert a.free_pages == 3 and a.refcount(pages[0]) == 1
        a.free(pages)   # last reference — recycles
        assert a.free_pages == 5 and a.refcount(pages[0]) == 0

    def test_share_guards(self):
        a = PageAllocator(num_pages=4)
        with pytest.raises(ValueError, match="never shared"):
            a.share([GARBAGE_PAGE])
        with pytest.raises(ValueError, match="free page"):
            a.share([2])  # never allocated

    def test_property_random_ops_never_leak_or_double_free(self):
        """The satellite band: random allocate/share/free sequences
        against a model of the refcounts — the pool never leaks, a
        stale free always raises, the garbage page never moves."""
        rng = np.random.RandomState(7)
        N = 17
        a = PageAllocator(num_pages=N)
        model = {}  # page -> refcount
        for _ in range(600):
            op = rng.randint(3)
            if op == 0:
                n = int(rng.randint(1, 4))
                got = a.allocate(n)
                if n > N - 1 - len(model):
                    assert got is None, "allocated past the pool"
                else:
                    assert got is not None and len(got) == n
                if got is not None:
                    for p in got:
                        assert p != GARBAGE_PAGE and p not in model
                        model[p] = 1
            elif op == 1 and model:
                p = int(rng.choice(sorted(model)))
                a.share([p])
                model[p] += 1
            elif op == 2 and model:
                p = int(rng.choice(sorted(model)))
                a.free([p])
                model[p] -= 1
                if model[p] == 0:
                    del model[p]
            # invariants, every step
            assert a.refcount(GARBAGE_PAGE) == 0
            assert a.free_pages == N - 1 - len(model), "page leak"
            for p, r in model.items():
                assert a.refcount(p) == r
        dead = [p for p in range(1, N) if p not in model]
        if dead:
            with pytest.raises(ValueError, match="double free"):
                a.free([dead[0]])
        for p, r in list(model.items()):
            a.free([p] * r)
        assert a.free_pages == N - 1, "pages leaked at drain"

    def test_release_skips_resident_held_chains(self):
        """Pressure relief must count pages actually RECYCLED, not
        trie refs dropped: a chain whose every page is still
        resident-held frees nothing — wiping it would only destroy the
        sharing while the admission stays blocked (release returns 0
        and the scheduler escalates to preemption instead)."""
        alloc = PageAllocator(num_pages=8)
        cache = PrefixCache(alloc, page_size=4)
        pages = alloc.allocate(2)
        prompt = list(range(8))
        cache.register(prompt, pages)  # trie ref on top: refcounts 2
        assert cache.release(10) == 0, "resident-held chain was wiped"
        assert cache.indexed_pages == 2
        assert cache.match(prompt).num_full == 2, (
            "sharing destroyed by a release that freed nothing")
        assert alloc.free_pages == 5
        alloc.free(pages)  # the resident evicts — trie is last holder
        assert cache.release(10) == 2  # now the drop actually recycles
        assert alloc.free_pages == 7
        assert cache.match(prompt).num_full == 0


# -------------------------------------------------------- prefix sharing
class TestPrefixSharing:
    @pytest.fixture(scope="class")
    def model(self):
        cfg = tiny_cfg()
        return cfg, init_params(cfg, jax.random.PRNGKey(0))

    def test_full_pages_deduped_pool_accounting_pinned(self, model):
        """N resident sequences sharing a system prompt hold exactly
        ONE refcounted copy of its full pages."""
        cfg, params = model
        rng = np.random.RandomState(8)
        sysp = rng.randint(0, 61, size=8).tolist()  # exactly 2 full pages
        n, max_new = 3, 12  # long enough that all 3 stay resident
        sched = _sched(params, cfg, prefix_sharing=True, max_batch=n)
        for i in range(n):
            sched.submit(Request(i, sysp + [i], max_new))
        sched.step()  # one admission sweep
        assert sched.num_active == n
        per_seq = 6   # ceil((9 + 12) / 4)
        expect_live = per_seq + (n - 1) * (per_seq - 2)
        assert sched.allocator.live_pages == expect_live, (
            "pool accounting: shared full pages were not deduped")
        assert sched.stats["shared_full_pages"] == 2 * (n - 1)
        shared = [int(p) for p in sched._page_tables[0, :2]]
        for i in range(1, n):
            assert [int(p) for p in sched._page_tables[i, :2]] == shared
        # n sequences + the trie each hold a reference
        assert all(sched.allocator.refcount(p) == n + 1 for p in shared)
        sched.run_until_drained()
        sched.prefix.release(10 ** 6)
        assert sched.allocator.free_pages == 39, "pages leaked"

    def test_cow_preserves_tokens_bitwise_vs_unshared(self, model):
        """Owner evicts -> tail page enters the trie; a same-prompt
        matcher shares it and COWs on its first divergent write — its
        stream must equal the unshared engine's bitwise."""
        cfg, params = model
        rng = np.random.RandomState(9)
        prompt = rng.randint(0, 61, size=10).tolist()  # 2 pages + tail
        sched = _sched(params, cfg, prefix_sharing=True)
        sched.submit(Request(0, list(prompt), 5))
        sched.run_until_drained()
        sched.submit(Request(1, list(prompt), 5))
        got = sched.run_until_drained()[-1]
        assert sched.stats["shared_tail_pages"] == 1
        assert sched.stats["cow_copies"] == 1

        plain = _sched(params, cfg)
        plain.submit(Request(1, list(prompt), 5))
        want = plain.run_until_drained()[0]
        assert got.tokens == want.tokens, (
            "COW changed the shared-tail sequence's stream")

    def test_oversubscription_admits_strictly_more(self, model):
        """The capacity win: a pool that fits ONE worst-case sequence
        unshared fits TWO with a shared prefix."""
        cfg, params = model
        rng = np.random.RandomState(10)
        sysp = rng.randint(0, 61, size=8).tolist()  # 2 full pages
        kw = dict(num_pages=6, page_size=4, pages_per_seq=3, max_batch=2)

        def max_resident(sharing):
            sched = _sched(params, cfg, prefix_sharing=sharing, **kw)
            for i in range(3):
                sched.submit(Request(i, sysp + [i], 3))  # 3 pages each
            peak = 0
            for _ in range(200):
                if sched.idle():
                    break
                sched.step()
                peak = max(peak, sched.num_active)
            assert sched.idle() and len(sched.completed) == 3
            return peak

        assert max_resident(False) == 1
        assert max_resident(True) == 2, (
            "shared prefixes must admit strictly more than worst-case "
            "reservation")

    def test_trie_release_under_pressure_keeps_serving(self, model):
        """A full trie must not wedge admission: the allocator runs
        dry, LRU chains release, the queue drains."""
        cfg, params = model
        rng = np.random.RandomState(11)
        sched = _sched(params, cfg, prefix_sharing=True, num_pages=8,
                       page_size=4, pages_per_seq=4, max_batch=1)
        for i in range(4):  # distinct prompts: the trie only grows
            sched.submit(Request(i, rng.randint(0, 61, size=8).tolist(), 4))
        done = sched.run_until_drained()
        assert len(done) == 4
        assert sched.prefix.stats["released_pages"] > 0, (
            "pool pressure never released trie chains — the test is "
            "not exercising the release path")

    def test_random_share_trace_never_leaks(self, model):
        """End-to-end chaos band: random prompts (some shared), random
        budgets, interleaved submits/drains — afterwards every page is
        accounted for and the garbage page never moved."""
        cfg, params = model
        rng = np.random.RandomState(12)
        sched = _sched(params, cfg, prefix_sharing=True, draft_len=2,
                       num_pages=24, pages_per_seq=10, max_batch=2)
        sysp = rng.randint(0, 61, size=9).tolist()
        rid = 0
        for _ in range(6):
            for _ in range(int(rng.randint(1, 4))):
                if rng.rand() < 0.6:
                    prompt = sysp + rng.randint(
                        0, 61, size=rng.randint(1, 4)).tolist()
                else:
                    prompt = rng.randint(
                        0, 61, size=rng.randint(2, 10)).tolist()
                sched.submit(Request(rid, prompt,
                                     int(rng.randint(1, 6))))
                rid += 1
            sched.run_until_drained()
            assert sched.allocator.refcount(GARBAGE_PAGE) == 0
        assert (sched.allocator.free_pages
                + sched.prefix.indexed_pages) == 23, "pages leaked"
        sched.prefix.release(10 ** 6)
        assert sched.allocator.free_pages == 23


# -------------------------------------------------------- chunked prefill
class TestChunkedPrefill:
    @pytest.fixture(scope="class")
    def model(self):
        cfg = tiny_cfg()
        return cfg, init_params(cfg, jax.random.PRNGKey(0))

    def test_long_prompt_beyond_padded_limit_matches_oneshot(self, model):
        """A prompt LONGER than max_prompt_len admits via chunks and
        reproduces the one-shot-prefill engine's greedy stream."""
        cfg, params = model
        rng = np.random.RandomState(13)
        prompt = rng.randint(0, 61, size=23).tolist()
        chunked = _sched(params, cfg, prefill_chunk=4, max_prompt=8)
        chunked.submit(Request(0, list(prompt), 6))
        got = chunked.run_until_drained()[0]
        assert chunked.stats["chunk_steps"] == 6  # ceil(23 / 4)

        oneshot = _sched(params, cfg, max_prompt=32)
        oneshot.submit(Request(0, list(prompt), 6))
        want = oneshot.run_until_drained()[0]
        assert got.tokens == want.tokens

        with pytest.raises(ValueError, match="max_prompt_len"):
            oneshot.submit(Request(1, rng.randint(0, 61, size=40).tolist(),
                                   2))

    def test_chunks_interleave_with_decode(self, model):
        """Resident streams keep emitting WHILE a long prompt
        chunk-prefills — the TTFT-spike fix."""
        cfg, params = model
        rng = np.random.RandomState(14)
        sched = _sched(params, cfg, prefill_chunk=4, max_prompt=8,
                       max_batch=2)
        sched.submit(Request(0, rng.randint(0, 61, size=5).tolist(), 30))
        sched.step()  # rid 0 resident and decoding
        sched.submit(Request(1, rng.randint(0, 61, size=20).tolist(), 3))
        sched.step()  # rid 1 admitted; its first chunk lands
        resident = sched._slots[0]
        emitted_during_chunking = []
        while any(s is not None and s.chunk_next is not None
                  for s in sched._slots):
            sched.step()
            emitted_during_chunking.append(len(resident.generated))
        assert len(emitted_during_chunking) >= 2
        assert emitted_during_chunking[-1] > emitted_during_chunking[0], (
            "the resident stream stalled while the long prompt "
            "chunk-prefilled")
        assert len(sched.run_until_drained()) == 2

    def test_shared_prefix_skips_chunk_compute(self, model):
        """Chunked prefill over a fully-cached prompt collapses to ONE
        recompute chunk (the last position), and the stream matches."""
        cfg, params = model
        rng = np.random.RandomState(15)
        prompt = rng.randint(0, 61, size=12).tolist()  # 3 full pages
        sched = _sched(params, cfg, prefill_chunk=4, max_prompt=8,
                       prefix_sharing=True)
        sched.submit(Request(0, list(prompt), 4))
        sched.run_until_drained()
        chunks_before = sched.stats["chunk_steps"]
        sched.submit(Request(1, list(prompt), 4))
        done = sched.run_until_drained()
        assert sched.stats["chunk_steps"] == chunks_before + 1, (
            "a fully-shared prompt must cost one recompute chunk, not "
            "a full prefill")
        assert done[0].tokens == done[1].tokens  # greedy, same prompt

    def test_chunk_step_compiles_once(self, model):
        cfg, params = model
        sched = _sched(params, cfg, prefill_chunk=4, max_prompt=8)
        rng = np.random.RandomState(16)
        for i, plen in enumerate((3, 9, 23, 17)):
            sched.submit(Request(i, rng.randint(0, 61, size=plen).tolist(),
                                 3))
        sched.run_until_drained()
        lw.assert_no_recompile(sched._chunk, label="prefill_chunk")
        lw.assert_no_recompile(sched._sample_head, label="sample_head")


# ---------------------------------------------------------------- lanes
class TestLanes:
    @pytest.fixture(scope="class")
    def model(self):
        cfg = tiny_cfg()
        return cfg, init_params(cfg, jax.random.PRNGKey(0))

    def test_preemption_frees_pages_for_interactive(self, model):
        """The SLO contract: a full pool of best-effort work yields to
        the interactive head via the evict→recycle path; survivors'
        streams stay bitwise correct; preempted work completes via
        continuation."""
        cfg, params = model
        rng = np.random.RandomState(17)
        kw = dict(num_pages=9, page_size=4, pages_per_seq=8, max_batch=2)
        prompts = [rng.randint(0, 61, size=6).tolist() for _ in range(3)]

        sched = _sched(params, cfg, **kw)
        sched.submit(Request(0, list(prompts[0]), 8, lane="best_effort"))
        sched.submit(Request(1, list(prompts[1]), 8, lane="best_effort"))
        sched.step()
        assert sched.num_active == 2 and sched.allocator.free_pages == 0
        sched.submit(Request(2, list(prompts[2]), 8, lane="interactive"))
        done = {c.rid: c for c in sched.run_until_drained()}
        assert sched.stats["preemptions"] >= 1
        assert set(done) == {0, 1, 2}
        assert all(len(c.tokens) == 8 for c in done.values()), (
            "a preempted generation lost tokens — continuation broke")
        preempted = [c for c in done.values() if c.preemptions]
        assert preempted and all(c.lane == "best_effort"
                                 for c in preempted)

        # bitwise correctness for every stream, preempted included:
        # greedy serving must equal the training forward's argmax walk
        for c in done.values():
            seq = list(c.prompt)
            for tok in c.tokens:
                logits = gpt_forward(params, jnp.asarray([seq]), cfg)
                assert int(jnp.argmax(logits[len(seq) - 1, 0])) == tok, (
                    f"rid={c.rid}: corrupted after preemption chaos")
                seq.append(tok)

    def test_best_effort_waits_for_interactive_queue(self, model):
        """Lane priority: while an interactive request waits, no
        best-effort request is admitted."""
        cfg, params = model
        rng = np.random.RandomState(18)
        sched = _sched(params, cfg, max_batch=1)
        sched.submit(Request(0, rng.randint(0, 61, size=4).tolist(), 3))
        sched.step()  # rid 0 occupies the only slot
        sched.submit(Request(1, rng.randint(0, 61, size=4).tolist(), 2,
                             lane="best_effort"))
        sched.submit(Request(2, rng.randint(0, 61, size=4).tolist(), 2,
                             lane="interactive"))
        order = []
        orig = sched._admit_into

        def record(slot, req, *plan):
            order.append(req.rid)
            return orig(slot, req, *plan)

        sched._admit_into = record
        sched.run_until_drained()
        assert order == [2, 1], (
            f"admission order {order}: best-effort overtook a waiting "
            f"interactive request")

    def test_histograms_split_by_lane(self, model):
        cfg, params = model
        rng = np.random.RandomState(19)
        with MetricsScope() as reg:
            sched = _sched(params, cfg)
            sched.submit(Request(0, rng.randint(0, 61, size=4).tolist(),
                                 3))
            sched.submit(Request(1, rng.randint(0, 61, size=4).tolist(),
                                 3, lane="best_effort"))
            sched.run_until_drained()
            lanes = {l.get("lane") for m in reg.metrics()
                     if m.name == "apex_serve_ttft_seconds"
                     for _, l, _ in m.samples()}
            assert {"interactive", "best_effort"} <= lanes, (
                f"TTFT histogram lanes {lanes}: the per-lane SLO "
                f"evidence is missing")

    def test_unknown_lane_refused(self, model):
        cfg, params = model
        sched = _sched(params, cfg)
        with pytest.raises(ValueError, match="lane"):
            sched.submit(Request(0, [1, 2], 2, lane="bulk"))


# ------------------------------------------------- seeds & recompile pins
class TestSeedDeterminism:
    @pytest.fixture(scope="class")
    def model(self):
        cfg = tiny_cfg()
        return cfg, init_params(cfg, jax.random.PRNGKey(0))

    def test_two_generations_one_slot_never_replay_a_seed(self, model):
        """The satellite regression: submit -> drain -> submit again
        lands in the SAME slot; its draw counter must advance
        monotonically across generations — a reset would replay
        generation 1's seeds (and, under temperature, its tokens)."""
        cfg, params = model
        sched = _sched(params, cfg, max_batch=1, temperature=0.9, top_k=5,
                       seed=11)
        used = []
        orig = sched._seed_at

        def spy(slot, draw):
            used.append((slot, draw))
            return orig(slot, draw)

        sched._seed_at = spy
        sched.submit(Request(0, [3, 4, 5], 4))
        g1 = sched.run_until_drained()[-1].tokens
        draws_after_g1 = int(sched._draws[0])
        sched.submit(Request(1, [3, 4, 5], 4))
        g2 = sched.run_until_drained()[-1].tokens
        assert len(g1) == len(g2) == 4
        assert int(sched._draws[0]) == draws_after_g1 + 4, (
            "slot draw counter reset between generations")
        assert len(used) == len(set(used)), (
            f"(slot, draw) seed replayed across generations: {used}")

    def test_preemption_readmission_stays_deterministic(self, model):
        """Same seeded trace with preemption in it, twice — bitwise the
        same served tokens (draw counters never reset on the preempt →
        re-admit path either)."""
        cfg, params = model

        def run():
            sched = _sched(params, cfg, num_pages=9, page_size=4,
                           pages_per_seq=8, max_batch=2, temperature=0.9,
                           top_k=6, seed=13)
            rng = np.random.RandomState(20)
            sched.submit(Request(0, rng.randint(0, 61, size=6).tolist(),
                                 8, lane="best_effort"))
            sched.submit(Request(1, rng.randint(0, 61, size=6).tolist(),
                                 8, lane="best_effort"))
            sched.step()
            sched.submit(Request(2, rng.randint(0, 61, size=6).tolist(),
                                 8))
            done = sched.run_until_drained()
            assert sched.stats["preemptions"] >= 1
            return _tokens_by_rid(done)

        assert run() == run()


class TestAssertNoRecompile:
    def test_passes_on_stable_shapes_and_reports_results(self):
        f = jax.jit(lambda x: x * 2)
        out = lw.assert_no_recompile(
            f, [(jnp.ones((3,)),), (jnp.zeros((3,)),)])
        assert len(out) == 2 and float(out[0][0]) == 2.0

    def test_fails_naming_the_offending_call(self):
        f = jax.jit(lambda x: x + 1)
        with pytest.raises(AssertionError, match="call 1"):
            lw.assert_no_recompile(
                f, [(jnp.ones((3,)),), (jnp.ones((4,)),)])

    def test_rejects_unjitted_and_uncalled(self):
        with pytest.raises(TypeError, match="_cache_size"):
            lw.assert_no_recompile(lambda x: x)
        with pytest.raises(AssertionError, match="never called"):
            lw.assert_no_recompile(jax.jit(lambda x: x))
