"""Inference-engine tests: prefill↔decode parity, the paged KV cache,
the fused sampling head, and the continuous-batching scheduler.

The parity band is the load-bearing contract: token-by-token decode
over the paged cache must reproduce the full-sequence TRAINING forward
(same weights, causal) — in fp32 to reduction-reorder ulps (XLA CPU
picks different matmul microkernels for an (S, S) score block and a
single-query row, so literally-bitwise equality across shapes does not
exist on this backend; the single-token case, where the shapes agree,
IS pinned bitwise), with GQA and tp=2 shard_map variants.  The
scheduler band pins the admission/eviction/recycling semantics and the
chaos seam (a decode-kernel failure degrades once, the server keeps
serving the SAME tokens).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.inference import (
    ContinuousBatchingScheduler, DecodeConfig, GARBAGE_PAGE, KVCacheConfig,
    PageAllocator, Request, alloc_pools, pages_needed, write_decode_kv,
    write_prompt_kv,
)
from apex_tpu.inference.decode import (
    decode_logits_tokenwise, make_decode_step, make_prefill,
)
from apex_tpu.models.gpt import (
    GPTConfig, forward_decode, gpt_forward, init_params, param_specs,
)
from apex_tpu.ops import decode_attention_pallas as dap
from apex_tpu.ops.decode_attention_pallas import (
    decode_attention_xla, paged_decode_attention_pallas,
)
from apex_tpu.ops.decode_sampling_pallas import (
    fused_sample_pallas, fused_sample_xla, gumbel_from_seed,
)
from apex_tpu.resilience.chaos import ChaosMonkey, ChaosPlan
from apex_tpu.resilience.fallback import get_registry


def tiny_cfg(**kw):
    base = dict(
        vocab_size=61, hidden_size=32, num_layers=2,
        num_attention_heads=4, max_seq_len=64,
        position_embedding_type="rope", compute_dtype=jnp.float32,
        checkpoint_layers=False,
    )
    base.update(kw)
    return GPTConfig(**base)


def _decode_logits_tokenwise(params, cfg, tokens, prefix, kcfg, pt_row,
                             attn_impl="xla"):
    return decode_logits_tokenwise(
        params, cfg,
        DecodeConfig(cache=kcfg, max_batch=1, attn_impl=attn_impl),
        tokens, prefix, pt_row)


# ------------------------------------------------------ prefill <-> decode
class TestDecodeParity:
    @pytest.mark.parametrize("pet,gqa", [
        ("learned", None), ("rope", None), ("rope", 2)])
    def test_decode_logits_match_training_fp32(self, pet, gqa):
        cfg = tiny_cfg(position_embedding_type=pet, num_query_groups=gqa,
                       num_layers=3)
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(1)
        S, prefix = 12, 5
        tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(1, S)))
        ref = gpt_forward(params, tokens, cfg)  # (S, 1, V)
        kcfg = KVCacheConfig(num_pages=8, page_size=4, pages_per_seq=5,
                             dtype=jnp.float32)
        pt_row = jnp.asarray([1, 2, 3, 4, 5], jnp.int32)
        dec = _decode_logits_tokenwise(params, cfg, tokens, prefix, kcfg,
                                       pt_row)
        np.testing.assert_allclose(
            np.asarray(dec), np.asarray(ref[prefix:, 0]),
            rtol=0, atol=5e-6,
            err_msg="token-by-token decode logits diverged from the "
                    "training forward beyond fp32 reduction-reorder ulps")

    @pytest.mark.parametrize("page", [4, 16])
    def test_decode_logits_match_training_through_the_kernels(self, page):
        """The same parity with the in-place write and the paged read
        as KERNELS (interpreted): the pools ride the layer loop's carry
        through two aliased Pallas calls a layer, the prompt lands
        through the prompt write, and the logits still match the
        training forward."""
        cfg = tiny_cfg(num_query_groups=2, num_layers=3)
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(1)
        S, prefix = 12, 5
        tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(1, S)))
        ref = gpt_forward(params, tokens, cfg)
        P = -(-S // page) + 1
        kcfg = KVCacheConfig(num_pages=P + 2, page_size=page,
                             pages_per_seq=P, dtype=jnp.float32)
        dec = _decode_logits_tokenwise(
            params, cfg, tokens, prefix, kcfg,
            jnp.arange(1, P + 1, dtype=jnp.int32), attn_impl="interpret")
        np.testing.assert_allclose(
            np.asarray(dec), np.asarray(ref[prefix:, 0]), rtol=0, atol=5e-6)

    def test_first_token_decode_is_bitwise(self):
        """At matching contraction shapes (a length-1 sequence) the
        decode expression IS the training expression: bitwise fp32.
        This pins that every per-op formula (LN, projections, RoPE,
        softmax fill, head) is shared, so the general-case tolerance
        above covers ONLY shape-dependent reduction reordering."""
        cfg = tiny_cfg(num_layers=2)
        params = init_params(cfg, jax.random.PRNGKey(0))
        tokens = jnp.asarray([[7]])
        ref = gpt_forward(params, tokens, cfg)[0, 0]
        kcfg = KVCacheConfig(num_pages=3, page_size=1, pages_per_seq=1,
                             dtype=jnp.float32)
        pools = alloc_pools(cfg.num_layers, cfg.kv_heads, cfg.head_dim, kcfg)
        hidden, _ = forward_decode(
            params, tokens[:, 0], jnp.asarray([0], jnp.int32),
            jnp.asarray([True]), pools, jnp.asarray([[1]], jnp.int32), cfg,
            attn_impl="xla")
        dec = jnp.matmul(hidden.astype(jnp.float32),
                         params["embed"].T.astype(jnp.float32))[0]
        assert bool(jnp.all(dec == ref)), (
            "single-token decode is no longer bitwise against the "
            "training forward — a shared-expression seam drifted")

    def test_decode_matches_training_bf16(self):
        """bf16 compute + bf16 KV storage: parity within bf16
        tolerance (the cache round-trips k/v through the storage dtype
        once; activations already round at every op)."""
        cfg = tiny_cfg(compute_dtype=jnp.bfloat16)
        params = init_params(cfg, jax.random.PRNGKey(2))
        rng = np.random.RandomState(3)
        S, prefix = 8, 3
        tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(1, S)))
        ref = gpt_forward(params, tokens, cfg)
        kcfg = KVCacheConfig(num_pages=6, page_size=4, pages_per_seq=2,
                             dtype=jnp.bfloat16)
        dec = _decode_logits_tokenwise(
            params, cfg, tokens, prefix, kcfg,
            jnp.asarray([1, 2], jnp.int32))
        np.testing.assert_allclose(
            np.asarray(dec), np.asarray(ref[prefix:, 0]),
            rtol=0.05, atol=0.1)

    def test_tp2_decode_matches_dense_training(self, devices8):
        """forward_decode inside a tp=2 shard_map (column/row-parallel
        projections, kv heads sharded over tp, vocab-parallel head)
        matches the DENSE training forward."""
        cfg = tiny_cfg(vocab_size=64)
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(1)
        S = 8
        tokens = jnp.asarray(rng.randint(0, 64, size=(1, S)))
        nxt = jnp.asarray([[5]], jnp.int32)
        full = jnp.concatenate([tokens, nxt], axis=1)
        ref = gpt_forward(params, full, cfg)[S, 0]

        kcfg = KVCacheConfig(num_pages=6, page_size=4, pages_per_seq=3,
                             dtype=jnp.float32)
        mesh = Mesh(np.array(devices8[:2]).reshape(2, 1), ("tp", "dp"))
        pool_spec = P(None, None, "tp", None, None)
        pools = alloc_pools(cfg.num_layers, cfg.kv_heads, cfg.head_dim, kcfg)
        pt_row = jnp.asarray([[1, 2, 3]], jnp.int32)

        def local(params, kpool, vpool, toks, pos, active, pt):
            _, kv = gpt_forward(params, toks[:, :S], cfg, axis_name="tp",
                                return_hidden=True, return_kv=True)
            ks = kv[0][:, 0].transpose(0, 2, 1, 3)
            vs = kv[1][:, 0].transpose(0, 2, 1, 3)
            kpool, vpool = write_prompt_kv(kpool, vpool, ks, vs, pt[0],
                                           jnp.int32(S))
            h, _ = forward_decode(params, toks[:, S], pos, active,
                                  {"k": kpool, "v": vpool}, pt, cfg,
                                  axis_name="tp", attn_impl="xla")
            return jnp.matmul(h.astype(jnp.float32),
                              params["embed"].T.astype(jnp.float32))

        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(param_specs(cfg), pool_spec, pool_spec,
                      P(), P(), P(), P()),
            out_specs=P(None, "tp"), check_vma=False)
        got = fn(params, pools["k"], pools["v"], full,
                 jnp.asarray([S], jnp.int32), jnp.asarray([True]), pt_row)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref),
                                   rtol=0, atol=5e-6)


# -------------------------------------------------- decode attention kernel
class TestDecodeAttentionKernel:
    def _case(self, rng, B=3, H=4, KVH=2, D=16, num_pages=9, page=8, P=4):
        q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
        kp = jnp.asarray(rng.randn(num_pages, KVH, D, page), jnp.float32)
        vp = jnp.asarray(rng.randn(num_pages, KVH, D, page), jnp.float32)
        pt = jnp.asarray(rng.randint(1, num_pages, size=(B, P)), jnp.int32)
        return q, kp, vp, pt

    def test_kernel_matches_reference_gqa_partial_inactive(self):
        rng = np.random.RandomState(0)
        q, kp, vp, pt = self._case(rng)
        lengths = jnp.asarray([0, 5, 25], jnp.int32)  # inactive/tail/full
        ref = decode_attention_xla(q, kp, vp, pt, lengths)
        out = paged_decode_attention_pallas(q, kp, vp, pt, lengths,
                                            interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0, atol=1e-5)
        assert float(jnp.max(jnp.abs(out[0]))) == 0.0, (
            "inactive (length 0) row must attend to nothing")

    def test_bf16_storage_widens_at_read(self):
        rng = np.random.RandomState(1)
        q, kp, vp, pt = self._case(rng)
        lengths = jnp.asarray([8, 16, 32], jnp.int32)
        ref = decode_attention_xla(q, kp.astype(jnp.bfloat16),
                                   vp.astype(jnp.bfloat16), pt, lengths)
        out = paged_decode_attention_pallas(
            q, kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16), pt,
            lengths, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=0.05, atol=0.05)

    def test_out_of_range_page_ids_clamp_not_wrap(self):
        """A corrupt page table (negative / past-pool ids) must behave
        exactly like its clamped self — in BOTH implementations (the
        APX107 contract at runtime)."""
        rng = np.random.RandomState(2)
        q, kp, vp, _ = self._case(rng, B=2, P=3)
        pt_bad = jnp.asarray([[-3, 2, 99], [1, -1, 1000]], jnp.int32)
        pt_ok = jnp.clip(pt_bad, 0, kp.shape[0] - 1)
        lengths = jnp.asarray([20, 24], jnp.int32)
        for impl in (decode_attention_xla,
                     lambda *a: paged_decode_attention_pallas(
                         *a, interpret=True)):
            a = impl(q, kp, vp, pt_bad, lengths)
            b = impl(q, kp, vp, pt_ok, lengths)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _blocked_case(rng, H, KVH, lengths, page, P, D=16, width=1,
                  pool_dtype=jnp.float32, q_dtype=jnp.float32, layers=2):
    """A stacked pool whose sequences own distinct pages (the
    allocator's contract), page 0 the garbage page; ``lengths`` has one
    entry a q row (``width`` rows a sequence)."""
    n_seq = len(lengths) // width
    num_pages = n_seq * P + 1
    shape = (layers, num_pages, KVH, D, page)
    kp = jnp.asarray(rng.randn(*shape), pool_dtype)
    vp = jnp.asarray(rng.randn(*shape), pool_dtype)
    pt = 1 + rng.permutation(n_seq * P).reshape(n_seq, P).astype(np.int32)
    q = jnp.asarray(rng.randn(len(lengths), H, D), q_dtype)
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(lengths, jnp.int32)


#: name -> (heads, kv heads, lengths a row as multiples of (page, 1),
#: heads that fit VMEM or None, kwargs).  Every case runs at a page
#: under 128 (the grid walks the page slots) and at 128 (the kernel
#: walks the live pages itself); 4 pages a sequence.
_LENGTHS = ((0, 0), (0, 1), (1, 0), (1, 1), (4, 0))     # 0 1 pg pg+1 4pg
_MIXED = ((0, 0), (1, -1), (1, 0), (4, -2))
# width 3: rows pg-1, pg, pg+1 of a sequence straddle pages 0|1; an
# inactive sequence; a sequence that ends on the table's last position
_WIDTH3 = ((1, -1), (1, 0), (1, 1), (0, 0), (0, 0), (0, 0),
           (4, -2), (4, -1), (4, 0))
BLOCKED_CASES = {
    # one query row a head (GPT-2), every length that matters
    "mha_lengths": (4, 4, _LENGTHS, None, {}),
    "mha_all_inactive": (4, 4, ((0, 0),) * 3, None, {}),
    "mha_live_rows_apart": (4, 4, ((0, 5), (0, 0), (0, 0), (2, 9), (0, 0),
                                   (4, 0), (0, 1), (0, 0)), None, {}),
    # a budget that holds 4 heads of 6: blocks of 3; of 5 (a prime): 1
    "mha6_two_head_blocks": (6, 6, _MIXED, 4, {}),
    "mha5_one_head_a_block": (5, 5, ((0, 3), (0, 0), (2, 1), (4, 0)), 2,
                              {}),
    "mha_bf16_cache_f32_query": (4, 4, _MIXED, None,
                                 {"pool_dtype": jnp.bfloat16}),
    "mha_bf16_cache_bf16_query": (4, 4, _MIXED, None,
                                  {"pool_dtype": jnp.bfloat16,
                                   "q_dtype": jnp.bfloat16}),
    "mha_width3_straddle": (4, 4, _WIDTH3, None, {"width": 3}),
    "mha6_width3_head_blocks": (6, 6, ((1, 0), (1, 1), (1, 2), (0, 1),
                                       (0, 2), (0, 3)), 4, {"width": 3}),
    # grouped queries, one kv head (MQA)
    "gqa16_4_lengths": (16, 4, _LENGTHS, None, {}),
    "gqa16_4_all_inactive": (16, 4, ((0, 0),) * 2, None, {}),
    "gqa16_4_two_head_blocks": (16, 4, _MIXED, 2, {}),
    "gqa16_4_bf16_cache_f32_query": (16, 4, _MIXED, None,
                                     {"pool_dtype": jnp.bfloat16}),
    "gqa16_4_width3_straddle": (16, 4, _WIDTH3, None, {"width": 3}),
    "mqa_lengths": (12, 1, _LENGTHS, None, {}),
    "mqa_bf16_cache_f32_query": (12, 1, _MIXED, None,
                                 {"pool_dtype": jnp.bfloat16}),
    "mqa_width3_straddle": (12, 1, _WIDTH3[:6], None, {"width": 3}),
    # the walk's ring of page slots (PR 50; "depth": what the plan gives
    # the case at page 128): lists shorter than the ring, of its depth
    # and longer; rows without a live position before, between and after
    # the live ones; a last row of one page
    "ring_lists_shorter_than_depth": (4, 4, ((0, 7), (1, 1), (1, 0), (2, 0),
                                             (0, 1), (1, 5)), None,
                                      {"depth": 3}),
    "ring_lists_of_depth": (4, 4, ((3, 0), (2, 1), (3, -3)), None,
                            {"depth": 3}),
    "ring_lists_longer_than_depth": (4, 4, ((5, 0), (9, 0), (6, 5), (8, 1)),
                                     None, {"P": 9, "depth": 3}),
    "ring_dead_rows_around": (4, 4, ((0, 0), (0, 0), (2, 3), (0, 0), (0, 0),
                                     (0, 0), (6, 1), (1, 0), (0, 0), (3, 1),
                                     (0, 0), (0, 0)), None,
                              {"P": 7, "depth": 3}),
    "ring_last_row_of_one_page": (16, 4, ((5, 0), (0, 0), (3, 3), (0, 9)),
                                  None, {"P": 5, "depth": 3}),
    "ring_one_live_row": (4, 4, ((0, 0), (0, 0), (2, 1), (0, 0)), None,
                          {"depth": 3}),
    # head blocks: the cursor goes through a row's blocks before the next
    # live row; a budget of 4 heads leaves the third slot beside blocks
    # of 3, one of 3 heads (or of 2 of 4) no slot beyond the two
    "ring_depth3_head_blocks": (6, 6, ((0, 0), (7, 0), (0, 3), (0, 0),
                                       (5, 1), (2, 0)), 4,
                                {"P": 7, "depth": 3}),
    "ring_depth2_head_blocks": (6, 6, ((3, 1), (0, 0), (7, 0), (0, 1)), 3,
                                {"P": 7, "depth": 2}),
    "ring_gqa_head_blocks_dead_rows": (16, 4, ((0, 0), (5, 1), (0, 0),
                                               (1, 0), (6, 0), (0, 0)), 2,
                                       {"P": 6, "depth": 2}),
    "ring_mha5_one_head_a_block": (5, 5, ((2, 1), (0, 0), (6, 0), (0, 1)), 2,
                                   {"P": 6, "depth": 3}),
    # two lengths a row (the block step): the walk runs to the longer
    # half; a dead half beside a live one, both dead, the first longer
    "ring_two_lengths_dead_half": (16, 4, (((0, 0), (2, 1)),
                                           ((3, 0), (0, 0)),
                                           ((0, 0), (0, 0)),
                                           ((1, 1), (6, 0)),
                                           ((5, 2), (1, 0)),
                                           ((0, 0), (0, 1))), None,
                                   {"P": 6, "depth": 3}),
    "ring_two_lengths_head_blocks": (16, 4, (((0, 0), (0, 0)),
                                             ((4, 0), (5, 3)),
                                             ((0, 0), (1, 0)),
                                             ((2, 2), (0, 0))), 2,
                                     {"P": 6, "depth": 2}),
    "ring_width3_long_lists": (4, 4, ((5, -1), (5, 0), (5, 1), (0, 0),
                                      (0, 0), (0, 0), (0, 1), (0, 2),
                                      (0, 3), (7, -2), (7, -1), (7, 0)),
                               None, {"width": 3, "P": 7, "depth": 3}),
    "ring_width2_head_blocks": (6, 6, ((2, 0), (2, 1), (0, 0), (0, 0),
                                       (6, -1), (6, 0)), 4,
                                {"width": 2, "P": 6, "depth": 3}),
}


#: name: ((slots, q heads, kv heads, head dim, page, pages a sequence,
#: pool pages, verify width), sha256 of the traced jaxpr's text, the
#: Pallas kernel's inside it).  PR 45 taught the kernels to take two
#: lengths a row (a shape of ``lengths``, (B, 2); these callers pass
#: (B,)) and left these programs to the letter as they were; PR 50
#: changed the walk on purpose (a ring of page slots, for every caller)
#: and took the pins again from its final tree: the page-128 programs'
#: moved, the small page's (the grid's form) did not.  The
#: shapes are the cells': GPT-2 large (20 slots, 20 heads of 64),
#: Falcon-H1 (96 slots, 20 query heads over 4 of 128), EvaByte (20 slots,
#: 32 heads of 128), page 128, bf16; the verify layout; a page under 128
#: lanes (the grid of page slots).  A PR that means to change what these
#: callers run replaces the pins; one that does not may not move them.
_ONE_LENGTH_JAXPRS = {
    "gpt2-large": ((20, 20, 20, 64, 128, 8, 161, 1), "ed764d7f99e1d7c9"),
    "falcon-h1": ((96, 20, 4, 128, 128, 20, 1024, 1), "9a6fe7bcc88f88b7"),
    "evabyte": ((20, 32, 32, 128, 128, 4, 501, 1), "5e46b7ca0065c12d"),
    "verify4": ((8, 20, 20, 64, 128, 8, 161, 4), "57c37bd537b56e65"),
    "small-page": ((4, 8, 2, 16, 16, 6, 40, 1), "1ef830047ca28e45"),
}


@pytest.mark.parametrize("name", sorted(_ONE_LENGTH_JAXPRS))
def test_one_length_callers_trace_the_kernel_they_traced(name):
    """``decode_attention`` with one length a row, what ``gpt.py``,
    ``falcon_h1.py``, ``evabyte.py`` and the verify layout pass: the
    traced program, the Pallas kernel's jaxpr inside it, is to the
    letter the pinned one, ONE kernel for all of them.  The block
    step's second length costs the other families' programs nothing, not
    an operation and not a second of tracing."""
    import hashlib

    (B, H, kvh, D, page, P, pages, width), want = _ONE_LENGTH_JAXPRS[name]
    S = jax.ShapeDtypeStruct
    pool = S((8, pages, kvh, D, page), jnp.bfloat16)
    text = str(jax.make_jaxpr(
        lambda q, k, v, pt, n, layer: dap.decode_attention(
            q, k, v, pt, n, impl="pallas", width=width, layer=layer))(
        S((B * width, H, D), jnp.bfloat16), pool, pool,
        S((B, P), jnp.int32), S((B * width,), jnp.int32), S((), jnp.int32)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want, (
        f"{name}: the one-length program changed")


class TestDecodeAttentionBlocking:
    """The kernel's unit of work is a live page of a sequence row, all
    of a block's kv heads (PERF.md, PR 27), in both of its forms:
    parity with the reference over head blocks, lengths, widths and
    dtypes, and the promise that a whole page past a length is never
    read."""

    @pytest.mark.parametrize("page", [8, 128])
    @pytest.mark.parametrize("name", sorted(BLOCKED_CASES))
    def test_blocked_kernel_matches_reference(self, name, page,
                                              monkeypatch):
        H, KVH, lengths, fit, kw = BLOCKED_CASES[name]
        kw = dict(kw)
        depth = kw.pop("depth", None)
        # a length a row, or a pair of them a row ((B, 2))
        lengths = np.asarray(lengths)
        lengths = lengths[..., 0] * page + lengths[..., 1]
        width = kw.get("width", 1)
        q, kp, vp, pt, ln = _blocked_case(
            np.random.RandomState(len(name)), H, KVH, lengths,
            **{"page": page, "P": 4, **kw})
        h_kv_blocks = 1
        if fit is not None:
            # the kernel has no argument for its head block, on purpose
            monkeypatch.setattr(dap, "_VMEM_BUDGET", fit * dap._head_bytes(
                H // KVH, q.shape[-1], page, kp.dtype))
            h_kv_blocks = KVH // max(d for d in range(1, fit + 1)
                                     if KVH % d == 0)
            assert h_kv_blocks > 1
        _, grid, slots = dap._plan(len(lengths), KVH, H // KVH, q.shape[-1],
                                   pt.shape[1], page, kp.dtype)
        assert grid == (len(lengths), h_kv_blocks) + (
            () if page == 128 else (pt.shape[1],))
        assert slots == ((depth or slots) if page == 128 else 2)
        ref = decode_attention_xla(q, kp, vp, pt, ln, width=width, layer=1)
        out = paged_decode_attention_pallas(q, kp, vp, pt, ln, width=width,
                                            interpret=True, layer=1)
        assert out.shape == ref.shape and out.dtype == ref.dtype
        tol = 1e-5 if kp.dtype == jnp.float32 else 0.05
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=0, atol=tol)
        dead = lengths.reshape(len(lengths), -1).max(axis=1) == 0
        assert float(np.abs(np.asarray(out, np.float32)[dead]).sum()) == 0.0

    @pytest.mark.parametrize("page", [8, 128])
    @pytest.mark.parametrize("H,KVH,width", [
        (4, 4, 1), (6, 6, 3), (16, 4, 1), (16, 4, 3), (12, 1, 1)],
        ids=["mha", "mha6_width3", "gqa16_4", "gqa16_4_width3", "mqa"])
    def test_dead_pages_are_never_read(self, H, KVH, width, page):
        """Every pool page that holds no live position of any sequence
        — the garbage page, the table's slots past each length, every
        page of an inactive sequence, the other layers — is poisoned;
        the output must not change by a bit.  (Whole pages only: the
        tail of a live page is masked per position.)"""
        P = 4
        first = [0, 1, page - 1, page, 2 * page + 3, P * page - width]
        lengths = [(f + w + 1 if f else 0)
                   for f in first for w in range(width)]
        q, kp, vp, pt, ln = _blocked_case(
            np.random.RandomState(H + KVH + width), H, KVH, lengths,
            page=page, P=P, width=width, pool_dtype=jnp.bfloat16, layers=3)
        live = np.zeros(kp.shape[1], bool)
        for row, n in zip(np.asarray(pt), np.asarray(ln).reshape(-1, width)
                          .max(axis=1)):
            live[row[:-(-n // page)]] = True
        assert not live[GARBAGE_PAGE] and live.sum() < len(live) - 1
        poison = np.where(np.arange(kp.size).reshape(kp.shape) % 2,
                          np.nan, np.inf)
        mask = np.ones(kp.shape, bool)
        mask[1, live] = False                # layer 1's live pages stay

        def run(k, v):
            return np.asarray(paged_decode_attention_pallas(
                q, k, v, pt, ln, width=width, interpret=True, layer=1),
                np.float32)

        clean = run(kp, vp)
        dirty = run(jnp.where(mask, poison, kp).astype(kp.dtype),
                    jnp.where(mask, poison, vp).astype(vp.dtype))
        assert np.isfinite(clean).all()
        np.testing.assert_array_equal(dirty, clean)

    @pytest.mark.parametrize("H,KVH,width,fit,halves", [
        (4, 4, 1, None, 1), (6, 6, 1, 4, 1), (16, 4, 3, None, 1),
        (16, 4, 1, 2, 2)],
        ids=["mha", "mha6_head_blocks", "gqa16_4_width3",
             "gqa16_4_two_lengths_head_blocks"])
    def test_every_copy_names_a_live_page(self, H, KVH, width, fit, halves,
                                          monkeypatch):
        """What the walk COPIES (a page of 128), from the copies
        themselves: the source of every copy that is started is a live
        page of its row, each once a walk of the row and head block, and
        as many are waited for as were started.  The table's
        entries past a row's last live page hold an id outside the pool
        (the launcher's clamp makes it the pool's last page, a sentinel
        no sequence owns) and a dead row's are negative (the garbage
        page after the clamp): neither is ever a copy's source, though
        the cursor runs ahead of the attended page across rows."""
        page, P = 128, 6
        first = [0, 0, 2 * page + 3, 1, 0, P * page - width, page, 0,
                 4 * page - 1, 0]
        lengths = np.array([(f + w + 1 if f else 0)
                            for f in first for w in range(width)])
        if halves == 2:     # the other half shorter, or the only live one
            lengths = np.stack([lengths // 2, lengths], axis=1)
            lengths[2], lengths[3] = lengths[2, ::-1], (0, 5)
        q, kp, vp, pt, ln = _blocked_case(
            np.random.RandomState(H + width), H, KVH, lengths, page=page,
            P=P, width=width, pool_dtype=jnp.bfloat16, layers=2)
        sentinel = kp.shape[1]               # one page more: no one's
        pad = [(0, 0), (0, 1)] + [(0, 0)] * 3
        kp, vp = jnp.pad(kp, pad), jnp.pad(vp, pad)
        n_blk = 1
        if fit is not None:
            monkeypatch.setattr(dap, "_VMEM_BUDGET", fit * dap._head_bytes(
                H // KVH, q.shape[-1], page, kp.dtype))
            n_blk = dap._plan(len(lengths), KVH, H // KVH, q.shape[-1], P,
                              page, kp.dtype)[1][1]
            assert n_blk > 1
        # the pages a q row walks (to the longer of two halves), and a
        # sequence's live pages: those of the longest of its rows
        walked = -(-lengths.reshape(len(lengths), -1).max(axis=1) // page)
        pt = np.array(pt)
        for row, n in zip(pt, walked.reshape(-1, width).max(axis=1)):
            row[n:] = sentinel + 7 if n else -3
        want = [int(x) for b, n in enumerate(walked)
                for x in list(pt[b // width, :n]) * n_blk]
        started, waits = [], []
        page_copies = dap._page_copies

        def recording(pools, bufs, sem, where, slot):
            if isinstance(where[1], jax.core.Tracer):
                jax.debug.callback(lambda x: started.append(int(x)),
                                   where[1])
            else:
                jax.debug.callback(lambda x: waits.append(int(x)), slot)
            return page_copies(pools, bufs, sem, where, slot)

        monkeypatch.setattr(dap, "_page_copies", recording)
        poison = np.zeros(kp.shape, bool)
        poison[:, [GARBAGE_PAGE, sentinel]] = True
        out = {}
        for name, fill in (("clean", 0.0), ("dirty", np.nan)):
            del started[:], waits[:]
            out[name] = np.asarray(paged_decode_attention_pallas(
                q, jnp.where(poison, fill, kp).astype(kp.dtype),
                jnp.where(poison, fill, vp).astype(vp.dtype),
                jnp.asarray(pt), ln, width=width, interpret=True, layer=1),
                np.float32)
            jax.effects_barrier()
            assert sorted(started) == sorted(want)
            assert len(waits) == len(want)
        assert np.isfinite(out["clean"]).all()
        np.testing.assert_array_equal(out["dirty"], out["clean"])

    @pytest.mark.parametrize("width", [1, 3])
    def test_block_index_names_live_pages_only(self, width):
        """What the pipeline FETCHES where the grid walks the page
        slots (a page under 128), from the index map itself: a live row
        names its live pages, each once and in order (a repeated index
        is not fetched again), and a row without a live position names
        one block."""
        page, P = 16, 8
        lengths = np.array([0, 1, 16, 17, 80, 128, 0, 40] * width)
        lengths = np.sort(lengths.reshape(width, -1), axis=0).T.reshape(-1)
        n_seq = len(lengths) // width
        pt = (1 + np.arange(n_seq * P)).astype(np.int32)
        for b, n in enumerate(lengths):
            named = [tuple(int(x) for x in dap._kv_block_index(
                b, 2, p, pt, lengths, np.array([5]), width=width,
                pages_per_seq=P, page_size=page)) for p in range(P)]
            assert all(i[0] == 5 and i[2:] == (2, 0, 0) for i in named)
            pages = [i[1] for i in named]
            row = pt[(b // width) * P:][:P]
            live = -(-int(n) // page)
            assert pages[:live] == list(row[:live])
            assert set(pages[live:]) <= {pages[max(live - 1, 0)]}
            assert len(set(pages)) == max(live, 1)


# --------------------------------------------------------- fused sampling
class TestFusedSampling:
    def _case(self, rng, N=5, H=32, V=307):
        x2 = jnp.asarray(rng.randn(N, H), jnp.float32)
        emb = jnp.asarray(rng.randn(V, H), jnp.float32)
        seeds = jnp.asarray(rng.randint(0, 2 ** 31, size=(N,)), jnp.uint32)
        return x2, emb, seeds

    @pytest.mark.parametrize("temperature,top_k", [
        (0.0, 0), (1.0, 0), (0.7, 13), (1.3, 1), (0.9, 400)])
    def test_kernel_matches_reference_bitwise(self, temperature, top_k):
        """Same counter-based Gumbel stream, same threshold semantics:
        the kernel and the reference draw the IDENTICAL token (fp32
        dots pin the logits bitwise on CPU)."""
        rng = np.random.RandomState(0)
        x2, emb, seeds = self._case(rng)
        a = fused_sample_xla(x2, emb, seeds, temperature, top_k)
        b = fused_sample_pallas(x2, emb, seeds, temperature, top_k,
                                dot_dtype=jnp.float32, interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_greedy_is_argmax(self):
        rng = np.random.RandomState(1)
        x2, emb, seeds = self._case(rng)
        logits = x2 @ emb.T
        np.testing.assert_array_equal(
            np.asarray(fused_sample_xla(x2, emb, seeds, 0.0, 0)),
            np.asarray(jnp.argmax(logits, axis=-1)))

    def test_top_k_restricts_support(self):
        """Over many seeds, every draw lands inside the top-k set."""
        rng = np.random.RandomState(2)
        x2, emb, _ = self._case(rng, N=1)
        k = 7
        logits = x2 @ emb.T
        topset = set(np.asarray(jax.lax.top_k(logits, k)[1][0]).tolist())
        xs = jnp.broadcast_to(x2, (256, x2.shape[1]))
        seeds = jnp.arange(256, dtype=jnp.uint32)
        toks = np.asarray(fused_sample_xla(xs, emb, seeds, 0.8, k))
        assert set(toks.tolist()) <= topset
        assert len(set(toks.tolist())) > 1, "top-k sampling degenerated " \
            "to greedy (no variety across seeds)"

    @pytest.mark.slow
    def test_temperature_sampling_tracks_softmax(self):
        """Empirical distribution over 4000 seeds vs the true softmax:
        total-variation distance at the sampling-noise scale."""
        rng = np.random.RandomState(3)
        x2, emb, _ = self._case(rng, N=1, V=101)
        n = 4000
        xs = jnp.broadcast_to(x2, (n, x2.shape[1]))
        toks = np.asarray(fused_sample_xla(
            xs, emb, jnp.arange(n, dtype=jnp.uint32), 1.0, 0))
        p_emp = np.bincount(toks, minlength=101) / n
        p_true = np.asarray(jax.nn.softmax(x2[0] @ emb.T))
        assert 0.5 * np.abs(p_emp - p_true).sum() < 0.05

    def test_gumbel_stream_is_open_interval(self):
        g = gumbel_from_seed(jnp.arange(4096, dtype=jnp.uint32)[:, None],
                             jnp.arange(64, dtype=jnp.int32)[None, :])
        assert bool(jnp.all(jnp.isfinite(g)))


# -------------------------------------------------------------- KV cache
class TestKVCache:
    def test_allocator_reserves_garbage_page(self):
        a = PageAllocator(num_pages=5)
        got = a.allocate(4)
        assert got == [1, 2, 3, 4] and GARBAGE_PAGE not in got
        assert a.allocate(1) is None, "over-allocation must refuse, " \
            "never hand out the garbage page"

    def test_allocator_recycles_and_guards(self):
        a = PageAllocator(num_pages=4)
        pages = a.allocate(3)
        a.free(pages)
        assert a.free_pages == 3
        with pytest.raises(ValueError, match="double free"):
            a.free([pages[0]])  # already back in the free list
        with pytest.raises(ValueError, match="reserved"):
            a.free([GARBAGE_PAGE])
        with pytest.raises(ValueError, match="outside"):
            a.free([99])

    def test_pages_needed(self):
        assert pages_needed(1, 4) == 1
        assert pages_needed(4, 4) == 1
        assert pages_needed(5, 4) == 2

    def test_inactive_decode_write_hits_garbage_page_only(self):
        rng = np.random.RandomState(0)
        kp = jnp.asarray(rng.randn(4, 1, 8, 2), jnp.float32)
        vp = kp + 1
        k_new = jnp.ones((2, 1, 8))
        pt = jnp.asarray([[2], [3]], jnp.int32)
        pos = jnp.asarray([0, 1], jnp.int32)
        active = jnp.asarray([False, False])
        nk, nv = write_decode_kv(kp, vp, k_new, k_new, pt, pos, active)
        np.testing.assert_array_equal(np.asarray(nk[1:]), np.asarray(kp[1:]))
        np.testing.assert_array_equal(np.asarray(nv[1:]), np.asarray(vp[1:]))

    def test_prompt_pad_tail_hits_garbage_page_only(self):
        kp = jnp.zeros((2, 5, 1, 8, 4))
        ks = jnp.ones((2, 6, 1, 8))
        row = jnp.asarray([2, 3], jnp.int32)
        nk, _ = write_prompt_kv(kp, kp, ks, ks, row, jnp.int32(5))
        # positions 0..4 land in pages 2 (0..3) and 3 (slot 0); the
        # padded position 5 must NOT touch page 3 slot 1
        assert float(jnp.sum(jnp.abs(nk[:, 3, :, :, 1:]))) == 0.0
        assert float(jnp.sum(nk[:, 2])) == 4 * 8 * 2
        assert float(jnp.sum(nk[:, 3, :, :, 0])) == 8 * 2


# ------------------------------------------------- the pool stays in place
def _np_write_rows(pool, new, tables, positions, mask, layer, width):
    """The plain reference of a decode/verify write on the head-dim-major
    pool: row by row, in NumPy.  Page 0 (garbage) is not modelled."""
    out = np.array(pool, np.float32)
    n_pages, page = out.shape[1], out.shape[-1]
    P = tables.shape[1]
    for i in range(new.shape[0]):
        page_ix = int(positions[i]) // page
        if not mask[i] or not 0 <= page_ix < P:
            continue
        dest = int(np.clip(tables[i // width, page_ix], 0, n_pages - 1))
        out[layer, dest, :, :, int(positions[i]) % page] = \
            np.asarray(new[i], np.float32)
    return out


class TestPoolInPlace:
    """The in-place kernel write (``apex_kv_write``, interpreted) and
    the kernel read on the head-dim-major stacked pool, against a plain
    NumPy write and ``decode_attention_xla``."""

    @pytest.mark.parametrize("width", [1, 3])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["fp32", "bf16"])
    @pytest.mark.parametrize("D", [64, 128])
    @pytest.mark.parametrize("page", [4, 16, 128])
    def test_write_then_attend_matches_plain_reference(self, page, D,
                                                       dtype, width):
        rng = np.random.RandomState(page + D + width)
        L, layer, N, KVH, H, S, P = 2, 1, 11, 2, 4, 4, 3
        kp = jnp.asarray(rng.randn(L, N, KVH, D, page), dtype)
        vp = jnp.asarray(rng.randn(L, N, KVH, D, page), dtype)
        # distinct live pages a sequence (the allocator's contract)
        pt = np.asarray(rng.permutation(np.arange(1, N))[:S * 2]
                        .reshape(S, 2).tolist(), np.int32)
        pt = np.concatenate([pt, np.zeros((S, P - 2), np.int32)], axis=1)
        # seq 0: a fresh sequence; seq 1: its rows straddle pages 0|1
        # (width 3) or sit on a page's last slot; seq 2: INACTIVE; seq 3:
        # mid-page
        first = np.array([0, page - 1, 1, min(page + 1, 2 * page - width)])
        pos = (first[:, None] + np.arange(width)[None]).reshape(-1)
        act = np.repeat(np.array([True, True, False, True]), width)
        kn = jnp.asarray(rng.randn(S * width, KVH, D), dtype)
        vn = jnp.asarray(rng.randn(S * width, KVH, D), dtype)

        got = {impl: write_decode_kv(
            kp, vp, kn, vn, jnp.asarray(pt), jnp.asarray(pos, jnp.int32),
            jnp.asarray(act), layer=layer, width=width, impl=impl)
            for impl in ("interpret", "xla")}
        for which, (pool, new) in enumerate(((kp, kn), (vp, vn))):
            want = _np_write_rows(pool, new, pt, pos, act, layer, width)
            for impl, pools in got.items():
                # everything but the garbage page, bit for bit: the
                # written columns, every untouched page and layer, and
                # the inactive slot's pages (its write lands on page 0
                # and nowhere else)
                np.testing.assert_array_equal(
                    np.asarray(pools[which], np.float32)[:, 1:],
                    want[:, 1:], err_msg=f"{impl} write, pool {which}")

        k_new, v_new = got["interpret"]
        q = jnp.asarray(rng.randn(S * width, H, D), jnp.float32)
        lengths = jnp.asarray(np.where(act, pos + 1, 0), jnp.int32)
        ref = decode_attention_xla(q, k_new, v_new, jnp.asarray(pt),
                                   lengths, width=width, layer=layer)
        out = paged_decode_attention_pallas(
            q, k_new, v_new, jnp.asarray(pt), lengths, width=width,
            interpret=True, layer=layer)
        tol = 1e-5 if dtype == jnp.float32 else 0.05
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=0, atol=tol)
        rows = np.asarray(out, np.float32).reshape(S, width, H, D)
        assert float(np.abs(rows[2]).max()) == 0.0, (
            "the inactive sequence must attend to nothing")

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["fp32", "bf16"])
    @pytest.mark.parametrize("page", [4, 16, 128])
    def test_prompt_write_window_and_padded_tail(self, page, dtype):
        """``start > 0`` (shared-prefix window) and a padded tail:
        only positions in ``[start, prompt_len)`` reach the sequence's
        pages, in every layer; kernel and XLA agree with a NumPy
        loop outside the garbage page."""
        rng = np.random.RandomState(page)
        L, N, KVH, D, P = 3, 9, 2, 64, 4
        S = 2 * page + page // 2 + 1         # a ragged last tile
        start, plen = page // 2 + 1, S - 2
        kp = jnp.asarray(rng.randn(L, N, KVH, D, page), dtype)
        vp = kp + 1
        ks = jnp.asarray(rng.randn(L, S, KVH, D), dtype)
        vs = jnp.asarray(rng.randn(L, S, KVH, D), dtype)
        row = np.asarray([5, 2, 7, 3], np.int32)
        want = [np.array(kp, np.float32), np.array(vp, np.float32)]
        for s in range(start, plen):
            for w, x in zip(want, (ks, vs)):
                w[:, row[s // page], :, :, s % page] = \
                    np.asarray(x[:, s], np.float32)
        for impl in ("interpret", "xla"):
            got = write_prompt_kv(kp, vp, ks, vs, jnp.asarray(row),
                                  jnp.int32(plen), start=jnp.int32(start),
                                  impl=impl)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(
                    np.asarray(g, np.float32)[:, 1:], w[:, 1:],
                    err_msg=f"{impl} prompt write")

    def test_positions_outside_the_table_go_to_the_garbage_page(self):
        """A position past the page table (a caller's bug) must not
        alias the table's last page: both paths drop it on page 0."""
        kp = jnp.zeros((1, 4, 1, 8, 4))
        kn = jnp.ones((2, 1, 8))
        pt = jnp.asarray([[2], [3]], jnp.int32)      # P = 1: positions 0..3
        pos = jnp.asarray([4, -1], jnp.int32)
        for impl in ("interpret", "xla"):
            nk, _ = write_decode_kv(kp, kp, kn, kn, pt, pos,
                                    jnp.asarray([True, True]), layer=0,
                                    impl=impl)
            assert float(jnp.abs(nk[:, 1:]).sum()) == 0.0, impl

    def test_one_layer_pool_is_layer_zero_of_a_stacked_view(self):
        rng = np.random.RandomState(0)
        kp = jnp.asarray(rng.randn(5, 2, 8, 4), jnp.float32)
        kn = jnp.asarray(rng.randn(2, 2, 8), jnp.float32)
        pt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        pos = jnp.asarray([5, 2], jnp.int32)
        act = jnp.asarray([True, True])
        flat = write_decode_kv(kp, kp, kn, kn, pt, pos, act,
                               impl="interpret")
        stacked = write_decode_kv(kp[None], kp[None], kn, kn, pt, pos, act,
                                  layer=0, impl="interpret")
        np.testing.assert_array_equal(np.asarray(flat[0]),
                                      np.asarray(stacked[0][0]))
        with pytest.raises(ValueError, match="layer"):
            write_decode_kv(kp[None], kp[None], kn, kn, pt, pos, act)
        with pytest.raises(ValueError, match="layer"):
            decode_attention_xla(kn, kp[None], kp[None], pt, pos + 1)


# ------------------------------------------- the tree the scheduler holds
def _family(name):
    """``(config, fp32 parameters, pools of the decode step)`` of a
    served family at a tiny size, bf16 compute."""
    if name == "latent":
        from apex_tpu.models import mla_moe

        cfg = mla_moe.MLAMoEConfig(
            vocab_size=61, hidden_size=32, num_dense_layers=1,
            num_moe_layers=2, num_attention_heads=2, q_lora_rank=12,
            kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=8,
            v_head_dim=16, intermediate_size=48, moe_intermediate_size=16,
            n_routed_experts=8, held_start=2, held_count=4, n_group=2,
            topk_group=1, num_experts_per_tok=2,
            rope_original_max_position=16, param_dtype=jnp.float32,
            compute_dtype=jnp.bfloat16)
        return cfg, mla_moe.init_params(cfg, jax.random.PRNGKey(4))
    cfg = tiny_cfg(compute_dtype=jnp.bfloat16, **{
        "gpt-learned": dict(position_embedding_type="learned"),
        "gpt-rope-gqa": dict(num_query_groups=2)}[name])
    return cfg, init_params(cfg, jax.random.PRNGKey(4))


def _paths(tree):
    return {tuple(k.key for k in path): leaf for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


#: per family: how many leaves the rule names, and leaves it must not
_RULE = {
    "gpt-learned": (6, [("embed",), ("pos_embed",), ("final_ln_scale",),
                        ("layers", "ln1_scale"), ("layers", "ln1_bias"),
                        ("layers", "ln2_scale"), ("layers", "ln2_bias"),
                        ("layers", "bq"), ("layers", "bk"), ("layers", "bv"),
                        ("layers", "bo"), ("layers", "fc1_b"),
                        ("layers", "fc2_b")]),
    "gpt-rope-gqa": (6, [("embed",), ("final_ln_bias",),
                         ("layers", "ln2_scale"), ("layers", "bo")]),
    "latent": (6 + 3 + 6 + 3, [
        ("embed",), ("head",), ("final_norm",), ("moe", "router"),
        ("moe", "router_bias"), ("moe", "attn_norm"), ("moe", "q_norm"),
        ("moe", "kv_norm"), ("dense", "ffn_norm"), ("moe", "we_gate"),
        ("moe", "we_up"), ("moe", "we_down")]),
}


@pytest.mark.parametrize("name", sorted(_RULE))
class TestServingParams:
    """``serving_params``: the leaves every served program reads only
    through a cast to the compute dtype are cast ONCE; the programs run
    either tree to the same bits (PERF.md, PR 29)."""

    DCFG = DecodeConfig(
        cache=KVCacheConfig(num_pages=9, page_size=4, pages_per_seq=4,
                            dtype=jnp.bfloat16),
        max_batch=2, max_prompt_len=8, temperature=0.0, attn_impl="xla",
        sample_impl="xla")

    def _pools(self, model):
        from apex_tpu.inference.kv_cache import COUNTERS, alloc_named_pools

        pools = alloc_named_pools(model.cache_spec(), self.DCFG.cache)
        if model.counter_names:
            pools[COUNTERS] = jnp.zeros((len(model.counter_names),),
                                        jnp.int32)
        return pools

    def test_only_what_the_rule_names_is_cast(self, name):
        from apex_tpu.observability import tracing

        cfg, params = _family(name)
        n_cast, kept = _RULE[name]
        with tracing.TracingScope() as tracer:
            got = cfg.served_model().serving_params(params)
        before, after = _paths(params), _paths(got)
        cast = [p for p in after if after[p] is not before[p]]
        assert len(cast) == n_cast
        assert all(after[p].dtype == jnp.bfloat16 for p in cast)
        for p in kept:
            assert after[p] is before[p] and after[p].dtype == jnp.float32
        (span,) = [s for s in tracer.spans()
                   if s["name"] == "serve.prepare_params"]
        size = lambda ps: sum(before[p].size * 4 for p in ps)  # noqa: E731
        assert span["attrs"]["cast_leaves"] == n_cast
        assert span["attrs"]["cast_bytes"] == size(cast)
        assert span["attrs"]["kept_bytes"] == size(
            [p for p in before if p not in cast])

    @pytest.mark.parametrize("case", ["again", "born-in-bf16",
                                      "float32-compute"])
    def test_nothing_to_cast_gives_the_same_arrays(self, name, case):
        """No copy and no program: the second call, a tree already in
        the compute dtype, and ``compute_dtype=float32``."""
        import dataclasses

        from apex_tpu.inference import decode
        from apex_tpu.observability import tracing

        cfg, params = _family(name)
        model = cfg.served_model()
        if case == "again":
            params = model.serving_params(params)
        elif case == "born-in-bf16":
            params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        else:
            model = dataclasses.replace(
                cfg, compute_dtype=jnp.float32).served_model()
        runs = decode._cast_leaves._cache_size()
        with tracing.TracingScope() as tracer:
            got = model.serving_params(params)
        assert got is params
        assert decode._cast_leaves._cache_size() == runs
        (span,) = tracer.spans()
        assert (span["attrs"]["cast_leaves"],
                span["attrs"]["cast_bytes"]) == (0, 0)

    @pytest.mark.parametrize("program", ["prefill", "decode_step",
                                         "tokenwise"])
    def test_programs_give_the_same_bits_from_either_tree(self, name,
                                                          program):
        cfg, params = _family(name)
        model = cfg.served_model()
        trees = (params, model.serving_params(params))
        d, rng = self.DCFG, np.random.RandomState(5)
        prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(1, 8)))
        row = jnp.asarray([1, 2, 3, 4], jnp.int32)
        if program == "tokenwise":
            a, b = (decode_logits_tokenwise(t, model, d, prompt, 3, row)
                    for t in trees)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            return
        outs = []
        for tree in trees:
            hidden, _ = jax.jit(lambda p: model.prefill(
                p, prompt, jnp.int32(6), "xla"))(tree)
            pools, first = make_prefill(model, d)(
                tree, self._pools(model), prompt, jnp.int32(6),
                jnp.int32(0), row, jnp.uint32(0))
            got = [hidden, first]
            if program == "decode_step":
                tables = jnp.zeros((2, 4), jnp.int32).at[0].set(row)
                args = (jnp.asarray([int(first), 0]),
                        jnp.asarray([6, 0]), jnp.asarray([True, False]))
                hidden, _ = jax.jit(lambda p, pl: model.decode(
                    p, *args, pl, tables, "xla"))(tree, pools)
                pools, nxt = make_decode_step(model, d)(
                    tree, pools, *args, tables, jnp.zeros((2,), jnp.uint32))
                got = [hidden[0], nxt[0]]
            outs.append(got + jax.tree.leaves(pools))
        for a, b in zip(*outs):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(
                np.asarray(a.astype(jnp.float32)),
                np.asarray(b.astype(jnp.float32)))

    def test_scheduler_holds_the_prepared_tree_only(self, name):
        """The fp32 matrices are freed once the caller drops its tree,
        and a tree prepared by the caller is held as it is (several
        schedulers then share one)."""
        import gc
        import weakref

        cfg, params = _family(name)
        n_cast, kept = _RULE[name]
        given = _paths(params)
        gone = [weakref.ref(leaf) for p, leaf in given.items()
                if p[-1] in cfg.served_model().cast_once_leaves]
        stays = weakref.ref(given[kept[0]])
        assert len(gone) == n_cast
        sched = ContinuousBatchingScheduler(params, cfg, self.DCFG)
        del params, given
        gc.collect()
        assert all(r() is None for r in gone) and stays() is not None
        held = _paths(sched.params)
        assert sum(a.dtype == jnp.bfloat16 for a in held.values()) == n_cast
        other = ContinuousBatchingScheduler(sched.params, cfg, self.DCFG)
        assert other.params is sched.params
        sched.submit(Request(rid=0, prompt=[3, 1, 4, 1, 5],
                             max_new_tokens=4))
        assert len(sched.run_until_drained()[0].tokens) == 4
        jax.block_until_ready(sched.params)
        assert sched.lower_decode_step() is not None


# -------------------------------------------------------------- scheduler
def _sched(params, cfg, *, num_pages=10, page_size=4, pages_per_seq=6,
           max_batch=3, temperature=0.0, top_k=0, attn="xla", sample="xla",
           max_prompt=8, seed=0, watchdog=None):
    dcfg = DecodeConfig(
        cache=KVCacheConfig(num_pages=num_pages, page_size=page_size,
                            pages_per_seq=pages_per_seq, dtype=jnp.float32),
        max_batch=max_batch, max_prompt_len=max_prompt,
        temperature=temperature, top_k=top_k,
        attn_impl=attn, sample_impl=sample,
        sample_dot_dtype=jnp.float32, base_seed=seed)
    return ContinuousBatchingScheduler(params, cfg, dcfg,
                                       watchdog=watchdog)


def _requests(rng, n, vocab, plen=(2, 7), max_new=(2, 6)):
    return [Request(rid=i,
                    prompt=list(rng.randint(0, vocab,
                                            size=rng.randint(*plen))),
                    max_new_tokens=int(rng.randint(*max_new)))
            for i in range(n)]


class TestScheduler:
    @pytest.fixture(scope="class")
    def model(self):
        cfg = tiny_cfg()
        return cfg, init_params(cfg, jax.random.PRNGKey(0))

    def test_greedy_serving_matches_training_forward(self, model):
        """Every served token is the training forward's argmax
        continuation — end-to-end decode parity through admission,
        page recycling, and eviction."""
        cfg, params = model
        sched = _sched(params, cfg)
        rng = np.random.RandomState(7)
        for r in _requests(rng, 6, cfg.vocab_size):
            sched.submit(r)
        done = sched.run_until_drained()
        assert len(done) == 6
        assert sched.stats["admitted"] == 6 and sched.stats["evicted"] == 6
        for c in done[:3]:
            seq = list(c.prompt)
            for tok in c.tokens:
                logits = gpt_forward(params, jnp.asarray([seq]), cfg)
                assert int(jnp.argmax(logits[len(seq) - 1, 0])) == tok
                seq.append(tok)

    def test_admission_only_when_pages_free(self, model):
        """Pool of 3 allocatable pages, requests needing 2 each: at
        most one resident at a time, queued requests wait."""
        cfg, params = model
        sched = _sched(params, cfg, num_pages=4, page_size=4,
                       pages_per_seq=2, max_batch=3)
        rng = np.random.RandomState(1)
        for i in range(3):
            sched.submit(Request(rid=i,
                                 prompt=list(rng.randint(0, 61, size=4)),
                                 max_new_tokens=3))
        max_resident = 0
        for _ in range(100):
            if sched.idle():
                break
            sched.step()
            max_resident = max(max_resident, sched.num_active)
        assert sched.idle() and len(sched.completed) == 3
        assert max_resident == 1, (
            f"pages for one 2-page request were free, yet "
            f"{max_resident} sequences were resident")

    def test_fifo_order_pinned_no_starvation(self, model):
        """A page-hungry queue head must NOT be overtaken by small
        requests behind it (FIFO admission, starvation-free)."""
        cfg, params = model
        sched = _sched(params, cfg, num_pages=7, page_size=4,
                       pages_per_seq=6, max_batch=3)
        admitted_order = []
        orig = sched._admit_into

        def record(slot, req, *plan):
            admitted_order.append(req.rid)
            return orig(slot, req, *plan)

        sched._admit_into = record
        rng = np.random.RandomState(2)
        # rid 0 small (occupies pages), rid 1 HUGE (blocks), rid 2 small
        sched.submit(Request(0, list(rng.randint(0, 61, size=4)), 8))
        sched.step()  # rid 0 resident, holds 3 of 6 pages
        sched.submit(Request(1, list(rng.randint(0, 61, size=8)), 16))
        sched.submit(Request(2, list(rng.randint(0, 61, size=2)), 2))
        done = sched.run_until_drained()
        assert admitted_order == [0, 1, 2], (
            f"admission order {admitted_order} broke FIFO — a small "
            f"request overtook the blocked head")
        assert len(done) == 3

    def test_page_recycling_serves_more_than_pool(self, model):
        """Total page demand across the run exceeds the pool several
        times over; eviction must recycle pages back to admission."""
        cfg, params = model
        sched = _sched(params, cfg, num_pages=5, page_size=4,
                       pages_per_seq=2, max_batch=2)
        rng = np.random.RandomState(3)
        n = 8
        for i in range(n):
            sched.submit(Request(i, list(rng.randint(0, 61, size=3)), 4))
        done = sched.run_until_drained()
        assert len(done) == n
        total_pages = n * pages_needed(3 + 4, 4)
        assert total_pages > 4, "test must oversubscribe the pool"
        assert sched.allocator.free_pages == 4, "pages leaked"

    def test_deterministic_under_seeded_trace(self, model):
        """Same seeded arrival trace + temperature sampling: bitwise
        the same served tokens, twice."""
        cfg, params = model

        def run():
            sched = _sched(params, cfg, temperature=0.9, top_k=5, seed=11)
            rng = np.random.RandomState(5)
            for r in _requests(rng, 5, cfg.vocab_size):
                sched.submit(r)
            return [(c.rid, tuple(c.tokens))
                    for c in sched.run_until_drained()]

        assert run() == run()

    def test_eos_stops_generation_early(self, model):
        cfg, params = model
        sched = _sched(params, cfg)
        sched.submit(Request(0, [5, 9, 12], max_new_tokens=20, eos_id=None))
        done = sched.run_until_drained()
        toks = done[0].tokens
        # re-serve with eos = some generated token: generation must cut
        # at its FIRST occurrence (greedy is deterministic, so the
        # prefix is reproduced exactly)
        eos = toks[-1]
        cut = toks.index(eos) + 1
        sched2 = _sched(params, cfg)
        sched2.submit(Request(0, [5, 9, 12], max_new_tokens=20, eos_id=eos))
        done2 = sched2.run_until_drained()
        assert done2[0].tokens == toks[:cut]

    def test_submit_validation(self, model):
        cfg, params = model
        sched = _sched(params, cfg)
        with pytest.raises(ValueError, match="max_prompt_len"):
            sched.submit(Request(0, list(range(9)), 2))
        with pytest.raises(ValueError, match="pages_per_seq"):
            sched.submit(Request(1, [1, 2], 1000))
        with pytest.raises(ValueError, match="empty"):
            sched.submit(Request(2, [], 2))

    def test_chaos_decode_kernel_failure_degrades_once_keeps_serving(
            self, model):
        """An injected decode-attention launch failure (the Mosaic
        stand-in) trips the registry ONCE; the serve loop degrades to
        the XLA reference and produces the SAME tokens."""
        cfg, params = model

        def serve():
            sched = _sched(params, cfg, attn="interpret",
                           sample="interpret", temperature=0.8, top_k=6,
                           seed=4)
            rng = np.random.RandomState(6)
            for r in _requests(rng, 4, cfg.vocab_size):
                sched.submit(r)
            return [(c.rid, tuple(c.tokens))
                    for c in sched.run_until_drained()]

        get_registry().reset()
        try:
            baseline = serve()
            get_registry().reset()
            monkey = ChaosMonkey(ChaosPlan.make(
                kernel_failures={"decode_attention": 1}))
            with monkey.active():
                served = serve()
            status = get_registry().status()["decode_attention"]
            assert status["tripped"] and status["fallback_calls"] >= 1
            assert served == baseline, (
                "the degraded (XLA) serve produced different tokens")
            assert monkey.injected.get("kernel:decode_attention") == 1
        finally:
            get_registry().reset()

    def test_decode_step_compiles_once_across_occupancy(self, model):
        """The compile-once contract at the scheduler level: varying
        occupancy (1..3 active), cache lengths, admissions and
        evictions all reuse ONE compiled decode step (pinned through
        the generalized ``analysis.lowered.assert_no_recompile``
        guard-rail, post-hoc spelling).  Spans are observers: with a
        tracer installed the step still compiles once, and the tokens
        are those of the untraced run."""
        import contextlib

        from apex_tpu.analysis import lowered as lw
        from apex_tpu.observability import tracing

        cfg, params = model
        served = {}
        for traced in (False, True):
            scope = tracing.TracingScope() if traced \
                else contextlib.nullcontext()
            with scope as tracer:
                sched = _sched(params, cfg)
                rng = np.random.RandomState(8)
                for r in _requests(rng, 7, cfg.vocab_size, plen=(2, 8),
                                   max_new=(2, 8)):
                    sched.submit(r)
                done = sched.run_until_drained()
            lw.assert_no_recompile(sched._decode, label="decode_step")
            assert sched.decode_cache_size() == 1
            served[traced] = {c.rid: tuple(c.tokens) for c in done}
        assert any(s["name"] == "serve.emit" for s in tracer.spans())
        assert served[True] == served[False]

    def test_chaos_wedged_decode_step_fires_serving_watchdog(self, model):
        """The serving-side watchdog contract: one decode step stalls
        (chaos ``wedge_step_at`` keyed on the decode-step counter), the
        per-step heartbeat stops, and the watchdog fires WHILE the step
        is hung — the scheduler's on_wedge hook logs every queued and
        in-flight request id (the requeue manifest for the layer above)
        and records ``apex_serve_wedges_total`` — instead of the server
        hanging forever.  ``on_fire`` captures the firing in place of
        the real exit-75 (which ``serve_gpt.py --watchdog-secs`` takes
        and the supervisor restarts on)."""
        import time

        from apex_tpu.observability import MetricsScope
        from apex_tpu.resilience import StepWatchdog

        cfg, params = model
        fired = []
        wd = StepWatchdog(0.5, poll_sec=0.05, first_deadline_sec=120.0,
                          on_fire=fired.append)
        with MetricsScope() as reg:
            sched = _sched(params, cfg, watchdog=wd)
            rng = np.random.RandomState(9)
            # warmup WITHOUT the watchdog thread: compiles prefill +
            # decode so the armed phase's step times are real step
            # times, not jit compiles tripping a spurious fire
            sched.submit(Request(100, list(rng.randint(0, 61, size=3)), 2))
            sched.run_until_drained()
            wedge_at = sched.stats["decode_steps"] + 1
            monkey = ChaosMonkey(ChaosPlan.make(
                wedge_step_at=wedge_at, wedge_step_seconds=2.0))
            for r in _requests(rng, 4, cfg.vocab_size):
                sched.submit(r)
            with wd, monkey.active():
                t0 = time.monotonic()
                done = sched.run_until_drained()
                hung = time.monotonic() - t0
            assert len(done) == 5  # warmup + 4: the wedge cost time, not work
            assert hung >= 1.5, "the injected wedge did not hold the step"
            assert monkey.injected.get("wedge_step") == 1
            assert fired and fired[0]["exit_code"] == 75
            assert reg.counter("apex_serve_wedges_total").value() == 1
            # the wedge fired while requests were still queued/in
            # flight: the manifest hook had rids to report (admitted 5
            # total, only the warmup was complete before the wedge)
            assert sched.stats["evicted"] == 5
