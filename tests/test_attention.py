"""Flash + ring attention tests — parity vs the naive O(S²) oracle,
forward and backward (mirrors apex/contrib/test/fmha and multihead_attn
parity-vs-unfused tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.ops.attention import flash_attention, flash_attention_with_lse, mha_reference
from apex_tpu.transformer.context_parallel import ring_attention


def qkv(seed=0, B=2, H=3, S=32, D=8):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    return mk(), mk(), mk()


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("block_k", [8, 16, 32])
    def test_forward_matches_reference(self, causal, block_k):
        q, k, v = qkv()
        out = flash_attention(q, k, v, causal=causal, block_k=block_k)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.slow
    def test_backward_matches_reference(self, causal):
        q, k, v = qkv(1)

        def f(q, k, v):
            return jnp.sum(jnp.sin(flash_attention(q, k, v, causal=causal, block_k=8)))

        def fr(q, k, v):
            return jnp.sum(jnp.sin(mha_reference(q, k, v, causal=causal)))

        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
        for a, r in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=2e-4, atol=2e-5)

    def test_lse_is_logsumexp(self):
        q, k, v = qkv(2, S=16)
        _, lse = flash_attention_with_lse(q, k, v, causal=False, block_k=8)
        scale = 1.0 / np.sqrt(q.shape[-1])
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        ref = jax.scipy.special.logsumexp(s, axis=-1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref), rtol=1e-4, atol=1e-5)

    def test_non_divisible_block(self):
        q, k, v = qkv(3, S=24)
        out = flash_attention(q, k, v, causal=True, block_k=7)  # falls back to divisor
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)


def padded_mask(B, S, lengths):
    m = np.zeros((B, S), bool)
    for b, n in enumerate(lengths):
        m[b, :n] = True
    return jnp.asarray(m)


class TestPaddedFlashAttention:
    """Key-padding masks through the flash path (scan composite),
    parity vs the dense oracle — the fmha varlen semantics."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("block_k", [8, 16, 32])
    def test_forward_matches_reference(self, causal, block_k):
        q, k, v = qkv(8)
        mask = padded_mask(2, 32, [32, 17])
        out = flash_attention(q, k, v, causal=causal, block_k=block_k,
                              kv_mask=mask, impl="scan")
        ref = mha_reference(q, k, v, causal=causal, kv_mask=mask)
        # compare valid query rows (padded rows see the same valid keys in
        # both paths, but have no defined semantics)
        np.testing.assert_allclose(np.asarray(out[1, :, :17]),
                                   np.asarray(ref[1, :, :17]), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.slow
    def test_backward_matches_reference(self, causal):
        q, k, v = qkv(9)
        mask = padded_mask(2, 32, [32, 21])
        mf = mask[:, None, :, None].astype(jnp.float32)

        def f(q, k, v):
            o = flash_attention(q, k, v, causal=causal, block_k=8,
                                kv_mask=mask, impl="scan")
            return jnp.sum(jnp.sin(o * mf))  # loss over valid rows only

        def fr(q, k, v):
            return jnp.sum(jnp.sin(mha_reference(q, k, v, causal=causal, kv_mask=mask) * mf))

        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
        for a, r in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=2e-4, atol=2e-5)

    def test_masked_keys_have_no_influence(self):
        q, k, v = qkv(10)
        mask = padded_mask(2, 32, [32, 20])
        out = flash_attention(q, k, v, kv_mask=mask, causal=False, impl="scan")
        k2 = k.at[1, :, 20:].set(77.0)
        v2 = v.at[1, :, 20:].set(-77.0)
        out2 = flash_attention(q, k2, v2, kv_mask=mask, causal=False, impl="scan")
        np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-6)


class TestBiasedFlashAttention:
    """Additive attention bias (OpenFold pair bias) through the scan
    path — forward parity and a REAL bias cotangent."""

    def _biased_ref(self, q, k, v, bias):
        scale = 1.0 / np.sqrt(q.shape[-1])
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale + bias
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    @pytest.mark.parametrize("bias_shape", [(2, 3, 32, 32), (1, 3, 32, 32), (2, 1, 1, 32)])
    def test_forward_matches_reference(self, bias_shape):
        q, k, v = qkv(12)
        bias = jnp.asarray(np.random.RandomState(13).randn(*bias_shape).astype(np.float32))
        out = flash_attention(q, k, v, causal=False, attn_bias=bias, impl="scan",
                              block_k=8)
        ref = self._biased_ref(q, k, v, bias)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("bias_shape", [(2, 3, 32, 32), (1, 1, 32, 32), (2, 1, 1, 32)])
    def test_bias_gradient_matches_reference(self, bias_shape):
        q, k, v = qkv(14)
        bias = jnp.asarray(np.random.RandomState(15).randn(*bias_shape).astype(np.float32))

        def f(bias):
            o = flash_attention(q, k, v, causal=False, attn_bias=bias, impl="scan",
                                block_k=16)
            return jnp.sum(jnp.sin(o))

        def fr(bias):
            return jnp.sum(jnp.sin(self._biased_ref(q, k, v, bias)))

        g = jax.grad(f)(bias)
        gr = jax.grad(fr)(bias)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=2e-4, atol=2e-5)

    def test_bias_composes_with_padding_mask(self):
        q, k, v = qkv(16)
        bias = jnp.asarray(np.random.RandomState(17).randn(2, 3, 32, 32).astype(np.float32))
        mask = padded_mask(2, 32, [32, 20])
        out = flash_attention(q, k, v, causal=False, attn_bias=bias, kv_mask=mask,
                              impl="scan")
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1]) + bias
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
        np.testing.assert_allclose(np.asarray(out[1, :, :20]), np.asarray(ref[1, :, :20]),
                                   rtol=1e-4, atol=1e-5)


    @pytest.mark.parametrize("pooled,seen,block,block_k", [
        (128, 128, None, None), (256, 100, None, None), (256, 0, None, None),
        (256, 100, 128, None), (128, 0, 128, None),
        (256, 100, 128, 512), (128, 0, 128, 384), (256, 256, 64, 512)])
    def test_pallas_key_bias_with_negative_k_offset(self, monkeypatch,
                                                    pooled, seen, block,
                                                    block_k):
        """The EVA window's call: keys = a pooled buffer then the
        window's own, the diagonal starts after the buffer (``k_offset =
        -pooled``) and a key bias hides the buffer's rows from ``seen``
        on.  Over one block the buffer's sub-tiles lie wholly under the
        diagonal: they are walked without a mask, their hidden columns
        weigh nothing.  Over several query blocks and ONE key block, as
        ``ops/eva.py`` asks for it, or over a grid of key blocks, the
        grid indices choose a block's static variant as without a
        bias."""
        W = 256
        col = np.arange(pooled + W)
        mask = jnp.asarray(((col < seen) | (col >= pooled))[None, :])
        assert_walk_matches_scan(monkeypatch, Sq=W, Sk=pooled + W,
                                 k_offset=-pooled, kv_mask=mask, seed=23,
                                 block=block, block_k=block_k)


class TestOpenFoldMHA:
    def test_attention_core_with_mask_and_bias(self):
        from apex_tpu.contrib.openfold_triton import CanSchTriMHA, attention_core

        assert CanSchTriMHA((1, 2, 16, 8))
        rng = np.random.RandomState(18)
        # OpenFold-ish leading dims: (batch, n_seq) extra axis
        q = jnp.asarray(rng.randn(2, 3, 4, 16, 8).astype(np.float32))
        k = jnp.asarray(rng.randn(2, 3, 4, 16, 8).astype(np.float32))
        v = jnp.asarray(rng.randn(2, 3, 4, 16, 8).astype(np.float32))
        mask = jnp.asarray(rng.rand(2, 3, 1, 1, 16) > 0.2)
        bias = jnp.asarray(rng.randn(2, 1, 4, 16, 16).astype(np.float32))

        out = attention_core(q, k, v, mask=mask, bias=bias)
        assert out.shape == q.shape

        s = jnp.einsum("...hqd,...hkd->...hqk", q, k) / np.sqrt(8.0) + bias
        s = jnp.where(mask, s, -1e9)
        ref = jnp.einsum("...hqk,...hkd->...hqd", jax.nn.softmax(s, axis=-1), v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)

    def test_pair_bias_gets_gradients(self):
        from apex_tpu.contrib.openfold_triton import attention_core

        rng = np.random.RandomState(19)
        q = jnp.asarray(rng.randn(1, 2, 16, 8).astype(np.float32))
        k, v = q + 0.1, q - 0.1
        bias = jnp.asarray(rng.randn(1, 2, 16, 16).astype(np.float32))
        g = jax.grad(lambda b: jnp.sum(attention_core(q, k, v, bias=b) ** 2))(bias)
        assert float(jnp.abs(g).max()) > 0  # trained pair bias: real cotangent
        assert bool(jnp.all(jnp.isfinite(g)))


def _walk_inputs(B, H, Hkv, Sq, Sk, D, seed, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda h, n: jnp.asarray(
        rng.randn(B, h, n, D).astype(np.float32)).astype(dtype)
    return mk(H, Sq), mk(Hkv, Sk), mk(Hkv, Sk), mk(H, Sq)


def assert_walk_matches_scan(monkeypatch, *, Sq, Sk=None, sub=128, block=None,
                             block_k=None, causal=True, q_offset=0,
                             k_offset=0, H=2,
                             Hkv=None, kv_mask=None, B=1, D=64, seed=3,
                             dead_rows=None, dtype=jnp.float32,
                             tol=(2e-5, 1e-4), runs=None):
    """The Pallas kernels (interpret mode), their blocks walked in
    ``sub`` x ``sub`` sub-tiles (``sub=None``: no tuned row, the
    dispatcher's own blocks and sub-tile; ``block``: the caller's grid
    blocks, ``block_k`` the key block where it is another), against the
    ``lax.scan``
    composite: forward and all three gradients, within ``tol`` (the
    tolerances of the Pallas classes here: 2e-5 forward, 1e-4
    gradients).  ``runs``: the call's plans must then hold a run of two
    sub-tiles or more in every kernel (fewer bodies than sub-tiles
    visited), so that a run's one update is held to the sum."""
    from apex_tpu.ops import flash_attention_pallas as fap

    Sk, block_k = Sk or Sq, block_k or block
    q, k, v, w = _walk_inputs(B, H, Hkv or H, Sq, Sk, D, seed, dtype)
    monkeypatch.setattr(fap, "_TUNED_BLOCKS", {})
    for phase in ("fwd", "bwd") if sub else ():
        fap.set_tuned_blocks({(Sq, D, jnp.dtype(dtype).name, phase): (
            block or Sq, block_k or Sk, sub)})
    if runs:
        for phase in ("fwd", "bwd", "dkv"):
            kind = "fwd" if phase == "fwd" else "bwd"
            bq, bk, (side, _, _) = fap.dispatched(
                Sq, Sk, D, dtype, kind, block, block_k)
            visited, masked, _, bodies = fap.live_subtiles(
                phase, Sq, Sk, q_offset, k_offset, bq, bk,
                side if side < max(bq, bk) else None, causal=causal)
            assert bodies < visited, (phase, bq, bk, side, visited, bodies)
    kw = dict(causal=causal, q_offset=q_offset, k_offset=k_offset,
              kv_mask=kv_mask)

    def pallas(q, k, v):
        return fap.flash_attention_pallas(
            q, k, v, block_q=block, block_k=block_k, interpret=True, **kw)

    def scan(q, k, v):
        return flash_attention(q, k, v, impl="scan", **kw)

    f32 = lambda x: np.asarray(x, np.float32)
    out, ref = pallas(q, k, v), scan(q, k, v)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(f32(out), f32(ref), atol=tol[0], rtol=tol[0])
    if dead_rows is not None:   # rows no key reaches: zero, not a mean
        np.testing.assert_array_equal(f32(out)[dead_rows], 0.0)
    loss = lambda fn: lambda *a: jnp.sum(
        fn(*a).astype(jnp.float32) * w.astype(jnp.float32))
    gp = jax.grad(loss(pallas), argnums=(0, 1, 2))(q, k, v)
    gs = jax.grad(loss(scan), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gs):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(f32(a), f32(b), atol=tol[1], rtol=tol[1])


#: name -> arguments of assert_walk_matches_scan; every case cuts a grid
#: block into sub-tiles of 128 and puts the diagonal somewhere else
WALK_CASES = {
    # one block of 4 x 4 sub-tiles: 6 skipped, 4 masked, 6 plain
    "block_4x4": dict(Sq=512),
    # 2 x 2 grid blocks of 2 x 2 sub-tiles: three static variants, the
    # grid indices choose among them
    "grid_2x2_blocks": dict(Sq=512, block=256),
    # ring chunks: a call wholly above (every row dead), on, and wholly
    # under the diagonal, and one the diagonal enters off a tile's edge
    "chunk_above": dict(Sq=256, q_offset=0, k_offset=256,
                        dead_rows=np.s_[:]),
    "chunk_on": dict(Sq=256, q_offset=256, k_offset=256),
    "chunk_under": dict(Sq=256, q_offset=512, k_offset=0),
    "chunk_off_edge": dict(Sq=256, Sk=512, q_offset=160, k_offset=64),
    "keys_start_late": dict(Sq=256, q_offset=0, k_offset=64,
                            dead_rows=np.s_[:, :, :64]),
    # Sq != Sk, causal (keys past the last query: never visited) and not
    "more_keys_causal": dict(Sq=256, Sk=512),
    "more_keys_full": dict(Sq=256, Sk=512, causal=False),
    "more_queries": dict(Sq=512, Sk=256, q_offset=0, k_offset=0),
    # grouped queries: the dkv walk over a group's q heads
    "gqa_4_2": dict(Sq=256, H=4, Hkv=2),
    "mqa_grid": dict(Sq=256, block=128, H=2, Hkv=1),
    # lengths the sub-tile does not divide: 384 falls back to 128 under
    # a target of 256, 320 has no lane-tile divisor and is one tile
    "falls_back_to_128": dict(Sq=384, sub=256),
    "one_tile_320": dict(Sq=320, sub=256),
}


class TestPaddedPallasFlashAttention:
    """Padding masks through the Pallas kernels (interpret mode)."""

    def _inputs(self, B=2, H=2, Sq=256, Sk=256, D=64, dtype=jnp.float32, seed=11):
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(B, H, Sq, D).astype(np.float32), dtype)
        k = jnp.asarray(rng.randn(B, H, Sk, D).astype(np.float32), dtype)
        v = jnp.asarray(rng.randn(B, H, Sk, D).astype(np.float32), dtype)
        return q, k, v

    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_reference(self, causal):
        from apex_tpu.ops.flash_attention_pallas import flash_attention_pallas

        q, k, v = self._inputs()
        mask = padded_mask(2, 256, [256, 130])
        out = flash_attention_pallas(q, k, v, causal=causal, kv_mask=mask,
                                     interpret=True)
        ref = mha_reference(q, k, v, causal=causal, kv_mask=mask)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(out[1, :, :130]),
                                   np.asarray(ref[1, :, :130]), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.slow
    def test_backward_matches_reference(self, causal):
        from apex_tpu.ops.flash_attention_pallas import flash_attention_pallas

        q, k, v = self._inputs(Sq=128, Sk=128)
        mask = padded_mask(2, 128, [128, 70])
        mf = mask[:, None, :, None].astype(jnp.float32)

        def loss_pallas(q, k, v):
            o = flash_attention_pallas(q, k, v, causal=causal, kv_mask=mask,
                                       interpret=True)
            return jnp.sum((o * mf) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum((mha_reference(q, k, v, causal=causal, kv_mask=mask) * mf) ** 2)

        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)

    def test_matches_scan_path_multi_block(self):
        """Mask must land on the right k-blocks when nk > 1."""
        from apex_tpu.ops.flash_attention_pallas import flash_attention_pallas

        q, k, v = self._inputs(Sq=256, Sk=256)
        mask = padded_mask(2, 256, [200, 64])
        out = flash_attention_pallas(q, k, v, causal=False, kv_mask=mask,
                                     block_q=128, block_k=128, interpret=True)
        ref = flash_attention(q, k, v, causal=False, kv_mask=mask, impl="scan")
        for b, n in enumerate([200, 64]):
            np.testing.assert_allclose(np.asarray(out[b, :, :n]),
                                       np.asarray(ref[b, :, :n]),
                                       atol=2e-5, rtol=2e-5)


    @pytest.mark.parametrize("lengths,causal", [
        ([256, 130], False), ([200, 64], False), ([256, 130], True),
        ([256, 0], False),   # a batch row with no valid key: dead rows
    ])
    def test_subtile_walk_with_kv_mask(self, monkeypatch, lengths, causal):
        """A key mask hides columns by data: every sub-tile is walked,
        none pays the diagonal's mask when the call is not causal, and a
        batch row whose keys are all padding stays zero with zero
        gradients."""
        mask = padded_mask(2, 256, lengths)
        dead = np.s_[1] if lengths[1] == 0 else None
        assert_walk_matches_scan(monkeypatch, Sq=256, B=2, causal=causal,
                                 kv_mask=mask, dead_rows=dead, seed=21)


CP = 4


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, causal, devices8):
        B, H, S, D = 2, 2, 32, 8
        q, k, v = qkv(4, B=B, H=H, S=S, D=D)
        ref = mha_reference(q, k, v, causal=causal)

        mesh = Mesh(np.array(devices8[:CP]), ("cp",))
        f = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "cp", causal=causal),
            mesh=mesh,
            in_specs=(P(None, None, "cp", None),) * 3,
            out_specs=P(None, None, "cp", None),
            check_vma=False,
        )
        out = f(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)

    @pytest.mark.slow
    def test_grads_match_full_attention(self, devices8):
        B, H, S, D = 1, 2, 16, 4
        q, k, v = qkv(5, B=B, H=H, S=S, D=D)

        def fr(q, k, v):
            return jnp.sum(jnp.sin(mha_reference(q, k, v, causal=True)))

        gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)

        mesh = Mesh(np.array(devices8[:CP]), ("cp",))

        def f(q, k, v):
            out = ring_attention(q, k, v, "cp", causal=True)
            # differentiate the LOCAL loss shard: dq is local by
            # construction, and dk/dv cotangents travel the reverse ring
            # (ppermute transpose), so per-device grads sum to the
            # total-loss gradient — no psum needed (one would overcount).
            return jnp.sum(jnp.sin(out))

        g = jax.shard_map(
            jax.grad(f, argnums=(0, 1, 2)),
            mesh=mesh,
            in_specs=(P(None, None, "cp", None),) * 3,
            out_specs=(P(None, None, "cp", None),) * 3,
            check_vma=False,
        )(q, k, v)
        for a, r in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=2e-4, atol=2e-5)


class TestRingOverlap:
    """``overlap=True`` (unrolled ring, hop r+1's ppermute issued before
    chunk r's compute) consumes the same values in the same merge order
    as the serial scan schedule, so fp32 out/dq/dk/dv are BITWISE equal
    op-by-op — pinned under ``disable_jit`` where each primitive runs
    alone and any difference is a reordering bug, never rounding.  The
    jitted pair is additionally pinned at 1-ulp scale: XLA fuses the
    while-loop body and the unrolled straight-line program differently
    (FMA contraction, iteration-0 constant folding), which no two
    differently-shaped equal-math programs escape — but that residue
    must stay at rounding scale, never a schedule-divergence scale."""

    @staticmethod
    def _fwd_bwd(kw):
        # one vjp pass: the fwd output AND all three grads from a single
        # ring traversal (the matrix runs op-by-op under disable_jit, so
        # a second fwd-only traversal would double the dominant cost);
        # the cos(out) cotangent varies per element, deterministically
        def fwd_bwd(q, k, v):
            out, vjp = jax.vjp(
                lambda q, k, v: ring_attention(q, k, v, "cp", **kw),
                q, k, v)
            return out, vjp(jnp.cos(out))

        return fwd_bwd

    def _run(self, cp, causal, impl, overlap, devices8):
        B, H, D = 1, 2, 16
        q, k, v = qkv(7, B=B, H=H, S=64 * cp, D=D)
        mesh = Mesh(np.array(devices8[:cp]), ("cp",))
        kw = dict(causal=causal, impl=impl, interpret=True, overlap=overlap)
        specs = (P(None, None, "cp", None),) * 3
        out, grads = jax.shard_map(
            self._fwd_bwd(kw), mesh=mesh, in_specs=specs,
            out_specs=(specs[0], specs), check_vma=False,
        )(q, k, v)
        return out, grads

    def _run_vmap(self, cp, causal, impl, overlap):
        # the ring emulated by vmap(axis_name="cp") over a chunk axis:
        # collectives see the same named axis, but each primitive runs
        # ONCE on batched arrays instead of per-device — the only way
        # the op-by-op matrix fits the fast tier.  Not available to the
        # pallas impl: a batched lax.switch evaluates every branch's
        # jaxpr eagerly, outside flash's disable_jit(False) window, and
        # pallas_call cannot execute eagerly.
        B, H, D = 1, 2, 16
        q, k, v = qkv(7, B=B, H=H, S=64 * cp, D=D)
        kw = dict(causal=causal, impl=impl, interpret=True, overlap=overlap)

        def split(x):  # (B, H, S, D) -> (cp, B, H, S/cp, D)
            return jnp.moveaxis(
                x.reshape(B, H, cp, x.shape[2] // cp, D), 2, 0)

        f = jax.vmap(self._fwd_bwd(kw), axis_name="cp", axis_size=cp)
        return f(split(q), split(k), split(v))

    def _assert_bitwise(self, serial, overlapped):
        out_s, g_s = serial
        out_o, g_o = overlapped
        np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_o))
        for name, a, b in zip(("dq", "dk", "dv"), g_s, g_o):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{name} diverged between serial and overlapped")

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("cp", [2, 4])
    def test_bitwise_parity_fwd_bwd_scan(self, cp, causal):
        with jax.disable_jit():
            serial = self._run_vmap(cp, causal, "scan", False)
            overlapped = self._run_vmap(cp, causal, "scan", True)
        self._assert_bitwise(serial, overlapped)

    @pytest.mark.parametrize("causal", [
        True, pytest.param(False, marks=pytest.mark.slow)])
    @pytest.mark.parametrize("cp", [
        2, pytest.param(4, marks=pytest.mark.slow)])
    def test_bitwise_parity_fwd_bwd_pallas(self, cp, causal, devices8):
        # eager shard_map pays per-device sequential dispatch (~15-45 s
        # per combo), so tier-1 keeps only cp=2 × causal=True — whose
        # lax.switch full-block case already exercises the unmasked
        # kernel — and the rest ride the slow tier (the full cp∈{2,4} ×
        # causal matrix stays fast above via the scan vmap harness)
        with jax.disable_jit():
            serial = self._run(cp, causal, "pallas", False, devices8)
            overlapped = self._run(cp, causal, "pallas", True, devices8)
        self._assert_bitwise(serial, overlapped)

    @pytest.mark.parametrize("causal", [
        True, pytest.param(False, marks=pytest.mark.slow)])
    def test_jitted_parity_rounding_scale(self, causal, devices8):
        out_s, g_s = self._run(2, causal, "scan", False, devices8)
        out_o, g_o = self._run(2, causal, "scan", True, devices8)
        np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_o),
                                   rtol=1e-6, atol=1e-7)
        for a, b in zip(g_s, g_o):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)


class TestPallasFlashAttention:
    """Pallas kernel parity vs the naive oracle, interpret mode on CPU."""

    def _inputs(self, B=2, H=2, Sq=256, Sk=256, D=64, dtype=jnp.float32, seed=0):
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(B, H, Sq, D).astype(np.float32), dtype)
        k = jnp.asarray(rng.randn(B, H, Sk, D).astype(np.float32), dtype)
        v = jnp.asarray(rng.randn(B, H, Sk, D).astype(np.float32), dtype)
        return q, k, v

    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_reference(self, causal):
        from apex_tpu.ops.flash_attention_pallas import flash_attention_pallas

        q, k, v = self._inputs()
        out = flash_attention_pallas(q, k, v, causal=causal, interpret=True)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_tuned_block_table_consulted(self, monkeypatch):
        """Sweep-installed per-shape blocks must reach the kernel when
        the caller passes none, lose to explicit args, and miss cleanly
        for unkeyed shapes (the _pick_block fallback)."""
        from apex_tpu.ops import flash_attention_pallas as fap

        q, k, v = self._inputs()
        monkeypatch.setattr(fap, "_TUNED_BLOCKS", {})
        fap.set_tuned_blocks({(256, 64, "float32"): (128, 128)})
        assert fap.tuned_blocks(256, 64, jnp.float32) == (128, 128)
        assert fap.tuned_blocks(512, 64, jnp.float32) is None

        seen = []
        orig = fap._pick_block

        def spy(seq, target, align=fap._LANES, **kw):
            seen.append(target)
            return orig(seq, target, align, **kw)

        monkeypatch.setattr(fap, "_pick_block", spy)
        out = fap.flash_attention_pallas(q, k, v, causal=True, interpret=True)
        assert seen[:2] == [128, 128]  # table hit, not the 1024 default
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        seen.clear()
        fap.flash_attention_pallas(q, k, v, causal=True, block_q=256,
                                   block_k=256, interpret=True)
        assert seen[:2] == [256, 256]  # explicit args beat the table
        # cross-attention (Sk != Sq) must NOT pick up the self-attn entry
        seen.clear()
        q2, k2, v2 = self._inputs(Sq=256, Sk=128)
        fap.flash_attention_pallas(q2, k2, v2, causal=False, interpret=True)
        assert seen[:2] == [1024, 1024]

    def test_tuned_blocks_json_round_trip(self, monkeypatch):
        """The sweep's printed tuned_blocks_table JSON must install
        directly, and dtype keys normalize (jnp.bfloat16 == 'bfloat16')."""
        import json

        from apex_tpu.ops import flash_attention_pallas as fap

        monkeypatch.setattr(fap, "_TUNED_BLOCKS", {})
        line = json.dumps(
            {"tuned_blocks_table": [[[1024, 64, "bfloat16"], [512, 256]]]})
        fap.set_tuned_blocks(json.loads(line)["tuned_blocks_table"])
        assert fap.tuned_blocks(1024, 64, jnp.bfloat16) == (512, 256)
        fap.set_tuned_blocks({(2048, 128, jnp.float32): (256, 512)})
        assert fap.tuned_blocks(2048, 128, "float32") == (256, 512)

    def test_tuned_blocks_per_phase_lookup(self, monkeypatch):
        """Per-phase keys resolve per phase; legacy 3-tuple entries are
        fwd-only; a bad phase fails loudly at both ends."""
        from apex_tpu.ops import flash_attention_pallas as fap

        monkeypatch.setattr(fap, "_TUNED_BLOCKS", {})
        fap.set_tuned_blocks({
            (256, 64, "float32", "fwd"): (128, 128),
            (256, 64, "float32", "bwd"): (64, 64),
        })
        assert fap.tuned_blocks(256, 64, jnp.float32, phase="fwd") == (128, 128)
        assert fap.tuned_blocks(256, 64, jnp.float32, phase="bwd") == (64, 64)
        # legacy flat key: a pre-split sweep measured the forward path
        monkeypatch.setattr(fap, "_TUNED_BLOCKS", {})
        fap.set_tuned_blocks({(256, 64, "float32"): (128, 128)})
        assert fap.tuned_blocks(256, 64, jnp.float32, phase="fwd") == (128, 128)
        assert fap.tuned_blocks(256, 64, jnp.float32, phase="bwd") is None
        with pytest.raises(ValueError, match="phase"):
            fap.tuned_blocks(256, 64, jnp.float32, phase="backward")
        with pytest.raises(ValueError, match="phase"):
            fap.set_tuned_blocks({(256, 64, "float32", "backward"): (8, 8)})

    def test_bwd_consults_its_own_phase_entry(self, monkeypatch):
        """The backward kernels must key the tuned table on their OWN
        phase — a fast-forward block choice (fwd 128) must not leak into
        the backward (tuned to 64 here), and vice versa."""
        from apex_tpu.ops import flash_attention_pallas as fap

        monkeypatch.setattr(fap, "_TUNED_BLOCKS", {})
        fap.set_tuned_blocks({
            (256, 64, "float32", "fwd"): (128, 128),
            (256, 64, "float32", "bwd"): (64, 64),
        })
        resolved = []
        orig = fap._clamped_blocks

        def spy(sq, sk, d, dtype, bq, bk, phase):
            r = orig(sq, sk, d, dtype, bq, bk, phase)
            resolved.append((phase,) + r)
            return r

        monkeypatch.setattr(fap, "_clamped_blocks", spy)
        q, k, v = self._inputs()

        def loss(q):
            o = fap.flash_attention_pallas(q, k, v, causal=True,
                                           interpret=True)
            return jnp.sum(o.astype(jnp.float32))

        jax.grad(loss)(q)
        assert ("fwd", 128, 128) in resolved
        assert ("bwd", 64, 64) in resolved
        # the custom_vjp residual fwd runs too; no call may cross phases
        assert all(r in (("fwd", 128, 128), ("bwd", 64, 64))
                   for r in resolved)

    def test_clamped_blocks_respect_vmem_budget(self):
        """_pick_block must never hand Mosaic a block pair whose
        APX304-priced footprint exceeds the VMEM budget — the long-seq
        defaults (target 1024/512) clamp instead of overflowing."""
        from apex_tpu.ops import flash_attention_pallas as fap
        from apex_tpu.ops._pallas_tiling import VMEM_BUDGET, flash_vmem_bytes

        for phase, target in (("fwd", 1024), ("bwd", 512)):
            for S in (2048, 4096, 8192):
                for D in (64, 128):
                    bq, bk = fap._clamped_blocks(S, S, D, jnp.bfloat16,
                                                 target, target, phase)
                    assert S % bq == 0 and S % bk == 0
                    assert flash_vmem_bytes(bq, bk, D, phase) <= VMEM_BUDGET, \
                        (phase, S, D, bq, bk)
        # an explicitly over-budget request clamps too (8192² fwd at
        # D=128 prices ~29 MiB of blocks and scratch alone)
        bq, bk = fap._clamped_blocks(8192, 8192, 128, jnp.bfloat16,
                                     8192, 8192, "fwd")
        assert flash_vmem_bytes(bq, bk, 128, "fwd") <= VMEM_BUDGET
        assert (bq, bk) != (8192, 8192)
        # the score-sized temporaries are a RUN's since the kernels
        # walk a block in sub-tiles (a sub-tile's rows by RUN_COLUMNS
        # columns at most): 3 of them forward, 5 backward, beside the
        # blocks and the scratch
        from apex_tpu.ops._pallas_tiling import RUN_COLUMNS
        sub = 256
        assert RUN_COLUMNS == 1024
        assert flash_vmem_bytes(1024, 1024, 64, "fwd") == 4 * (
            4 * 1024 * 64 + 1024 + 2 * 1024 * 128 + 1024 * 64 + 3 * sub * 1024)
        assert flash_vmem_bytes(1024, 1024, 64, "bwd") == 4 * (
            8 * 1024 * 64 + 2 * 1024 + 5 * sub * 1024)
        assert flash_vmem_bytes(1024, 1024, 64, "bwd", sub=512) == 4 * (
            8 * 1024 * 64 + 2 * 1024 + 5 * 512 * 1024)
        assert flash_vmem_bytes(512, 512, 64, "fwd") == 4 * (
            4 * 512 * 64 + 512 + 2 * 512 * 128 + 512 * 64 + 3 * sub * 512)
        # so whole-sequence backward blocks are admissible at the train
        # cell's shape (the old (bq, bk) temporaries priced them at 18 MB)
        assert fap._clamped_blocks(1024, 1024, 64, jnp.bfloat16,
                                   1024, 1024, "bwd") == (1024, 1024)
        # a block no lane-tile multiple divides is one tile, priced whole
        assert flash_vmem_bytes(320, 320, 64, "fwd") == 4 * (
            4 * 320 * 64 + 320 + 2 * 320 * 128 + 320 * 64 + 3 * 320 * 320)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.slow
    def test_backward_matches_reference(self, causal):
        from apex_tpu.ops.flash_attention_pallas import flash_attention_pallas

        q, k, v = self._inputs(Sq=128, Sk=128)

        def loss_pallas(q, k, v):
            return jnp.sum(flash_attention_pallas(q, k, v, causal=causal, interpret=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)

    def test_ring_offsets_match_scan_path(self):
        """q_offset/k_offset causal masking agrees with the scan path."""
        q, k, v = self._inputs(Sq=128, Sk=256)
        from apex_tpu.ops.flash_attention_pallas import flash_attention_pallas

        out = flash_attention_pallas(q, k, v, causal=True, q_offset=256, k_offset=64,
                                     interpret=True)
        ref = flash_attention(q, k, v, causal=True, q_offset=256, k_offset=64,
                              impl="scan")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_fully_masked_rows_zero(self):
        """Rows with no visible keys (ring warmup blocks) produce zeros."""
        from apex_tpu.ops.flash_attention_pallas import flash_attention_pallas

        q, k, v = self._inputs(Sq=128, Sk=128)
        # every key is in the future of every query
        out = flash_attention_pallas(q, k, v, causal=True, q_offset=0, k_offset=1024,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)

    def test_bf16(self):
        from apex_tpu.ops.flash_attention_pallas import flash_attention_pallas

        q, k, v = self._inputs(dtype=jnp.bfloat16)
        out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2
        )

    @pytest.mark.slow
    def test_partially_masked_block_rows_zero(self):
        """Rows fully masked but sharing a q-block with visible rows must
        still be zero (and carry zero grads), independent of block size."""
        from apex_tpu.ops.flash_attention_pallas import flash_attention_pallas

        q, k, v = self._inputs(Sq=128, Sk=128)
        # keys start at global position 64: query rows 0..63 see nothing
        for blocks in ((128, 128), (64, 64)):
            out = flash_attention_pallas(q, k, v, causal=True, q_offset=0,
                                         k_offset=64, block_q=blocks[0],
                                         block_k=blocks[1], interpret=True)
            np.testing.assert_allclose(np.asarray(out[:, :, :64]), 0.0, atol=1e-6)
        # scan path too
        out_s = flash_attention(q, k, v, causal=True, k_offset=64, impl="scan")
        np.testing.assert_allclose(np.asarray(out_s[:, :, :64]), 0.0, atol=1e-6)

        def loss(qq):
            o = flash_attention_pallas(qq, k, v, causal=True, q_offset=0,
                                       k_offset=64, interpret=True)
            return jnp.sum(o ** 2)

        dq = jax.grad(loss)(q)
        np.testing.assert_allclose(np.asarray(dq[:, :, :64]), 0.0, atol=1e-6)

    @pytest.mark.parametrize("row", ["tuned_sub128", "no_row"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("heads", ["mha", "gqa"])
    @pytest.mark.parametrize("place", ["offsets_0", "ring_hop", "eva_window"])
    @pytest.mark.parametrize("hidden", ["no_bias", "key_bias", "dead_rows"])
    def test_runs_match_scan(self, monkeypatch, hidden, place, heads, dtype,
                             row):
        """Forward, dq, dk and dv against the ``lax.scan`` composite
        wherever the diagonal, a key bias, the head grouping, the dtype
        and the tuned table put the walk, each at a size whose strips
        hold a run of two sub-tiles or more in all three kernels: the
        run's ONE product and one softmax update against the sum a
        sub-tile at a time."""
        # no row: 768 is one forward block of 3 x 3 sub-tiles of 256 and
        # backward blocks of 384 in sub-tiles of 128
        Sq = 512 if row == "tuned_sub128" else 768
        pooled = 256 if place == "eva_window" else 0
        Sk, B = Sq + pooled, 2 if hidden == "dead_rows" else 1
        q_offset, k_offset = {"offsets_0": (0, 0), "ring_hop": (Sq, 0),
                              "eva_window": (0, -pooled)}[place]
        col = np.arange(Sk)
        mask, dead = None, None
        if hidden == "key_bias":   # the buffer's tail, or keys here and there
            keep = ((col < pooled // 2) | (col >= pooled)) & (col % 7 != 3)
            mask = jnp.asarray(keep[None, :])
        elif hidden == "dead_rows":   # a batch row whose keys are all padding
            mask, dead = padded_mask(2, Sk, [Sk - 5, 0]), np.s_[1]
        bf16 = dtype == "bfloat16"
        assert_walk_matches_scan(
            monkeypatch, Sq=Sq, Sk=Sk, q_offset=q_offset, k_offset=k_offset,
            sub=128 if row == "tuned_sub128" else None, kv_mask=mask, B=B,
            H=4 if heads == "gqa" else 2, Hkv=2, dead_rows=dead, seed=29,
            dtype=jnp.dtype(dtype), runs=True,
            tol=(3e-2, 6e-2) if bf16 else (2e-5, 1e-4))

    @pytest.mark.parametrize("case", sorted(WALK_CASES))
    def test_subtile_walk_matches_scan(self, monkeypatch, case):
        assert_walk_matches_scan(monkeypatch, **WALK_CASES[case])

    @pytest.mark.parametrize("case", ["chunk_above", "chunk_on",
                                      "chunk_off_edge", "grid_2x2_blocks"])
    def test_subtile_walk_keeps_lse(self, monkeypatch, case):
        """``lse`` keeps its shape and its values, NEG_INF for a row no
        key reaches (the ring merges chunks by it)."""
        from apex_tpu.ops import flash_attention_pallas as fap

        c = dict(WALK_CASES[case])
        c.pop("dead_rows", None)
        Sq, Sk, block = c["Sq"], c.get("Sk", c["Sq"]), c.get("block")
        q, k, v, _ = _walk_inputs(1, 2, 2, Sq, Sk, 64, 5)
        monkeypatch.setattr(fap, "_TUNED_BLOCKS", {})
        fap.set_tuned_blocks({(Sq, 64, "float32", "fwd"): (Sq, Sk, 128)})
        qo, ko = c.get("q_offset", 0), c.get("k_offset", 0)
        out, lse = fap.flash_fwd_pallas(
            q[0], k[0], v[0], 0.125, True, qo, ko, block_q=block,
            block_k=block, interpret=True)
        ref, ref_lse = flash_attention_with_lse(
            q, k, v, causal=True, softmax_scale=0.125, q_offset=qo,
            k_offset=ko)
        assert lse.shape == (2, Sq, 1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref[0]),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(lse[..., 0]),
                                   np.asarray(ref_lse[0]), rtol=1e-5)

    def test_subtile_falls_back_to_a_divisor(self, monkeypatch):
        """The sub-tile is brought down to a lane-tile multiple that
        divides both blocks; where there is none the block is one tile.
        A tuned row's third column is read whatever the key length."""
        from apex_tpu.ops import flash_attention_pallas as fap

        monkeypatch.setattr(fap, "_TUNED_BLOCKS", {})
        f32 = jnp.float32
        # (sub_q, sub_k, sub-tiles a run at most: RUN_COLUMNS of columns)
        assert fap._subtiles(1024, 64, f32, "fwd", 1024, 1024) == (256, 256, 4)
        assert fap._subtiles(384, 64, f32, "fwd", 384, 384) == (128, 128, 8)
        assert fap._subtiles(320, 64, f32, "fwd", 320, 320) == (320, 320, 1)
        assert fap._subtiles(2048, 128, f32, "fwd", 1024, 512) == (256, 256, 4)
        assert fap._subtiles(40, 64, f32, "fwd", 40, 40) == (40, 40, 1)
        fap.set_tuned_blocks({(2048, 128, "float32", "fwd"): (1024, 512, 512)})
        assert fap.tuned_blocks(2048, 128, f32) == (1024, 512)
        assert fap.tuned_subtile(2048, 128, f32) == 512
        assert fap.tuned_subtile(2048, 128, f32, phase="bwd") is None
        assert fap._subtiles(2048, 128, f32, "fwd", 1024, 512) == (512, 512, 2)
        assert fap._subtiles(2048, 128, f32, "fwd", 1024, 640) == (128, 128, 8)

    def test_live_subtiles_counts(self):
        """The static counter the kernels' code is built from: visited
        + skipped is the square, the masked ones lie on the diagonal,
        forward and dkv agree on a square call; the fourth figure, the
        bodies the kernel's CODE holds: a run of unmasked sub-tiles is
        one, a crossed sub-tile one, a static variant counted once
        however many grid blocks run it."""
        from apex_tpu.ops.flash_attention_pallas import live_subtiles

        for phase in ("fwd", "bwd", "dkv"):
            # one block, strips of 0 + 1, 1 + 1, 2 + 1, 3 + 1 sub-tiles:
            # 1 + 2 + 2 + 2 bodies; with runs of one, a body a sub-tile
            assert live_subtiles(phase, 512, 512, 0, 0, 512, 512, 128) \
                == (10, 4, 6, 7)
            assert live_subtiles(phase, 512, 512, 0, 0, 512, 512, 128,
                                 run=1) == (10, 4, 6, 10)
            # four blocks, three variants: on the diagonal (1 + 2, run
            # by two blocks), under it (a run a strip), above it (none)
            assert live_subtiles(phase, 512, 512, 0, 0, 256, 256, 128) \
                == (10, 4, 6, 5)
            # ring chunks: above, on, under the diagonal
            assert live_subtiles(phase, 256, 256, 0, 256, 256, 256, 128) \
                == (0, 0, 4, 0)
            assert live_subtiles(phase, 256, 256, 256, 256, 256, 256, 128) \
                == (3, 2, 1, 3)
            assert live_subtiles(phase, 256, 256, 512, 0, 256, 256, 128) \
                == (4, 0, 0, 2)
            # not causal: everything, nothing masked, a run a strip (two
            # query strips of four keys, or four key strips of two)
            assert live_subtiles(phase, 256, 512, 0, 0, 256, 512, 128,
                                 causal=False) \
                == (8, 0, 0, 4 if phase == "dkv" else 2)
            # the diagonal off a tile's edge crosses two tiles a strip
            assert live_subtiles(phase, 256, 512, 160, 64, 256, 512, 128) \
                == (5, 4, 3, 5)
        # the EVA window over 1,024 pooled rows as ops/eva.py asks for
        # it: two query blocks of 1,024 over ONE key block of six
        # sub-tiles of 512: the buffer's two and the triangle's ten
        # less its diagonal plain, in runs of two; a variant a block
        assert live_subtiles("fwd", 2048, 3072, 0, -1024, 1024, 3072, 512) \
            == (18, 4, 6, 12)
        assert live_subtiles("fwd", 2048, 3072, 0, -1024, 1024, 3072, 512,
                             run=1) == (18, 4, 6, 18)
        # a block that is one tile (sub None)
        assert live_subtiles("fwd", 320, 320, 0, 0, 320, 320, None) \
            == (1, 1, 0, 1)

    def test_impl_validation(self):
        q, k, v = self._inputs(Sq=128, Sk=128)
        with pytest.raises(ValueError, match="impl"):
            flash_attention(q, k, v, impl="pallaz")


class TestRingAttentionPallas:
    """Ring with per-chunk-pair Pallas kernels (interpret mode)."""

    pytestmark = pytest.mark.slow  # interpret-mode ring grads: ~10 s

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention_with_grads(self, causal, devices8):
        B, H, S, D = 1, 2, 512, 8  # S_local = 128: kernel-eligible
        q, k, v = qkv(7, B=B, H=H, S=S, D=D)
        mesh = Mesh(np.array(devices8[:4]), ("cp",))

        def fr(q, k, v):
            return jnp.sum(jnp.sin(mha_reference(q, k, v, causal=causal)))

        ref = mha_reference(q, k, v, causal=causal)
        gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)

        def f(q, k, v):
            return ring_attention(q, k, v, "cp", causal=causal,
                                  impl="pallas", interpret=True)

        out = jax.shard_map(
            f, mesh=mesh, in_specs=(P(None, None, "cp", None),) * 3,
            out_specs=P(None, None, "cp", None), check_vma=False,
        )(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)

        g = jax.shard_map(
            jax.grad(lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v))), argnums=(0, 1, 2)),
            mesh=mesh, in_specs=(P(None, None, "cp", None),) * 3,
            out_specs=(P(None, None, "cp", None),) * 3, check_vma=False,
        )(q, k, v)
        for a, r in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=2e-4, atol=2e-5)


class TestGroupedQueryAttention:
    """GQA: k/v with fewer heads than q.  The Pallas kernels read the
    group-shared kv blocks via index maps (no HBM repeat); the scan
    path repeats heads.  Oracle = dense attention with repeated kv."""

    def _inputs(self, B=2, H=4, Hkv=2, Sq=256, Sk=256, D=64, seed=0):
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(B, H, Sq, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, Hkv, Sk, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, Hkv, Sk, D).astype(np.float32))
        return q, k, v

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("hkv", [1, 2])  # MQA and 2-way groups
    def test_pallas_forward_matches_reference(self, causal, hkv):
        from apex_tpu.ops.flash_attention_pallas import flash_attention_pallas

        q, k, v = self._inputs(Hkv=hkv)
        out = flash_attention_pallas(q, k, v, causal=causal, interpret=True)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("hkv", [1, 2])  # MQA (group=heads) and group=2
    @pytest.mark.slow
    def test_pallas_backward_matches_reference(self, causal, hkv):
        """dk/dv must be the GROUP SUM over the kv head's q heads — the
        kernel accumulates it in VMEM across the extended inner grid."""
        from apex_tpu.ops.flash_attention_pallas import flash_attention_pallas

        q, k, v = self._inputs(Hkv=hkv, Sq=128, Sk=128)

        def loss_pallas(q, k, v):
            return jnp.sum(flash_attention_pallas(q, k, v, causal=causal, interpret=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)

    @pytest.mark.slow
    def test_pallas_backward_with_padding_mask(self):
        """The dkv pass's bias rows index the (B·kv_heads) grid
        (b // kv_heads); a regression to b // heads would read the
        wrong batch's mask.  B>1 with different per-batch masks makes
        that misread change the numbers."""
        from apex_tpu.ops.flash_attention_pallas import flash_attention_pallas

        q, k, v = self._inputs(B=3, H=4, Hkv=2, Sq=128, Sk=128)
        rng = np.random.RandomState(5)
        lengths = rng.randint(32, 129, size=3)
        kv_mask = jnp.asarray(np.arange(128)[None, :] < lengths[:, None])

        def loss_pallas(q, k, v):
            return jnp.sum(flash_attention_pallas(
                q, k, v, causal=False, kv_mask=kv_mask, interpret=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=False, kv_mask=kv_mask) ** 2)

        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)

    def test_scan_path_matches_reference(self):
        q, k, v = self._inputs(Sq=64, Sk=64, D=8)
        out = flash_attention(q, k, v, causal=True, impl="scan", block_k=16)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)
        # backward through the repeat sums the group
        gp = jax.grad(lambda *a: jnp.sum(flash_attention(*a, causal=True, impl="scan", block_k=16) ** 2),
                      argnums=(1, 2))(q, k, v)
        gr = jax.grad(lambda *a: jnp.sum(mha_reference(*a, causal=True) ** 2),
                      argnums=(1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)

    def test_pallas_gqa_with_padding_mask(self):
        from apex_tpu.ops.flash_attention_pallas import flash_attention_pallas

        q, k, v = self._inputs()
        rng = np.random.RandomState(3)
        lengths = rng.randint(128, 257, size=q.shape[0])
        kv_mask = jnp.asarray(np.arange(256)[None, :] < lengths[:, None])
        out = flash_attention_pallas(q, k, v, causal=False, kv_mask=kv_mask,
                                     interpret=True)
        ref = mha_reference(q, k, v, causal=False, kv_mask=kv_mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_indivisible_heads_rejected(self):
        from apex_tpu.ops.flash_attention_pallas import flash_attention_pallas

        q, k, v = self._inputs(H=4, Hkv=3)
        with pytest.raises(ValueError, match="not divisible"):
            flash_attention_pallas(q, k, v, interpret=True)
