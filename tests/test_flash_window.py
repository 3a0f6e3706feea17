"""The sliding window of the flash kernels (``window=``): forward, dq
and dkv through the Pallas interpreter against a dense band mask and
against the scan twin; a window that holds the sequence is the causal
call; and the counter of sub-tiles under a window, from the plans the
kernels' code is built from."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import flash_attention_pallas as fap
from apex_tpu.ops.attention import flash_attention, mha_reference


def _inputs(S, H, Hkv, D=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    mk = lambda k, h: jax.random.normal(k, (1, h, S, D), jnp.float32)
    return mk(ks[0], H), mk(ks[1], Hkv), mk(ks[2], Hkv), mk(ks[3], H)


def _value_and_grads(attend, q, k, v, g):
    return jax.value_and_grad(
        lambda q, k, v: jnp.sum(attend(q, k, v) * g), (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("S,W,H,Hkv,bq,bk", [
    (512, 128, 4, 2, 256, 256),     # the band inside the blocks' sub-tiles
    (512, 200, 2, 1, 256, 128),     # a window that is no multiple of a tile
    (512, 100, 2, 2, 128, 256),     # both edges cross one sub-tile
    (1024, 256, 8, 1, 512, 512),    # GQA 8:1: dkv sums the group's heads
    (256, 64, 2, 1, 256, 256),      # one grid block
])
def test_fwd_dq_and_dkv_against_a_dense_band(S, W, H, Hkv, bq, bk):
    q, k, v, g = _inputs(S, H, Hkv)
    want = _value_and_grads(
        lambda q, k, v: mha_reference(q, k, v, window=W), q, k, v, g)
    for name, attend in (
            ("pallas", lambda q, k, v: fap.flash_attention_pallas(
                q, k, v, window=W, interpret=True, block_q=bq, block_k=bk)),
            ("scan", lambda q, k, v: flash_attention(
                q, k, v, window=W, impl="scan", block_k=64))):
        got = _value_and_grads(attend, q, k, v, g)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(a, b, atol=2e-3, rtol=1e-4,
                                       err_msg=name)


def test_a_window_that_holds_the_sequence_is_the_causal_call():
    q, k, v, g = _inputs(512, 2, 1)
    causal = _value_and_grads(
        lambda q, k, v: fap.flash_attention_pallas(
            q, k, v, interpret=True, block_q=256, block_k=256), q, k, v, g)
    for W in (512, 4096):
        wide = _value_and_grads(
            lambda q, k, v: fap.flash_attention_pallas(
                q, k, v, window=W, interpret=True, block_q=256,
                block_k=256), q, k, v, g)
        for a, b in zip(jax.tree.leaves(wide), jax.tree.leaves(causal)):
            np.testing.assert_allclose(a, b, atol=1e-5)
    for phase in ("fwd", "dq", "dkv"):
        assert fap.live_subtiles(phase, 512, 512, 0, 0, 256, 256, 128,
                                 window=512)[:3] == \
            fap.live_subtiles(phase, 512, 512, 0, 0, 256, 256, 128)[:3]


def test_a_window_needs_a_causal_call():
    q, k, v, _ = _inputs(256, 2, 2)
    for attend in (fap.flash_attention_pallas, flash_attention):
        with pytest.raises(ValueError, match="window"):
            attend(q, k, v, causal=False, window=64)


def _band_tiles(S, W, sub):
    """(visited, masked) sub-tiles of the band by brute force."""
    i = np.arange(S)[:, None] - np.arange(S)[None, :]
    seen = (i >= 0) & (i < W)
    n = S // sub
    tiles = seen.reshape(n, sub, n, sub).transpose(0, 2, 1, 3)
    some, whole = tiles.any((2, 3)), tiles.all((2, 3))
    return int(some.sum()), int((some & ~whole).sum())


@pytest.mark.parametrize("S,W,bq,bk,sub", [
    (1024, 256, 512, 512, 128), (1024, 384, 256, 512, 128),
    (2048, 512, 1024, 512, 256), (1024, 200, 512, 256, 128),
    (8192, 2048, 1024, 1024, 256), (8192, 2048, 512, 512, 256),
])
def test_live_subtiles_under_a_window(S, W, bq, bk, sub):
    """The kernels visit exactly the sub-tiles the band touches, mask
    exactly those an edge crosses, and the grid holds no block the band
    does not reach beyond the longest walk."""
    want = _band_tiles(S, W, sub)
    total = (S // sub) ** 2
    for phase in ("fwd", "dq", "dkv"):
        visited, masked, skipped, bodies = fap.live_subtiles(
            phase, S, S, 0, 0, bq, bk, sub, window=W)
        assert (visited, masked) == want, phase
        assert visited + skipped == total and bodies > 0
        band = fap._band(phase, W, 0, 0, bq, bk, S // bq, S // bk)
        walked = band.n_live * (S // (bk if phase == "dkv" else bq))
        assert walked <= (S // bq) * (S // bk)
        if S == 8192:           # the cell's shape: under half the grid
            assert 2 * walked < (S // bq) * (S // bk)
    # against the causal triangle at the cell's shape: 7/16 of its area
    if (S, W) == (8192, 2048):
        causal = fap.live_subtiles("fwd", S, S, 0, 0, bq, bk, sub)[0]
        assert 0.43 < want[0] / causal < 0.5


def test_no_window_is_the_code_there_was():
    """``window=None`` gives no index map an offset to compute: the
    first-live-block arithmetic (a floor division and a clip) is in a
    windowed call's jaxpr and not in a causal call's."""
    q, k, v, _ = _inputs(512, 2, 1)
    trace = lambda **kw: str(jax.make_jaxpr(
        lambda q, k, v: fap.flash_attention_pallas(
            q, k, v, interpret=True, block_q=256, block_k=256, **kw))(
                q, k, v))
    assert "floor_divide" not in trace() and "clip" not in trace()
    assert "floor_divide" in trace(window=128) and "clip" in trace(window=128)
