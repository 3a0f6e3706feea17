"""Chunked fused LM-head+CE (ops/fused_ce.py) parity tests.

The fused op must be a drop-in for ``logsumexp - target`` on the same
fp32 head matmul: identical loss and identical gradients (dx AND the
tied-embedding dembed), dense and vocab-parallel, op-level and through
``gpt_loss``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.models.gpt import GPTConfig, gpt_loss, init_params, param_specs
from apex_tpu.ops.fused_ce import fused_lm_head_ce

S, B, H, V = 32, 3, 16, 48


def _dense_ce(x, embed, targets):
    logits = jnp.matmul(x.astype(jnp.float32), embed.T.astype(jnp.float32))
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return lse - tgt


def _data(dtype):
    k = jax.random.PRNGKey(0)
    kx, ke, kt = jax.random.split(k, 3)
    x = jax.random.normal(kx, (S, B, H), dtype)
    embed = jax.random.normal(ke, (V, H), dtype)
    targets = jax.random.randint(kt, (S, B), 0, V)
    return x, embed, targets


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_loss_matches_dense(chunk):
    x, embed, targets = _data(jnp.float32)
    ref = _dense_ce(x, embed, targets)
    got = fused_lm_head_ce(x, embed, targets, chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grads_match_dense(dtype):
    x, embed, targets = _data(dtype)

    def mean_ref(x, e):
        return jnp.mean(_dense_ce(x, e, targets))

    def mean_fused(x, e):
        return jnp.mean(fused_lm_head_ce(x, e, targets, 8))

    (dx_r, de_r) = jax.grad(mean_ref, argnums=(0, 1))(x, embed)
    (dx_f, de_f) = jax.grad(mean_fused, argnums=(0, 1))(x, embed)
    # fp32 everything inside both paths; only the final cast differs in
    # accumulation order across chunks
    tol = dict(rtol=1e-5, atol=1e-6) if dtype == jnp.float32 else \
        dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(dx_f, np.float32),
                               np.asarray(dx_r, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(de_f, np.float32),
                               np.asarray(de_r, np.float32), **tol)


def test_vocab_parallel_matches_dense(devices8):
    tp = 4
    x, embed, targets = _data(jnp.float32)

    def mean_ref(x, e):
        return jnp.mean(_dense_ce(x, e, targets))

    ref = mean_ref(x, embed)
    (dx_r, de_r) = jax.grad(mean_ref, argnums=(0, 1))(x, embed)

    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))

    def local(x, e_local, t):
        def mean_fused(x, e):
            return jnp.mean(fused_lm_head_ce(x, e, t, 8, "tp"))

        loss = mean_fused(x, e_local)
        dx, de = jax.grad(mean_fused, argnums=(0, 1))(x, e_local)
        # dx is a shard-local partial (the matmul-like contract); the
        # caller's copy-to-region would psum it — do so here
        return loss, jax.lax.psum(dx, "tp"), de

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(), P("tp", None), P()),
                      out_specs=(P(), P(), P("tp", None)),
                      check_vma=False)
    loss, dx, de = f(x, embed, targets)
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(de), np.asarray(de_r),
                               rtol=1e-5, atol=1e-6)


CFG = GPTConfig(
    vocab_size=64, hidden_size=32, num_layers=2, num_attention_heads=4,
    max_seq_len=16, compute_dtype=jnp.float32, checkpoint_layers=False,
    fused_ce=True, fused_ce_chunk=8,
)


def _batch():
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, CFG.vocab_size, size=(2, 16)))
    return tokens, jnp.roll(tokens, -1, axis=1)


def test_gpt_loss_fused_matches_dense():
    tokens, targets = _batch()
    params = init_params(CFG, jax.random.PRNGKey(0))
    dense_cfg = dataclasses.replace(CFG, fused_ce=False)
    ref, ref_g = jax.value_and_grad(gpt_loss)(params, tokens, targets, dense_cfg)
    got, got_g = jax.value_and_grad(gpt_loss)(params, tokens, targets, CFG)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        got_g, ref_g)


def test_gpt_loss_fused_falls_back_on_indivisible():
    tokens, targets = _batch()
    params = init_params(CFG, jax.random.PRNGKey(0))
    cfg = dataclasses.replace(CFG, fused_ce_chunk=7)  # 16 % 7 != 0
    dense_cfg = dataclasses.replace(CFG, fused_ce=False)
    ref = gpt_loss(params, tokens, targets, dense_cfg)
    got = gpt_loss(params, tokens, targets, cfg)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-7)


def test_bert_fused_matches_dense():
    from apex_tpu.models.bert import BertConfig, bert_mlm_loss
    from apex_tpu.models.bert import init_params as bert_init

    rng = np.random.RandomState(1)
    cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=2,
                     num_attention_heads=4, max_seq_len=16,
                     compute_dtype=jnp.float32, checkpoint_layers=False,
                     fused_ce=True, fused_ce_chunk=8)
    params = bert_init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(rng.randint(0, 64, size=(2, 16)))
    targets = jnp.asarray(rng.randint(0, 64, size=(2, 16)))
    mask = jnp.asarray(rng.randint(0, 2, size=(2, 16)))
    dense_cfg = dataclasses.replace(cfg, fused_ce=False)
    ref, ref_g = jax.value_and_grad(bert_mlm_loss)(
        params, tokens, targets, mask, dense_cfg)
    got, got_g = jax.value_and_grad(bert_mlm_loss)(
        params, tokens, targets, mask, cfg)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        got_g, ref_g)


def test_t5_fused_matches_dense():
    from apex_tpu.models.t5 import T5Config, t5_loss
    from apex_tpu.models.t5 import init_params as t5_init

    rng = np.random.RandomState(2)
    cfg = T5Config(vocab_size=64, hidden_size=32, num_encoder_layers=2,
                   num_decoder_layers=2, num_attention_heads=4,
                   max_src_len=16, max_tgt_len=16,
                   compute_dtype=jnp.float32, checkpoint_layers=False,
                   fused_ce=True, fused_ce_chunk=8)
    params = t5_init(cfg, jax.random.PRNGKey(0))
    src = jnp.asarray(rng.randint(0, 64, size=(2, 16)))
    dec = jnp.asarray(rng.randint(0, 64, size=(2, 16)))
    targets = jnp.asarray(rng.randint(0, 64, size=(2, 16)))
    dense_cfg = dataclasses.replace(cfg, fused_ce=False)
    ref, ref_g = jax.value_and_grad(t5_loss)(params, src, dec, targets, dense_cfg)
    got, got_g = jax.value_and_grad(t5_loss)(params, src, dec, targets, cfg)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        got_g, ref_g)


def test_pp_fused_matches_dense_oracle(devices8):
    """The pipeline post-stage head (models/gpt.py post_fn) must produce
    the same loss/params through the fused path as the dense oracle."""
    from apex_tpu.models.gpt import make_pp_train_step
    from apex_tpu.optimizers import FusedAdam

    cfg = dataclasses.replace(CFG, num_layers=4)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2),
                ("dp", "pp", "tp"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-2)
    state = opt.init(params)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(8, 16)))
    targets = jnp.roll(tokens, -1, axis=1)

    step = make_pp_train_step(cfg, opt, mesh, num_microbatches=2)
    new_params, _, loss = step(params, state, tokens, targets)

    dense_cfg = dataclasses.replace(cfg, fused_ce=False)
    ref_loss, ref_grads = jax.value_and_grad(gpt_loss)(
        params, tokens, targets, dense_cfg)
    ref_params, _ = opt.update(ref_grads, opt.init(params), params)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-4)
    for (ka, a), (kb, b) in zip(
        jax.tree_util.tree_leaves_with_path(new_params),
        jax.tree_util.tree_leaves_with_path(ref_params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-5,
            err_msg=jax.tree_util.keystr(ka))


def test_gpt_loss_fused_tp_matches_single_device(devices8):
    tokens, targets = _batch()
    params = init_params(CFG, jax.random.PRNGKey(0))
    dense_cfg = dataclasses.replace(CFG, fused_ce=False)
    ref, ref_g = jax.value_and_grad(gpt_loss)(params, tokens, targets, dense_cfg)

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    specs = param_specs(CFG, "tp")
    f = jax.shard_map(
        jax.value_and_grad(lambda p, t, y: gpt_loss(p, t, y, CFG, axis_name="tp")),
        mesh=mesh, in_specs=(specs, P(), P()), out_specs=(P(), specs),
        check_vma=False)
    loss, grads = f(params, tokens, targets)
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
        jax.device_get(grads), jax.device_get(ref_g))
