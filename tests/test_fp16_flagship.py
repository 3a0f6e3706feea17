"""fp16 dynamic loss scaling through the flagship parallel train steps.

The reference's flagship call stack is amp × DDP × Megatron: loss
scaling runs INSIDE distributed training, with found_inf agreed across
the model-parallel group (``apex/amp/handle.py:16``,
``apex/transformer/amp/grad_scaler.py:21-126``).  These tests prove the
TPU analog end to end: ``make_train_step``/``make_pp_train_step`` with a
``DynamicLossScaler`` must track a single-device scaled-fp16-style
oracle step for step — including an overflow step (scaled loss
saturates fp32 → every rank skips, scale backs off, the Adam step
counter holds) and subsequent growth.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from apex_tpu.amp import DynamicLossScaler
from apex_tpu.models.gpt import (
    GPTConfig,
    gpt_loss,
    init_params,
    make_pp_train_step,
    make_train_step,
)
from apex_tpu.optimizers import FusedAdam

pytestmark = pytest.mark.slow

STEPS = 6


def tiny_config(dtype=jnp.float32, **kw):
    return GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=4, num_attention_heads=4,
        max_seq_len=16, compute_dtype=dtype, checkpoint_layers=True, **kw
    )


def make_scaler():
    """init_scale 2**127 makes the FIRST scaled loss overflow fp32 on
    every path identically (the loss scalar itself saturates — immune to
    reduction-order noise); backoff 2**-4 lands the next step at a
    comfortably finite scale; growth_interval 3 exercises a growth
    (clamped to max_scale) inside a 6-step run."""
    return DynamicLossScaler(
        init_scale=2.0 ** 127, backoff_factor=2.0 ** -4,
        growth_factor=2.0, growth_interval=3, hysteresis=1,
    )


def data(batch, seq=16, seed=0):
    rng = np.random.RandomState(seed)
    tok = jnp.asarray(rng.randint(0, 64, size=(batch, seq)))
    return tok, jnp.roll(tok, -1, axis=1)


def oracle_trajectory(config, scaler, tokens, targets, nsteps=STEPS):
    """Single-device scaled train loop: the fp16 oracle of reference
    §3.2 (scale → backward → unscale+found_inf → predicated step →
    scale update), one jit program."""
    params = init_params(config, jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-2)
    state = opt.init(params)
    sstate = scaler.init()

    @jax.jit
    def step(params, state, sstate, tok, tgt):
        def f(p):
            return gpt_loss(p, tok, tgt, config) * sstate.loss_scale

        sloss, grads = jax.value_and_grad(f)(params)
        loss = sloss / sstate.loss_scale
        grads, finite = scaler.unscale(sstate, grads)
        params, state = opt.update(grads, state, params, grads_finite=finite)
        sstate = scaler.update(sstate, finite)
        return params, state, sstate, loss

    losses, scales = [], []
    for _ in range(nsteps):
        params, state, sstate, loss = step(params, state, sstate, tokens, targets)
        losses.append(float(loss))
        scales.append(float(sstate.loss_scale))
    return params, state, sstate, np.asarray(losses), np.asarray(scales)


def assert_trajectory_matches(params, state, sstate, losses, scales, oracle):
    o_params, o_state, o_sstate, o_losses, o_scales = oracle
    # scaler decisions must be IDENTICAL (they're discrete)
    np.testing.assert_array_equal(scales, o_scales)
    assert int(sstate.growth_tracker) == int(o_sstate.growth_tracker)
    assert int(sstate.hysteresis) == int(o_sstate.hysteresis)
    # the overflow step must not have advanced Adam's step counter
    assert int(state.step) == int(o_state.step)
    # losses: inf on the overflow step on BOTH, close elsewhere
    assert np.isinf(losses[0]) and np.isinf(o_losses[0])
    np.testing.assert_allclose(losses[1:], o_losses[1:], rtol=1e-4)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(o_params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-5)


def test_scaled_tp_dp_matches_oracle(devices8):
    """make_train_step(loss_scaler=...) at tp=2 × dp=4 vs the oracle."""
    config = tiny_config(sequence_parallel=True)
    scaler = make_scaler()
    mesh = Mesh(np.array(devices8).reshape(4, 2), ("dp", "tp"))
    params = init_params(config, jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-2)
    state = opt.init(params)
    sstate = scaler.init()
    step = make_train_step(config, opt, mesh, loss_scaler=scaler)
    tok, tgt = data(batch=8)

    losses, scales = [], []
    for _ in range(STEPS):
        params, state, sstate, loss = step(params, state, sstate, tok, tgt)
        losses.append(float(loss))
        scales.append(float(sstate.loss_scale))

    oracle = oracle_trajectory(tiny_config(), scaler, tok, tgt)
    assert_trajectory_matches(params, state, sstate,
                              np.asarray(losses), np.asarray(scales), oracle)
    # sanity: it actually trained after the overflow step
    assert losses[-1] < losses[1]


def test_scaled_tp_dp_fused_ce_matches_oracle(devices8):
    """The chunked fused LM-head+CE (ops/fused_ce.py) under dynamic loss
    scaling at tp=2 × dp=4: the custom_vjp must carry the scaled
    cotangent (incl. the saturating overflow step) identically to the
    dense head — discrete scaler decisions AND the post-recovery
    trajectory match the dense-head oracle."""
    config = tiny_config(sequence_parallel=True, fused_ce=True,
                         fused_ce_chunk=8)
    scaler = make_scaler()
    mesh = Mesh(np.array(devices8).reshape(4, 2), ("dp", "tp"))
    params = init_params(config, jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-2)
    state = opt.init(params)
    sstate = scaler.init()
    step = make_train_step(config, opt, mesh, loss_scaler=scaler)
    tok, tgt = data(batch=8)

    losses, scales = [], []
    for _ in range(STEPS):
        params, state, sstate, loss = step(params, state, sstate, tok, tgt)
        losses.append(float(loss))
        scales.append(float(sstate.loss_scale))

    oracle = oracle_trajectory(tiny_config(), scaler, tok, tgt)
    assert_trajectory_matches(params, state, sstate,
                              np.asarray(losses), np.asarray(scales), oracle)
    assert losses[-1] < losses[1]


def test_scaled_pp_tp_dp_matches_oracle(devices8):
    """make_pp_train_step(loss_scaler=...) at tp=2 × pp=2 × dp=2 vs the
    oracle — found_inf agreed across stages, skip in lockstep."""
    config = tiny_config()
    scaler = make_scaler()
    mesh = Mesh(np.array(devices8).reshape(2, 2, 2), ("dp", "pp", "tp"))
    params = init_params(config, jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-2)
    state = opt.init(params)
    sstate = scaler.init()
    step = make_pp_train_step(config, opt, mesh, num_microbatches=2,
                              loss_scaler=scaler)
    tok, tgt = data(batch=8)

    losses, scales = [], []
    for _ in range(STEPS):
        params, state, sstate, loss = step(params, state, sstate, tok, tgt)
        losses.append(float(loss))
        scales.append(float(sstate.loss_scale))

    oracle = oracle_trajectory(tiny_config(), scaler, tok, tgt)
    assert_trajectory_matches(params, state, sstate,
                              np.asarray(losses), np.asarray(scales), oracle)


def test_scaled_moe_trains_with_dp_vote(devices8):
    """MoE expert grads are dp-sharded, so make_train_step adds dp to
    the found_inf vote axes; the scaled MoE step must compile with that
    extra collective and train."""
    config = tiny_config(moe_num_experts=4, moe_top_k=2)
    scaler = DynamicLossScaler(init_scale=2.0 ** 16)
    mesh = Mesh(np.array(devices8).reshape(4, 2), ("dp", "tp"))
    params = init_params(config, jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-2)
    state = opt.init(params)
    sstate = scaler.init()
    step = make_train_step(config, opt, mesh, loss_scaler=scaler)
    tok, tgt = data(batch=8)

    losses = []
    for _ in range(5):
        params, state, sstate, loss = step(params, state, sstate, tok, tgt)
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses


def test_found_inf_vote_spans_given_axes(devices8):
    """One rank's overflow must veto the step on EVERY rank of every
    sync axis (the dp-sharded-expert-grads / ZeRO-local-grads case)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from apex_tpu.transformer.amp.grad_scaler import sync_found_inf

    mesh = Mesh(np.array(devices8).reshape(4, 2), ("dp", "tp"))
    # finite everywhere except dp rank 2 (all tp ranks of it)
    flags = jnp.asarray([True, True, False, True])

    def f(flag):
        return sync_found_inf(flag[0], ("dp", "tp")).astype(jnp.int32)

    out = shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P())(flags)
    assert int(out) == 0  # every rank agreed: not finite


def test_scaled_vpp_interleaved_matches_oracle(devices8):
    """The interleaved (vpp=2) schedule composes with the loss scaler:
    scaled steps at tp2×pp2×dp2 vpp2 match the single-device scaled
    oracle (scaled backward seed through the ring, unscale, finite vote
    over tp+pp, growth on schedule).  Overflow/backoff semantics are
    covered by the 1F1B test — forcing an overflow via a saturating
    scale is knife-edge-dependent on microbatch count (cotangents scale
    with 1/M), so this variant pins the finite path."""
    from apex_tpu.models.gpt import params_from_vpp_layout, params_to_vpp_layout

    config = tiny_config()
    scaler = DynamicLossScaler(init_scale=2.0 ** 10, growth_factor=2.0,
                               growth_interval=2, hysteresis=1)
    mesh = Mesh(np.array(devices8).reshape(2, 2, 2), ("dp", "pp", "tp"))
    params = init_params(config, jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-2)
    vparams = params_to_vpp_layout(params, pp=2, vpp=2)
    vstate = opt.init(vparams)
    sstate = scaler.init()
    step = make_pp_train_step(config, opt, mesh, num_microbatches=4,
                              virtual_pipeline_size=2, loss_scaler=scaler)
    tok, tgt = data(batch=8)

    losses, scales = [], []
    for _ in range(3):
        vparams, vstate, sstate, loss = step(vparams, vstate, sstate, tok, tgt)
        losses.append(float(loss))
        scales.append(float(sstate.loss_scale))

    o_scaler = DynamicLossScaler(init_scale=2.0 ** 10, growth_factor=2.0,
                                 growth_interval=2, hysteresis=1)
    o_params, o_state, o_sstate, o_losses, o_scales = oracle_trajectory(
        tiny_config(), o_scaler, tok, tgt, nsteps=3)
    np.testing.assert_array_equal(np.asarray(scales), o_scales)
    assert scales[-1] == 2.0 ** 11  # growth fired at the interval
    np.testing.assert_allclose(np.asarray(losses), o_losses, rtol=1e-4)
    new_params = params_from_vpp_layout(vparams, pp=2, vpp=2)
    for a, b in zip(jax.tree.leaves(new_params), jax.tree.leaves(o_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-5)


def test_fp16_compute_trains_through_pipeline(devices8):
    """True float16 compute through tp×pp×dp with a standard dynamic
    scaler: finite losses, decreasing trend, params stay finite."""
    config = tiny_config(dtype=jnp.float16)
    scaler = DynamicLossScaler(init_scale=2.0 ** 16)
    mesh = Mesh(np.array(devices8).reshape(2, 2, 2), ("dp", "pp", "tp"))
    params = init_params(config, jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-2)
    state = opt.init(params)
    sstate = scaler.init()
    step = make_pp_train_step(config, opt, mesh, num_microbatches=2,
                              loss_scaler=scaler)
    tok, tgt = data(batch=8)

    losses = []
    for _ in range(STEPS):
        params, state, sstate, loss = step(params, state, sstate, tok, tgt)
        losses.append(float(loss))
    finite_losses = [l for l in losses if np.isfinite(l)]
    assert len(finite_losses) >= 4, losses
    assert finite_losses[-1] < finite_losses[0], losses
    for leaf in jax.tree.leaves(params):
        assert np.all(np.isfinite(np.asarray(leaf, np.float32)))
