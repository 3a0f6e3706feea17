"""ZeRO optimizer tests — the dp-sharded parity band.

Mirrors apex/contrib/test/optimizers/test_dist_adam.py with a stricter
standard: the per-leaf fused optimizers are the NUMERICS ORACLE, and on
fp32 trees with exactly-representable grads the resident-sharded bucket
engine must match them **bit for bit** (elementwise expression trees are
shared; the dp reduce adds no rounding when every addend is exactly
representable).  LAMB (reduction-fed trust ratios) gets a tight
allclose, same convention as ``tests/test_fused_optimizers.py``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.analysis import lowered as lw
from apex_tpu.contrib.optimizers import DistributedFusedAdam, DistributedFusedLAMB
from apex_tpu.optimizers import FusedAdam, FusedLAMB
from apex_tpu.optimizers import bucketing

DP = 8


def make_tree(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "a": jnp.asarray(rng.randn(13, 5).astype(np.float32)),
        "b": {"w": jnp.asarray(rng.randn(31).astype(np.float32))},
    }


def make_mixed_tree(seed=0):
    """fp32 + bf16 leaves: two dtype buckets."""
    t = make_tree(seed)
    rng = np.random.RandomState(seed + 1)
    t["h"] = jnp.asarray(rng.randn(24, 8).astype(np.float32)).astype(
        jnp.bfloat16)
    return t


def exact_grads(rng, tree):
    """Grads whose dp sum and mean are EXACT in fp32/bf16: small
    integers × 2⁻³ (sums ≤ 64 stay integral ×2⁻³; /8 is a power of
    two) — the construction that makes end-to-end bit-exactness a fair
    assertion rather than a rounding lottery."""
    return jax.tree.map(
        lambda x: jnp.asarray(
            (rng.randint(-8, 9, size=x.shape) * 0.125).astype(np.float32)
        ).astype(x.dtype),
        tree)


def assert_bitwise(tree_a, tree_b, err=""):
    for (ka, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(tree_a),
        jax.tree_util.tree_leaves_with_path(tree_b),
    ):
        a, b = np.asarray(a), np.asarray(b)
        view = np.uint16 if a.dtype == jnp.bfloat16 else None
        av = a.view(view) if view else a
        bv = b.view(view) if view else b
        np.testing.assert_array_equal(
            av, bv, err_msg=f"{err}{jax.tree_util.keystr(ka)}")


def zero_step(dist, mesh, params, state, g, **kw):
    sspec = dist.state_partition_spec()
    return jax.shard_map(
        lambda p, s, gg: dist.update(gg, s, p, **kw),
        mesh=mesh, in_specs=(P(), sspec, P()), out_specs=(P(), sspec),
        check_vma=False,
    )(params, state, g)


# --------------------------------------------------------------- Adam parity
class TestDistributedFusedAdam:
    def test_matches_fused_adam_bit_exact(self, devices8):
        """fp32+bf16 tree, 4 steps: the sharded trajectory must equal
        the per-leaf oracle's BITWISE.  Oracle is
        ``FusedAdam(master_weights=True)`` — ZeRO's resident fp32
        master integrates half-precision params in fp32 exactly like
        the oracle's master copy (an oracle without masters would
        re-round to bf16 every step, a semantic ZeRO exists to avoid)."""
        params = make_mixed_tree()
        mesh = Mesh(np.array(devices8), ("dp",))
        dist = DistributedFusedAdam(lr=1e-2, weight_decay=0.01, axis_name="dp")
        state = dist.init(params, world_size=DP)

        ref = FusedAdam(lr=1e-2, weight_decay=0.01, master_weights=True)
        ref_state = ref.init(params)
        ref_params = params
        rng = np.random.RandomState(50)
        for _ in range(4):
            g = exact_grads(rng, params)
            params, state = zero_step(dist, mesh, params, state, g)
            ref_params, ref_state = ref.update(g, ref_state, ref_params)
        assert_bitwise(params, ref_params)

    def test_update_collective_structure(self, devices8):
        """The acceptance contract of the bucketed design, read off the
        lowering: a 2-dtype tree emits (at least) one reduce-scatter
        and one all-gather PER BUCKET — the bf16 bucket's in bf16
        element type (half the wire bytes) — no grad all-reduce, and no
        whole-tree fp32 concatenate anywhere in the step (the
        ``_flatten`` stub this engine replaced).  Asserted on the
        StableHLO lowering via ``analysis.lowered`` (the reusable
        second-tier checkers): the CPU backend's compile upcasts bf16
        collectives, a TPU-irrelevant detail."""
        params = make_mixed_tree()
        total_f32 = sum(int(np.prod(x.shape))
                        for x in jax.tree.leaves(params))
        mesh = Mesh(np.array(devices8), ("dp",))
        dist = DistributedFusedAdam(lr=1e-2, axis_name="dp")
        state = dist.init(params, world_size=DP)
        sspec = dist.state_partition_spec()
        g = jax.tree.map(jnp.ones_like, params)

        f = jax.jit(jax.shard_map(
            lambda p, s, gg: dist.update(gg, s, p),
            mesh=mesh, in_specs=(P(), sspec, P()), out_specs=(P(), sspec),
            check_vma=False,
        ))
        txt = f.lower(params, state, g).as_text()
        lw.count_collectives(txt, "reduce_scatter", minimum=2)
        lw.assert_collective_dtype(txt, "reduce_scatter", "bf16")
        lw.assert_collective_dtype(txt, "reduce_scatter", "f32")
        lw.count_collectives(txt, "all_gather", minimum=2)
        lw.assert_collective_dtype(txt, "all_gather", "bf16")
        lw.count_collectives(txt, "all_reduce", maximum=0)
        lw.assert_no_whole_tree_concat(txt, total_f32)

    def test_state_is_sharded_per_bucket(self, devices8):
        params = make_mixed_tree()
        dist = DistributedFusedAdam(lr=1e-2, axis_name="dp")
        state = dist.init(params, world_size=DP)
        plan = dist._plan
        assert len(plan.buckets) == 2  # fp32 + bf16
        for arr, b in zip(state.exp_avg, plan.buckets):
            assert arr.shape == (b.total,)
            assert b.total % DP == 0  # shards split evenly
        spec = dist.state_partition_spec()
        assert spec.exp_avg == tuple(P("dp") for _ in plan.buckets)
        assert spec.step == P()

    def test_bucket_cap_splits_collectives(self, devices8):
        """bucket_cap_mb actually splits: a tiny cap turns the fp32
        bucket into several, each with its own reduce-scatter — the
        overlap granularity knob doing its job.  (The cap clamps at one
        dtype tile — 1024 fp32 elements — so the leaves here exceed
        that.)"""
        rng = np.random.RandomState(2)
        params = {
            "w1": jnp.asarray(rng.randn(40, 40).astype(np.float32)),
            "w2": jnp.asarray(rng.randn(1300).astype(np.float32)),
            "w3": jnp.asarray(rng.randn(50, 30).astype(np.float32)),
        }
        capped = DistributedFusedAdam(
            lr=1e-2, axis_name="dp", bucket_cap_mb=4096 / 2 ** 20)
        state = capped.init(params, world_size=DP)
        n_capped = len(capped._plan.buckets)
        assert n_capped >= 2, "cap should split the fp32 bucket"
        # every leaf still lands exactly once, offsets intact
        seen = sorted(bl.leaf_id for b in capped._plan.buckets
                      for bl in b.leaves)
        assert seen == list(range(capped._plan.n_leaves))

        mesh = Mesh(np.array(devices8), ("dp",))
        sspec = capped.state_partition_spec()
        g = jax.tree.map(jnp.ones_like, params)
        txt = jax.jit(jax.shard_map(
            lambda p, s, gg: capped.update(gg, s, p),
            mesh=mesh, in_specs=(P(), sspec, P()), out_specs=(P(), sspec),
            check_vma=False,
        )).lower(params, state, g).as_text()
        lw.count_collectives(txt, "reduce_scatter",
                             minimum=n_capped, maximum=n_capped)

    def test_resident_shard_state_is_donated(self, devices8):
        """The resident claim at the lowering level: every per-bucket
        m/v/master shard input of a ``donate_argnums`` step is aliased
        to an output in the compiled module's ``input_output_alias``
        table — the ZeRO state updates in place.  (Under shard_map jax
        marks the inputs ``jax.buffer_donor`` and the ALIASING shows up
        at compile time, unlike a plain jit's ``tf.aliasing_output``.)"""
        params = make_tree()
        mesh = Mesh(np.array(devices8), ("dp",))
        dist = DistributedFusedAdam(lr=1e-2, axis_name="dp")
        state = dist.init(params, world_size=DP)
        sspec = dist.state_partition_spec()
        g = jax.tree.map(jnp.ones_like, params)
        n_buckets = len(dist._plan.buckets)

        sharded = jax.shard_map(
            lambda p, s, gg: dist.update(gg, s, p),
            mesh=mesh, in_specs=(P(), sspec, P()), out_specs=(P(), sspec),
            check_vma=False)
        step = jax.jit(lambda s, p: sharded(p, s, g)[::-1],
                       donate_argnums=(0,))
        low = step.lower(state, params)
        # step counter + m/v/master per bucket all declared donatable
        # AND actually aliased in the compiled input_output_alias table
        assert len(jax.tree_util.tree_leaves(state)) == 1 + 3 * n_buckets
        lw.assert_donation_covers(low, state)

    @pytest.mark.slow
    def test_overflow_skip(self, devices8):
        params = make_tree()
        mesh = Mesh(np.array(devices8), ("dp",))
        dist = DistributedFusedAdam(lr=1e-2, axis_name="dp")
        state = dist.init(params, world_size=DP)
        g = jax.tree.map(lambda x: jnp.full(x.shape, jnp.inf), params)
        new_params, new_state = zero_step(
            dist, mesh, params, state, g, grads_finite=jnp.bool_(False))
        assert_bitwise(new_params, params)
        assert int(new_state.step) == 0

    @pytest.mark.slow
    def test_update_scaled_folds_unscale_vote_clip(self, devices8):
        """``update_scaled`` on the sharded read must match the oracle's
        fused amp tail: same unscale, same torch-semantics global clip
        (Σx² agreed across the dp shards), same vote, and an inf grad
        skips the step on every rank."""
        params = make_tree()
        mesh = Mesh(np.array(devices8), ("dp",))
        dist = DistributedFusedAdam(lr=1e-2, weight_decay=0.01, axis_name="dp")
        state = dist.init(params, world_size=DP)
        sspec = dist.state_partition_spec()
        ref = FusedAdam(lr=1e-2, weight_decay=0.01, master_weights=True)
        ref_state = ref.init(params)

        rng = np.random.RandomState(3)
        g = jax.tree.map(
            lambda x: jnp.asarray(rng.randn(*x.shape).astype(np.float32)) * 4.0,
            params)
        scale = jnp.float32(4.0)

        def local(p, s, gg):
            return dist.update_scaled(gg, s, p, scale=scale, clip_norm=1.0)

        p2, s2, fin = jax.shard_map(
            local, mesh=mesh, in_specs=(P(), sspec, P()),
            out_specs=(P(), sspec, P()), check_vma=False,
        )(params, state, g)
        rp, rs_, rfin = ref.update_scaled(g, ref_state, params, scale=scale,
                                          clip_norm=1.0)
        assert bool(fin) and bool(rfin)
        assert int(s2.step) == 1
        for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(rp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)

        bad = jax.tree.map(
            lambda x: jnp.full(x.shape, jnp.inf, jnp.float32), params)
        p3, s3, fin3 = jax.shard_map(
            local, mesh=mesh, in_specs=(P(), sspec, P()),
            out_specs=(P(), sspec, P()), check_vma=False,
        )(params, state, bad)
        assert not bool(fin3)
        assert int(s3.step) == 0
        assert_bitwise(p3, params)

    def test_overlap_param_sync_matches(self, devices8):
        """``overlap_param_sync=True`` changes the gather/commit ORDER
        (pre-vote gather, per-leaf predicated select), never the
        values."""
        params = make_tree()
        mesh = Mesh(np.array(devices8), ("dp",))
        rng = np.random.RandomState(9)
        g = jax.tree.map(
            lambda x: jnp.asarray(rng.randn(*x.shape).astype(np.float32)),
            params)

        def run(overlap):
            dist = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                        axis_name="dp",
                                        overlap_param_sync=overlap)
            state = dist.init(params, world_size=DP)
            sspec = dist.state_partition_spec()
            return jax.shard_map(
                lambda p, s, gg: dist.update_scaled(gg, s, p),
                mesh=mesh, in_specs=(P(), sspec, P()),
                out_specs=(P(), sspec, P()), check_vma=False,
            )(params, state, g)

        p_a, s_a, _ = run(False)
        p_b, s_b, _ = run(True)
        assert_bitwise(p_a, p_b)
        assert_bitwise(s_a.master_shard, s_b.master_shard)


# ------------------------------------------------------- sync dtype knobs
class TestSyncDtypeValidation:
    """The reference's grad_sync_dtype/param_sync_dtype were silently
    accepted-and-dropped by the old stub; now they are wired, the
    still-unsupported combinations must raise, not no-op."""

    def test_quantized_grad_sync_accepted_wide_ints_rejected(self):
        """int8 and both fp8 formats are now legal grad_sync_dtype
        values (the quantized wire); every OTHER integer keeps raising
        at construction."""
        for ok in (jnp.int8, jnp.float8_e4m3fn, jnp.float8_e5m2,
                   "int8", "float8_e5m2"):
            opt = DistributedFusedAdam(lr=1e-2, grad_sync_dtype=ok)
            assert opt._quantized
        for bad in (jnp.int32, jnp.int16, jnp.uint8, int):
            with pytest.raises(ValueError, match="grad_sync_dtype"):
                DistributedFusedAdam(lr=1e-2, grad_sync_dtype=bad)

    def test_quantized_param_sync_rejected(self):
        """param sync has no error-feedback channel — a gather is not a
        sum — so the quantized dtypes stay grad-only."""
        for bad in (jnp.int8, jnp.float8_e4m3fn):
            with pytest.raises(ValueError,
                               match="param_sync_dtype.*error-feedback"):
                DistributedFusedAdam(lr=1e-2, param_sync_dtype=bad)

    def test_remainder_mode_param_sync_must_be_bf16(self):
        with pytest.raises(ValueError, match="bfloat16"):
            DistributedFusedAdam(lr=1e-2, store_param_remainders=True,
                                 param_sync_dtype=jnp.float32)
        # None and bf16 are fine
        DistributedFusedAdam(lr=1e-2, store_param_remainders=True)
        DistributedFusedAdam(lr=1e-2, store_param_remainders=True,
                             param_sync_dtype=jnp.bfloat16)

    def test_lamb_validates_too(self):
        with pytest.raises(ValueError, match="grad_sync_dtype"):
            DistributedFusedLAMB(lr=1e-2, grad_sync_dtype=jnp.int32)
        assert DistributedFusedLAMB(lr=1e-2,
                                    grad_sync_dtype=jnp.int8)._quantized

    def test_grad_sync_dtype_override_changes_wire_type(self, devices8):
        """grad_sync_dtype=float32 forces the bf16 bucket's
        reduce-scatter up to f32 — the knob is live, not recorded."""
        params = make_mixed_tree()
        mesh = Mesh(np.array(devices8), ("dp",))
        dist = DistributedFusedAdam(lr=1e-2, axis_name="dp",
                                    grad_sync_dtype=jnp.float32)
        state = dist.init(params, world_size=DP)
        sspec = dist.state_partition_spec()
        g = jax.tree.map(jnp.ones_like, params)
        txt = jax.jit(jax.shard_map(
            lambda p, s, gg: dist.update(gg, s, p),
            mesh=mesh, in_specs=(P(), sspec, P()), out_specs=(P(), sspec),
            check_vma=False,
        )).lower(params, state, g).as_text()
        lw.assert_collective_dtype(txt, "reduce_scatter", "f32",
                                   mode="all")

    def test_bucket_cap_must_be_positive(self):
        with pytest.raises(ValueError, match="bucket_cap_mb"):
            DistributedFusedAdam(lr=1e-2, bucket_cap_mb=0)

    @pytest.mark.slow
    def test_fp16_grad_sync_predivides(self, devices8):
        """fp16 sync takes the predivide branch (overflow control);
        the trajectory still tracks the oracle to fp16 grad rounding."""
        params = make_tree()
        mesh = Mesh(np.array(devices8), ("dp",))
        dist = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                    axis_name="dp",
                                    grad_sync_dtype=jnp.float16)
        state = dist.init(params, world_size=DP)
        ref = FusedAdam(lr=1e-2, weight_decay=0.01, master_weights=True)
        ref_state = ref.init(params)
        ref_params = params
        rng = np.random.RandomState(31)
        for _ in range(2):
            g = exact_grads(rng, params)  # fp16-exact too (ints * 2^-3)
            params, state = zero_step(dist, mesh, params, state, g)
            ref_params, ref_state = ref.update(g, ref_state, ref_params)
        assert_bitwise(params, ref_params)


# ------------------------------------------------------------ state dicts
class TestShardedStateDict:
    """Per-rank save + cross-world reshard (reference
    distributed_fused_adam.py:2527,2959), on the bucket layout."""

    def _grads(self, params, rng):
        return jax.tree.map(
            lambda x: jnp.asarray(rng.randn(*x.shape).astype(np.float32)),
            params)

    @pytest.mark.slow
    @pytest.mark.parametrize("via_disk", [False, True], ids=["memory", "disk"])
    def test_save_dp4_load_dp2_resumes_identically(self, devices8, tmp_path,
                                                   via_disk):
        """Per-rank save at dp=4, resume at dp=2, trajectory parity vs
        the uninterrupted run.  ``via_disk`` composes ZeRO with io: the
        shard dicts round-trip through per-rank files bit-exactly."""
        params0 = make_tree(3)
        rng = np.random.RandomState(7)

        mesh4 = Mesh(np.array(devices8[:4]), ("dp",))
        opt4 = DistributedFusedAdam(lr=1e-2, weight_decay=0.01, axis_name="dp")
        state = opt4.init(params0, world_size=4)
        params = params0
        for _ in range(3):
            params, state = zero_step(opt4, mesh4, params, state,
                                      self._grads(params, rng))
        shards = [opt4.sharded_state_dict(state, r, 4) for r in range(4)]
        assert shards[0]["format"] == DistributedFusedAdam.SHARD_FORMAT

        if via_disk:
            from apex_tpu import io

            zdir = tmp_path / "zero"
            for r, sd in enumerate(shards):
                io.save_sharded_checkpoint(zdir, sd, r, 4)
            with io.AsyncCheckpointer() as ck:
                ck.save(tmp_path / "params.ckpt", params)
            loaded = io.load_sharded_checkpoint(zdir)
            state2 = DistributedFusedAdam.load_sharded_state_dicts(
                loaded, world_size=2)
            state2_mem = DistributedFusedAdam.load_sharded_state_dicts(
                shards, world_size=2)
            for a, b in zip(jax.tree.leaves(state2), jax.tree.leaves(state2_mem)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            params_r = jax.tree.map(jnp.asarray,
                                    io.load_checkpoint(tmp_path / "params.ckpt"))
        else:
            state2 = DistributedFusedAdam.load_sharded_state_dicts(
                shards, world_size=2)
            params_r = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)), params)
        assert int(state2.step) == 3

        mesh2 = Mesh(np.array(devices8[:2]), ("dp",))
        opt2 = DistributedFusedAdam(lr=1e-2, weight_decay=0.01, axis_name="dp")
        opt2.init(params0, world_size=2)  # rebuild the dp=2 plan
        for _ in range(2):
            params_r, state2 = zero_step(opt2, mesh2, params_r, state2,
                                         self._grads(params_r, rng))

        rng_o = np.random.RandomState(7)
        opt_o = DistributedFusedAdam(lr=1e-2, weight_decay=0.01, axis_name="dp")
        state_o = opt_o.init(params0, world_size=4)
        params_o = params0
        for _ in range(5):
            params_o, state_o = zero_step(opt_o, mesh4, params_o, state_o,
                                          self._grads(params_o, rng_o))

        for a, r in zip(jax.tree.leaves(params_r), jax.tree.leaves(params_o)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-6, atol=1e-7)

    def test_incomplete_shard_set_rejected(self, devices8):
        params = make_tree(4)
        opt = DistributedFusedAdam(lr=1e-2, axis_name="dp")
        state = opt.init(params, world_size=4)
        shards = [opt.sharded_state_dict(state, r, 4) for r in range(4)]
        with pytest.raises(ValueError, match="incomplete"):
            DistributedFusedAdam.load_sharded_state_dicts(shards[:3],
                                                          world_size=2)
        with pytest.raises(ValueError, match="format"):
            DistributedFusedAdam.load_sharded_state_dicts(
                [{**shards[0], "format": "bogus"}], world_size=2)

    def test_sharded_state_dict_requires_init(self):
        opt = DistributedFusedAdam(lr=1e-2, axis_name="dp")
        from apex_tpu.contrib.optimizers.distributed_fused_adam import (
            DistributedFusedAdamState,
        )

        stub = DistributedFusedAdamState(
            step=jnp.int32(0), exp_avg=(jnp.zeros(8),),
            exp_avg_sq=(jnp.zeros(8),), master_shard=(jnp.zeros(8),))
        with pytest.raises(ValueError, match="init"):
            opt.sharded_state_dict(stub, 0, 2)

    def test_indivisible_model_shard_rejected(self):
        """A param whose sharded DIMENSION isn't divisible by its mesh
        axes must be rejected — floor division would silently misalign
        the flat ZeRO layout."""
        from apex_tpu.contrib.optimizers.distributed_fused_adam import (
            local_total_and_axes,
        )

        params = {"w": jnp.zeros((13, 5))}
        with pytest.raises(ValueError, match="not divisible"):
            local_total_and_axes(params, {"w": P("tp", None)},
                                 {"tp": 2}, zero_axis="dp")
        with pytest.raises(ValueError, match="not divisible"):
            local_total_and_axes(params, {"w": P("tp", None)},
                                 {"tp": 5}, zero_axis="dp")
        total, axes, repl = local_total_and_axes(
            params, {"w": P(None, "tp")}, {"tp": 5}, zero_axis="dp")
        assert total == 13 and axes == ("tp",) and repl == [1]

    def test_master_kind_mismatch_refused(self):
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), make_tree())
        opt_rem = DistributedFusedAdam(lr=1e-2, store_param_remainders=True)
        state = opt_rem.init(params, world_size=2)
        sd = opt_rem.state_dict(state)
        assert sd["master_kind"] == "remainder_u16"
        opt_f32 = DistributedFusedAdam(lr=1e-2)
        opt_f32.init(params, world_size=2)
        with pytest.raises(ValueError, match="master_kind"):
            opt_f32.load_state_dict(sd)
        opt_rem.load_state_dict(sd)  # matching kind loads
        # a pre-bucket (v1 flat) dict has no format field: refused with
        # the format message, not a misleading bucket-layout crash
        v1 = {"step": 0, "exp_avg": np.zeros(8, np.float32),
              "exp_avg_sq": np.zeros(8, np.float32),
              "master_shard": np.zeros(8, np.float32)}
        with pytest.raises(ValueError, match="format"):
            opt_f32.load_state_dict(v1)

    def test_zero_composed_with_tp_matches_fused_adam(self, devices8):
        """dp=4 × tp=2: params sharded over tp, ZeRO state over
        (tp, dp), BIT-exact vs the per-leaf oracle on exact grads."""
        rng = np.random.RandomState(11)
        params = {
            "w": jnp.asarray(rng.randn(8, 6).astype(np.float32)),
            "b": jnp.asarray(rng.randn(12).astype(np.float32)),
        }
        pspecs = {"w": P("tp", None), "b": P(None)}
        mesh = Mesh(np.array(devices8).reshape(4, 2), ("dp", "tp"))

        dist = DistributedFusedAdam(lr=1e-2, weight_decay=0.01, axis_name="dp")
        state = dist.init(params, world_size=4, param_specs=pspecs,
                          axis_sizes={"tp": 2})
        sspec = dist.state_partition_spec()
        assert sspec.exp_avg[0] == P(("tp", "dp"))

        ref = FusedAdam(lr=1e-2, weight_decay=0.01, master_weights=True)
        ref_state = ref.init(params)
        ref_params = params

        for _ in range(3):
            g = exact_grads(rng, params)
            params, state = jax.shard_map(
                lambda p, s, gg: dist.update(gg, s, p),
                mesh=mesh, in_specs=(pspecs, sspec, pspecs),
                out_specs=(pspecs, sspec), check_vma=False,
            )(params, state, g)
            ref_params, ref_state = ref.update(g, ref_state, ref_params)
        assert_bitwise(params, ref_params)


# ------------------------------------------------------------ ZeRO resume
class TestZeroAutoResume:
    """The --auto-resume protocol at pod scale: per-rank shard dicts in
    step_* directories, discovered by ``io.latest_distributed_step``
    with world_size > 1 — and the precision-mismatch failure mode."""

    def _train(self, opt, mesh, params, state, rng, steps):
        for _ in range(steps):
            g = jax.tree.map(
                lambda x: jnp.asarray(rng.randn(*x.shape).astype(np.float32)),
                params)
            params, state = zero_step(opt, mesh, params, state, g)
        return params, state

    @pytest.mark.slow
    def test_step_dir_roundtrip_world2(self, devices8, tmp_path):
        from apex_tpu import io

        params0 = make_tree(5)
        mesh = Mesh(np.array(devices8[:2]), ("dp",))
        opt = DistributedFusedAdam(lr=1e-2, weight_decay=0.01, axis_name="dp")
        state = opt.init(params0, world_size=2)
        rng = np.random.RandomState(13)
        params, state = self._train(opt, mesh, params0, state, rng, 2)

        # each "process" saves its rank's shard dict into the step dir
        step_dir = tmp_path / f"step_{2:08d}"
        for r in range(2):
            io.save_sharded_checkpoint(
                step_dir,
                {"params": jax.tree.map(np.asarray, params),
                 "opt": opt.sharded_state_dict(state, r, 2)},
                r, 2)
        # an INCOMPLETE newer dir (kill mid-save) must be skipped
        newer = tmp_path / f"step_{3:08d}"
        io.save_sharded_checkpoint(newer, {"torn": np.zeros(3)}, 0, 2)
        (newer / "shard_00000-of-00002.ckpt").rename(newer / "gone.tmp")

        assert io.latest_distributed_step(tmp_path) == 2
        loaded = io.load_sharded_checkpoint(step_dir)
        state_r = DistributedFusedAdam.load_sharded_state_dicts(
            [d["opt"] for d in loaded], world_size=2)
        params_r = jax.tree.map(jnp.asarray, loaded[0]["params"])
        assert int(state_r.step) == 2

        # resumed continuation must equal the uninterrupted run bitwise
        p_cont, s_cont = self._train(opt, mesh, params, state,
                                     np.random.RandomState(17), 1)
        p_res, s_res = self._train(opt, mesh, params_r, state_r,
                                   np.random.RandomState(17), 1)
        assert_bitwise(p_cont, p_res)
        for a, b in zip(jax.tree.leaves(s_cont), jax.tree.leaves(s_res)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_remainder_ckpt_into_fp32_mode_fails_loudly(self, devices8):
        """A bf16 ``store_param_remainders`` state restored into an
        fp32-master optimizer must raise the precision-mismatch message
        at trace time — never a shape/NoneType crash mid-math."""
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), make_tree(6))
        mesh = Mesh(np.array(devices8[:2]), ("dp",))
        opt_rem = DistributedFusedAdam(lr=1e-2, store_param_remainders=True)
        state = opt_rem.init(params, world_size=2)

        # the raw-pytree restore path (pretrain_gpt --auto-resume saves
        # the state tree itself): the wrong-mode optimizer sees uint16
        # shards where it expects fp32 masters
        opt_f32 = DistributedFusedAdam(lr=1e-2)
        opt_f32.init(params, world_size=2)
        g = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
        with pytest.raises(ValueError, match="store_param_remainders"):
            zero_step(opt_f32, mesh, params, state, g)
        # and the reshard path refuses with the master_kind message
        shards = [opt_rem.sharded_state_dict(state, r, 2) for r in range(2)]
        with pytest.raises(ValueError, match="master_kind"):
            DistributedFusedAdam.load_sharded_state_dicts(
                shards, world_size=2, store_param_remainders=False)


# ------------------------------------------------------------------- LAMB
class TestDistributedFusedLAMB:
    @pytest.mark.slow
    def test_matches_fused_lamb(self, devices8):
        """Trust ratios are reduction-fed, so LAMB gets the tight
        allclose band (``tests/test_fused_optimizers.py``'s), not bitwise."""
        params = make_tree()
        mesh = Mesh(np.array(devices8), ("dp",))
        dist = DistributedFusedLAMB(lr=1e-2, weight_decay=0.01,
                                    max_grad_norm=1.0, axis_name="dp")
        state = dist.init(params, world_size=DP)
        ref = FusedLAMB(lr=1e-2, weight_decay=0.01, max_grad_norm=1.0)
        ref_state = ref.init(params)
        ref_params = params
        rng = np.random.RandomState(23)
        for _ in range(4):
            g = jax.tree.map(
                lambda x: jnp.asarray(rng.randn(*x.shape).astype(np.float32)),
                params)
            params, state = zero_step(dist, mesh, params, state, g)
            ref_params, ref_state = ref.update(g, ref_state, ref_params)
        for a, r in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-4, atol=1e-5)

    @pytest.mark.slow
    @pytest.mark.parametrize("dp_varying_grads", [False, True])
    def test_zero_lamb_composed_with_tp_matches_fused_lamb(
            self, devices8, dp_varying_grads):
        """dp=4 × tp=2: trust ratios and the clip norm must use GLOBAL
        per-tensor norms — psum over tp WITHOUT double-counting
        tp-replicated leaves, and over dp on the AVERAGED grad."""
        rng = np.random.RandomState(21)
        params = {
            "w": jnp.asarray(rng.randn(8, 6).astype(np.float32)),
            "b": jnp.asarray(rng.randn(12).astype(np.float32)),
        }
        pspecs = {"w": P("tp", None), "b": P(None)}
        mesh = Mesh(np.array(devices8).reshape(4, 2), ("dp", "tp"))

        dist = DistributedFusedLAMB(lr=1e-2, weight_decay=0.01,
                                    axis_name="dp", max_grad_norm=1.0)
        state = dist.init(params, world_size=4, param_specs=pspecs,
                          axis_sizes={"tp": 2})
        sspec = dist.state_partition_spec()
        assert sspec.exp_avg[0] == P(("tp", "dp"))

        ref = FusedLAMB(lr=1e-2, weight_decay=0.01, max_grad_norm=1.0)
        ref_state = ref.init(params)
        ref_params = params

        gspecs = jax.tree.map(lambda s: P("dp", *tuple(s)), pspecs)
        step = jax.shard_map(
            lambda p, s, gg: dist.update(
                jax.tree.map(lambda x: x[0], gg), s, p),
            mesh=mesh, in_specs=(pspecs, sspec, gspecs),
            out_specs=(pspecs, sspec), check_vma=False,
        )

        for _ in range(3):
            g_stack = jax.tree.map(
                lambda x: jnp.asarray(
                    rng.randn(4, *x.shape).astype(np.float32)
                    if dp_varying_grads
                    else np.broadcast_to(
                        rng.randn(*x.shape).astype(np.float32), (4, *x.shape)
                    ).copy()
                ),
                params,
            )
            params, state = step(params, state, g_stack)
            g_mean = jax.tree.map(lambda x: jnp.mean(x, axis=0), g_stack)
            ref_params, ref_state = ref.update(g_mean, ref_state, ref_params)

        for a, r in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-5, atol=1e-6)


# --------------------------------------------------- store_param_remainders
class TestStoreParamRemainders:
    """fp32 master = bf16 param bits + stored 16-bit remainder
    (reference distributed_fused_adam.py store_param_remainders)."""

    def test_split_combine_bitwise_roundtrip(self):
        from apex_tpu.contrib.optimizers.distributed_fused_adam import (
            _master_from_remainder,
            _split_master,
        )

        rng = np.random.RandomState(3)
        master = jnp.asarray(
            (rng.randn(257) * 10 ** rng.uniform(-3, 3, 257)).astype(np.float32))
        p_bf16, rem = _split_master(master)
        back = _master_from_remainder(p_bf16.astype(jnp.float32), rem)
        np.testing.assert_array_equal(
            np.asarray(master).view(np.uint32),
            np.asarray(back).view(np.uint32))

    def test_requires_bf16_params(self, devices8):
        opt = DistributedFusedAdam(lr=1e-2, store_param_remainders=True)
        with pytest.raises(ValueError, match="bf16"):
            opt.init(make_tree(), world_size=DP)

    @pytest.mark.slow
    def test_master_trajectory_matches_fp32_mode(self, devices8):
        """The reconstructed master must track the fp32-master mode's
        master bitwise: precision is identical, only storage differs
        (params differ by the documented <=1-ulp trunc-vs-RNE)."""
        from apex_tpu.contrib.optimizers.distributed_fused_adam import (
            _master_from_remainder,
        )

        params0 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), make_tree(7))
        mesh = Mesh(np.array(devices8), ("dp",))
        rng = np.random.RandomState(11)
        grads = [
            jax.tree.map(lambda x: jnp.asarray(
                rng.randn(*x.shape).astype(np.float32)), params0)
            for _ in range(4)
        ]

        def run(store_rem):
            opt = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                       store_param_remainders=store_rem)
            state = opt.init(params0, world_size=DP)
            pp = params0
            for g in grads:
                pp, state = zero_step(opt, mesh, pp, state, g)
            return opt, pp, state

        opt_r, p_r, s_r = run(True)
        opt_f, p_f, s_f = run(False)

        assert all(a.dtype == jnp.uint16 for a in s_r.master_shard)
        plan = opt_r._plan
        leaves_r = jax.tree.leaves(p_r)
        for bi, b in enumerate(plan.buckets):
            parts = [np.asarray(leaves_r[bl.leaf_id], np.float32).reshape(-1)
                     for bl in b.leaves]
            flat = np.pad(np.concatenate(parts), (0, b.pad))
            master_r = _master_from_remainder(jnp.asarray(flat),
                                              s_r.master_shard[bi])
            np.testing.assert_array_equal(
                np.asarray(master_r).view(np.uint32),
                np.asarray(s_f.master_shard[bi]).view(np.uint32))
        for a, b in zip(jax.tree.leaves(p_r), jax.tree.leaves(p_f)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=1e-2, atol=1e-3)

    @pytest.mark.slow
    def test_overflow_skip_keeps_params(self, devices8):
        params0 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), make_tree(9))
        mesh = Mesh(np.array(devices8), ("dp",))
        opt = DistributedFusedAdam(lr=1e-2, store_param_remainders=True)
        state = opt.init(params0, world_size=DP)
        g = jax.tree.map(
            lambda x: jnp.full(x.shape, jnp.nan, jnp.float32), params0)
        params, state = zero_step(opt, mesh, params0, state, g,
                                  grads_finite=jnp.bool_(False))
        assert int(state.step) == 0
        assert_bitwise(params, params0)


# --------------------------------------------------- quantized grad sync
class TestQuantizedGradSync:
    """int8/fp8 wire traffic with error-feedback residuals
    (``_quantized_sync`` + the engine's quantized ``_prepare_grads``
    branch): bitwise error accounting, residual state discipline, and
    the compressed checkpoint format (v3)."""

    def _qstep(self, opt, mesh, p, s, g, **kw):
        return zero_step(opt, mesh, p, s, g, **kw)

    def test_error_feedback_roundtrip_bitwise(self, devices8):
        """The telescoping identity, BITWISE on crafted inputs:
        transmitted₁ + transmitted₂ + Σ residual₂ == Σ (g₁ + g₂).
        Values are integers/half-integers with per-block amaxes pinned
        to 127·2ᵏ, so the shared scale is an exact power of two and
        every add/multiply in the chain is exact in fp32."""
        from apex_tpu.contrib.optimizers import _quantized_sync as qs

        mesh = Mesh(np.array(devices8[:2]), ("dp",))
        spec = qs.qspec_of("int8")
        N = 2 * qs.QBLOCK
        rng = np.random.RandomState(0)

        def one(h_stack):
            def f(h):
                h = h.reshape(-1)
                rank = jax.lax.axis_index("dp")
                shard, res = qs.quantized_reduce_scatter(
                    h, "dp", spec, rank, 2)
                full = jax.lax.all_gather(shard, "dp", axis=0, tiled=True)
                return full[None], res[None]

            out = jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P("dp"),
                out_specs=(P("dp"), P("dp")), check_vma=False))(h_stack)
            return map(np.asarray, out)

        def ints(scale):
            # random ints plus a pinned ±127·scale per block per rank:
            # a_loc = 127·scale each, a_sum = 254·scale, s = 2·scale
            h = (rng.randint(-100, 101, size=(2, N)) * scale
                 ).astype(np.float32)
            h[:, 0] = 127.0 * scale
            h[:, qs.QBLOCK] = -127.0 * scale
            return h

        g1 = ints(1)
        t1, res1 = one(jnp.asarray(g1))
        h2 = ints(2)       # the step-2 PRE-quantization values...
        g2 = h2 - res1     # ...reached by grads that absorb residual₁
        t2, res2 = one(jnp.asarray(h2))
        lhs = t1[0] + t2[0] + res2.sum(axis=0)
        rhs = (g1 + g2).sum(axis=0)
        np.testing.assert_array_equal(lhs.view(np.uint32),
                                      rhs.view(np.uint32))
        assert np.abs(res1).max() > 0  # feedback actually engaged

    def test_int8_sum_cannot_overflow_the_wire(self, devices8):
        """Adversarial amaxes: every rank at the int8 clip ceiling.
        The per-rank bounds Σ⌊qmax·amax_r/Σamax⌋ ≤ 127 keep the wire
        sum in range — the dequantized result stays finite and close."""
        from apex_tpu.contrib.optimizers import _quantized_sync as qs

        mesh = Mesh(np.array(devices8), ("dp",))
        spec = qs.qspec_of("int8")
        N = qs.QBLOCK * 8
        h = np.full((8, N), 3.14159e4, np.float32)  # same sign, all big

        def f(h):
            h = h.reshape(-1)
            rank = jax.lax.axis_index("dp")
            shard, _ = qs.quantized_reduce_scatter(h, "dp", spec, rank, 8)
            return jax.lax.all_gather(shard, "dp", axis=0, tiled=True)[None]

        out = np.asarray(jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False))(jnp.asarray(h)))
        assert np.isfinite(out).all()
        # ⌊127/8⌋ per-rank levels: single-shot accuracy is ~1/15 here
        # (the error-feedback residual is what recovers it over steps)
        np.testing.assert_allclose(out[0], h.sum(axis=0), rtol=0.08)

    def test_nonfinite_grads_leave_residual_unchanged(self, devices8):
        """The guarded-step no-op contract: a non-finite grad (which
        the int8 wire itself would MASK — nan casts to a finite int)
        must fail the vote via the pre-quantization values and leave
        params, state, AND the error-feedback residuals untouched."""
        params = make_tree()
        mesh = Mesh(np.array(devices8), ("dp",))
        opt = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                   axis_name="dp", grad_sync_dtype="int8")
        state = opt.init(params, world_size=DP)
        sspec = opt.state_partition_spec()
        rng = np.random.RandomState(5)
        g = jax.tree.map(
            lambda x: jnp.asarray(rng.randn(*x.shape).astype(np.float32)),
            params)

        def scaled(p, s, gg):
            return opt.update_scaled(gg, s, p)

        step = jax.shard_map(
            scaled, mesh=mesh, in_specs=(P(), sspec, P()),
            out_specs=(P(), sspec, P()), check_vma=False)
        p1, s1, fin = step(params, state, g)
        assert bool(fin)
        assert any(float(jnp.abs(r.astype(jnp.float32)).max()) > 0
                   for r in s1.residual)

        bad = jax.tree.map(
            lambda x: x.at[(0,) * x.ndim].set(jnp.nan), g)
        p2, s2, fin2 = step(p1, s1, bad)
        assert not bool(fin2)
        assert int(s2.step) == 1
        assert_bitwise(p2, p1)
        assert_bitwise(s2.residual, s1.residual)

    @pytest.mark.slow
    @pytest.mark.parametrize("wire", ["int8", "float8_e4m3fn",
                                      "float8_e5m2"])
    def test_loss_curve_within_band_of_fp32_sync(self, devices8, wire):
        """The convergence contract (the documented tolerance band,
        docs/optimizers.md): the tiny GPT dp-sharded config trained
        with a quantized wire stays within 5% relative of the
        fp32-sync loss at EVERY step, and within 1% on the mean of the
        last 10 of 50 steps."""
        from apex_tpu.models.gpt import (
            GPTConfig, init_params, make_train_step,
        )

        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_attention_heads=4, max_seq_len=16,
                        compute_dtype=jnp.float32, checkpoint_layers=False)
        mesh = Mesh(np.array(devices8).reshape(DP, 1), ("dp", "tp"))
        params0 = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        data = [jnp.asarray(rng.randint(0, 64, size=(DP, 16)))
                for _ in range(50)]

        def run(sync):
            opt = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                       axis_name="dp", grad_sync_dtype=sync)
            state = opt.init(params0, world_size=DP)
            step = make_train_step(cfg, opt, mesh, donate_state=True)
            p = jax.tree.map(lambda x: x.copy(), params0)
            losses = []
            for tok in data:
                p, state, loss = step(p, state, tok,
                                      jnp.roll(tok, -1, axis=1))
                losses.append(float(loss))
            return np.asarray(losses)

        base = run(jnp.float32)
        quant = run(wire)
        rel = np.abs(quant - base) / np.abs(base)
        assert np.isfinite(quant).all()
        assert rel.max() <= 0.05, f"per-step dev {rel.max():.4f}"
        assert rel[-10:].mean() <= 0.01, f"tail dev {rel[-10:].mean():.4f}"

    @pytest.mark.slow
    def test_lamb_quantized_trajectory_close_to_wide(self, devices8):
        """LAMB on the int8 wire: trust-ratio segment sums operate on
        the DEQUANTIZED fp32 shards, so the trajectory tracks the
        wide-wire LAMB to quantization noise."""
        params = make_tree()
        mesh = Mesh(np.array(devices8), ("dp",))
        rng = np.random.RandomState(23)
        grads = [jax.tree.map(
            lambda x: jnp.asarray(rng.randn(*x.shape).astype(np.float32)),
            params) for _ in range(3)]

        def run(**kw):
            opt = DistributedFusedLAMB(lr=1e-2, weight_decay=0.01,
                                       max_grad_norm=1.0, axis_name="dp",
                                       **kw)
            state = opt.init(params, world_size=DP)
            p = params
            for g in grads:
                p, state = zero_step(opt, mesh, p, state, g)
            return p, state

        p_w, _ = run()
        p_q, s_q = run(grad_sync_dtype="int8")
        assert all(r.dtype == jnp.float32 for r in s_q.residual)
        for a, b in zip(jax.tree.leaves(p_q), jax.tree.leaves(p_w)):
            # trust ratios divide by per-tensor update norms, so the
            # int8 noise floor is a touch higher than Adam's
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0.05, atol=2e-2)

    def test_quantized_composes_with_tp_and_remainder_master(self, devices8):
        """dp=4 × tp=2 with an int8 wire: residuals shard
        P(("tp","dp")) and each (tp, dp) rank quantizes its LOCAL
        bucket against dp-only shared scales.  Plus the bf16
        remainder-master mode on an fp8 wire — storage-dtype residuals
        (bf16) compose with the uint16 master."""
        rng = np.random.RandomState(11)
        params = {"w": jnp.asarray(rng.randn(8, 6).astype(np.float32)),
                  "b": jnp.asarray(rng.randn(12).astype(np.float32))}
        pspecs = {"w": P("tp", None), "b": P(None)}
        mesh = Mesh(np.array(devices8).reshape(4, 2), ("dp", "tp"))
        dist = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                    axis_name="dp", grad_sync_dtype="int8")
        state = dist.init(params, world_size=4, param_specs=pspecs,
                          axis_sizes={"tp": 2})
        sspec = dist.state_partition_spec()
        assert sspec.residual[0] == P(("tp", "dp"))
        g = jax.tree.map(
            lambda x: jnp.asarray(rng.randn(*x.shape).astype(np.float32)),
            params)
        p2, s2 = jax.shard_map(
            lambda p, s, gg: dist.update(gg, s, p),
            mesh=mesh, in_specs=(pspecs, sspec, pspecs),
            out_specs=(pspecs, sspec), check_vma=False,
        )(params, state, g)
        assert int(s2.step) == 1
        assert all(np.isfinite(np.asarray(x)).all()
                   for x in jax.tree.leaves(p2))

        pb = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
        mesh2 = Mesh(np.array(devices8[:4]), ("dp",))
        opt = DistributedFusedAdam(lr=1e-2, store_param_remainders=True,
                                   axis_name="dp",
                                   grad_sync_dtype="float8_e5m2")
        st = opt.init(pb, world_size=4)
        assert all(r.dtype == jnp.bfloat16 for r in st.residual)
        g2 = jax.tree.map(
            lambda x: jnp.asarray(rng.randn(*x.shape).astype(np.float32)),
            pb)
        _, s3 = zero_step(opt, mesh2, pb, st, g2)
        assert int(s3.step) == 1

    def test_compressed_resume_bitwise(self, devices8):
        """Format v3 auto-resume: per-rank shard dicts round-trip the
        residuals bitwise at the saved world size, and the resumed
        continuation equals the uninterrupted run bit for bit."""
        params0 = make_tree(5)
        mesh = Mesh(np.array(devices8[:2]), ("dp",))
        opt = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                   axis_name="dp", grad_sync_dtype="int8")
        state = opt.init(params0, world_size=2)
        rng = np.random.RandomState(13)

        def train(p, s, seed, steps):
            r = np.random.RandomState(seed)
            for _ in range(steps):
                g = jax.tree.map(
                    lambda x: jnp.asarray(r.randn(*x.shape)
                                          .astype(np.float32)), p)
                p, s = zero_step(opt, mesh, p, s, g)
            return p, s

        params, state = train(params0, state, 13, 2)
        shards = [opt.sharded_state_dict(state, r, 2) for r in range(2)]
        assert shards[0]["format"] == "apex_tpu_zero2_v3"
        assert shards[0]["residual_kind"] == "ef"
        state_r = DistributedFusedAdam.load_sharded_state_dicts(
            shards, world_size=2, grad_sync_dtype="int8")
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(state_r)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        p_cont, s_cont = train(params, state, 17, 1)
        p_res, s_res = train(params, state_r, 17, 1)
        assert_bitwise(p_cont, p_res)
        for a, b in zip(jax.tree.leaves(s_cont), jax.tree.leaves(s_res)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_cross_world_reshard_preserves_residual_sum(self, devices8):
        """dp=2 save → dp=4 load: the optimizer trajectory sees only
        Σ_r (g_r + residual_r), so the reshard collapses the per-rank
        errors onto new rank 0 — sum preserved exactly, re-padded with
        the one ``padded_total`` formula."""
        params0 = make_tree(7)
        mesh = Mesh(np.array(devices8[:2]), ("dp",))
        opt = DistributedFusedAdam(lr=1e-2, axis_name="dp",
                                   grad_sync_dtype="int8")
        state = opt.init(params0, world_size=2)
        rng = np.random.RandomState(3)
        g = jax.tree.map(
            lambda x: jnp.asarray(rng.randn(*x.shape).astype(np.float32)),
            params0)
        _, state = zero_step(opt, mesh, params0, state, g)
        shards = [opt.sharded_state_dict(state, r, 2) for r in range(2)]
        state4 = DistributedFusedAdam.load_sharded_state_dicts(
            shards, world_size=4)
        opt4 = DistributedFusedAdam(lr=1e-2, axis_name="dp",
                                    grad_sync_dtype="int8")
        opt4.init(params0, world_size=4)
        for old, new, b in zip(state.residual, state4.residual,
                               opt4._plan.buckets):
            assert new.shape[0] == 4 * b.total
            np.testing.assert_allclose(
                np.asarray(old, np.float64).sum(),
                np.asarray(new, np.float64).sum(), rtol=1e-6)

    def test_compressed_state_mismatch_fails_loudly(self, devices8):
        """The remainder-master discipline, mirrored: compressed state
        into an uncompressed optimizer (and the reverse) is refused by
        every load path — and the raw-pytree trace path fails at trace
        time, never a shape crash mid-math."""
        params = make_tree(6)
        mesh = Mesh(np.array(devices8[:2]), ("dp",))
        opt_q = DistributedFusedAdam(lr=1e-2, axis_name="dp",
                                     grad_sync_dtype="int8")
        s_q = opt_q.init(params, world_size=2)
        opt_w = DistributedFusedAdam(lr=1e-2, axis_name="dp")
        s_w = opt_w.init(params, world_size=2)
        g = jax.tree.map(jnp.zeros_like, params)

        # whole-dict load, both directions
        with pytest.raises(ValueError, match="residual_kind"):
            opt_w.load_state_dict(opt_q.state_dict(s_q))
        with pytest.raises(ValueError, match="residual_kind"):
            opt_q.load_state_dict(opt_w.state_dict(s_w))
        # reshard path with the target wire declared
        shards = [opt_q.sharded_state_dict(s_q, r, 2) for r in range(2)]
        with pytest.raises(ValueError, match="residual_kind"):
            DistributedFusedAdam.load_sharded_state_dicts(
                shards, world_size=2, grad_sync_dtype=None)
        # raw-pytree trace path: the state and spec trees disagree at
        # the residual field, so shard_map's own in_specs check refuses
        # the call at trace time, before any math (jax 0.9 words it
        # "pytree structure error: different lengths of tuple" and no
        # longer prints the field's name) ...
        with pytest.raises(ValueError, match="pytree structure"):
            zero_step(opt_w, mesh, params, s_q, g)
        with pytest.raises(ValueError, match="pytree structure"):
            zero_step(opt_q, mesh, params, s_w, g)
        # ... and the optimizer's own check, behind that boundary, names
        # the residual and the knob
        with pytest.raises(ValueError, match="residual.*grad_sync_dtype"):
            opt_w._check_residual_state(opt_w._plan, s_q.residual)
        with pytest.raises(ValueError, match="grad_sync_dtype.*residual"):
            opt_q._check_residual_state(opt_q._plan, s_w.residual)

    def test_quantized_state_spec_and_wire_accounting(self, devices8):
        """Residuals ride the state spec (donatable like m/v) at full
        local-bucket length per rank; wire accounting charges the fp32
        scale vectors to the quantized modes."""
        params = make_mixed_tree()
        opt = DistributedFusedAdam(lr=1e-2, axis_name="dp",
                                   grad_sync_dtype="float8_e5m2")
        state = opt.init(params, world_size=DP)
        plan = opt._plan
        spec = opt.state_partition_spec()
        assert spec.residual == tuple(P("dp") for _ in plan.buckets)
        for r, b in zip(state.residual, plan.buckets):
            assert r.shape == (DP * b.total,)
            assert r.dtype == jnp.dtype(b.dtype)  # storage, never wire
        wb = opt.wire_bytes_per_step()
        assert wb["grad_scales"] == sum(
            (b.total // 1024) * 4 for b in plan.buckets)
        # an uncompressed optimizer keeps the residual field EMPTY
        opt_w = DistributedFusedAdam(lr=1e-2, axis_name="dp")
        s_w = opt_w.init(params, world_size=DP)
        assert s_w.residual == ()
        assert opt_w.state_partition_spec().residual == ()


# ------------------------------------------------------ hierarchical sync
HIER_AXES = ("dp_out", "dp_in")
HIER_SIZES = {"dp_out": 2, "dp_in": 2}


def hier_mesh(devices8):
    return Mesh(np.array(devices8[:4]).reshape(2, 2), HIER_AXES)


class TestHierarchicalGradSync:
    """The multi-hop (fast, slow) dp split (``_hierarchical_sync`` +
    the engine's ``dp_axes=`` knob): flat-parity bands, the bitwise
    requantization-error telescoping, residual/state discipline, and
    the construction-time validation."""

    def test_wide_fp32_bitwise_vs_flat_dp4(self, devices8):
        """The acceptance parity band: hierarchical fp32-wire sync on
        the (2, 2) mesh equals flat dp=4 BITWISE over 4 steps — on
        exactly-representable (dyadic) grads, where the only thing the
        two hops could change (the dp-sum association: (a+b)+(c+d) vs
        a flat reduce's order) is exact either way.  Arbitrary fp32
        grads reorder adds ACROSS hops and track to reduction ulps —
        the gpt-level band below pins that."""
        params = make_mixed_tree()
        flat = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                    axis_name="dp")
        s_f = flat.init(params, world_size=4)
        hier = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                    dp_axes=HIER_AXES)
        s_h = hier.init(params, world_size=4, axis_sizes=HIER_SIZES)
        assert hier.hier_plan.world == 4
        mesh_f = Mesh(np.array(devices8[:4]), ("dp",))
        mesh_h = hier_mesh(devices8)
        p_f = p_h = params
        rng = np.random.RandomState(50)
        for _ in range(4):
            g = exact_grads(rng, params)
            p_f, s_f = zero_step(flat, mesh_f, p_f, s_f, g)
            p_h, s_h = zero_step(hier, mesh_h, p_h, s_h, g)
        assert_bitwise(p_f, p_h)
        for a, b in zip(jax.tree.leaves(s_f), jax.tree.leaves(s_h)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_gpt_step_fp32_loss_band_vs_flat(self, devices8):
        """The full ``make_train_step`` trajectory, hierarchical (2, 2)
        vs flat dp=4 on REAL grads: fp32 adds reorder only across the
        two hops, so per-step losses agree to a 1-ulp-class band
        (measured ~6e-8 rel on this config; pinned at 1e-6) — NOT
        bitwise, which is why the bitwise acceptance rides the
        dyadic-grads engine test above."""
        from apex_tpu.models.gpt import GPTConfig, init_params, \
            make_train_step

        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_attention_heads=4, max_seq_len=16,
                        compute_dtype=jnp.float32, checkpoint_layers=False)
        params0 = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        data = [jnp.asarray(rng.randint(0, 64, size=(4, 16)))
                for _ in range(5)]

        def run(mesh, dp_axis, **opt_kw):
            sizes = HIER_SIZES if "dp_axes" in opt_kw else None
            opt = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                       **opt_kw)
            state = opt.init(params0, world_size=4, axis_sizes=sizes)
            step = make_train_step(cfg, opt, mesh, dp_axis=dp_axis,
                                   donate_state=True)
            p = jax.tree.map(lambda x: x.copy(), params0)
            losses = []
            for tok in data:
                p, state, loss = step(p, state, tok,
                                      jnp.roll(tok, -1, axis=1))
                losses.append(float(loss))
            return np.asarray(losses)

        mesh_f = Mesh(np.array(devices8[:4]).reshape(4, 1), ("dp", "tp"))
        mesh_h = Mesh(np.array(devices8[:4]).reshape(2, 2, 1),
                      ("dp_out", "dp_in", "tp"))
        l_f = run(mesh_f, "dp", axis_name="dp")
        l_h = run(mesh_h, HIER_AXES, dp_axes=HIER_AXES)
        np.testing.assert_allclose(l_h, l_f, rtol=1e-6)

    def test_requantization_error_telescopes_bitwise(self, devices8):
        """The crafted dyadic-scale acceptance test: on the (2, 2)
        mesh, transmitted + Σ_r residual_r == Σ_r h_r BITWISE through
        BOTH hops.  Per-rank block amaxes are pinned (126, 128)·scale,
        so hop 1's shared scale is 2·scale exactly; the partial-sum
        block amaxes then pin to 254·scale per slice, so hop 2's
        REQUANTIZATION scale is 4·scale exactly — every divide, round,
        clip, and add in the chain is exact fp32 arithmetic, and the
        hop-2 error provably lands in the residual (the pinned entries
        have zero hop-1 error but ±2·scale hop-2 error)."""
        from apex_tpu.contrib.optimizers import _hierarchical_sync as hsync
        from apex_tpu.contrib.optimizers import _quantized_sync as qs

        spec = qs.qspec_of("int8")
        plan = hsync.hierarchical_plan(HIER_AXES, HIER_SIZES)
        mesh = hier_mesh(devices8)
        N = 4 * qs.QBLOCK  # 4 blocks/rank; chunk = 2 blocks ≥ block·outer
        rng = np.random.RandomState(0)

        def craft(scale):
            # rng ints well under the pins; per block, rank dp_in=0
            # pins ±126·scale and dp_in=1 pins ±128·scale (amax sum
            # 254·scale → s1 = 2·scale), alternating sign per block
            h = (rng.randint(-100, 101, size=(4, N)) * scale
                 ).astype(np.float32)
            for d in range(4):  # device order: d = dp_out*2 + dp_in
                pin = 126.0 if d % 2 == 0 else 128.0
                for b in range(4):
                    h[d, b * qs.QBLOCK] = pin * scale * (-1.0) ** b
            return h

        def one(h_stack):
            def f(h):
                h = h.reshape(-1)
                shard, res = hsync.quantized_two_hop_reduce_scatter(
                    h, plan, spec)
                full = hsync.two_hop_all_gather(shard, plan)
                return full[None], res[None]

            out = jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P(HIER_AXES),
                out_specs=(P(HIER_AXES), P(HIER_AXES)),
                check_vma=False))(h_stack)
            return map(np.asarray, out)

        for scale in (1.0, 4.0):  # dyadic scales, both exact
            h = craft(scale)
            t, res = one(jnp.asarray(h))
            lhs = t[0] + res.sum(axis=0)
            rhs = h.sum(axis=0)
            np.testing.assert_array_equal(
                lhs.view(np.uint32), rhs.view(np.uint32))
            # hop-1 error engaged (odd rng ints halve inexactly)...
            assert np.abs(res).max() > 0
            # ...and the hop-2 REQUANTIZATION error telescopes too: at
            # the pinned entries hop 1 is exact (126/2, 128/2 are
            # integers) while hop 2 rounds 254/4 = 63.5 → 63 (clipped),
            # leaving exactly ±2·scale in the owning rank's chunk
            assert abs(abs(res[0, 0]) - 2.0 * scale) < 1e-6

    def test_hier_int8_nonfinite_step_leaves_residual_unchanged(
            self, devices8):
        """The guarded no-op contract survives the second hop: a nan
        grad fails the (pre-quantization) vote and leaves params AND
        the folded two-hop residuals untouched."""
        params = make_tree()
        mesh = hier_mesh(devices8)
        opt = DistributedFusedAdam(lr=1e-2, dp_axes=HIER_AXES,
                                   grad_sync_dtype="int8")
        state = opt.init(params, world_size=4, axis_sizes=HIER_SIZES)
        sspec = opt.state_partition_spec()
        rng = np.random.RandomState(5)
        g = jax.tree.map(
            lambda x: jnp.asarray(rng.randn(*x.shape).astype(np.float32)),
            params)
        step = jax.shard_map(
            lambda p, s, gg: opt.update_scaled(gg, s, p),
            mesh=mesh, in_specs=(P(), sspec, P()),
            out_specs=(P(), sspec, P()), check_vma=False)
        p1, s1, fin = step(params, state, g)
        assert bool(fin)
        assert any(float(jnp.abs(r.astype(jnp.float32)).max()) > 0
                   for r in s1.residual)
        bad = jax.tree.map(lambda x: x.at[(0,) * x.ndim].set(jnp.nan), g)
        p2, s2, fin2 = step(p1, s1, bad)
        assert not bool(fin2)
        assert_bitwise(p2, p1)
        assert_bitwise(s2.residual, s1.residual)

    def test_state_reshards_flat_to_hier_bitwise_same_world(self, devices8):
        """flat dp=4 state → hierarchical (2, 2) optimizer at the SAME
        world: shard ownership is unchanged by design (same chunk per
        flat rank, same padded_total), so the reshard is bitwise and
        the hierarchical continuation runs on it."""
        params = make_tree(9)
        mesh_f = Mesh(np.array(devices8[:4]), ("dp",))
        opt_f = DistributedFusedAdam(lr=1e-2, axis_name="dp",
                                     grad_sync_dtype="int8")
        s_f = opt_f.init(params, world_size=4)
        rng = np.random.RandomState(21)
        g = jax.tree.map(
            lambda x: jnp.asarray(rng.randn(*x.shape).astype(np.float32)),
            params)
        p1, s1 = zero_step(opt_f, mesh_f, params, s_f, g)
        shards = [opt_f.sharded_state_dict(s1, r, 4) for r in range(4)]
        s_h = DistributedFusedAdam.load_sharded_state_dicts(
            shards, world_size=4, grad_sync_dtype="int8")
        for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s_h)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        opt_h = DistributedFusedAdam(lr=1e-2, dp_axes=HIER_AXES,
                                     grad_sync_dtype="int8")
        opt_h.init(params, world_size=4, axis_sizes=HIER_SIZES)
        p2, s2 = zero_step(opt_h, hier_mesh(devices8), p1, s_h, g)
        assert int(s2.step) == 2
        assert all(np.isfinite(np.asarray(x)).all()
                   for x in jax.tree.leaves(p2))

    def test_hier_validation(self, devices8):
        """Construction-time discipline: malformed splits, missing
        axis sizes, world mismatches, and step/optimizer axis-layout
        disagreement all fail loudly with the knob named."""
        from apex_tpu.models.gpt import (
            GPTConfig, make_pp_train_step, make_train_step,
        )

        params = make_tree()
        with pytest.raises(ValueError, match="distinct"):
            DistributedFusedAdam(lr=1e-3, dp_axes=("dp", "dp"))
        with pytest.raises(ValueError, match="two"):
            DistributedFusedAdam(lr=1e-3, dp_axes=("dp",))
        opt = DistributedFusedAdam(lr=1e-3, dp_axes=HIER_AXES)
        with pytest.raises(ValueError, match="axis_sizes"):
            opt.init(params, world_size=4)
        with pytest.raises(ValueError, match="world_size"):
            DistributedFusedAdam(lr=1e-3, dp_axes=HIER_AXES).init(
                params, world_size=8, axis_sizes=HIER_SIZES)

        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_attention_heads=2, max_seq_len=16,
                        compute_dtype=jnp.float32)
        mesh_h = Mesh(np.array(devices8[:4]).reshape(2, 2, 1),
                      ("dp_out", "dp_in", "tp"))
        mesh_f = Mesh(np.array(devices8[:4]).reshape(4, 1), ("dp", "tp"))
        flat_opt = DistributedFusedAdam(lr=1e-3, axis_name="dp")
        flat_opt.init(params, world_size=4)
        # hier step needs a hier optimizer with the SAME split
        with pytest.raises(ValueError, match="dp_axes"):
            make_train_step(cfg, flat_opt, mesh_h, dp_axis=HIER_AXES)
        # hier optimizer refuses a flat step
        hier_opt = DistributedFusedAdam(lr=1e-3, dp_axes=HIER_AXES)
        hier_opt.init(params, world_size=4, axis_sizes=HIER_SIZES)
        with pytest.raises(ValueError, match="hierarchical"):
            make_train_step(cfg, hier_opt, mesh_f, dp_axis="dp")
        # the pipeline step's dp sync is flat-only, loudly
        with pytest.raises(NotImplementedError, match="hierarchical"):
            make_pp_train_step(cfg, hier_opt, mesh_h, num_microbatches=2,
                               dp_axis=HIER_AXES)

    @pytest.mark.slow
    @pytest.mark.parametrize("wire", ["int8", "float8_e4m3fn",
                                      "float8_e5m2"])
    def test_hier_loss_curve_within_band_of_fp32_sync(self, devices8,
                                                      wire):
        """The PR 6 convergence contract on the hierarchical wire:
        tiny-GPT on the (2, 2) mesh, 50 steps — every quantized-wire
        loss ≤5% rel of the fp32-wire sync, last-10 mean ≤1%, with the
        requantized slow hop and the folded residuals in the loop."""
        from apex_tpu.models.gpt import (
            GPTConfig, init_params, make_train_step,
        )

        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_attention_heads=4, max_seq_len=16,
                        compute_dtype=jnp.float32, checkpoint_layers=False)
        mesh = Mesh(np.array(devices8[:4]).reshape(2, 2, 1),
                    ("dp_out", "dp_in", "tp"))
        params0 = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        data = [jnp.asarray(rng.randint(0, 64, size=(4, 16)))
                for _ in range(50)]

        def run(sync):
            opt = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                       dp_axes=HIER_AXES,
                                       grad_sync_dtype=sync)
            state = opt.init(params0, world_size=4,
                             axis_sizes=HIER_SIZES)
            step = make_train_step(cfg, opt, mesh, dp_axis=HIER_AXES,
                                   donate_state=True)
            p = jax.tree.map(lambda x: x.copy(), params0)
            losses = []
            for tok in data:
                p, state, loss = step(p, state, tok,
                                      jnp.roll(tok, -1, axis=1))
                losses.append(float(loss))
            return np.asarray(losses)

        base = run(jnp.float32)
        quant = run(wire)
        rel = np.abs(quant - base) / np.abs(base)
        assert np.isfinite(quant).all()
        assert rel.max() <= 0.05, f"per-step dev {rel.max():.4f}"
        assert rel[-10:].mean() <= 0.01, f"tail dev {rel[-10:].mean():.4f}"


# -------------------------------------------------------- step-builder seam
class TestStepBuilderSeam:
    def test_zero_axis_mismatch_raises(self, devices8):
        from apex_tpu.models.gpt import GPTConfig, make_train_step

        mesh = Mesh(np.array(devices8).reshape(4, 2), ("dp", "tp"))
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_attention_heads=4, max_seq_len=16,
                        compute_dtype=jnp.float32)
        opt = DistributedFusedAdam(lr=1e-3, axis_name="data")  # wrong axis
        with pytest.raises(ValueError, match="dp"):
            make_train_step(cfg, opt, mesh)


# ------------------------------------------------- 3-level (dcn) sync
DCN_AXES = ("dcn", "dp_out", "dp_in")
DCN_SIZES = {"dcn": 2, "dp_out": 2, "dp_in": 2}


def dcn_mesh(devices8):
    return Mesh(np.array(devices8).reshape(2, 2, 2), DCN_AXES)


class TestThreeLevelGradSync:
    """The (dcn, dp_out, dp_in) three-hop split: flat-parity bitwise on
    dyadic grads, the three-hop residual telescoping with the dcn hop's
    requantization error provably in the residual, the exact
    ``1/(dp_in·dp_out)`` cross-DCN wire fraction, and validation."""

    def test_wide_fp32_bitwise_vs_flat_dp8(self, devices8):
        """Three hops reassociate the dp sum as ((a+b)+(c+d))+… — on
        exactly-representable grads that is exact either way, so the
        (2, 2, 2) engine equals flat dp=8 BITWISE over 4 steps, the
        same acceptance the two-level split carries at dp=4."""
        params = make_mixed_tree()
        flat = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                    axis_name="dp")
        s_f = flat.init(params, world_size=DP)
        hier = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                    dp_axes=DCN_AXES)
        s_h = hier.init(params, world_size=DP, axis_sizes=DCN_SIZES)
        assert hier.hier_plan.world == DP
        mesh_f = Mesh(np.array(devices8), ("dp",))
        mesh_h = dcn_mesh(devices8)

        # one jitted step per engine, reused across the loop — the
        # shared zero_step retraces per call, which dominates this
        # test's wall time; both sides run the SAME jitted pipeline so
        # the bitwise comparison stays apples-to-apples
        def stepper(dist, mesh):
            sspec = dist.state_partition_spec()
            return jax.jit(jax.shard_map(
                lambda p, s, gg: dist.update(gg, s, p),
                mesh=mesh, in_specs=(P(), sspec, P()),
                out_specs=(P(), sspec), check_vma=False))

        step_f, step_h = stepper(flat, mesh_f), stepper(hier, mesh_h)
        p_f = p_h = params
        rng = np.random.RandomState(51)
        for _ in range(4):
            g = exact_grads(rng, params)
            p_f, s_f = step_f(p_f, s_f, g)
            p_h, s_h = step_h(p_h, s_h, g)
        assert_bitwise(p_f, p_h)
        for a, b in zip(jax.tree.leaves(s_f), jax.tree.leaves(s_h)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_three_hop_requantization_telescopes_bitwise(self, devices8):
        """The crafted dyadic-scale identity at THREE hops:
        transmitted + Σ_r residual_r == Σ_r h_r bitwise on the
        (2, 2, 2) mesh.  Per dcn group, dp_out slice 0 carries the
        (126, 128)·scale dp_in pins and dp_out slice 1 is all zeros,
        which pins every hop's shared scale dyadic: hop 1 gets
        s₁ = 2·scale (254/127); hop 2 sees per-block amaxes 254·scale
        from slice 0 and 0 from slice 1, so s₂ = 2·scale and the
        requantization 254/2 = 127 ≤ bound 127 is EXACT; hop 3 (dcn)
        sums 254 + 254 → s₃ = 4·scale, and its requantization rounds
        the pinned 254/4 = 63.5 up then clips to the 63 bound — leaving
        exactly ±2·scale per dcn rank, the cross-DCN hop's error landing
        in the residual."""
        from apex_tpu.contrib.optimizers import _hierarchical_sync as hsync
        from apex_tpu.contrib.optimizers import _quantized_sync as qs

        spec = qs.qspec_of("int8")
        plan = hsync.hierarchical_plan(DCN_AXES, DCN_SIZES)
        mesh = dcn_mesh(devices8)
        N = 8 * qs.QBLOCK  # 8 blocks/rank; dcn chunk = 1 block
        rng = np.random.RandomState(0)

        def craft(scale):
            h = (rng.randint(-100, 101, size=(8, N)) * scale
                 ).astype(np.float32)
            for d in range(8):  # d = dcn*4 + dp_out*2 + dp_in
                if (d // 2) % 2 == 1:  # dp_out slice 1: silent
                    h[d] = 0.0
                    continue
                pin = 126.0 if d % 2 == 0 else 128.0
                for b in range(N // qs.QBLOCK):
                    h[d, b * qs.QBLOCK] = pin * scale * (-1.0) ** b
            return h

        def one(h_stack):
            def f(h):
                h = h.reshape(-1)
                shard, res = hsync.quantized_multi_hop_reduce_scatter(
                    h, plan, spec)
                full = hsync.multi_hop_all_gather(shard, plan)
                return full[None], res[None]

            out = jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P(DCN_AXES),
                out_specs=(P(DCN_AXES), P(DCN_AXES)),
                check_vma=False))(h_stack)
            return map(np.asarray, out)

        for scale in (1.0, 4.0):
            h = craft(scale)
            t, res = one(jnp.asarray(h))
            lhs = t[0] + res.sum(axis=0)
            rhs = h.sum(axis=0)
            np.testing.assert_array_equal(
                lhs.view(np.uint32), rhs.view(np.uint32))
            # hop-1 error engaged (odd rng ints halve inexactly)...
            assert np.abs(res).max() > 0
            # ...hop 2 is exact by construction, and the hop-3 (dcn)
            # requantization error telescopes: rank (0,0,0) owns block
            # 0, where hop 1 is exact (126/2, 128/2 integral), hop 2 is
            # exact (254/2 = 127 at the 127 bound), and hop 3 clips
            # 63.5 → 63 — exactly +2·scale in its residual
            assert abs(res[0, 0] - 2.0 * scale) < 1e-6

    def test_cross_dcn_wire_bytes_exact_fraction(self):
        """The acceptance Fraction: the slowest (dcn) hop carries
        EXACTLY 1/(dp_in·dp_out) of the flat plan's grad-sync bytes at
        the same wire dtype — scales included, as exact rationals, not
        a float ratio."""
        from fractions import Fraction

        params = {"w": jnp.zeros((8 * 1024,), jnp.float32)}
        flat = DistributedFusedAdam(lr=1e-3, axis_name="dp",
                                    grad_sync_dtype="int8")
        flat.init(params, world_size=DP)
        h3 = DistributedFusedAdam(lr=1e-3, dp_axes=DCN_AXES,
                                  grad_sync_dtype="int8")
        h3.init(params, world_size=DP, axis_sizes=DCN_SIZES)
        wf = flat.wire_bytes_per_step()
        w3 = h3.wire_bytes_per_step()
        assert set(w3["hops"]) == set(DCN_AXES)
        base = wf["hops"]["dp"]
        cut = Fraction(1, DCN_SIZES["dp_in"] * DCN_SIZES["dp_out"])
        for key in ("grad_payload", "grad_scales", "grad_sync",
                    "param_sync"):
            assert Fraction(w3["hops"]["dcn"][key], base[key]) == cut
            assert Fraction(w3["hops"]["dp_out"][key], base[key]) \
                == Fraction(1, DCN_SIZES["dp_in"])
            assert w3["hops"]["dp_in"][key] == base[key]

    def test_three_level_validation(self, devices8):
        params = make_tree()
        with pytest.raises(ValueError, match="two or three"):
            DistributedFusedAdam(lr=1e-3, dp_axes=("a", "b", "c", "d"))
        with pytest.raises(ValueError, match="distinct"):
            DistributedFusedAdam(lr=1e-3, dp_axes=("dcn", "dp", "dp"))
        opt = DistributedFusedAdam(lr=1e-3, dp_axes=DCN_AXES)
        with pytest.raises(ValueError, match="axis_sizes"):
            opt.init(params, world_size=8,
                     axis_sizes={"dcn": 2, "dp_out": 2})
        with pytest.raises(ValueError, match="world_size"):
            DistributedFusedAdam(lr=1e-3, dp_axes=DCN_AXES).init(
                params, world_size=4, axis_sizes=DCN_SIZES)


# --------------------------------------------- backward-overlapped sync
class TestOverlappedGradSync:
    """``make_train_step(overlap_grad_sync=True)``: each bucket's sync
    collective is traced inside the backward, between the segment vjps
    — the SAME per-bucket ops on the SAME values as the unoverlapped
    build, merely reordered in the trace.  So fp32 losses and params
    are pinned BITWISE against ``overlap_grad_sync=False`` (Adam and
    LAMB, flat and hierarchical), and the quantized wires too (the
    error-feedback chain sees identical inputs).  The interleaved
    lowering itself is pinned in tests/test_lowered_invariants.py."""

    CFG = dict(vocab_size=64, hidden_size=32, num_layers=2,
               num_attention_heads=4, max_seq_len=16,
               compute_dtype=jnp.float32, checkpoint_layers=False)

    def _pair(self, devices8, make_opt, topo, scaler=None,
              grad_sync_dtype=None, steps=5):
        """Run overlap on/off through the real step builder; assert
        loss lists equal and params bitwise."""
        from apex_tpu.models.gpt import (
            GPTConfig, init_params, make_train_step,
        )

        cfg = GPTConfig(**self.CFG)
        params0 = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        data = [jnp.asarray(rng.randint(0, 64, size=(8, 16)))
                for _ in range(steps)]
        devs = np.array(devices8)
        if topo == "flat":
            mesh = Mesh(devs.reshape(8, 1), ("dp", "tp"))
            dp_axis, sizes = "dp", None
        elif topo == "hier":
            mesh = Mesh(devs.reshape(2, 4, 1), ("dp_out", "dp_in", "tp"))
            dp_axis, sizes = HIER_AXES, {"dp_out": 2, "dp_in": 4}
        else:  # "dcn"
            mesh = Mesh(devs.reshape(2, 2, 2, 1),
                        ("dcn", "dp_out", "dp_in", "tp"))
            dp_axis, sizes = DCN_AXES, dict(DCN_SIZES)

        def run(overlap):
            opt = make_opt(dp_axis)
            if hasattr(opt, "state_partition_spec"):
                state = opt.init(params0, world_size=DP,
                                 axis_sizes=sizes)
            else:
                state = opt.init(params0)
            kw = {"loss_scaler": scaler} if scaler else {}
            step = make_train_step(cfg, opt, mesh, dp_axis=dp_axis,
                                   overlap_grad_sync=overlap,
                                   grad_sync_dtype=grad_sync_dtype,
                                   donate_state=True, **kw)
            p = jax.tree.map(lambda x: x.copy(), params0)
            sc = scaler.init() if scaler else None
            losses = []
            for tok in data:
                tgt = jnp.roll(tok, -1, axis=1)
                if scaler:
                    p, state, sc, loss = step(p, state, sc, tok, tgt)
                else:
                    p, state, loss = step(p, state, tok, tgt)
                losses.append(float(loss))
            return losses, p

        base, ovl = run(False), run(True)
        assert ovl[0] == base[0], \
            f"{topo}: losses diverged {base[0]} vs {ovl[0]}"
        assert_bitwise(ovl[1], base[1], err=f"{topo}: ")

    @pytest.mark.parametrize("topo", ["flat", "hier"])
    @pytest.mark.parametrize("opt_cls", [DistributedFusedAdam,
                                         DistributedFusedLAMB])
    def test_fp32_bitwise_vs_unoverlapped(self, devices8, topo, opt_cls):
        """The headline acceptance: 5 fp32 steps, flat dp=8 and the
        (2, 4) hierarchical split, Adam and LAMB — losses equal,
        params bitwise."""
        def mk(dp_axis):
            kw = ({"dp_axes": dp_axis} if isinstance(dp_axis, tuple)
                  else {"axis_name": dp_axis})
            return opt_cls(lr=1e-3, weight_decay=0.01,
                           bucket_cap_mb=0.02, **kw)

        self._pair(devices8, mk, topo)

    def test_fp32_bitwise_three_level(self, devices8):
        """The (dcn, dp_out, dp_in) pipeline: per-hop wires issued
        inside the backward, still bitwise vs the unoverlapped trace."""
        self._pair(devices8,
                   lambda ax: DistributedFusedAdam(
                       lr=1e-3, bucket_cap_mb=0.02, dp_axes=ax),
                   "dcn")

    @pytest.mark.parametrize("topo,wire", [
        ("flat", "int8"),
        # the dcn leg re-proves what flat-int8 + the fp32 three-level
        # pair already pin — extra assurance, slow tier
        pytest.param("dcn", "int8", marks=pytest.mark.slow),
        ("flat", "float8_e5m2")])
    def test_quantized_wire_bitwise(self, devices8, topo, wire):
        """The compressed wires: identical per-bucket quantize →
        scatter → dequantize chains on identical cotangents, so the
        overlap build is bitwise too — stronger than the PR 6
        convergence band the wire itself is held to."""
        def mk(dp_axis):
            kw = ({"dp_axes": dp_axis} if isinstance(dp_axis, tuple)
                  else {"axis_name": dp_axis})
            return DistributedFusedAdam(lr=1e-3, bucket_cap_mb=0.02,
                                        grad_sync_dtype=wire, **kw)

        self._pair(devices8, mk, topo)

    @pytest.mark.slow
    @pytest.mark.parametrize("topo", ["flat", "dcn"])
    def test_replicated_quantized_overlap_bitwise(self, devices8, topo):
        """The non-ZeRO per-bucket path (``grad_sync_dtype=`` on a
        replicated optimizer): quantized pmean per bucket inside the
        backward, bitwise vs the post-backward sweep."""
        self._pair(devices8, lambda ax: FusedAdam(lr=1e-3), topo,
                   grad_sync_dtype="int8")

    @pytest.mark.slow
    def test_scaled_lamb_overlap_bitwise(self, devices8):
        """Loss scaling composes: the wires carry SCALED cotangents
        (unscale folds into the update tail), so the scaler variant is
        bitwise too — hierarchical LAMB, the hardest composition."""
        from apex_tpu.amp import DynamicLossScaler

        self._pair(devices8,
                   lambda ax: DistributedFusedLAMB(
                       lr=1e-3, bucket_cap_mb=0.02, dp_axes=ax),
                   "hier", scaler=DynamicLossScaler(init_scale=2.0 ** 10))

    def test_overlap_validation(self, devices8):
        """The knob fails loudly where there is nothing to overlap:
        GSPMD (no explicit collectives), dp_axis=None (no dp sync),
        and a replicated optimizer without a per-bucket wire."""
        from apex_tpu.models.gpt import GPTConfig, make_train_step

        cfg = GPTConfig(**self.CFG)
        devs = np.array(devices8)
        mesh = Mesh(devs.reshape(8, 1), ("dp", "tp"))
        with pytest.raises(NotImplementedError, match="GSPMD"):
            make_train_step(cfg, FusedAdam(lr=1e-3), mesh,
                            spmd="auto", overlap_grad_sync=True)
        with pytest.raises(ValueError, match="dp_axis=None"):
            make_train_step(cfg, FusedAdam(lr=1e-3),
                            Mesh(devs.reshape(8, 1), ("x", "tp")),
                            dp_axis=None, overlap_grad_sync=True)
        with pytest.raises(ValueError, match="per-bucket dp grad sync"):
            make_train_step(cfg, FusedAdam(lr=1e-3), mesh,
                            overlap_grad_sync=True)


# ------------------------------------------- static wire accounting
def test_zero_wire_bytes_accounting_ratios():
    """``wire_bytes_per_step`` validated at the accounting level (pure
    plan arithmetic, no step compile) — EXACT ratios, scale-vector bytes included per hop
    (never the old payload approximation): an int8 wire carries
    ``1 + 4/QBLOCK`` bytes per element (payload + its share of the
    fp32 per-block scale psum), so the cut vs the 2-byte bf16 default
    is exactly ``2 / (1 + 4/1024) = 512/257``, and vs a 4-byte fp32
    wire exactly ``1024/257``."""
    from fractions import Fraction

    import jax.numpy as jnp

    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.contrib.optimizers._quantized_sync import QBLOCK

    params = {"w": jnp.zeros((512, 256), jnp.bfloat16),
              "b": jnp.zeros((8192,), jnp.bfloat16)}

    def wire(**kw):
        opt = DistributedFusedAdam(lr=1e-3, **kw)
        opt.init(params, world_size=4)
        return opt.wire_bytes_per_step()

    bf16 = wire()                                  # default: storage dtype
    i8 = wire(grad_sync_dtype="int8")
    f8 = wire(grad_sync_dtype=jnp.float8_e5m2)
    f32 = wire(grad_sync_dtype=jnp.float32)
    assert i8["grad_scales"] > 0 and bf16["grad_scales"] == 0
    # i8 bytes/element = 1 payload + 4/QBLOCK scales — exact, no
    # rounding: bucket totals are QBLOCK multiples by construction
    assert i8["grad_scales"] * QBLOCK == i8["grad_payload"] * 4
    per_elt_i8 = Fraction(QBLOCK + 4, QBLOCK)
    assert Fraction(bf16["grad_sync"], i8["grad_sync"]) \
        == Fraction(2, 1) / per_elt_i8             # = 512/257
    assert Fraction(f32["grad_sync"], i8["grad_sync"]) \
        == Fraction(4, 1) / per_elt_i8             # = 1024/257
    assert f8["grad_sync"] == i8["grad_sync"]      # both 1-byte wires
    # param gather is never quantized (no error-feedback channel)
    assert i8["param_sync"] == bf16["param_sync"]
    # the flat plan reports its one hop under the dp axis, and the
    # top-level fields are exactly that hop
    assert set(i8["hops"]) == {"dp"}
    assert i8["hops"]["dp"]["grad_sync"] == i8["grad_sync"]


def test_hierarchical_wire_bytes_cross_slice_cut_exact():
    """The ``hier_*_sync`` modes' per-hop accounting: the slow (outer)
    hop's bytes — payload AND scales — are exactly ``1/dp_in`` of the
    flat plan's at the same wire dtype (the cross-slice cut); the fast
    (inner) hop carries the full bucket like the flat plan."""
    import jax.numpy as jnp

    from apex_tpu.contrib.optimizers import DistributedFusedAdam

    params = {"w": jnp.zeros((512, 256), jnp.bfloat16),
              "b": jnp.zeros((8192,), jnp.bfloat16)}

    def wire(**kw):
        sizes = kw.pop("axis_sizes", None)
        opt = DistributedFusedAdam(lr=1e-3, **kw)
        opt.init(params, world_size=4, axis_sizes=sizes)
        return opt.wire_bytes_per_step()

    flat = wire(grad_sync_dtype="int8")
    hier = wire(grad_sync_dtype="int8", dp_axes=("dp_out", "dp_in"),
                axis_sizes={"dp_out": 2, "dp_in": 2})
    inner, outer = hier["hops"]["dp_in"], hier["hops"]["dp_out"]
    # fast hop == the flat wire (full bucket, same dtype, same scales)
    assert inner["grad_sync"] == flat["grad_sync"]
    assert inner["param_sync"] == flat["param_sync"]
    # slow hop: exactly 1/dp_in of the flat plan, scales included
    assert outer["grad_payload"] * 2 == flat["grad_payload"]
    assert outer["grad_scales"] * 2 == flat["grad_scales"]
    assert outer["grad_sync"] * 2 == flat["grad_sync"]
    assert outer["param_sync"] * 2 == flat["param_sync"]
    # top-level fields sum the hops (total wire traffic of the step)
    assert hier["grad_sync"] == inner["grad_sync"] + outer["grad_sync"]
    # both hops stay at the compressed dtype: equal bytes/element
    # implies the slow hop never widened (3/2 = full + half buckets)
    assert hier["grad_payload"] * 2 == flat["grad_payload"] * 3
