"""The fused optimizers' update, the amp tail folded into it, and the
bucket plans beside them.

The optimizers keep a tree of per-leaf slots and update it a leaf at a
time (``_leaf_update`` behind ``OptimizerBase._dispatch``).  The
contract held here:

- **every optimizer against an oracle that shares none of its code**:
  ``tests/optimizer_oracles.py``, float64 NumPy from the reference's
  formulas, over a two-dtype tree, with ``clip_norm`` (torch's
  ``clip_grad_norm_`` rule, then the step) and with bf16 storage behind
  fp32 masters;
- **bit-exact vs optax.adamw in fp32** for FusedAdam (the audited bench
  baseline — a speed comparison is only meaningful if the two compute
  the same function);
- the amp path (``update_scaled``) folds unscale/clip/finite-vote into
  the same grad read with identical results to the separate sweeps,
  and commits on the vote ``finite_sync`` hands back;
- a non-finite step is a device-side NO-OP (params, state, step
  counter all unchanged);
- bucket plans (``optimizers/bucketing.py``: the layout of the ZeRO
  engine and the bucketed gradient syncs) pack, pad and unpack as the
  ``multi_tensor_*`` ops and the applier expect.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from optimizer_oracles import assert_matches_oracle, stepped
from optimizer_oracles import run as oracle_run

from apex_tpu.multi_tensor_apply import multi_tensor_applier
from apex_tpu.optimizers import (
    FusedAdagrad,
    FusedAdam,
    FusedLAMB,
    FusedNovoGrad,
    FusedSGD,
)
from apex_tpu.optimizers import bucketing
from apex_tpu.ops.multi_tensor import (
    multi_tensor_l2norm,
    multi_tensor_scale,
    tree_not_finite,
)

CLASSES = {"adam": FusedAdam, "sgd": FusedSGD, "lamb": FusedLAMB,
           "novograd": FusedNovoGrad, "adagrad": FusedAdagrad}
#: what the optimizer AND its oracle are built with; everything else is
#: each side's own default
HYPERS = {
    "adam": dict(lr=1e-2, weight_decay=0.01),
    "sgd": dict(lr=1e-2, momentum=0.9, weight_decay=0.01),
    "lamb": dict(lr=1e-2, weight_decay=0.01),
    "novograd": dict(lr=1e-2, weight_decay=0.01),
    "adagrad": dict(lr=1e-2, weight_decay=0.01),
}
OPTS = {name: functools.partial(CLASSES[name], **HYPERS[name])
        for name in CLASSES}

#: the slots of each optimizer's state that hold one array a leaf, under
#: the names the oracle returns them by
SLOTS = {"adam": ("exp_avg", "exp_avg_sq"), "sgd": ("momentum_buffer",),
         "lamb": ("exp_avg", "exp_avg_sq"),
         "novograd": ("exp_avg", "exp_avg_sq"), "adagrad": ("sum",)}

#: Adam/SGD/Adagrad steps are elementwise-only: two compositions of the
#: same step agree to the bit.  LAMB and NovoGrad reduce per-leaf norms
#: and any path with ``clip_norm`` is reduction-fed; XLA:CPU may order
#: a reduction differently from one program to the next (few-ulp
#: drift), so those get a tight allclose instead.
BITEXACT = {"adam", "sgd", "adagrad"}


def make_tree(seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return {
        "w": jnp.asarray(rng.randn(9, 17).astype(np.float32)).astype(dtype),
        "sub": {
            "b": jnp.asarray(rng.randn(33).astype(np.float32)).astype(dtype),
            # scalar leaf: exercises the shape-() packing path
            "s": jnp.asarray(np.float32(rng.randn())).astype(dtype),
        },
    }


def make_mixed_tree(seed=0):
    """fp32 and bf16 leaves interleaved → a two-bucket plan."""
    t = make_tree(seed)
    t["h"] = jnp.asarray(
        np.random.RandomState(seed + 1).randn(21).astype(np.float32)
    ).astype(jnp.bfloat16)
    t["sub"]["h2"] = jnp.asarray(
        np.random.RandomState(seed + 2).randn(5, 7).astype(np.float32)
    ).astype(jnp.bfloat16)
    return t


def grads_like(params, seed=7, dtype=None):
    rng = np.random.RandomState(seed)
    return jax.tree.map(
        lambda p: jnp.asarray(
            np.asarray(rng.randn(*p.shape), np.float32)).astype(
            dtype or p.dtype),
        params,
    )


def assert_trees(a, b, exact=True, err=""):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        xa, ya = np.asarray(x, np.float32), np.asarray(y, np.float32)
        if exact:
            np.testing.assert_array_equal(xa, ya, err_msg=err)
        else:
            np.testing.assert_allclose(xa, ya, rtol=1e-5, atol=1e-6,
                                       err_msg=err)


# --------------------------------------------------------------- the plan
class TestBucketPlan:
    def test_layout(self):
        t = make_mixed_tree()
        plan = bucketing.plan_of(t)
        assert len(plan.buckets) == 2  # one fp32 + one bf16 bucket
        assert {b.dtype for b in plan.buckets} == {"float32", "bfloat16"}
        # bucket order is the dtypes' first appearance in tree_flatten
        # order — deterministic for a fixed treedef
        first_seen = list(dict.fromkeys(plan.leaf_dtypes))
        assert [b.dtype for b in plan.buckets] == first_seen
        for b in plan.buckets:
            # leaves back-to-back, tail padded to the dtype tile
            off = 0
            for bl in b.leaves:
                assert bl.offset == off
                off += bl.size
            assert b.size == off
            assert b.total >= b.size and b.total % 128 == 0

    def test_plan_is_cached_and_hashable(self):
        t = make_mixed_tree()
        assert bucketing.plan_of(t) is bucketing.plan_of(
            jax.tree.map(lambda x: x + 1, t))
        hash(bucketing.plan_of(t))

    def test_pack_unpack_roundtrip(self):
        t = make_mixed_tree()
        plan = bucketing.plan_of(t)
        back = bucketing.unpack(plan, bucketing.pack(plan, t))
        for x, y in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
            assert x.dtype == y.dtype
            np.testing.assert_array_equal(
                np.asarray(x, np.float32), np.asarray(y, np.float32))

    def test_pad_region_is_zero(self):
        t = make_tree()
        plan = bucketing.plan_of(t)
        (arr,) = bucketing.pack(plan, t)
        b = plan.buckets[0]
        if b.pad:
            assert not np.asarray(arr[b.size:]).any()


# --------------------------------------------- the update vs its oracle
class TestBucketLeafParity:
    """Every optimizer's per-leaf update against the float64 oracle
    (the class keeps the name its cases had while they compared the
    per-leaf update with the bucket engine, so their node ids stand)."""

    @pytest.mark.parametrize("name", sorted(OPTS))
    @pytest.mark.parametrize("mixed", [False, True])
    def test_update_parity(self, name, mixed):
        params = make_mixed_tree() if mixed else make_tree()
        grads_seq = [grads_like(params, seed=7 + i) for i in range(3)]
        p, s = stepped(OPTS[name](), params, grads_seq)
        want = oracle_run(name, params, grads_seq, **HYPERS[name])
        # a bf16 leaf is rounded to storage every step (no master
        # here): held to optimizer_oracles.BF16_BAND, the rest to the
        # file's band (assert_trees(exact=False): rtol 1e-5, atol 1e-6)
        loose = [x.dtype == jnp.bfloat16 for x in jax.tree.leaves(params)]
        assert int(s.step) == 3
        assert [x.dtype for x in jax.tree.leaves(p)] == [
            x.dtype for x in jax.tree.leaves(params)]
        assert_matches_oracle(p, want["params"], loose,
                              err=f"{name} params vs oracle")
        for slot in SLOTS[name]:
            assert_matches_oracle(getattr(s, slot), want[slot], loose,
                                  err=f"{name} {slot} vs oracle")

    @pytest.mark.parametrize("name", sorted(OPTS))
    def test_clip_parity(self, name):
        """``clip_norm`` folded into the update's grad read ≡ clip the
        gradients by torch's ``clip_grad_norm_`` rule, then step (the
        norm of these gradients is ~13, so 0.5 clips every step)."""
        params = make_tree()
        grads_seq = [grads_like(params, seed=7 + i) for i in range(3)]
        p, s = stepped(OPTS[name](), params, grads_seq, clip_norm=0.5)
        want = oracle_run(name, params, grads_seq, clip_norm=0.5,
                          **HYPERS[name])
        assert_matches_oracle(p, want["params"],
                              err=f"{name} clip_norm vs oracle")
        for slot in SLOTS[name]:
            assert_matches_oracle(getattr(s, slot), want[slot],
                                  err=f"{name} clipped {slot} vs oracle")

    @pytest.mark.parametrize("name", sorted(OPTS))
    def test_master_weights_parity(self, name):
        """bf16 storage, fp32 masters: the master follows the oracle's
        unrounded trajectory, and the parameters handed back are the
        master rounded ONCE (the oracle's final value rounded once, to
        within ``optimizer_oracles.BF16_BAND``: the two roundings can
        fall either side of a tie)."""
        params = make_tree(dtype=jnp.bfloat16)
        grads_seq = [grads_like(params, seed=7 + i) for i in range(3)]
        p, s = stepped(OPTS[name](master_weights=True), params, grads_seq)
        want = oracle_run(name, params, grads_seq, master_weights=True,
                          **HYPERS[name])
        assert_matches_oracle(s.master, want["master"],
                              err=f"{name} master vs oracle")
        assert_trees(p, jax.tree.map(lambda m: m.astype(jnp.bfloat16),
                                     s.master),
                     exact=True, err=f"{name} params are the master cast")
        assert_matches_oracle(p, want["params"], [True] * 3,
                              err=f"{name} params vs oracle rounded once")


# -------------------------------------------------------- optax parity
class TestOptaxParity:
    @pytest.mark.parametrize("wd", [0.0, 0.01])
    def test_adamw_bit_exact_fp32(self, wd):
        """The bench A/B's correctness leg: FusedAdam computes
        bit-for-bit the same fp32 function as
        ``optax.adamw`` — so any measured speed gap is implementation,
        not numerics.  Run op-by-op (unjitted): each primitive compiles
        alone, so XLA cannot form different FMA groupings in the two
        trajectories — bit-exactness of the MATH, isolated from
        program-level codegen (the jitted comparison below)."""
        params = make_tree()
        grads = grads_like(params)
        opt = FusedAdam(lr=1e-2, weight_decay=wd)
        ox = optax.adamw(1e-2, b1=0.9, b2=0.999, eps=1e-8, weight_decay=wd)

        p_f, s_f = params, opt.init(params)
        p_o, s_o = params, ox.init(params)
        for _ in range(4):
            p_f, s_f = opt.update(grads, s_f, p_f)
            upd, s_o = ox.update(grads, s_o, p_o)
            p_o = optax.apply_updates(p_o, upd)
        assert_trees(p_f, p_o, exact=True, err="FusedAdam vs optax.adamw")

    @pytest.mark.parametrize("wd", [0.0, 0.01])
    def test_adamw_jitted_trajectory(self, wd):
        """Whole-step jitted, 4 steps: identical math, but two
        SEPARATELY compiled programs — XLA:CPU forms FMAs differently
        per program, so the trajectories may drift by ulps (measured
        ~3e-8 abs at step 2).  Pinned to a few-ulp band: a real
        numerics bug (wrong β association, dropped bias correction)
        shows up orders of magnitude above it."""
        params = make_tree()
        grads = grads_like(params)
        opt = FusedAdam(lr=1e-2, weight_decay=wd)
        ox = optax.adamw(1e-2, b1=0.9, b2=0.999, eps=1e-8, weight_decay=wd)

        step_f = jax.jit(lambda g, s, p: opt.update(g, s, p))

        def _o(g, s, p):
            upd, s = ox.update(g, s, p)
            return optax.apply_updates(p, upd), s

        step_o = jax.jit(_o)
        p_f, s_f = params, opt.init(params)
        p_o, s_o = params, ox.init(params)
        for _ in range(4):
            p_f, s_f = step_f(grads, s_f, p_f)
            p_o, s_o = step_o(grads, s_o, p_o)
        for x, y in zip(jax.tree.leaves(p_f), jax.tree.leaves(p_o)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=0, atol=5e-7)

    def test_adamw_bf16_storage_close_to_optax_fp32(self):
        """bf16 params: fp32 math inside, storage rounding outside —
        within one bf16 ulp of the fp32 optax trajectory per step."""
        params32 = make_tree()
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params32)
        grads = grads_like(params32)
        opt = FusedAdam(lr=1e-2, weight_decay=0.01)
        p_f, s_f = opt.update(grads, opt.init(params), params)
        ox = optax.adamw(1e-2, weight_decay=0.01)
        upd, _ = ox.update(grads, ox.init(params32), params32)
        p_o = optax.apply_updates(params32, upd)
        for x, y in zip(jax.tree.leaves(p_f), jax.tree.leaves(p_o)):
            np.testing.assert_allclose(
                np.asarray(x, np.float32), np.asarray(y), rtol=1e-2)


# ------------------------------------------------------ the fused amp path
class TestScaledPath:
    @pytest.mark.parametrize("name", sorted(OPTS))
    def test_update_scaled_matches_separate_sweeps(self, name):
        """unscale+clip+vote folded into the grad read ≡ the explicit
        sweep composition (scaler.unscale → clip_grad_norm_ → update
        predicated on the vote)."""
        from apex_tpu.amp import DynamicLossScaler
        from apex_tpu.contrib.clip_grad import clip_grad_norm_

        params = make_tree()
        scaler = DynamicLossScaler(init_scale=1024.0)
        sstate = scaler.init()
        grads16 = jax.tree.map(
            lambda g: (g * sstate.loss_scale).astype(jnp.float16),
            grads_like(params))
        opt = OPTS[name]()
        p1, s1, fin = opt.update_scaled(
            grads16, opt.init(params), params, scale=sstate.loss_scale,
            clip_norm=1.0)
        assert bool(fin)
        g, fin2 = scaler.unscale(sstate, grads16)
        g, _ = clip_grad_norm_(g, 1.0)
        p2, s2 = opt.update(g, opt.init(params), params, grads_finite=fin2)
        assert bool(fin2)
        # the clip coefficient is reduction-fed on both sides
        assert_trees(p1, p2, exact=False,
                     err=f"{name} fused vs composed amp tail")
        assert_trees(jax.tree.leaves(s1), jax.tree.leaves(s2), exact=False,
                     err=f"{name} fused vs composed amp tail: state")

    @pytest.mark.parametrize("name", sorted(OPTS))
    def test_nonfinite_step_is_noop(self, name):
        """grads_finite=False: params, state slots, and the step counter
        all hold (the capturable noop_flag semantics)."""
        params = make_tree()
        grads = grads_like(params)
        bad = jax.tree.map(lambda g: g.at[..., 0].set(jnp.inf)
                           if g.ndim else g, grads)
        opt = OPTS[name]()
        state0 = opt.init(params)
        # one clean step first so momentum buffers are nonzero
        p1, s1, fin1 = opt.update_scaled(grads, state0, params)
        assert bool(fin1)
        p2, s2, fin2 = opt.update_scaled(bad, s1, p1)
        assert not bool(fin2)
        assert_trees(p2, p1, exact=True, err=f"{name} params moved on inf")
        assert int(s2.step) == int(s1.step)
        assert_trees(jax.tree.leaves(s2), jax.tree.leaves(s1), exact=True,
                     err=f"{name} state moved on inf")

    @pytest.mark.parametrize("name", sorted(OPTS))
    @pytest.mark.parametrize("answer", [False, True])
    def test_finite_sync_decides_the_commit(self, name, answer):
        """``finite_sync`` is handed this rank's all-finite vote and
        what it answers is the vote: the one ``update_scaled`` returns
        and the one the commit is predicated on.  The gradients here
        are finite, so a False can only be another rank's overflow
        (``make_train_step`` passes the model-parallel agreement): the
        step is then a no-op here too; a True commits exactly the step
        taken without a sync."""
        params = make_tree()
        grads = grads_like(params)
        opt = OPTS[name]()
        # one clean step first so momentum buffers are nonzero
        p1, s1, _ = opt.update_scaled(grads, opt.init(params), params)
        seen = []

        def sync(local):
            seen.append(bool(local))
            return jnp.bool_(answer)

        p2, s2, fin = opt.update_scaled(grads, s1, p1, scale=2.0,
                                        finite_sync=sync)
        assert seen == [True] and bool(fin) is answer
        if answer:
            want_p, want_s, _ = opt.update_scaled(grads, s1, p1, scale=2.0)
        else:
            want_p, want_s = p1, s1
        assert int(s2.step) == int(want_s.step) == 1 + answer
        assert_trees(p2, want_p, exact=True,
                     err=f"{name} params after finite_sync -> {answer}")
        assert_trees(jax.tree.leaves(s2), jax.tree.leaves(want_s),
                     exact=True,
                     err=f"{name} state after finite_sync -> {answer}")

    def test_scaler_integration(self):
        """update_scaled's vote drives DynamicLossScaler.update: backoff
        on inf, growth bookkeeping on clean steps."""
        from apex_tpu.amp import DynamicLossScaler

        params = make_tree()
        scaler = DynamicLossScaler(init_scale=2.0 ** 10)
        sstate = scaler.init()
        opt = FusedAdam(lr=1e-2)
        ostate = opt.init(params)
        bad = jax.tree.map(lambda g: g * jnp.inf, grads_like(params))
        p, s, fin = opt.update_scaled(bad, ostate, params,
                                      scale=sstate.loss_scale)
        s2 = scaler.update(sstate, fin)
        assert float(s2.loss_scale) < float(sstate.loss_scale)


# ------------------------------------- optimizers outside the fused tail
class TestUpdateScaledRouting:
    def test_swa_routes_through_its_update_override(self):
        """``FusedAdamSWA`` overrides ``update`` with extra SWA state
        the fused tail doesn't maintain: it declares
        ``supports_update_scaled = False`` and the scaled train-step
        tail must take the explicit sweep path — calling the override,
        so the SWA average and n_averaged actually advance."""
        from apex_tpu.amp import DynamicLossScaler
        from apex_tpu.contrib.openfold_triton import FusedAdamSWA
        from apex_tpu.models.gpt import _apply_scaled_update

        opt = FusedAdamSWA(lr=1e-2)
        assert not opt.supports_update_scaled

        params = make_tree()
        scaler = DynamicLossScaler(init_scale=4.0)
        sstate = scaler.init()
        state = opt.init(params)
        grads = jax.tree.map(lambda g: g * sstate.loss_scale,
                             grads_like(params))
        new_p, new_state, new_sstate = _apply_scaled_update(
            scaler, sstate, grads, opt, state, params, sync_axes=[])
        assert int(new_state.n_averaged) == 1
        assert int(new_state.adam.step) == 1

    def test_plain_optimizers_support_the_fused_tail(self):
        for name, mk in OPTS.items():
            assert mk().supports_update_scaled, name


# ----------------------------------------------- sharded clip agreement
class TestClipSumsqReduce:
    def test_sharded_and_replicated_leaves_agree_with_oracle(self):
        """Inside a tp=2 shard_map, a tp-sharded leaf's Σx² must psum
        over tp while a replicated leaf's must NOT — the grouped
        reduction :func:`models.gpt.clip_sumsq_reduce` builds from the
        PartitionSpecs.  The oracle is the plain unsharded Σx²."""
        from jax.sharding import Mesh, PartitionSpec as P

        from apex_tpu.models.gpt import clip_sumsq_reduce

        mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
        specs = {"w": P("tp", None), "b": P(None)}
        grads = {
            "w": jnp.arange(8.0, dtype=jnp.float32).reshape(4, 2),
            "b": jnp.asarray([3.0, -1.0], jnp.float32),
        }
        oracle = sum(float(jnp.sum(jnp.square(g)))
                     for g in jax.tree.leaves(grads))
        reduce = clip_sumsq_reduce(specs)

        def local(g):
            sq = [jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)]
            return reduce(sq)

        total = jax.shard_map(
            local, mesh=mesh, in_specs=(specs,), out_specs=P(),
            check_vma=False)(grads)
        np.testing.assert_allclose(np.asarray(total), oracle, rtol=1e-6)

    def test_engine_clip_inside_shard_map_matches_unsharded(self):
        """The whole fused pass under a tp shard_map: update with
        clip_norm + the spec-built sumsq_reduce on sharded params
        equals the unsharded update with clip_norm."""
        from jax.sharding import Mesh, PartitionSpec as P

        from apex_tpu.models.gpt import clip_sumsq_reduce

        params = {"w": jnp.asarray(
            np.random.RandomState(0).randn(8, 6), jnp.float32),
            "b": jnp.asarray(np.random.RandomState(1).randn(6),
                             jnp.float32)}
        grads = grads_like(params, seed=3)
        specs = {"w": P("tp", None), "b": P(None)}
        opt = FusedAdam(lr=1e-2, weight_decay=0.01)
        state = opt.init(params)

        p_ref, _ = opt.update(grads, state, params, clip_norm=0.1)

        mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
        reduce = clip_sumsq_reduce(specs)
        sspec = type(state)(step=P(), exp_avg=specs, exp_avg_sq=specs,
                            master=None)

        def local(p, s, g):
            new_p, _ = opt.update(g, s, p, clip_norm=0.1,
                                  sumsq_reduce=reduce)
            return new_p

        p_sh = jax.shard_map(
            local, mesh=mesh, in_specs=(specs, sspec, specs),
            out_specs=specs, check_vma=False)(params, state, grads)
        assert_trees(jax.device_get(p_sh), jax.device_get(p_ref),
                     exact=False, err="sharded clip vs unsharded oracle")


# --------------------------------------- multi_tensor ops on bucket views
class TestMultiTensorBucketViews:
    def test_l2norm_per_leaf_matches_tree(self):
        t = make_tree()
        plan = bucketing.plan_of(t)
        b = bucketing.Buckets(plan, bucketing.pack(plan, t))
        g1, per1 = multi_tensor_l2norm(t, per_tensor=True)
        g2, per2 = multi_tensor_l2norm(b, per_tensor=True)
        # same sum of squares, but over a leaf's own shape on one side
        # and its flat bucket slice on the other: XLA:CPU vectorizes the
        # two reductions differently (1 ulp apart on jax 0.9), so the
        # contract is fp32 rounding, not bit equality
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-6)
        assert len(per1) == len(per2) == len(jax.tree.leaves(t))
        for a, c in zip(per1, per2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=1e-6)

    def test_scale_on_buckets_returns_buckets(self):
        t = make_tree()
        plan = bucketing.plan_of(t)
        b = bucketing.Buckets(plan, bucketing.pack(plan, t))
        out, found = multi_tensor_scale(b, 2.0)
        assert isinstance(out, bucketing.Buckets)
        assert not bool(found)
        assert_trees(bucketing.unpack(out.plan, out.arrays),
                     jax.tree.map(lambda x: x * 2, t), exact=True)


# ----------------------------------------------- the applier conventions
class TestMultiTensorApplier:
    """Parity with the reference calling convention
    ``multi_tensor_applier(op, noop_flag, tensor_lists, *args)``:
    the returned flag accumulates (OR) across calls exactly as the
    kernels' shared noop buffer does."""

    def test_returns_result_and_flag(self):
        t = make_tree()
        out, flag = multi_tensor_applier(multi_tensor_scale, None, [t], 2.0)
        assert flag.dtype == jnp.int32 and int(flag) == 0
        assert_trees(out, jax.tree.map(lambda x: x * 2, t), exact=True)

    def test_found_inf_sets_flag(self):
        t = {"a": jnp.asarray([1.0, jnp.nan])}
        _, flag = multi_tensor_applier(multi_tensor_scale, 0, [t], 1.0)
        assert int(flag) == 1

    def test_flag_is_sticky_across_calls(self):
        """Reference: a set noop buffer stays set — chained clean calls
        cannot clear a previous call's overflow vote."""
        t = make_tree()
        _, flag = multi_tensor_applier(
            multi_tensor_scale, jnp.int32(1), [t], 1.0)
        assert int(flag) == 1
        _, flag = multi_tensor_applier(multi_tensor_scale, flag, [t], 1.0)
        assert int(flag) == 1

    def test_voteless_op_passes_flag_through(self):
        t = make_tree()
        norm, flag = multi_tensor_applier(multi_tensor_l2norm, None, [t])
        assert norm.ndim == 0 and int(flag) == 0
        _, flag = multi_tensor_applier(multi_tensor_l2norm, 1, [t])
        assert int(flag) == 1
