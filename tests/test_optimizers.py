"""Optimizer parity tests — mirrors tests/L0/run_optimizers of the
reference, which checks fused optimizers against torch.optim references
(``test_adam.py:52-63``, ``test_fused_optimizer.py``, ``test_lamb.py``).
Here torch (CPU) is the oracle for Adam/AdamW/SGD/Adagrad, and the
float64 NumPy references of ``tests/optimizer_oracles.py`` are the
oracle for LAMB (as the reference's test_lamb.py does), for NovoGrad
and for ``clip_norm`` under parameter groups."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
from optimizer_oracles import assert_matches_oracle, stepped
from optimizer_oracles import run as oracle_run

from apex_tpu.optimizers import (
    FusedAdagrad,
    FusedAdam,
    FusedLAMB,
    FusedNovoGrad,
    FusedSGD,
)


def make_tree(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "a": rng.randn(7, 5).astype(np.float32),
        "b": {"w": rng.randn(11).astype(np.float32), "s": rng.randn(1).astype(np.float32)},
    }


def tree_to_torch(tree):
    return [torch.nn.Parameter(torch.tensor(x)) for x in jax.tree.leaves(tree)]


def set_torch_grads(tparams, gtree):
    for p, g in zip(tparams, jax.tree.leaves(gtree)):
        p.grad = torch.tensor(np.asarray(g))


def assert_tree_close(jtree, tparams, rtol=1e-5, atol=1e-6):
    for j, t in zip(jax.tree.leaves(jtree), tparams):
        np.testing.assert_allclose(
            np.asarray(j), t.detach().numpy(), rtol=rtol, atol=atol
        )


NSTEPS = 5


class TestFusedAdam:
    @pytest.mark.parametrize("wd", [0.0, 0.1])
    def test_adamw_parity(self, wd):
        opt = FusedAdam(lr=1e-2, weight_decay=wd, adam_w_mode=True)
        params, tparams = None, None
        p = jax.tree.map(jnp.asarray, make_tree())
        t = tree_to_torch(p)
        topt = torch.optim.AdamW(t, lr=1e-2, betas=(0.9, 0.999), eps=1e-8, weight_decay=wd)
        params, tparams = run_pair_with(opt, topt, p, t)
        assert_tree_close(params, tparams, rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("wd", [0.0, 0.1])
    def test_adam_l2_parity(self, wd):
        opt = FusedAdam(lr=1e-2, weight_decay=wd, adam_w_mode=False)
        p = jax.tree.map(jnp.asarray, make_tree())
        t = tree_to_torch(p)
        topt = torch.optim.Adam(t, lr=1e-2, betas=(0.9, 0.999), eps=1e-8, weight_decay=wd)
        params, tparams = run_pair_with(opt, topt, p, t)
        assert_tree_close(params, tparams, rtol=1e-4, atol=1e-5)

    def test_skip_on_overflow(self):
        opt = FusedAdam(lr=1e-2)
        params = jax.tree.map(jnp.asarray, make_tree())
        state = opt.init(params)
        grads = jax.tree.map(lambda x: jnp.full(x.shape, jnp.inf), params)
        new_params, new_state = opt.update(grads, state, params, grads_finite=jnp.bool_(False))
        for a, b in zip(jax.tree.leaves(new_params), jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(new_state.step) == 0

    def test_master_weights_bf16(self):
        opt = FusedAdam(lr=1e-2, master_weights=True)
        params32 = jax.tree.map(jnp.asarray, make_tree())
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params32)
        state = opt.init(params)
        assert state.master is not None
        grads = jax.tree.map(lambda x: jnp.ones(x.shape, jnp.bfloat16), params)
        new_params, new_state = opt.update(grads, state, params)
        # params remain bf16; master stays fp32 and moved
        for p in jax.tree.leaves(new_params):
            assert p.dtype == jnp.bfloat16
        for m in jax.tree.leaves(new_state.master):
            assert m.dtype == jnp.float32

    def test_jit_update(self):
        opt = FusedAdam(lr=1e-2)
        params = jax.tree.map(jnp.asarray, make_tree())
        state = opt.init(params)
        grads = jax.tree.map(jnp.ones_like, params)
        step = jax.jit(lambda g, s, p: opt.update(g, s, p))
        p1, s1 = step(grads, state, params)
        p2, s2 = opt.update(grads, state, params)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def run_pair_with(opt, topt, params, tparams, nsteps=NSTEPS, seed=0, **kw):
    state = opt.init(params)
    rng = np.random.RandomState(seed + 100)
    for _ in range(nsteps):
        gnp = jax.tree.map(lambda x: rng.randn(*np.asarray(x).shape).astype(np.float32), params)
        grads = jax.tree.map(jnp.asarray, gnp)
        params, state = opt.update(grads, state, params, **kw)
        set_torch_grads(tparams, gnp)
        topt.step()
    return params, tparams


class TestFusedSGD:
    @pytest.mark.parametrize("momentum,nesterov,wd", [(0.0, False, 0.0), (0.9, False, 0.0), (0.9, True, 0.0), (0.9, False, 0.05)])
    def test_sgd_parity(self, momentum, nesterov, wd):
        opt = FusedSGD(lr=0.1, momentum=momentum, nesterov=nesterov, weight_decay=wd)
        p = jax.tree.map(jnp.asarray, make_tree())
        t = tree_to_torch(p)
        topt = torch.optim.SGD(t, lr=0.1, momentum=momentum, nesterov=nesterov, weight_decay=wd)
        params, tparams = run_pair_with(opt, topt, p, t)
        assert_tree_close(params, tparams, rtol=1e-5, atol=1e-6)


class TestFusedAdagrad:
    @pytest.mark.parametrize("wd", [0.0, 0.1])
    def test_adagrad_parity(self, wd):
        # torch adagrad: p -= lr * g / (sqrt(h)+eps) with L2 wd folded in —
        # matches ADAGRAD_MODE_0
        opt = FusedAdagrad(lr=0.1, eps=1e-10, weight_decay=wd)
        p = jax.tree.map(jnp.asarray, make_tree())
        t = tree_to_torch(p)
        topt = torch.optim.Adagrad(t, lr=0.1, eps=1e-10, weight_decay=wd)
        params, tparams = run_pair_with(opt, topt, p, t)
        assert_tree_close(params, tparams, rtol=1e-4, atol=1e-5)


def random_grads(params, nsteps=NSTEPS, seed=3, scale=1.0):
    rng = np.random.RandomState(seed)
    return [jax.tree.map(
        lambda x: jnp.asarray(rng.randn(*x.shape).astype(np.float32) * scale),
        params) for _ in range(nsteps)]


class TestFusedLAMB:
    @pytest.mark.parametrize("wd,use_nvlamb", [(0.01, False), (0.0, False), (0.0, True)])
    def test_lamb_vs_numpy(self, wd, use_nvlamb):
        hp = dict(lr=1e-2, betas=(0.9, 0.999), eps=1e-6, weight_decay=wd,
                  use_nvlamb=use_nvlamb)
        params = jax.tree.map(jnp.asarray, make_tree())
        grads_seq = random_grads(params, scale=5.0)
        p, _ = stepped(FusedLAMB(**hp), params, grads_seq)
        ref = oracle_run("lamb", params, grads_seq, **hp)
        assert_matches_oracle(p, ref["params"], rtol=2e-4, atol=2e-5)


class TestFusedNovoGrad:
    @staticmethod
    def _against_oracle(**hp):
        params = jax.tree.map(jnp.asarray, make_tree())
        grads_seq = random_grads(params)
        p, state = stepped(FusedNovoGrad(**hp), params, grads_seq)
        ref = oracle_run("novograd", params, grads_seq, **hp)
        assert_matches_oracle(p, ref["params"])
        assert_matches_oracle(state.exp_avg, ref["exp_avg"])
        # the second moment is one blended norm a tensor
        assert_matches_oracle(state.exp_avg_sq, ref["exp_avg_sq"])

    @pytest.mark.parametrize("init_zero", [False, True])
    @pytest.mark.parametrize("grad_averaging", [True, False])
    @pytest.mark.parametrize("wd", [0.0, 0.01])
    def test_novograd_vs_numpy(self, wd, grad_averaging, init_zero):
        self._against_oracle(lr=1e-2, weight_decay=wd,
                             grad_averaging=grad_averaging,
                             init_zero=init_zero)

    @pytest.mark.parametrize("mode", [dict(norm_type=0),
                                      dict(reg_inside_moment=True)],
                             ids=["inf-norm", "reg-inside-moment"])
    def test_novograd_modes_vs_numpy(self, mode):
        """The L-inf blend (``β2·gn + (1-β2)·max|g|``) and
        MOMENT_MODE_0 (decay and normalisation inside the moment)."""
        self._against_oracle(lr=1e-2, weight_decay=0.01, **mode)

    def test_novograd_runs_and_descends(self):
        # quadratic bowl: params should move toward zero
        opt = FusedNovoGrad(lr=0.05, weight_decay=0.0)
        params = {"w": jnp.asarray(np.ones(16, np.float32) * 3)}
        state = opt.init(params)
        for _ in range(50):
            grads = jax.tree.map(lambda p: 2 * p, params)
            params, state = opt.update(grads, state, params)
        assert np.abs(np.asarray(params["w"])).max() < 3.0

    def test_norm_blend_init(self):
        # first step with init from grad norm: v1 = ||g||
        opt = FusedNovoGrad(lr=0.1)
        params = {"w": jnp.asarray(np.ones(4, np.float32))}
        state = opt.init(params)
        g = {"w": jnp.asarray(np.full(4, 2.0, np.float32))}
        _, state = opt.update(g, state, params)
        expected = np.sqrt(4 * 4.0)  # ||g|| = 4
        np.testing.assert_allclose(float(jax.tree.leaves(state.exp_avg_sq)[0]), expected, rtol=1e-5)


class TestParamGroups:
    """Functional param_groups (reference optimizers iterate per-group
    lr/weight_decay): path->group mapping + per-group overrides."""

    def _groups(self, path, leaf):
        return "no_decay" if ("bias" in path or "norm" in path) else "default"

    @pytest.mark.parametrize("name,cls,hp", [
        ("adam", FusedAdam, dict(weight_decay=0.1)),
        ("lamb", FusedLAMB, dict(weight_decay=0.1, max_grad_norm=1e9)),
        ("sgd", FusedSGD, dict(lr=0.1, momentum=0.9, weight_decay=0.1)),
        ("adagrad", FusedAdagrad, dict(weight_decay=0.1)),
    ], ids=["adam", "lamb", "sgd", "adagrad"])
    def test_clip_norm_with_groups_vs_oracle(self, name, cls, hp):
        """``clip_norm`` is ONE global norm over every group's leaves
        (torch's ``clip_grad_norm_`` rule), and each group then steps at
        its own rate: ``head`` at its absolute ``lr`` whatever the
        schedule says, ``bias`` at half the runtime lr without decay,
        the rest at the runtime lr."""
        params = {"w": jnp.asarray(make_tree()["a"]),
                  "head": jnp.asarray(make_tree(1)["a"]),
                  "bias": jnp.asarray(make_tree(2)["b"]["w"])}
        groups = {"head": {"lr": 0.05},
                  "bias": {"lr_scale": 0.5, "weight_decay": 0.0}}
        opt = cls(param_group_fn=lambda path, leaf: path[2:-2],
                  group_hypers=groups, **hp)
        grads_seq = random_grads(params, nsteps=3)   # norm ~9: 1.0 clips
        p, _ = stepped(opt, params, grads_seq, lr=0.02, clip_norm=1.0)
        ref = oracle_run(
            name, params, grads_seq, clip_norm=1.0,
            leaf_hypers=[groups.get(k, {}) for k in sorted(params)],
            **dict(hp, lr=0.02))
        assert_matches_oracle(p, ref["params"])

    def test_adam_no_decay_group(self):
        from apex_tpu.optimizers import FusedAdam

        params = {"w": jnp.ones((4, 4)), "bias": jnp.ones((4,)),
                  "norm_scale": jnp.ones((4,))}
        grads = jax.tree.map(jnp.zeros_like, params)  # wd effect only

        grouped = FusedAdam(lr=0.1, weight_decay=0.5,
                            param_group_fn=self._groups,
                            group_hypers={"no_decay": {"weight_decay": 0.0}})
        st = grouped.init(params)
        p2, _ = grouped.update(grads, st, params)
        # zero grad + AdamW: p -= lr*wd*p only where decay applies
        np.testing.assert_allclose(np.asarray(p2["w"]), 0.95, rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(p2["bias"]), 1.0)
        np.testing.assert_array_equal(np.asarray(p2["norm_scale"]), 1.0)

    def test_adam_per_group_lr(self):
        from apex_tpu.optimizers import FusedAdam

        params = {"w": jnp.ones((4,)), "head_w": jnp.ones((4,))}
        grads = {"w": jnp.full((4,), 0.1), "head_w": jnp.full((4,), 0.1)}
        opt = FusedAdam(lr=0.1, weight_decay=0.0,
                        param_group_fn=lambda p, l: "head" if "head" in p else "body",
                        group_hypers={"head": {"lr": 0.0}})
        st = opt.init(params)
        p2, _ = opt.update(grads, st, params)
        assert float(p2["w"][0]) != 1.0
        np.testing.assert_array_equal(np.asarray(p2["head_w"]), 1.0)  # lr=0

    def test_ungrouped_matches_hand_oracle(self):
        """No param_group_fn → exact AdamW numerics (pins the default
        code path against a hand-computed oracle)."""
        from apex_tpu.optimizers import FusedAdam

        params = {"w": jnp.asarray([1.0, 2.0])}
        grads = {"w": jnp.asarray([0.1, -0.2])}
        a = FusedAdam(lr=0.01, weight_decay=0.01)
        pa, _ = a.update(grads, a.init(params), params)

        g = np.array([0.1, -0.2]); p = np.array([1.0, 2.0])
        m = 0.1 * g; v = 0.001 * g * g
        u = (m / (1 - 0.9)) / (np.sqrt(v / (1 - 0.999)) + 1e-8) + 0.01 * p
        np.testing.assert_allclose(np.asarray(pa["w"]), p - 0.01 * u, rtol=1e-6)

    def test_lr_scale_composes_with_schedule(self):
        """lr_scale multiplies the runtime lr (the schedule-friendly
        per-group knob); absolute 'lr' replaces it."""
        from apex_tpu.optimizers import FusedAdam

        params = {"w": jnp.ones((4,)), "head": jnp.ones((4,))}
        grads = {"w": jnp.full((4,), 0.1), "head": jnp.full((4,), 0.1)}
        opt = FusedAdam(lr=999.0, weight_decay=0.0,
                        param_group_fn=lambda p, l: "head" if "head" in p else "body",
                        group_hypers={"head": {"lr_scale": 0.5}})
        st = opt.init(params)
        runtime_lr = 0.01
        p2, _ = opt.update(grads, st, params, lr=runtime_lr)
        dw = 1.0 - float(p2["w"][0])      # stepped at runtime lr
        dh = 1.0 - float(p2["head"][0])   # stepped at 0.5 * runtime lr
        np.testing.assert_allclose(dh, dw * 0.5, rtol=1e-5)

    def test_typod_group_name_raises(self):
        from apex_tpu.optimizers import FusedAdam

        params = {"w": jnp.ones((2,))}
        grads = {"w": jnp.ones((2,))}
        opt = FusedAdam(lr=0.1, param_group_fn=lambda p, l: "body",
                        group_hypers={"no-decay": {"weight_decay": 0.0}})
        with pytest.raises(ValueError, match="no-decay"):
            opt.update(grads, opt.init(params), params)

    def test_typod_override_key_raises(self):
        """A typo'd override key ('weight_dacay') must fail loudly, not
        be silently ignored by the h.get() lookups."""
        from apex_tpu.optimizers import FusedAdam, FusedSGD

        params = {"w": jnp.ones((2,))}
        grads = {"w": jnp.ones((2,))}
        opt = FusedAdam(lr=0.1, param_group_fn=lambda p, l: "body",
                        group_hypers={"body": {"weight_dacay": 0.0}})
        with pytest.raises(ValueError, match="weight_dacay"):
            opt.update(grads, opt.init(params), params)
        # optimizer-specific keys are allowed only where that optimizer
        # reads them: momentum is FusedSGD's, not FusedAdam's
        opt2 = FusedAdam(lr=0.1, param_group_fn=lambda p, l: "body",
                         group_hypers={"body": {"momentum": 0.5}})
        with pytest.raises(ValueError, match="momentum"):
            opt2.update(grads, opt2.init(params), params)
        opt3 = FusedSGD(lr=0.1, momentum=0.9, param_group_fn=lambda p, l: "body",
                        group_hypers={"body": {"momentum": 0.5}})
        opt3.update(grads, opt3.init(params), params)  # valid for SGD

    def test_lamb_trust_ratio_exclusion(self):
        from apex_tpu.optimizers import FusedLAMB

        params = {"w": jnp.full((8,), 2.0), "ln_g": jnp.full((8,), 2.0)}
        grads = {"w": jnp.full((8,), 0.3), "ln_g": jnp.full((8,), 0.3)}
        opt = FusedLAMB(
            lr=0.1, weight_decay=0.1, max_grad_norm=1e9,
            param_group_fn=lambda p, l: "ln" if p.startswith("['ln") else "w",
            group_hypers={"ln": {"use_trust_ratio": False, "weight_decay": 0.0}})
        st = opt.init(params)
        p2, _ = opt.update(grads, st, params)

        # oracle: ln_g takes a plain Adam-style step (no trust ratio, no wd)
        bc1, bc2 = 1 - 0.9, 1 - 0.999
        m = 0.1 * 0.3
        v = 0.001 * 0.3 ** 2
        u = (m / bc1) / (np.sqrt(v / bc2) + 1e-6)
        np.testing.assert_allclose(np.asarray(p2["ln_g"]), 2.0 - 0.1 * u, rtol=1e-5)
        # w uses the trust ratio: ||p||/||u_w|| scaling, so a different step
        assert not np.allclose(np.asarray(p2["w"]), np.asarray(p2["ln_g"]))

    def test_sgd_per_group_momentum_and_decay(self):
        from apex_tpu.optimizers import FusedSGD

        params = {"w": jnp.ones((4,)), "bn_scale": jnp.ones((4,))}
        grads = {"w": jnp.full((4,), 0.1), "bn_scale": jnp.full((4,), 0.1)}
        opt = FusedSGD(lr=0.1, momentum=0.9, weight_decay=0.5,
                       param_group_fn=lambda p, l: "bn" if "bn" in p else "w",
                       group_hypers={"bn": {"weight_decay": 0.0, "momentum": 0.0}})
        st = opt.init(params)
        p2, st = opt.update(grads, st, params)
        # bn: plain SGD, no decay: p - lr*g
        np.testing.assert_allclose(np.asarray(p2["bn_scale"]), 1.0 - 0.1 * 0.1, rtol=1e-6)
        # w: wd folded in before momentum; first step buf = g
        np.testing.assert_allclose(np.asarray(p2["w"]), 1.0 - 0.1 * (0.1 + 0.5), rtol=1e-6)
        # second step, exact: buf=0.6 (first step), g2 = 0.1 + 0.5*0.94
        # = 0.57, steady = 0.9*0.6 + 0.57 = 1.11, p3 = 0.94 - 0.1*1.11
        p3, st = opt.update(grads, st, p2)
        np.testing.assert_allclose(np.asarray(p3["w"]), 0.94 - 0.111, rtol=1e-6)
        # bn stays momentum-free: another plain lr*g step
        np.testing.assert_allclose(np.asarray(p3["bn_scale"]), 0.99 - 0.01, rtol=1e-6)

    def test_adagrad_no_decay_group(self):
        from apex_tpu.optimizers import FusedAdagrad

        params = {"w": jnp.ones((4,)), "b": jnp.ones((4,))}
        opt = FusedAdagrad(lr=0.1, weight_decay=0.5,
                           param_group_fn=lambda p, l: "b" if p == "['b']" else "w",
                           group_hypers={"b": {"weight_decay": 0.0}})
        st = opt.init(params)
        g1 = {"w": jnp.full((4,), 0.1), "b": jnp.full((4,), 0.1)}
        p2, st = opt.update(g1, st, params)
        # zero grad: only weight decay moves params — the no-decay group
        # must hold still (first-step adagrad normalizes to sign(g), so
        # the wd difference is only visible from step 2 on)
        g0 = jax.tree.map(jnp.zeros_like, g1)
        p3, st = opt.update(g0, st, p2)
        np.testing.assert_array_equal(np.asarray(p3["b"]), np.asarray(p2["b"]))
        assert not np.allclose(np.asarray(p3["w"]), np.asarray(p2["w"]))
