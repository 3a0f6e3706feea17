"""Elastic fault-tolerant training (`apex_tpu.resilience.elastic`).

The executable spec of the TorchTitan-class scenarios on the virtual
8-device CPU mesh:

- the SCENARIO MATRIX: gpt × {replicated, ZeRO, ZeRO+int8 sync} ×
  {same-world, shrink, grow} resume, each asserting loss-trajectory
  continuation against the uninterrupted run (and bitwise state at the
  saved world);
- pod-scale chaos: kill-one-host-of-N → elastic resume at the smaller
  world; a wedged collective (ONE rank stalled inside the compiled
  step) → the step watchdog notices, drains, and reports;
- the step watchdog's heartbeat/deadline/drain contract and the
  supervisor restart-backoff schedule.

Everything here rides the quick tier: tiny model, per-(mode, world)
step compiles shared across the matrix via a module-scoped cache.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from apex_tpu import io, resilience
from apex_tpu.contrib.optimizers import DistributedFusedAdam
from apex_tpu.models.gpt import (
    GPTConfig, init_params, make_train_step, param_specs,
)
from apex_tpu.optimizers import FusedAdam
from apex_tpu.resilience import (
    ChaosHostKilled,
    ChaosMonkey,
    ChaosPlan,
    ElasticRunController,
    StepGuard,
    StepWatchdog,
    restart_backoff,
    restore_elastic_checkpoint,
    save_elastic_checkpoint,
)

CFG = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                num_attention_heads=2, max_seq_len=16,
                compute_dtype=jnp.float32)
BATCH, SEQ = 8, 16

MODES = ("replicated", "zero", "zero_int8")
#: transition -> (save world, resume world)
TRANSITIONS = {"same": (2, 2), "shrink": (4, 2), "grow": (2, 4)}


def batch(i):
    """Step ``i``'s global batch — a function of the step index alone,
    so runs at different dp worlds consume identical data."""
    rng = np.random.RandomState(1000 + i)
    d = rng.randint(0, CFG.vocab_size, size=(BATCH, SEQ + 1))
    return jnp.asarray(d[:, :-1]), jnp.asarray(d[:, 1:])


@pytest.fixture(scope="module")
def rig(devices8):
    """(optimizer, fresh state, compiled step, fresh params) per
    (mode, world) — cached so the 9 matrix cells share 6 compiles."""
    cache = {}

    def get(mode, world):
        key = (mode, world)
        if key not in cache:
            mesh = Mesh(np.array(devices8[:world]).reshape(world, 1),
                        ("dp", "tp"))
            params0 = init_params(CFG, jax.random.PRNGKey(0))
            if mode == "replicated":
                opt = FusedAdam(lr=1e-2, weight_decay=0.01)
                state0 = opt.init(params0)
            else:
                opt = DistributedFusedAdam(
                    lr=1e-2, weight_decay=0.01, axis_name="dp",
                    grad_sync_dtype="int8" if mode == "zero_int8" else None)
                state0 = opt.init(params0, world_size=world,
                                  param_specs=param_specs(CFG),
                                  axis_sizes={"tp": 1})
            step = make_train_step(CFG, opt, mesh)
            cache[key] = (opt, state0, step, params0)
        return cache[key]

    return get


_ORACLES = {}


def oracle(rig, mode, world, steps=6):
    """The uninterrupted ``steps``-step run at ``world`` — the
    continuation reference; cached per (mode, world)."""
    key = (mode, world)
    if key not in _ORACLES:
        opt, state, step, params = rig(mode, world)
        losses = []
        for i in range(steps):
            params, state, loss = step(params, state, *batch(i))
            losses.append(float(loss))
        _ORACLES[key] = (params, losses)
    return _ORACLES[key]


def tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------- scenario matrix
@pytest.mark.parametrize("transition", sorted(TRANSITIONS))
@pytest.mark.parametrize("mode", MODES)
class TestScenarioMatrix:
    def test_resume_continues_loss_trajectory(self, rig, tmp_path, mode,
                                              transition):
        """Train 3 steps at world A, elastic-save, restore (resharding
        when A != B) at world B, train 3 more on the same data schedule:
        the resumed trajectory must continue the uninterrupted run's —
        within reduction-order ulps for the fp32 modes, a quantization
        band for the int8 wire — and a same-world resume is BITWISE."""
        w0, w1 = TRANSITIONS[transition]
        opt0, state, step0, params = rig(mode, w0)
        for i in range(3):
            params, state, _ = step0(params, state, *batch(i))
        save_elastic_checkpoint(
            tmp_path, 3, params=params, opt_state=state, optimizer=opt0,
            world_size=w0, mesh_axes={"tp": 1})

        opt1, _, step1, _ = rig(mode, w1)
        r = restore_elastic_checkpoint(
            tmp_path, optimizer=opt1, world_size=w1, mesh_axes={"tp": 1})
        assert r is not None and r.step == 3
        if mode == "replicated":
            # replicated state is dp-invariant: saved as world 1,
            # elastic by construction
            assert r.saved_world == 1 and not r.resharded
        else:
            assert r.saved_world == w0
            assert r.resharded == (w0 != w1)
        tree_equal(r.params, params)  # params dp-replicated: bitwise

        p_r, s_r = r.params, r.opt_state
        resumed = []
        for i in range(3, 6):
            p_r, s_r, loss = step1(p_r, s_r, *batch(i))
            resumed.append(float(loss))

        _, ref = oracle(rig, mode, w0)
        band = 0.05 if mode == "zero_int8" else 5e-3
        np.testing.assert_allclose(resumed, ref[3:], rtol=band)
        if transition == "same":
            ref_params, _ = oracle(rig, mode, w1)
            tree_equal(p_r, ref_params)


# ------------------------------------------- hierarchical layout elasticity
class TestHierarchicalElasticChain:
    """ISSUE 12's elastic coverage: the dp LAYOUT (flat vs the
    hierarchical (outer, inner) split) is as elastic as the dp world
    size — shard ownership keeps the flat chunk-per-rank layout and the
    one ``padded_total`` formula, so checkpoints cross flat <->
    hierarchical with no special case in the elastic machinery."""

    def _hier_rig(self, devices8):
        mesh = Mesh(np.array(devices8[:4]).reshape(2, 2, 1),
                    ("dp_out", "dp_in", "tp"))
        params0 = init_params(CFG, jax.random.PRNGKey(0))
        opt = DistributedFusedAdam(
            lr=1e-2, weight_decay=0.01, dp_axes=("dp_out", "dp_in"),
            grad_sync_dtype="int8")
        opt.init(params0, world_size=4, param_specs=param_specs(CFG),
                 axis_sizes={"tp": 1, "dp_out": 2, "dp_in": 2})
        step = make_train_step(CFG, opt, mesh,
                               dp_axis=("dp_out", "dp_in"))
        return opt, step

    @staticmethod
    def _residual_sum(state):
        return sum(float(np.asarray(r, np.float64).sum())
                   for r in state.residual)

    def test_flat4_to_hier22_to_flat2_resume_chain(self, rig, tmp_path,
                                                   devices8):
        """The three-layout chain on the int8 wire: train flat dp=4,
        resume on the hierarchical (2, 2) mesh (same world — BITWISE
        state restore, no reshard), train two more steps through the
        two-hop sync, then resume flat at dp=2 (world change — the
        error-feedback residuals sum-collapse onto new rank 0, sum
        preserved exactly) — with every loss inside the quantized
        continuation band of the uninterrupted flat run."""
        opt4, state, step4, params = rig("zero_int8", 4)
        for i in range(2):
            params, state, _ = step4(params, state, *batch(i))
        dir_a = tmp_path / "a"
        save_elastic_checkpoint(
            dir_a, 2, params=params, opt_state=state, optimizer=opt4,
            world_size=4, mesh_axes={"tp": 1})

        # hop 1 of the chain: flat save → HIERARCHICAL restore.  Same
        # world (2·2 = 4), so nothing reshards and the state is bitwise
        # — the layout change is invisible to the checkpoint.
        opt_h, step_h = self._hier_rig(devices8)
        r = restore_elastic_checkpoint(
            dir_a, optimizer=opt_h, world_size=4, mesh_axes={"tp": 1})
        assert r is not None and r.step == 2
        assert r.saved_world == 4 and not r.resharded
        tree_equal(r.params, params)
        for a, b in zip(jax.tree.leaves(state),
                        jax.tree.leaves(r.opt_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        p_h, s_h = r.params, r.opt_state
        hier_losses = []
        for i in range(2, 4):
            p_h, s_h, loss = step_h(p_h, s_h, *batch(i))
            hier_losses.append(float(loss))
        res_sum_h = self._residual_sum(s_h)
        dir_b = tmp_path / "b"
        save_elastic_checkpoint(
            dir_b, 4, params=p_h, opt_state=s_h, optimizer=opt_h,
            world_size=4, mesh_axes={"tp": 1})

        # hop 2: hierarchical save → FLAT dp=2 restore.  The world
        # changes (4 → 2), so the full state reshards through the one
        # padded_total formula and the per-rank residuals collapse
        # onto new rank 0 — error SUM preserved exactly.
        opt2, _, step2, _ = rig("zero_int8", 2)
        r2 = restore_elastic_checkpoint(
            dir_b, optimizer=opt2, world_size=2, mesh_axes={"tp": 1})
        assert r2 is not None and r2.step == 4
        assert r2.saved_world == 4 and r2.resharded
        tree_equal(r2.params, p_h)
        np.testing.assert_allclose(self._residual_sum(r2.opt_state),
                                   res_sum_h, rtol=1e-6)

        p_f, s_f = r2.params, r2.opt_state
        flat_losses = []
        for i in range(4, 6):
            p_f, s_f, loss = step2(p_f, s_f, *batch(i))
            flat_losses.append(float(loss))

        # the whole chain continues the uninterrupted flat-dp=4
        # trajectory inside the int8 band — layout changes cost only
        # quantization-order noise, never a restart from scratch
        _, ref = oracle(rig, "zero_int8", 4)
        np.testing.assert_allclose(hier_losses + flat_losses, ref[2:6],
                                   rtol=0.05)

    def test_three_level_checkpoint_restores_flat_and_two_level(
            self, rig, tmp_path, devices8):
        """A checkpoint saved on the (dcn, dp_out, dp_in) = (2, 2, 2)
        mesh restores into a flat dp=8 optimizer AND a two-level
        (2, 4) one bitwise, with no special case: shard ownership is
        the flat chunk-per-rank layout under ONE ``padded_total``
        formula at every hop depth, and the index records only the dp
        world."""
        mesh3 = Mesh(np.array(devices8).reshape(2, 2, 2, 1),
                     ("dcn", "dp_out", "dp_in", "tp"))
        axes3 = ("dcn", "dp_out", "dp_in")
        sizes3 = {"tp": 1, "dcn": 2, "dp_out": 2, "dp_in": 2}
        params = init_params(CFG, jax.random.PRNGKey(0))
        opt3 = DistributedFusedAdam(
            lr=1e-2, weight_decay=0.01, dp_axes=axes3,
            grad_sync_dtype="int8")
        state = opt3.init(params, world_size=8,
                          param_specs=param_specs(CFG), axis_sizes=sizes3)
        step3 = make_train_step(CFG, opt3, mesh3, dp_axis=axes3)
        params, state, _ = step3(params, state, *batch(0))
        save_elastic_checkpoint(
            tmp_path, 1, params=params, opt_state=state, optimizer=opt3,
            world_size=8, mesh_axes={"tp": 1})

        # flat dp=8 restore: same world, bitwise, no reshard
        opt_f, _, step_f, _ = rig("zero_int8", 8)
        r = restore_elastic_checkpoint(
            tmp_path, optimizer=opt_f, world_size=8, mesh_axes={"tp": 1})
        assert r is not None and r.saved_world == 8 and not r.resharded
        for a, b in zip(jax.tree.leaves(state),
                        jax.tree.leaves(r.opt_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        _, _, loss = step_f(r.params, r.opt_state, *batch(1))
        assert np.isfinite(float(loss))

        # two-level (2, 4) restore: same world, bitwise, no reshard
        mesh2 = Mesh(np.array(devices8).reshape(2, 4, 1),
                     ("dp_out", "dp_in", "tp"))
        opt2 = DistributedFusedAdam(
            lr=1e-2, weight_decay=0.01, dp_axes=("dp_out", "dp_in"),
            grad_sync_dtype="int8")
        opt2.init(params, world_size=8, param_specs=param_specs(CFG),
                  axis_sizes={"tp": 1, "dp_out": 2, "dp_in": 4})
        r2 = restore_elastic_checkpoint(
            tmp_path, optimizer=opt2, world_size=8, mesh_axes={"tp": 1})
        assert r2 is not None and not r2.resharded
        for a, b in zip(jax.tree.leaves(state),
                        jax.tree.leaves(r2.opt_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        step2 = make_train_step(CFG, opt2, mesh2,
                                dp_axis=("dp_out", "dp_in"))
        _, _, loss2 = step2(r2.params, r2.opt_state, *batch(1))
        assert np.isfinite(float(loss2))

    def test_hier_checkpoint_restores_flat_without_special_case(
            self, rig, tmp_path, devices8):
        """A checkpoint SAVED on the hierarchical mesh restores into a
        flat same-world optimizer bitwise: the index records only the
        dp world and model axes — the (outer, inner) split never leaks
        into the format."""
        opt_h, step_h = self._hier_rig(devices8)
        params = init_params(CFG, jax.random.PRNGKey(0))
        state = opt_h.init(params, world_size=4,
                           param_specs=param_specs(CFG),
                           axis_sizes={"tp": 1, "dp_out": 2, "dp_in": 2})
        params, state, _ = step_h(params, state, *batch(0))
        save_elastic_checkpoint(
            tmp_path, 1, params=params, opt_state=state, optimizer=opt_h,
            world_size=4, mesh_axes={"tp": 1})
        opt4, _, step4, _ = rig("zero_int8", 4)
        r = restore_elastic_checkpoint(
            tmp_path, optimizer=opt4, world_size=4, mesh_axes={"tp": 1})
        assert r is not None and not r.resharded
        for a, b in zip(jax.tree.leaves(state),
                        jax.tree.leaves(r.opt_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        p, s, loss = step4(r.params, r.opt_state, *batch(1))
        assert np.isfinite(float(loss))


# ------------------------------------------------------------- pod chaos
class TestPodChaos:
    def test_kill_one_host_of_n_then_elastic_resume(self, rig, tmp_path):
        """Host 2 of 4 dies HARD at step 2 (no save, no drain); the
        supervisor reschedules the survivors at dp=2 and the run
        resumes from the last COMPLETE step dir, resharded."""
        opt4, state, step4, params = rig("zero", 4)
        monkey = ChaosMonkey(ChaosPlan.make(kill_at={2: 2}))
        ctl = ElasticRunController(tmp_path, opt4, world_size=4,
                                   mesh_axes={"tp": 1}, chaos=monkey,
                                   rank=2)
        with pytest.raises(ChaosHostKilled) as ei:
            for i in range(4):
                ctl.on_step(i)
                params, state, _ = step4(params, state, *batch(i))
                ctl.save(i + 1, params, state)
        assert ei.value.code == resilience.EXIT_KILLED
        assert monkey.injected.get("kill:2") == 1

        opt2, _, step2, _ = rig("zero", 2)
        r = restore_elastic_checkpoint(
            tmp_path, optimizer=opt2, world_size=2, mesh_axes={"tp": 1})
        assert r.step == 2 and r.resharded and r.saved_world == 4
        p, s, loss = step2(r.params, r.opt_state, *batch(2))
        assert np.isfinite(float(loss))

    def test_kill_plan_is_per_rank(self, rig, tmp_path):
        """Only the planned host dies: rank 0's controller sails past
        the step that kills rank 2."""
        opt4, state, step4, params = rig("zero", 4)
        monkey = ChaosMonkey(ChaosPlan.make(kill_at={2: 1}))
        ctl = ElasticRunController(tmp_path, opt4, world_size=4,
                                   mesh_axes={"tp": 1}, chaos=monkey,
                                   rank=0)
        for i in range(3):
            ctl.on_step(i)  # never raises: this "host" is rank 0
        assert not monkey.injected

    def test_wedged_collective_rank_trips_watchdog(self, devices8):
        """The wedge-a-collective-site fault: rank 1 stalls INSIDE the
        compiled step (io_callback before the grad/loss sync), so rank
        0 blocks device-side in the collective.  Only the host-side
        watchdog can see it — and does, while the step is still hung."""
        mesh = Mesh(np.array(devices8[:2]).reshape(2, 1), ("dp", "tp"))
        guard = StepGuard()
        monkey = ChaosMonkey(ChaosPlan.make(
            wedge_collective_rank=1, wedge_collective_at_step=1,
            wedge_collective_seconds=1.5))
        opt = FusedAdam(lr=1e-2)
        params = init_params(CFG, jax.random.PRNGKey(0))
        state = opt.init(params)
        step = make_train_step(CFG, opt, mesh, step_guard=guard,
                               chaos=monkey)
        gs = guard.init()
        # step 0: off-plan — compiles, runs fast
        params, state, gs, loss = step(params, state, gs, *batch(0))
        assert np.isfinite(float(loss))

        fired = []
        wd = StepWatchdog(0.4, poll_sec=0.05, on_fire=fired.append)
        with wd:
            wd.beat(1)
            t0 = time.monotonic()
            params, state, gs, loss = step(params, state, gs, *batch(1))
            assert np.isfinite(float(loss))
            dt_hung = time.monotonic() - t0
        assert monkey.injected.get("wedge_collective") == 1
        assert dt_hung >= 1.0, "the wedged rank did not hold the step"
        assert fired and fired[0]["step"] == 1
        assert fired[0]["exit_code"] == resilience.EXIT_WEDGED

    def test_host_side_step_wedge(self):
        """The whole-step dispatch wedge (hung-dispatch shape): the plan
        sleeps at exactly the armed step."""
        monkey = ChaosMonkey(ChaosPlan.make(wedge_step_at=2,
                                            wedge_step_seconds=0.2))
        assert monkey.maybe_wedge_step(1) == 0.0
        t0 = time.monotonic()
        assert monkey.maybe_wedge_step(2) == 0.2
        assert time.monotonic() - t0 >= 0.2
        assert monkey.injected.get("wedge_step") == 1


# ---------------------------------------------------------- step watchdog
class _StubCheckpointer:
    def __init__(self, gate=None):
        self.calls = 0
        self._gate = gate

    def wait_until_finished(self):
        self.calls += 1
        if self._gate is not None:
            self._gate.wait()


def _wait_for(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return pred()


class TestStepWatchdog:
    def test_fires_after_deadline_and_drains(self):
        ck = _StubCheckpointer()
        fired = []
        with StepWatchdog(0.2, checkpointer=ck, poll_sec=0.05,
                          on_fire=fired.append) as wd:
            wd.beat(5)
            assert _wait_for(lambda: wd.fired)
        assert fired[0]["step"] == 5
        assert fired[0]["drain"] == "drained" and ck.calls == 1
        assert fired[0]["exit_code"] == resilience.EXIT_WEDGED

    def test_heartbeat_staves_off_firing(self):
        with StepWatchdog(0.5, poll_sec=0.05, on_fire=lambda i: None) as wd:
            for i in range(8):
                wd.beat(i)
                time.sleep(0.1)
            assert not wd.fired

    def test_first_interval_gets_compile_grace(self):
        """Unbeaten, the FIRST deadline applies (jit compile); the
        steady-state deadline takes over after the first beat."""
        with StepWatchdog(0.15, first_deadline_sec=1.0, poll_sec=0.05,
                          on_fire=lambda i: None) as wd:
            time.sleep(0.4)
            assert not wd.fired  # 0.4 < the 1.0 first allowance
            assert _wait_for(lambda: wd.fired, timeout=2.0)

    def test_per_beat_deadline_override(self):
        """``beat(step, deadline=...)`` loosens ONE interval (the
        loop's first-step compile grace) without touching the rest."""
        with StepWatchdog(0.15, first_deadline_sec=10.0, poll_sec=0.05,
                          on_fire=lambda i: None) as wd:
            wd.beat(0, deadline=1.0)
            time.sleep(0.4)
            assert not wd.fired  # inside the per-beat override
            wd.beat(1)
            assert _wait_for(lambda: wd.fired, timeout=2.0)
            assert wd.fire_info["step"] == 1

    def test_drain_is_bounded(self):
        """A wedged filesystem must not wedge the watchdog's own exit:
        the drain runs on a helper thread with a timeout."""
        gate = threading.Event()  # never set: the flush hangs forever
        ck = _StubCheckpointer(gate=gate)
        fired = []
        with StepWatchdog(0.1, checkpointer=ck, poll_sec=0.05,
                          drain_timeout_sec=0.2,
                          on_fire=fired.append) as wd:
            assert _wait_for(lambda: wd.fired)
        gate.set()
        assert fired[0]["drain"] == "drain_timeout"

    def test_drain_routes_through_preemption_guard(self):
        """With a PreemptionHandler the watchdog's drain takes the
        re-entrancy-guarded path."""
        ck = _StubCheckpointer()
        pre = resilience.PreemptionHandler()
        fired = []
        with StepWatchdog(0.1, checkpointer=ck, preemption=pre,
                          poll_sec=0.05, on_fire=fired.append) as wd:
            assert _wait_for(lambda: wd.fired)
        assert fired[0]["drain"] == "drained" and ck.calls == 1

    def test_deadline_validation(self):
        with pytest.raises(ValueError):
            StepWatchdog(0.0)

    def test_restart_backoff_contract(self):
        """Deterministic per (seed, attempt), full-jitter exponential,
        capped."""
        a = [restart_backoff(k, base=2.0, cap=30.0, seed=7)
             for k in range(6)]
        b = [restart_backoff(k, base=2.0, cap=30.0, seed=7)
             for k in range(6)]
        assert a == b  # deterministic schedule
        for k, v in enumerate(a):
            assert 0.0 <= v <= min(30.0, 2.0 * 2 ** k)
        assert restart_backoff(3, seed=1) != restart_backoff(3, seed=2)
        with pytest.raises(ValueError):
            restart_backoff(-1)


# ------------------------------------------------- restore validation
class TestElasticValidation:
    def test_empty_dir_is_fresh_start(self, rig, tmp_path):
        opt, _, _, _ = rig("zero", 2)
        assert restore_elastic_checkpoint(
            tmp_path, optimizer=opt, world_size=2,
            mesh_axes={"tp": 1}) is None

    def test_kind_mismatch_refused(self, rig, tmp_path):
        """A ZeRO checkpoint cannot restore into a replicated optimizer
        (and vice versa): the --zero flag must agree."""
        opt, state, _, params = rig("zero", 2)
        save_elastic_checkpoint(tmp_path, 1, params=params,
                                opt_state=state, optimizer=opt,
                                world_size=2, mesh_axes={"tp": 1})
        with pytest.raises(ValueError, match="kind"):
            restore_elastic_checkpoint(
                tmp_path, optimizer=FusedAdam(lr=1e-2), world_size=2,
                mesh_axes={"tp": 1})

    def test_model_axes_mismatch_refused(self, rig, tmp_path):
        """Only dp is elastic: a tp change between save and resume is a
        state-layout change and fails loudly."""
        opt, state, _, params = rig("zero", 2)
        save_elastic_checkpoint(tmp_path, 1, params=params,
                                opt_state=state, optimizer=opt,
                                world_size=2, mesh_axes={"tp": 1})
        with pytest.raises(ValueError, match="data-parallel-only"):
            restore_elastic_checkpoint(tmp_path, optimizer=opt,
                                       world_size=2, mesh_axes={"tp": 2})

    def test_non_elastic_dir_refused(self, tmp_path):
        io.save_sharded_checkpoint(tmp_path / "step_00000001",
                                   {"x": np.zeros(3)}, 0, 1)
        opt = DistributedFusedAdam(lr=1e-2, axis_name="dp")
        opt.init({"w": jnp.zeros(8)}, world_size=2)
        with pytest.raises(ValueError, match="elastic"):
            restore_elastic_checkpoint(tmp_path, optimizer=opt,
                                       world_size=2)

    def test_optimizer_world_mismatch_refused(self, rig, tmp_path):
        """restore() refuses an optimizer init'd for a different world
        than the live one — the bucket plan would disagree with the
        resharded state at first trace."""
        opt4, state, _, params = rig("zero", 4)
        save_elastic_checkpoint(tmp_path, 1, params=params,
                                opt_state=state, optimizer=opt4,
                                world_size=4, mesh_axes={"tp": 1})
        with pytest.raises(ValueError, match="init"):
            restore_elastic_checkpoint(tmp_path, optimizer=opt4,
                                       world_size=2, mesh_axes={"tp": 1})

    def test_scaler_guard_rng_ride_rank0(self, rig, tmp_path):
        """The dp-replicated pieces of the FULL train state — scaler,
        StepGuard counts, RNG tracker — round-trip through the elastic
        dir (and survive a reshard, which never touches rank 0's
        payload)."""
        opt4, state, _, params = rig("zero", 4)
        guard = StepGuard(max_consecutive_bad=5)
        gs = guard.update(guard.init(), jnp.bool_(False))
        rng_sd = {"states": {"dropout": np.arange(4, dtype=np.uint32)},
                  "counts": {"dropout": 3}}
        scaler_sd = {"loss_scale": np.float32(1024.0), "growth": 7}
        save_elastic_checkpoint(
            tmp_path, 2, params=params, opt_state=state, optimizer=opt4,
            world_size=4, mesh_axes={"tp": 1},
            scaler_state=scaler_sd, guard_state=guard.state_dict(gs),
            rng_state=rng_sd)
        opt2, _, _, _ = rig("zero", 2)
        r = restore_elastic_checkpoint(tmp_path, optimizer=opt2,
                                       world_size=2, mesh_axes={"tp": 1})
        assert r.resharded
        back = guard.load_state_dict(
            {k: int(np.asarray(v)) for k, v in r.guard.items()})
        assert guard.state_dict(back) == guard.state_dict(gs)
        assert float(np.asarray(r.scaler["loss_scale"])) == 1024.0
        np.testing.assert_array_equal(
            np.asarray(r.rng["states"]["dropout"]),
            rng_sd["states"]["dropout"])
        assert int(np.asarray(r.rng["counts"]["dropout"])) == 3

    def test_controller_prunes_bounded_disk(self, rig, tmp_path):
        opt, state, step, params = rig("zero", 2)
        ctl = ElasticRunController(tmp_path, opt, world_size=2,
                                   mesh_axes={"tp": 1}, keep=2)
        for i in range(5):
            ctl.save(i + 1, params, state)
        left = sorted(p.name for p in tmp_path.glob("step_*"))
        assert left == ["step_00000004", "step_00000005"]
        r = ctl.restore()
        assert r.step == 5
