"""apex_tpu.resilience: the survivability pillar, proven by chaos.

Every fault these tests inject is one the project has actually suffered
(VERDICT r5): NaN gradients mid-run, Pallas kernels failing at launch on
hardware they were never proven on, preemptions landing between
checkpoint flushes, and sections wedging forever.  The chaos harness
(:mod:`apex_tpu.resilience.chaos`) injects them deterministically into
the virtual 8-device mesh, so the recovery machinery — kernel fallback
registry, step guard, preemption-safe resume — is proven end to end on
CPU today with the same seams real faults will take on TPU.

Rides the quick tier (no ``slow`` marks): every model here is tiny and
every loop is a handful of steps.
"""

import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from apex_tpu import resilience
from apex_tpu.amp import DynamicLossScaler
from apex_tpu.io import AsyncCheckpointer, latest_checkpoint, load_checkpoint
from apex_tpu.models.gpt import GPTConfig, init_params, make_train_step
from apex_tpu.optimizers import FusedAdam
from apex_tpu.resilience import (
    BadStepBudgetExceeded,
    ChaosKernelFailure,
    ChaosMonkey,
    ChaosPlan,
    KernelFallbackRegistry,
    PreemptionHandler,
    StepGuard,
    get_registry,
    load_rng_tracker_state_dict,
    rng_tracker_state_dict,
    trip_from_exception,
)
from apex_tpu.resilience.chaos import check_kernel


@pytest.fixture(autouse=True)
def _clean_registry():
    """Each test starts with an untripped process-global registry."""
    get_registry().reset()
    yield
    get_registry().reset()


# ------------------------------------------------------------ step guard
class TestStepGuard:
    def test_counts_consecutive_and_total(self):
        g = StepGuard(max_consecutive_bad=3)
        s = g.init()
        for finite in (True, False, False, True, False):
            s = g.update(s, jnp.bool_(finite))
        assert int(s.step) == 5
        assert int(s.total_skipped) == 3
        assert int(s.consecutive_bad) == 1  # streak reset by the True

    def test_budget_check_raises_with_state(self):
        g = StepGuard(max_consecutive_bad=2)
        s = g.init()
        s = g.update(s, jnp.bool_(False))
        g.check(s)  # 1 < 2: fine
        s = g.update(s, jnp.bool_(False))
        assert bool(g.exhausted(s))
        with pytest.raises(BadStepBudgetExceeded) as ei:
            g.check(s)
        assert "2 consecutive" in str(ei.value)
        assert int(ei.value.guard_state.total_skipped) == 2

    def test_state_dict_roundtrip(self):
        g = StepGuard()
        s = g.update(g.update(g.init(), jnp.bool_(False)), jnp.bool_(True))
        back = g.load_state_dict(g.state_dict(s))
        assert g.state_dict(back) == g.state_dict(s)
        assert g.state_dict(g.load_state_dict(None)) == g.state_dict(g.init())

    def test_budget_validation(self):
        with pytest.raises(ValueError):
            StepGuard(max_consecutive_bad=0)


# --------------------------------------------------- fallback registry
class TestKernelFallbackRegistry:
    def test_kernel_path_counts(self):
        r = KernelFallbackRegistry()
        assert r.call("fused_ce", lambda: "kernel", lambda: "fallback") \
            == "kernel"
        st = r.status()["fused_ce"]
        assert (st["kernel_calls"], st["fallback_calls"]) == (1, 0)
        assert not r.tripped("fused_ce")

    def test_failure_trips_once_and_degrades(self):
        r = KernelFallbackRegistry()
        calls = {"kernel": 0}

        def kernel():
            calls["kernel"] += 1
            raise RuntimeError("Mosaic lowering surprise")

        assert r.call("layer_norm", kernel, lambda: "fallback") == "fallback"
        # degrade ONCE: the tripped kernel is never re-entered
        assert r.call("layer_norm", kernel, lambda: "fallback") == "fallback"
        assert calls["kernel"] == 1
        st = r.status()["layer_norm"]
        assert st["tripped"] and "Mosaic" in st["error"]
        assert st["fallback_calls"] == 2

    def test_reset_rearms(self):
        r = KernelFallbackRegistry()
        r.trip("flash_attention", RuntimeError("boom"))
        r.reset("flash_attention")
        assert not r.tripped("flash_attention")
        assert r.call("flash_attention", lambda: "k", lambda: "f") == "k"

    def test_trip_from_exception_attributes_by_marker(self):
        got = trip_from_exception(
            RuntimeError("error while lowering _dx_kernel for fused_ce"))
        assert got == ["fused_ce"]
        assert get_registry().tripped("fused_ce")
        assert not get_registry().tripped("flash_attention")

    def test_trip_from_exception_shared_marker_trips_every_owner(self):
        """``_fwd_kernel`` is a def in BOTH flash_attention_pallas.py
        and fused_ce_pallas.py: an error naming only it must trip both
        owners (the innocent one pays throughput; tripping the wrong
        one alone would re-lower the broken kernel and crash)."""
        got = trip_from_exception(
            RuntimeError("lowering failed in _fwd_kernel at vmem limit"))
        assert sorted(got) == ["flash_attention", "fused_ce"]
        assert not get_registry().tripped("layer_norm")

    def test_trip_from_exception_generic_mosaic_trips_all(self):
        from apex_tpu.resilience.fallback import KERNELS

        got = trip_from_exception(
            RuntimeError("INTERNAL: Mosaic failed to compile module"))
        # an unattributable Mosaic error must trip EVERY registered
        # kernel (incl. the decode pair) — pin against the registry
        # itself so a new kernel cannot silently escape the net
        assert sorted(got) == sorted(KERNELS)
        assert {"flash_attention", "fused_ce", "layer_norm",
                "decode_attention", "decode_sampling"} <= set(got)

    def test_trip_from_exception_ignores_unrelated(self):
        assert trip_from_exception(ValueError("shape mismatch")) == []
        assert not any(v["tripped"]
                       for v in get_registry().status().values())

    def test_trip_from_exception_ignores_bare_op_names(self):
        """XLA runtime errors embed HLO names derived from the traced
        Python functions: an OOM whose dump mentions `layer_norm` or
        `flash_attention` is NOT a kernel failure and must not be
        attributed — the caller would swallow the real error and burn a
        full recompile per retry with innocent kernels degraded."""
        got = trip_from_exception(RuntimeError(
            "RESOURCE_EXHAUSTED: out of memory while allocating for "
            "fusion.123 (derived from layer_norm and flash_attention)"))
        assert got == []
        assert not any(v["tripped"]
                       for v in get_registry().status().values())

    def test_trip_from_exception_bare_pallas_is_not_generic(self):
        """"pallas" is the API name, not a failure signature: it shows
        up in module paths and buffer names of successfully-compiled
        kernels inside unrelated errors (OOM dumps).  Only "mosaic" —
        the TPU kernel compiler — is a trip-everything trigger."""
        got = trip_from_exception(RuntimeError(
            "RESOURCE_EXHAUSTED: while allocating buffer for "
            "jit(step)/pallas/pallas_call.py custom-call"))
        assert got == []
        assert not any(v["tripped"]
                       for v in get_registry().status().values())

    def test_argument_error_untrips_after_fallback_rejects(self):
        """A validation error raised inside the kernel closure trips the
        kernel — but when the reference impl rejects the SAME call, the
        fault is the arguments, not the kernel: the trip is undone so
        later valid calls still reach the kernel."""
        reg = KernelFallbackRegistry()

        def bad(which):
            def impl():
                raise ValueError(f"H %% Hkv != 0 ({which})")
            return impl

        with pytest.raises(ValueError, match="fallback"):
            reg.call("flash_attention", bad("kernel"), bad("fallback"))
        assert not reg.tripped("flash_attention")
        assert reg.call("flash_attention", lambda: "kernel",
                        lambda: "fallback") == "kernel"

    def test_registry_disengaged_multiprocess(self, monkeypatch):
        """A per-process degrade lowers mismatched collective programs
        across hosts (device-side deadlock with no error): multi-process
        runs never engage the registry, even under chaos."""
        from apex_tpu.resilience import registry_engaged

        monkeypatch.setattr(jax, "process_count", lambda: 2)
        assert not registry_engaged(forced=False)
        with ChaosMonkey(ChaosPlan.make()).active():
            assert not registry_engaged(forced=False)

    def test_trip_from_exception_ignores_oom_with_marker_names(self):
        """An HBM OOM's buffer dump names allocations by op metadata —
        including the ``*_pallas`` entry-point names of kernels that
        compiled fine.  Resource exhaustion is a runtime failure, not a
        lowering failure: nothing trips, the real error surfaces."""
        got = trip_from_exception(RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            "1073741824 bytes; largest allocation: custom-call "
            "fused_ce_fwd_pallas from jit(step)"))
        assert got == []
        assert not any(v["tripped"]
                       for v in get_registry().status().values())

    def test_registry_engaged_semantics(self):
        """A forced kernel impl bypasses the registry (fail loudly);
        the chaos harness re-engages it (CPU tests force `interpret`
        to reach the kernel path at all)."""
        from apex_tpu.resilience import registry_engaged

        assert registry_engaged(forced=False)
        assert not registry_engaged(forced=True)
        with ChaosMonkey(ChaosPlan.make()).active():
            assert registry_engaged(forced=True)

    def test_forced_impl_bypasses_tripped_registry(self, monkeypatch):
        """`fused_ce_impl="interpret"` is a demand: run THIS impl or
        fail loudly.  A registry tripped elsewhere in the process must
        not silently swap the kernel for its reference — kernel-vs-
        oracle tests would pass vacuously."""
        from apex_tpu.ops.fused_ce import fused_lm_head_ce

        # fp32 dot accumulation so the two impls compare tightly (the
        # test_fused_ce_pallas.py convention)
        monkeypatch.setenv("APEX_TPU_FUSED_CE_DOT", "float32")
        S, B, H, V = 8, 2, 16, 32
        x = jax.random.normal(jax.random.PRNGKey(0), (S, B, H), jnp.float32)
        e = jax.random.normal(jax.random.PRNGKey(1), (V, H), jnp.float32)
        t = jax.random.randint(jax.random.PRNGKey(2), (S, B), 0, V)

        get_registry().trip("fused_ce", RuntimeError("tripped elsewhere"))
        loss = fused_lm_head_ce(x, e, t, 8, None, "interpret")
        ref = fused_lm_head_ce(x, e, t, 8, None, "off")
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                                   rtol=1e-5)
        st = get_registry().status()["fused_ce"]
        assert st["fallback_calls"] == 0  # bypassed: the kernel ran

    def test_env_forced_impl_bypasses_tripped_registry(self, monkeypatch):
        """APEX_TPU_FUSED_CE_PALLAS=interpret with impl=None is just as
        forced as an explicit impl arg: the env-driven kernel-vs-oracle
        fixtures rely on the kernel actually running, so the registry
        must stay out of the way (a silent degrade would compare the
        scan impl against itself)."""
        from apex_tpu.ops.fused_ce import fused_lm_head_ce

        monkeypatch.setenv("APEX_TPU_FUSED_CE_DOT", "float32")
        monkeypatch.setenv("APEX_TPU_FUSED_CE_PALLAS", "interpret")
        S, B, H, V = 8, 2, 16, 32
        x = jax.random.normal(jax.random.PRNGKey(0), (S, B, H), jnp.float32)
        e = jax.random.normal(jax.random.PRNGKey(1), (V, H), jnp.float32)
        t = jax.random.randint(jax.random.PRNGKey(2), (S, B), 0, V)

        get_registry().trip("fused_ce", RuntimeError("tripped elsewhere"))
        loss = fused_lm_head_ce(x, e, t, 8, None, None)
        ref = fused_lm_head_ce(x, e, t, 8, None, "off")
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                                   rtol=1e-5)
        st = get_registry().status()["fused_ce"]
        assert st["fallback_calls"] == 0  # bypassed: the kernel ran


# ------------------------------------------------------------- chaos
class TestChaosMonkey:
    def test_grad_fault_poisons_exactly_planned_steps(self):
        m = ChaosMonkey(ChaosPlan.make(nan_grad_steps=[1, 3]))
        vals = [float(m.grad_fault(jnp.int32(i))) for i in range(5)]
        assert np.isnan(vals[1]) and np.isnan(vals[3])
        assert vals[0] == vals[2] == vals[4] == 1.0

    def test_grad_fault_unarmed_is_constant_one(self):
        m = ChaosMonkey(ChaosPlan.make())
        assert float(m.grad_fault(jnp.int32(7))) == 1.0

    def test_kernel_failure_budget_burns_down(self):
        m = ChaosMonkey(ChaosPlan.make(kernel_failures={"fused_ce": 2}))
        with m.active():
            with pytest.raises(ChaosKernelFailure):
                check_kernel("fused_ce")
            check_kernel("layer_norm")  # unarmed kernel: no injection
            with pytest.raises(ChaosKernelFailure):
                check_kernel("fused_ce")
            check_kernel("fused_ce")  # budget exhausted: clean
        assert m.injected["kernel:fused_ce"] == 2
        check_kernel("fused_ce")  # monkey deactivated: never fires

    def test_registry_fallback_on_injected_failure(self):
        """The registry seam: an armed plan degrades the kernel call
        exactly like a real launch failure would."""
        r = KernelFallbackRegistry()
        m = ChaosMonkey(ChaosPlan.make(kernel_failures={"layer_norm": 1}))
        with m.active():
            assert r.call("layer_norm", lambda: "k", lambda: "f") == "f"
        assert r.tripped("layer_norm")

    def test_wedge_sleeps_and_counts(self):
        import time

        m = ChaosMonkey(ChaosPlan.make(wedge_seconds={"site.x": 0.05}))
        with m.active():
            t0 = time.monotonic()
            assert m.maybe_wedge("site.x") == 0.05
            assert time.monotonic() - t0 >= 0.05
            assert m.maybe_wedge("site.y") == 0.0
        assert m.injected["wedge:site.x"] == 1

    def test_preemption_delivered_at_planned_step(self):
        m = ChaosMonkey(ChaosPlan.make(preempt_at_step=3))
        pre = PreemptionHandler()
        assert not m.maybe_preempt(2, pre) and not pre.preempted
        assert m.maybe_preempt(3, pre)
        assert pre.preempted and "chaos" in pre.reason


# -------------------------------------------------------- preemption
class TestPreemptionHandler:
    def test_sigterm_sets_flag_and_restores_handler(self):
        prev = signal.getsignal(signal.SIGTERM)
        with PreemptionHandler() as pre:
            assert not pre.preempted
            os.kill(os.getpid(), signal.SIGTERM)
            assert pre.preempted
            assert "SIGTERM" in pre.reason
        assert signal.getsignal(signal.SIGTERM) is prev

    def test_deadline_counts_as_preemption(self):
        pre = PreemptionHandler(deadline_sec=10.0, grace_sec=30.0)
        assert pre.preempted  # inside the grace window already
        assert "deadline" in pre.reason

    def test_drain_makes_async_saves_durable(self, tmp_path):
        ck = AsyncCheckpointer()
        try:
            pre = PreemptionHandler()
            pre.simulate()
            ck.save(tmp_path / "step_00000001.ckpt", {"x": jnp.arange(4.0)})
            pre.drain(ck)
            got = load_checkpoint(tmp_path / "step_00000001.ckpt")
            np.testing.assert_array_equal(got["x"], np.arange(4.0))
        finally:
            ck.close()

    def test_drain_is_not_reentrant_but_waits_for_completion(self):
        """A second drain landing while one is in flight (watchdog
        thread firing mid-preemption-drain, schedulers resending
        SIGTERM) never re-enters the flush — but it WAITS for the
        in-flight one: returning early would let a watchdog report
        'drained' and exit while the first flush is still writing."""
        import threading
        import time

        entered = []
        release = threading.Event()
        started = threading.Event()

        class SlowCkpt:
            def wait_until_finished(self):
                entered.append(1)
                started.set()
                release.wait(5.0)

        pre = PreemptionHandler()
        ck = SlowCkpt()
        t = threading.Thread(target=pre.drain, args=(ck,))
        t.start()
        assert started.wait(5.0)
        t0 = time.monotonic()
        reentrant_done = threading.Event()

        def second():
            pre.drain(ck)  # must block until the first flush lands
            reentrant_done.set()

        threading.Thread(target=second).start()
        time.sleep(0.2)
        assert not reentrant_done.is_set()  # still waiting on flush #1
        release.set()
        t.join(5.0)
        assert reentrant_done.wait(5.0)
        assert time.monotonic() - t0 >= 0.2
        assert len(entered) == 1            # ONE flush served both
        # after the in-flight drain completes, a NEW drain runs again
        pre.drain(ck)
        assert len(entered) == 2

    def test_reentrant_drain_sees_inflight_failure(self):
        """A caller that piggybacks on an in-flight drain must NOT
        report success when that flush failed — a watchdog would log
        'drained' and exit over an unflushed save."""
        import threading

        release = threading.Event()
        started = threading.Event()

        class FailingCkpt:
            def wait_until_finished(self):
                started.set()
                release.wait(5.0)
                raise RuntimeError("disk full mid-flush")

        pre = PreemptionHandler()
        ck = FailingCkpt()
        first_err = []

        def first():
            try:
                pre.drain(ck)
            except RuntimeError as e:
                first_err.append(e)

        t = threading.Thread(target=first)
        t.start()
        assert started.wait(5.0)
        waiter_err = []

        def second():
            try:
                pre.drain(ck)
            except RuntimeError as e:
                waiter_err.append(e)

        t2 = threading.Thread(target=second)
        t2.start()
        release.set()
        t.join(5.0)
        t2.join(5.0)
        assert first_err and "disk full" in str(first_err[0])
        assert waiter_err and "in-flight drain failed" in str(waiter_err[0])

    def test_sigterm_during_drain_only_sets_flag(self):
        """SIGTERM arriving DURING the drain: the handler sets the flag
        and chains — it never calls drain itself, so the in-flight
        flush completes exactly once and the process can still exit 0
        (the process-level twin lives in test_gpt_example.py)."""
        import threading

        entered = []
        release = threading.Event()

        class SlowCkpt:
            def wait_until_finished(self):
                entered.append(1)
                # SIGTERM lands while the main thread is INSIDE drain
                os.kill(os.getpid(), signal.SIGTERM)
                release.wait(2.0)

        with PreemptionHandler() as pre:
            pre.simulate("first notice")
            release.set()
            pre.drain(SlowCkpt())
            assert pre.preempted  # the mid-drain signal registered
        assert len(entered) == 1

    def test_rng_tracker_roundtrip_continues_streams(self):
        """A resume that reset the fork counter would replay dropout
        masks; the snapshot must continue the stream exactly."""
        from apex_tpu.transformer.tensor_parallel.random import (
            RNGStatesTracker,
        )

        tracker = RNGStatesTracker()
        tracker.add("model-parallel-rng", 17)
        tracker.fork("model-parallel-rng")  # burn one: counter now 1
        snap = rng_tracker_state_dict(tracker)

        fresh = RNGStatesTracker()
        load_rng_tracker_state_dict(snap, fresh)
        a = tracker.fork("model-parallel-rng")
        b = fresh.fork("model-parallel-rng")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert fresh.counts_ == tracker.counts_ == {
            "model-parallel-rng": 2}


# --------------------------------------------------- end-to-end survival
CFG = GPTConfig(
    vocab_size=64, hidden_size=32, num_layers=2, num_attention_heads=4,
    max_seq_len=16, compute_dtype=jnp.float32, checkpoint_layers=False,
)


def _data(seed=0, batch=8, seq=16):
    rng = np.random.RandomState(seed)
    tok = jnp.asarray(rng.randint(0, CFG.vocab_size, size=(batch, seq)))
    return tok, jnp.roll(tok, -1, axis=1)


def _mesh(devices8):
    return Mesh(np.array(devices8).reshape(4, 2), ("dp", "tp"))


def _leaves_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


class TestEndToEndSurvival:
    def test_nan_step_skipped_scaler_backs_off_then_training_resumes(
            self, devices8):
        """Injected NaN grads at step 1: the update is skipped
        device-side (params bitwise unchanged, Adam step counter held),
        the scaler backs off, the guard counts it — and step 2 trains
        normally from the pre-fault params."""
        scaler = DynamicLossScaler(init_scale=2.0 ** 8, hysteresis=1)
        guard = StepGuard(max_consecutive_bad=3)
        chaos = ChaosMonkey(ChaosPlan.make(nan_grad_steps=[1]))
        opt = FusedAdam(lr=1e-2)
        params = init_params(CFG, jax.random.PRNGKey(0))
        state = opt.init(params)
        sstate, gstate = scaler.init(), guard.init()
        step = make_train_step(CFG, opt, _mesh(devices8),
                               loss_scaler=scaler, step_guard=guard,
                               chaos=chaos)
        tok, tgt = _data()

        params, state, sstate, gstate, loss0 = step(
            params, state, sstate, gstate, tok, tgt)
        assert np.isfinite(float(loss0))
        before = jax.tree.map(np.asarray, params)
        before_opt_step = int(state.step)
        scale_before = float(sstate.loss_scale)

        params, state, sstate, gstate, loss1 = step(
            params, state, sstate, gstate, tok, tgt)
        assert not np.isfinite(float(loss1))          # the poisoned step
        assert _leaves_equal(params, before)          # update skipped
        assert int(state.step) == before_opt_step     # Adam counter held
        assert float(sstate.loss_scale) < scale_before  # backoff
        assert int(gstate.total_skipped) == 1
        assert int(gstate.consecutive_bad) == 1
        guard.check(gstate)  # within budget: no raise

        params, state, sstate, gstate, loss2 = step(
            params, state, sstate, gstate, tok, tgt)
        assert np.isfinite(float(loss2))
        assert int(gstate.consecutive_bad) == 0       # streak reset
        assert not _leaves_equal(params, before)      # trained again

    def test_bad_step_budget_aborts_unscaled_loop(self, devices8):
        """No loss scaler: the guard brings its own all_finite vote, and
        a NaN storm exhausts the budget into a clean abort signal."""
        guard = StepGuard(max_consecutive_bad=2)
        chaos = ChaosMonkey(ChaosPlan.make(nan_grad_steps=[0, 1, 2, 3]))
        opt = FusedAdam(lr=1e-2)
        params = init_params(CFG, jax.random.PRNGKey(0))
        state = opt.init(params)
        gstate = guard.init()
        step = make_train_step(CFG, opt, _mesh(devices8), step_guard=guard,
                               chaos=chaos)
        tok, tgt = _data()

        with pytest.raises(BadStepBudgetExceeded) as ei:
            for _ in range(4):
                params, state, gstate, _ = step(params, state, gstate,
                                                tok, tgt)
                guard.check(gstate)
        assert int(ei.value.guard_state.consecutive_bad) == 2

    def test_kernel_failure_falls_back_and_matches_reference(
            self, devices8):
        """Injected fused-CE kernel-launch failure: the registry
        degrades to the scan impl with the run alive, and the loss
        trajectory MATCHES the reference impl's exactly (the fallback
        IS the numerics specification)."""
        import dataclasses

        cfg = dataclasses.replace(CFG, fused_ce=True, fused_ce_chunk=8,
                                  fused_ce_impl="interpret")
        ref_cfg = dataclasses.replace(CFG, fused_ce=True, fused_ce_chunk=8,
                                      fused_ce_impl="off")
        tok, tgt = _data()

        def run(config, chaos_plan=None):
            get_registry().reset()
            opt = FusedAdam(lr=1e-2)
            params = init_params(config, jax.random.PRNGKey(0))
            state = opt.init(params)
            guard = StepGuard()
            gstate = guard.init()
            chaos = ChaosMonkey(chaos_plan or ChaosPlan.make())
            with chaos.active():
                step = make_train_step(config, opt, _mesh(devices8),
                                       step_guard=guard, chaos=chaos)
                losses = []
                for _ in range(3):
                    params, state, gstate, loss = step(params, state,
                                                       gstate, tok, tgt)
                    losses.append(float(loss))
            return params, losses

        # huge budget: every call fails until the registry trips
        plan = ChaosPlan.make(kernel_failures={"fused_ce": 10 ** 6})
        surv_params, surv_losses = run(cfg, plan)
        assert get_registry().tripped("fused_ce")
        assert all(np.isfinite(surv_losses))

        ref_params, ref_losses = run(ref_cfg)
        np.testing.assert_allclose(surv_losses, ref_losses, rtol=1e-6)
        for a, b in zip(jax.tree.leaves(surv_params),
                        jax.tree.leaves(ref_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)

    def test_preemption_resume_bitwise_identical(self, devices8, tmp_path):
        """Injected preemption at step 2: the loop saves, drains the
        async queue, and exits; a fresh 'process' discovers the
        checkpoint via latest_checkpoint and resumes at the same step
        with bitwise-identical params, guard, and scaler state."""
        scaler = DynamicLossScaler(init_scale=2.0 ** 8)
        guard = StepGuard()
        chaos = ChaosMonkey(ChaosPlan.make(preempt_at_step=2))
        opt = FusedAdam(lr=1e-2)
        params = init_params(CFG, jax.random.PRNGKey(0))
        state = opt.init(params)
        sstate, gstate = scaler.init(), guard.init()
        step = make_train_step(CFG, opt, _mesh(devices8),
                               loss_scaler=scaler, step_guard=guard)
        tok, tgt = _data()
        pre = PreemptionHandler()  # no install: chaos delivers it

        stopped_at = None
        with AsyncCheckpointer() as ck:
            for i in range(5):
                params, state, sstate, gstate, _ = step(
                    params, state, sstate, gstate, tok, tgt)
                chaos.maybe_preempt(i, pre)
                if pre.preempted:
                    ck.save(tmp_path / f"step_{i + 1:08d}.ckpt", {
                        "params": params, "state": state,
                        "scaler": scaler.state_dict(sstate),
                        "guard": guard.state_dict(gstate),
                        "step": np.int64(i + 1),
                    })
                    pre.drain(ck)
                    stopped_at = i + 1
                    break
        assert stopped_at == 3  # preempt delivered AFTER loop step 2

        # ---- fresh process: discover, validate, resume
        path = latest_checkpoint(tmp_path)
        assert path.endswith("step_00000003.ckpt")
        ck2 = load_checkpoint(path)
        assert int(ck2["step"]) == stopped_at
        assert _leaves_equal(ck2["params"], params)   # bitwise
        assert _leaves_equal(ck2["state"], state)
        r_sstate = scaler.load_state_dict(ck2["scaler"])
        r_gstate = guard.load_state_dict(ck2["guard"])
        assert float(r_sstate.loss_scale) == float(sstate.loss_scale)
        assert guard.state_dict(r_gstate) == guard.state_dict(gstate)

        # the resumed step must run and train
        r_params = jax.tree.map(jnp.asarray, ck2["params"])
        r_state = jax.tree.map(jnp.asarray, ck2["state"])
        r_params, r_state, r_sstate, r_gstate, loss = step(
            r_params, r_state, r_sstate, r_gstate, tok, tgt)
        assert np.isfinite(float(loss))
        assert not _leaves_equal(r_params, ck2["params"])

    def test_full_survival_story(self, devices8, tmp_path):
        """The acceptance scenario in one loop: a NaN step (skipped,
        scaler backs off), a kernel-launch failure (falls back, loss
        matches the reference trajectory), and a preemption (resumes
        from the flushed checkpoint at the same step, params bitwise
        identical)."""
        import dataclasses

        cfg = dataclasses.replace(CFG, fused_ce=True, fused_ce_chunk=8,
                                  fused_ce_impl="interpret")
        ref_cfg = dataclasses.replace(cfg, fused_ce_impl="off")
        tok, tgt = _data()
        plan = ChaosPlan.make(nan_grad_steps=[1],
                              kernel_failures={"fused_ce": 10 ** 6},
                              preempt_at_step=3)

        def loop(config, chaos_plan, ckpt_dir=None, steps=5):
            get_registry().reset()
            scaler = DynamicLossScaler(init_scale=2.0 ** 8, hysteresis=1)
            guard = StepGuard(max_consecutive_bad=3)
            chaos = ChaosMonkey(chaos_plan)
            opt = FusedAdam(lr=1e-2)
            params = init_params(config, jax.random.PRNGKey(0))
            state = opt.init(params)
            sstate, gstate = scaler.init(), guard.init()
            pre = PreemptionHandler()
            losses = []
            with chaos.active():
                step = make_train_step(config, opt, _mesh(devices8),
                                       loss_scaler=scaler,
                                       step_guard=guard, chaos=chaos)
                with AsyncCheckpointer() as ck:
                    for i in range(steps):
                        params, state, sstate, gstate, loss = step(
                            params, state, sstate, gstate, tok, tgt)
                        losses.append(float(loss))
                        guard.check(gstate)
                        chaos.maybe_preempt(i, pre)
                        if ckpt_dir and pre.preempted:
                            ck.save(
                                ckpt_dir / f"step_{i + 1:08d}.ckpt",
                                {"params": params,
                                 "step": np.int64(i + 1)})
                            pre.drain(ck)
                            break
            return params, gstate, losses

        params, gstate, losses = loop(cfg, plan, ckpt_dir=tmp_path)
        # kernel failure absorbed
        assert get_registry().tripped("fused_ce")
        # NaN step absorbed and counted
        assert not np.isfinite(losses[1])
        assert int(gstate.total_skipped) == 1
        # preempted after loop step 3 (4 losses recorded), durable save
        assert len(losses) == 4
        ck = load_checkpoint(latest_checkpoint(tmp_path))
        assert int(ck["step"]) == 4
        assert _leaves_equal(ck["params"], params)  # bitwise at resume

        # the degraded run's trajectory == the reference impl's, fault
        # for fault (same chaos plan, no kernel failures needed: "off"
        # IS the fallback impl the degraded run used)
        ref_plan = ChaosPlan.make(nan_grad_steps=[1], preempt_at_step=3)
        _, _, ref_losses = loop(ref_cfg, ref_plan, ckpt_dir=None)
        np.testing.assert_allclose(losses[0:1] + losses[2:],
                                   ref_losses[0:1] + ref_losses[2:4],
                                   rtol=1e-6)
