"""Self-healing supervisor (`apex_tpu.resilience.supervisor`) — the
restart state machine driven deterministically with fake children, a
pinned clock, and the rng seam, plus the checkpoint corruption-probe /
quarantine layer it invokes (`io.probe_checkpoint` / `io
.probe_checkpoint_dir` / `io.quarantine_checkpoint`) on real files.

Everything here is quick-tier: no subprocesses, no jitted steps — the
process-level gauntlet (ONE ``pretrain_gpt.py --supervise`` surviving
kill → wedge → corrupt-checkpoint) lives in tests/test_gpt_example.py.
"""

import json
import random
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from apex_tpu import io
from apex_tpu.resilience import (
    EXIT_CRASH_LOOP,
    EXIT_KILLED,
    EXIT_WEDGED,
    Supervisor,
    SupervisorFault,
    SupervisorFaultScript,
    corrupt_newest_checkpoint,
    restart_backoff,
    strip_supervisor_argv,
)


class FakeChild:
    def __init__(self, rc):
        self.rc = rc
        self.terminated = 0
        self.killed = 0

    def wait(self, timeout=None):
        return self.rc

    def terminate(self):
        self.terminated += 1

    def kill(self):
        self.killed += 1


class MaxJitter:
    """rng seam pinning the jitter to its upper bound: delays become
    exactly ``min(cap, base * 2**attempt)``."""

    def uniform(self, a, b):
        return b


def make_sup(codes, *, progress=None, spawned=None, sleeps=None, **kw):
    """Supervisor over a scripted sequence of child exit codes."""
    it = iter(codes)
    spawned = spawned if spawned is not None else []
    sleeps = sleeps if sleeps is not None else []

    def spawn(argv):
        child = FakeChild(next(it))
        spawned.append((list(argv), child))
        return child

    kw.setdefault("rng", MaxJitter())
    kw.setdefault("backoff_base", 1.0)
    kw.setdefault("backoff_cap", 8.0)
    kw.setdefault("progress_fn", progress if progress is not None
                  else lambda: 0)
    return Supervisor(["trainer", "--flag"], spawn_fn=spawn,
                      sleep_fn=sleeps.append, time_fn=lambda: 0.0, **kw)


class TestStateMachine:
    def test_clean_exit_no_restart(self):
        sleeps = []
        sup = make_sup([0], sleeps=sleeps)
        assert sup.run() == 0
        assert sup.restarts == 0 and sleeps == []

    def test_wedged_then_clean_restarts_with_pinned_backoff(self):
        """Exit 75 → ONE restart after exactly restart_backoff(0) (the
        rng seam pins the jitter), then the clean child ends the job."""
        sleeps = []
        sup = make_sup([EXIT_WEDGED, 0], sleeps=sleeps)
        assert sup.run() == 0
        assert sup.restarts == 1
        assert sleeps == [restart_backoff(0, base=1.0, cap=8.0,
                                          rng=MaxJitter())] == [1.0]

    def test_killed_then_clean(self):
        sup = make_sup([EXIT_KILLED, 0])
        assert sup.run() == 0 and sup.restarts == 1

    def test_unknown_nonzero_also_restarts(self):
        """The tentpole table: any nonzero restarts (the breaker, not
        the code, bounds environmental crash damage)."""
        sup = make_sup([3, 0])
        assert sup.run() == 0 and sup.restarts == 1

    def test_crash_loop_trips_breaker_with_pinned_schedule(self):
        """The acceptance contract: K consecutive no-progress failures
        exit EXIT_CRASH_LOOP after a deterministic backoff schedule —
        never an unbounded restart loop.  K=3 → exactly two sleeps
        (restart_backoff(0), restart_backoff(1) at max jitter), then
        the breaker, with no third sleep."""
        sleeps = []
        sup = make_sup([1, 1, 1], sleeps=sleeps, crash_loop_threshold=3)
        assert sup.run() == EXIT_CRASH_LOOP
        assert sup.restarts == 2
        assert sleeps == [1.0, 2.0]  # min(8, 1*2^0), min(8, 1*2^1)

    def test_backoff_respects_cap(self):
        sleeps = []
        sup = make_sup([1] * 6, sleeps=sleeps, crash_loop_threshold=6,
                       backoff_base=1.0, backoff_cap=4.0)
        assert sup.run() == EXIT_CRASH_LOOP
        assert sleeps == [1.0, 2.0, 4.0, 4.0, 4.0]

    def test_progress_resets_the_streak(self):
        """A child that banked new steps before dying is NOT a crash
        loop: the streak resets and the job survives more failures
        than the threshold."""
        state = {"p": 0}

        def progress():
            state["p"] += 1  # every relaunch advanced the run
            return state["p"]

        sup = make_sup([EXIT_KILLED] * 5 + [0], progress=progress,
                       crash_loop_threshold=2)
        assert sup.run() == 0
        assert sup.restarts == 5

    def test_max_restarts_exhaustion_returns_child_code(self):
        sup = make_sup([9, 9], max_restarts=1, crash_loop_threshold=99)
        assert sup.run() == 9
        assert sup.restarts == 1

    def test_repeated_wedge_at_same_progress_lengthens_backoff(self):
        """The goodput-adaptive rule: a second wedge with NO new
        progress doubles the (already longer) backoff and a third
        triples it — hammering a deterministic wedge is how pods
        burn."""
        sleeps = []
        sup = make_sup([EXIT_WEDGED, EXIT_WEDGED, EXIT_WEDGED, 0],
                       sleeps=sleeps, crash_loop_threshold=99)
        assert sup.run() == 0
        # streaks 1,2,3 → base delays 1, 2, 4; wedge repeats 0,1,2 →
        # factors 1, 2, 3
        assert sleeps == [1.0, 4.0, 12.0]

    def test_wedge_at_new_progress_does_not_lengthen(self):
        seen = iter([1, 2, 3])
        sleeps = []
        sup = make_sup([EXIT_WEDGED, EXIT_WEDGED, 0], sleeps=sleeps,
                       progress=lambda: next(seen), crash_loop_threshold=9)
        assert sup.run() == 0
        assert sleeps == [1.0, 1.0]  # streak resets, no repeat factor

    def test_sigterm_forwarded_once_then_grace_kill(self):
        """The drain contract: SIGTERM forwards to the child EXACTLY
        once (resent notices are absorbed), SIGKILL lands only after
        the grace window, and the supervisor never restarts a child it
        was asked to stop — it reports the child's final code."""
        clock = {"t": 0.0}
        holder = {}

        class HangingChild:
            def __init__(self):
                self.terminated = 0
                self.killed = 0

            def wait(self, timeout=None):
                if self.killed:
                    return 137
                sup = holder["sup"]
                sup.request_stop()
                sup.request_stop()  # schedulers resend the notice
                clock["t"] += 1.0   # each poll advances the clock
                raise subprocess.TimeoutExpired(cmd="x", timeout=timeout)

            def terminate(self):
                self.terminated += 1

            def kill(self):
                self.killed += 1

        child = HangingChild()
        sup = Supervisor(["trainer"], grace_sec=2.5,
                         spawn_fn=lambda argv: child,
                         sleep_fn=lambda s: None,
                         time_fn=lambda: clock["t"],
                         progress_fn=lambda: 0)
        holder["sup"] = sup
        assert sup.run() == 137
        assert child.terminated == 1, "SIGTERM must forward exactly once"
        assert child.killed == 1, "grace expiry must SIGKILL"
        assert sup.restarts == 0, "a stopped child is never restarted"

    def test_stop_during_backoff_prevents_respawn(self):
        spawned = []

        def sleep(_):
            sup.request_stop()

        it = iter([EXIT_WEDGED])

        def spawn(argv):
            c = FakeChild(next(it))
            spawned.append(c)
            return c

        sup = Supervisor(["t"], spawn_fn=spawn, sleep_fn=sleep,
                         time_fn=lambda: 0.0, progress_fn=lambda: 0,
                         rng=MaxJitter())
        assert sup.run() == EXIT_WEDGED
        assert len(spawned) == 1
        # no relaunch happened, so none may be counted
        assert sup.restarts == 0

    def test_stop_before_first_spawn_launches_nothing(self):
        """SIGTERM landing before the (first) spawn — e.g. during a
        slow progress read — must not launch a child the scheduler
        already wants dead."""
        spawned = []
        sup = Supervisor(["t"],
                         spawn_fn=lambda argv: spawned.append(argv),
                         sleep_fn=lambda s: None, time_fn=lambda: 0.0,
                         progress_fn=lambda: 0)
        sup.request_stop()
        assert sup.run() == 0
        assert spawned == [] and sup.restarts == 0

    def test_stop_racing_the_spawn_still_forwards_term(self):
        """SIGTERM arriving while _spawn is in flight (the handler saw
        _child=None): the fresh child must still get the TERM + grace
        contract."""
        child = FakeChild(143)

        def spawn(argv):
            # the signal lands "during" the spawn call
            sup._stop_requested = True
            return child

        sup = Supervisor(["t"], spawn_fn=spawn, sleep_fn=lambda s: None,
                         time_fn=lambda: 0.0, progress_fn=lambda: 0)
        assert sup.run() == 143
        assert child.terminated == 1
        assert sup.restarts == 0

    def test_signal_death_returncode_normalized_to_128_plus_sig(self):
        """Popen reports a signal death as -SIGNUM; the supervisor must
        speak the process table's 128+SIGNUM — a raw -9 would garble
        the final exit status (SystemExit(-9) exits 247) and 137 would
        never match a REAL SIGKILL."""
        sup = make_sup([-9, -9], max_restarts=1, crash_loop_threshold=99)
        assert sup.run() == 137  # 128 + SIGKILL, reported as-is

    def test_long_healthy_runtime_counts_as_progress(self):
        """The stateless-child (serving) breaker contract: a child that
        RAN past min_healthy_runtime_sec before failing resets the
        streak even with no step counters — three transient wedges
        days apart must not add up to a circuit-breaker trip."""
        clock = {"t": 0.0}
        children = iter([EXIT_WEDGED] * 5 + [0])

        class LongChild(FakeChild):
            def wait(self, timeout=None):
                clock["t"] += 100.0  # each child "serves" 100s
                return self.rc

        sup = Supervisor(["server"],
                         spawn_fn=lambda argv: LongChild(next(children)),
                         sleep_fn=lambda s: None,
                         time_fn=lambda: clock["t"],
                         progress_fn=lambda: 0,  # stateless: no steps
                         min_healthy_runtime_sec=60.0,
                         crash_loop_threshold=2, rng=MaxJitter())
        assert sup.run() == 0
        assert sup.restarts == 5  # survived 5 wedges, no breaker

    def test_fast_failing_stateless_child_still_trips_breaker(self):
        """...while a child that dies FASTER than the healthy-runtime
        floor, with no progress, is still a crash loop."""
        clock = {"t": 0.0}

        class FastChild(FakeChild):
            def wait(self, timeout=None):
                clock["t"] += 1.0  # dies in 1s, floor is 60s
                return self.rc

        sup = Supervisor(["server"],
                         spawn_fn=lambda argv: FastChild(1),
                         sleep_fn=lambda s: None,
                         time_fn=lambda: clock["t"],
                         progress_fn=lambda: 0,
                         min_healthy_runtime_sec=60.0,
                         crash_loop_threshold=3, rng=MaxJitter())
        assert sup.run() == EXIT_CRASH_LOOP
        assert sup.restarts == 2

    def test_broken_progress_fn_degrades_not_crashes(self):
        def boom():
            raise OSError("metrics volume gone")

        sup = make_sup([1, 1], progress=boom, crash_loop_threshold=2)
        assert sup.run() == EXIT_CRASH_LOOP  # degraded to "no progress"

    def test_validation(self):
        with pytest.raises(ValueError, match="crash_loop_threshold"):
            Supervisor(["x"], crash_loop_threshold=0)
        with pytest.raises(ValueError, match="max_restarts"):
            Supervisor(["x"], max_restarts=-1)


# --------------------------------------------------------- fault scripts
class TestFaultScript:
    def test_per_attempt_args_are_appended_once(self):
        spawned = []
        script = SupervisorFaultScript.from_dict({
            "0": {"args": ["--chaos-kill-at-step", "3"]},
        })
        sup = make_sup([EXIT_KILLED, 0], spawned=spawned,
                       fault_script=script)
        assert sup.run() == 0
        assert spawned[0][0] == ["trainer", "--flag",
                                 "--chaos-kill-at-step", "3"]
        assert spawned[1][0] == ["trainer", "--flag"]  # attempt 1 clean

    def test_from_dict_rejects_unknown_keys(self):
        with pytest.raises(ValueError, match="unknown key"):
            SupervisorFaultScript.from_dict({"0": {"argz": []}})

    def test_corrupt_without_checkpoint_dir_refused(self):
        script = SupervisorFaultScript.from_dict(
            {"0": {"corrupt_newest_checkpoint": True}})
        sup = make_sup([0], fault_script=script)
        with pytest.raises(ValueError, match="checkpoint_dir"):
            sup.run()

    def test_from_file_round_trip(self, tmp_path):
        p = tmp_path / "faults.json"
        p.write_text(json.dumps({"2": {"args": ["--x"],
                                       "corrupt_newest_checkpoint": True}}))
        s = SupervisorFaultScript.from_file(p)
        assert s.fault_for(0) is None
        f = s.fault_for(2)
        assert f.extra_args == ("--x",) and f.corrupt_newest_checkpoint


# ------------------------------------------------- corruption + quarantine
def _tree(seed):
    rng = np.random.RandomState(seed)
    return {"w": rng.randn(16, 8).astype(np.float32),
            "b": rng.randn(32).astype(np.float32)}


def _publish_step(dir_path, step, world=1):
    for r in range(world):
        io.save_sharded_checkpoint(
            f"{dir_path}/step_{step:08d}", _tree(step * 10 + r), r, world)


class TestCorruptionProbe:
    def test_probe_passes_healthy_and_crc_catches_bit_flips(self, tmp_path):
        p = tmp_path / "step_00000001.ckpt"
        io.save_checkpoint(p, _tree(0))
        io.probe_checkpoint(p)  # healthy: no raise
        size = p.stat().st_size
        corrupt_newest_checkpoint(tmp_path)  # size-preserving flip
        assert p.stat().st_size == size, "the fault must preserve size"
        io.validate_checkpoint(p)  # shallow check CANNOT see it ...
        with pytest.raises(ValueError, match="crc32"):
            io.probe_checkpoint(p)  # ... the deep probe can
        with pytest.raises(ValueError, match="crc32"):
            io.load_checkpoint(p)  # and a restore fails loudly too

    def test_probe_dir_names_newest_complete_step_dir(self, tmp_path):
        _publish_step(tmp_path, 1, world=2)
        _publish_step(tmp_path, 2, world=2)
        assert io.probe_checkpoint_dir(tmp_path) is None
        corrupt_newest_checkpoint(tmp_path)
        bad = io.probe_checkpoint_dir(tmp_path)
        assert bad is not None
        assert bad.path.endswith("step_00000002")
        assert "crc32" in bad.reason

    def test_probe_dir_nothing_to_probe(self, tmp_path):
        assert io.probe_checkpoint_dir(tmp_path / "missing") is None
        assert io.probe_checkpoint_dir(tmp_path) is None  # empty dir

    def test_quarantine_moves_dir_and_writes_reason(self, tmp_path):
        _publish_step(tmp_path, 1)
        _publish_step(tmp_path, 2)
        corrupt_newest_checkpoint(tmp_path)
        bad = io.probe_checkpoint_dir(tmp_path)
        dest = io.quarantine_checkpoint(tmp_path, bad.path, bad.reason)
        assert not (tmp_path / "step_00000002").exists()
        assert (tmp_path / "quarantine" / "step_00000002").exists()
        reason = json.loads(
            (tmp_path / "quarantine"
             / "step_00000002.reason.json").read_text())
        assert "crc32" in reason["reason"] and reason["quarantined_to"] == dest
        # the dir is healthy again: the next restore resumes from step
        # 1 (quarantine/'s contents are not step_* dirs of this root,
        # so they are never restore candidates)
        assert io.probe_checkpoint_dir(tmp_path) is None
        assert io.latest_distributed_step(tmp_path) == 1

    def test_supervisor_quarantines_after_failure(self, tmp_path):
        """The integrated path: child fails, the default probe finds
        the corrupt newest step dir, the supervisor quarantines it and
        the relaunch proceeds."""
        _publish_step(tmp_path, 1)
        _publish_step(tmp_path, 2)
        corrupt_newest_checkpoint(tmp_path)
        sup = make_sup([1, 0], checkpoint_dir=tmp_path,
                       crash_loop_threshold=5)
        assert sup.run() == 0
        assert len(sup.quarantined) == 1
        assert sup.quarantined[0].endswith("step_00000002")
        assert io.latest_distributed_step(tmp_path) == 1

    def test_corrupt_newest_requires_a_checkpoint(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            corrupt_newest_checkpoint(tmp_path)

    def test_incomplete_only_publish_is_quarantined_not_crash_looped(
            self, tmp_path):
        """A hard kill can interrupt the FIRST publish: step dirs exist
        but none is complete, so the resume side refuses loudly by
        design — which under a supervisor would crash-loop forever.
        The probe reports the newest incomplete dir for quarantine;
        the relaunch starts fresh with the bytes preserved."""
        _publish_step(tmp_path, 1, world=2)
        (tmp_path / "step_00000001"
         / "shard_00001-of-00002.ckpt").unlink()  # the un-flushed shard
        with pytest.raises(io.AllCheckpointsTornError):
            io.latest_distributed_step(tmp_path)  # the child's crash
        bad = io.probe_checkpoint_dir(tmp_path)
        assert bad is not None and bad.path.endswith("step_00000001")
        assert "incomplete publish" in bad.reason
        sup = make_sup([1, 0], checkpoint_dir=tmp_path,
                       crash_loop_threshold=5)
        assert sup.run() == 0
        assert (tmp_path / "quarantine" / "step_00000001").exists()
        assert io.latest_distributed_step(tmp_path) == -1  # fresh start

    def test_incomplete_dir_is_not_progress(self, tmp_path):
        """The default progress signal must count only COMPLETE
        checkpoints: a hard kill's half-published newest dir looking
        like progress would skip the quarantine probe and cost an
        extra crash (seen as a bench flake under load: 2 restarts
        where the contract says 1)."""
        sup = make_sup([0], checkpoint_dir=tmp_path,
                       progress_fn=None)  # None -> the real default
        _publish_step(tmp_path, 1, world=2)
        (tmp_path / "step_00000001"
         / "shard_00001-of-00002.ckpt").unlink()
        assert sup._default_progress() == 0  # incomplete: not progress
        _publish_step(tmp_path, 2, world=2)  # a complete dir counts
        assert sup._default_progress() == 2

    def test_kill_into_incomplete_publish_heals_in_one_restart(
            self, tmp_path):
        """The full cycle the bench pins: attempt 0's kill interrupts
        the only publish; the supervisor must see NO progress, probe,
        quarantine, and succeed on attempt 1 — exactly one restart."""
        _publish_step(tmp_path, 1, world=2)
        (tmp_path / "step_00000001"
         / "shard_00001-of-00002.ckpt").unlink()
        sup = make_sup([EXIT_KILLED, 0], checkpoint_dir=tmp_path,
                       progress_fn=None, crash_loop_threshold=3)
        assert sup.run() == 0
        assert sup.restarts == 1
        assert (tmp_path / "quarantine" / "step_00000001").exists()

    def test_incomplete_newest_with_complete_sibling_not_quarantined(
            self, tmp_path):
        """When a COMPLETE dir exists, the resume side already skips
        the incomplete newest one — the probe must leave it alone (it
        may even still be mid-flush from the killed writer's queue)."""
        _publish_step(tmp_path, 1, world=2)
        _publish_step(tmp_path, 2, world=2)
        (tmp_path / "step_00000002"
         / "shard_00001-of-00002.ckpt").unlink()
        assert io.probe_checkpoint_dir(tmp_path) is None
        assert io.latest_distributed_step(tmp_path) == 1


# ----------------------------------------------------------- small seams
class TestSeams:
    def test_restart_backoff_rng_seam_pins_delays(self):
        """The satellite contract: rng= overrides the per-(seed,
        attempt) derivation, existing callers unchanged."""
        a = [restart_backoff(k, base=2.0, cap=30.0,
                             rng=random.Random(123)) for k in range(5)]
        b = [restart_backoff(k, base=2.0, cap=30.0,
                             rng=random.Random(123)) for k in range(5)]
        assert a == b
        for k, v in enumerate(a):
            assert 0.0 <= v <= min(30.0, 2.0 * 2 ** k)
        assert restart_backoff(2, base=4.0, cap=99.0,
                               rng=MaxJitter()) == 16.0
        # the seeded path is byte-for-byte the pre-seam behavior
        assert restart_backoff(3, seed=7) == restart_backoff(3, seed=7)

    def test_strip_supervisor_argv_both_spellings(self):
        argv = ["--supervise", "--steps", "6", "--max-restarts", "4",
                "--backoff-base=0.5", "--zero", "--fault-script",
                "f.json", "--checkpoint", "ck"]
        assert strip_supervisor_argv(argv) == [
            "--steps", "6", "--zero", "--checkpoint", "ck"]

    def test_fault_dataclass_defaults(self):
        f = SupervisorFault()
        assert f.extra_args == () and not f.corrupt_newest_checkpoint


def test_restart_cycle_leaves_parent_without_backend(tmp_path):
    """A chip belongs to one process: the supervisor parent logs,
    counts metrics and backs off between two real children, and must
    come out of that restart cycle WITHOUT a JAX backend — a parent that
    initialised one (formatting a log record used to) would take the
    device from the child it is about to spawn.  Runs in a fresh
    interpreter, since this one already holds the CPU backend."""
    child = ("import os, sys; p = sys.argv[1]\n"
             "if os.path.exists(p): sys.exit(0)\n"
             "open(p, 'w').close(); sys.exit(137)")
    parent = f"""
import json, sys
from jax._src import xla_bridge
from apex_tpu.observability import metrics
from apex_tpu.resilience import Supervisor
sup = Supervisor([sys.executable, "-c", {child!r}, {str(tmp_path / "marker")!r}],
                 metrics_dir={str(tmp_path)!r}, run_id="t",
                 backoff_base=0.01, backoff_cap=0.01)
rc = sup.run()
metrics.get_metrics().prometheus_text()
print(json.dumps({{"rc": rc, "restarts": sup.restarts,
                  "backend": xla_bridge.backends_are_initialized()}}))
"""
    r = subprocess.run([sys.executable, "-c", parent], capture_output=True,
                       text=True, timeout=120,
                       cwd=str(Path(__file__).resolve().parents[1]))
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out == {"rc": 0, "restarts": 1, "backend": False}, (out, r.stderr)
    assert "supervisor.restarting" in r.stderr  # it did log in between
