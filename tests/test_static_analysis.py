"""Self-tests for ``apex_tpu.analysis`` — and the tier-1 rider that
keeps the repo clean.

Layout: per-rule positive/negative fixture pairs (the positives for
APX102/302/401 are the literal pre-fix ADVICE r5 snippets from
bench.py:876, ops/fused_ce_pallas.py:58, and models/gpt.py:447 — the
findings this subsystem exists to scale), engine unit tests (traced
index, axis-registry discovery, baseline), and the repo-wide clean
check ``python -m apex_tpu.analysis apex_tpu examples`` rides on.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from apex_tpu.analysis import (
    DEFAULT_RULES,
    BaselineError,
    analyze_file,
    analyze_paths,
    apply_baseline,
    discover_axis_registry,
    load_baseline,
    write_baseline,
)
from apex_tpu.analysis import sarif
from apex_tpu.analysis.rules_collectives import (
    CollectiveAxisOutsideShardMapNest,
    CollectiveAxisUnboundUnderJit,
    CollectiveOutsideSpmdContext,
    CollectiveTupleAxisUnbound,
    UnknownCollectiveAxis,
)
from apex_tpu.analysis.rules_divergence import (
    TaintedEngineDispatchDivergence,
    TaintedPredicateGuardsCollective,
    TaintedValueShapesCompiledProgram,
)
from apex_tpu.analysis.rules_donation import DonatedBufferReuse
from apex_tpu.analysis.rules_sharding import (
    DonatedShardingMismatch,
    ShardingSpecAxisUnbound,
    ShardingSpecRankMismatch,
)
from apex_tpu.analysis.rules_host_sync import (
    BlockingHostSyncInStepLoop, UnseamedDispatchTiming,
)
from apex_tpu.analysis.rules_inference import KvPoolScatterBypassesSeam
from apex_tpu.analysis.rules_io import NonAtomicCheckpointWrite
from apex_tpu.analysis.rules_resilience import (
    RetryWithoutBackoff, SwallowedExceptionInRecoveryPath,
)
from apex_tpu.analysis.rules_precision import (
    KvCacheReadDtypeMismatch,
    PageTableGatherUnclamped,
    QuantizedSyncStateDtype,
    Fp32ConstantInBf16Path,
    ScratchAccumDtypeMismatch,
    UnclampedTakeAlongAxis,
)
from apex_tpu.analysis.rules_threading import (
    BlockingCallUnderContendedLock,
    LockOrderInversion,
    SharedMutationWithoutLock,
)
from apex_tpu.analysis.rules_tiling import (
    BlockShapeTilingViolation,
    BlockSpecIndexMapArity,
    HardCodedSublaneAlignment,
    VmemFootprintOverBudget,
)
from apex_tpu.analysis.rules_trace import (
    ProcessGlobalEnvMutation,
    TraceTimeHostStateRead,
)

REPO = Path(__file__).resolve().parent.parent
AXES = frozenset({"dp", "pp", "cp", "tp", "dcn"})


def run(src, tmp_path, rules, axes=AXES):
    p = tmp_path / "fixture.py"
    p.write_text(textwrap.dedent(src))
    return analyze_file(str(p), list(rules), set(axes))


def rule_ids(findings):
    return [f.rule for f in findings]


# ------------------------------------------------- APX101 trace-time reads
class TestTraceTimeHostStateRead:
    def test_positive_env_read_via_helper_under_jit(self, tmp_path):
        """The fused_ce.py shape: the env read lives in a helper that a
        jitted function calls — caught through the module call graph."""
        got = run("""
            import os
            import jax

            def _mode():
                return os.environ.get("APEX_TPU_FUSED_CE_PALLAS", "auto")

            @jax.jit
            def f(x):
                if _mode() == "on":
                    return x * 2
                return x
            """, tmp_path, [TraceTimeHostStateRead()])
        assert rule_ids(got) == ["APX101"]
        assert got[0].symbol == "_mode"
        assert "frozen into the first trace" in got[0].message

    def test_positive_clock_in_pallas_kernel_via_partial_alias(self, tmp_path):
        """The fused_ce_pallas shape: kernel bound with functools.partial
        into a local name, then handed to pl.pallas_call."""
        got = run("""
            import functools
            import time
            from jax.experimental import pallas as pl

            def _kernel(x_ref, o_ref, *, nv):
                o_ref[:] = x_ref[:] * time.time()

            def launch(x, nv):
                kernel = functools.partial(_kernel, nv=nv)
                return pl.pallas_call(kernel, grid=(nv,))(x)
            """, tmp_path, [TraceTimeHostStateRead()])
        assert rule_ids(got) == ["APX101"]
        assert "wall clock" in got[0].message

    def test_positive_host_rng_under_defvjp(self, tmp_path):
        got = run("""
            import numpy as np
            import jax

            @jax.custom_vjp
            def op(x):
                return x

            def _fwd(x):
                return x, None

            def _bwd(res, g):
                return (g * np.random.rand(),)

            op.defvjp(_fwd, _bwd)
            """, tmp_path, [TraceTimeHostStateRead()])
        assert rule_ids(got) == ["APX101"]
        assert "host RNG" in got[0].message

    def test_positive_bare_environ_get_and_lambda(self, tmp_path):
        """Blind spots closed in review: the bare-import spelling
        (`from os import environ`) and a hazard inside `jax.jit(lambda
        ...)` (lambdas have no FunctionDef to index)."""
        got = run("""
            from os import environ, getenv

            import jax

            @jax.jit
            def f(x):
                return x if environ.get("FLAG") else -x

            g = jax.jit(lambda x: x if getenv("FLAG") else -x)
            """, tmp_path, [TraceTimeHostStateRead()])
        assert rule_ids(got) == ["APX101", "APX101"]

    def test_positive_lambda_calling_local_helper(self, tmp_path):
        got = run("""
            import os

            import jax

            def _mode():
                return os.environ.get("FLAG", "auto")

            g = jax.jit(lambda x: x * 2 if _mode() == "on" else x)
            """, tmp_path, [TraceTimeHostStateRead()])
        assert rule_ids(got) == ["APX101"]
        assert got[0].symbol == "_mode"

    def test_negative_host_side_read(self, tmp_path):
        """Same reads, no trace context: host-side config code is fine."""
        got = run("""
            import os
            import time

            def pick_backend():
                return os.environ.get("BACKEND", "tpu")

            def stamp():
                return time.time()
            """, tmp_path, [TraceTimeHostStateRead()])
        assert got == []

    def test_negative_module_level_read(self, tmp_path):
        got = run("""
            import os
            import jax

            _FLAG = os.environ.get("FLAG", "1")

            @jax.jit
            def f(x):
                return x + 1
            """, tmp_path, [TraceTimeHostStateRead()])
        assert got == []


# --------------------------------------------- APX102 env-var mutation
class TestProcessGlobalEnvMutation:
    def test_positive_advice_r5_bench_py_876(self, tmp_path):
        """The literal pre-fix bench.py:876 shape (ADVICE r5): flip the
        env var, rerun, restore — invisible to already-traced jits."""
        got = run("""
            import os

            def bench_gpt_fce(bench_gpt, roof):
                os.environ["APEX_TPU_FUSED_CE_PALLAS"] = "0"
                try:
                    r = bench_gpt(12, 768, 12, 1024, 8, roof, fused_ce=True)
                finally:
                    os.environ.pop("APEX_TPU_FUSED_CE_PALLAS", None)
                return r
            """, tmp_path, [ProcessGlobalEnvMutation()])
        assert rule_ids(got) == ["APX102", "APX102"]
        assert "os.environ[...] assignment" in got[0].message
        assert "os.environ.pop" in got[1].message

    def test_negative_module_level_startup_config(self, tmp_path):
        """Startup env config before anything traces is the accepted
        idiom — only mid-process mutation inside functions is flagged."""
        got = run("""
            import os

            os.environ.setdefault("JAX_PLATFORMS", "cpu")
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            """, tmp_path, [ProcessGlobalEnvMutation()])
        assert got == []


# --------------------------------------------- APX103 donated-buffer reuse
class TestDonatedBufferReuse:
    def test_positive_read_after_donate_new_name(self, tmp_path):
        """The classic shape: the step's result is bound to NEW names
        while the stale donated name is read for logging afterwards —
        a no-op on CPU, garbage on TPU (ROADMAP donation/aliasing
        open item)."""
        got = run("""
            import jax

            def make(step_fn):
                return jax.jit(step_fn, donate_argnums=(0, 1))

            step = jax.jit(lambda p, s: (p, s), donate_argnums=(0, 1))

            def train(params, state, norm_of):
                new_params, new_state = step(params, state)
                norm = norm_of(params)
                return new_params, new_state, norm
            """, tmp_path, [DonatedBufferReuse()])
        assert rule_ids(got) == ["APX103"]
        assert "`params` is donated" in got[0].message
        assert "rebound" in got[0].message

    def test_positive_partial_decorator_spelling(self, tmp_path):
        """@partial(jax.jit, donate_argnums=...) defs are tracked by
        their function name (a benchmark harness's step idiom)."""
        got = run("""
            from functools import partial

            import jax

            @partial(jax.jit, donate_argnums=(0,))
            def step(params, grads):
                return params

            def train(params, grads, save):
                out = step(params, grads)
                save(params)
                return out
            """, tmp_path, [DonatedBufferReuse()])
        assert rule_ids(got) == ["APX103"]

    def test_negative_early_return_branches(self, tmp_path):
        """A donating call that is itself a `return` value: nothing
        later in the function can run after it in the same invocation,
        so a read on the sibling branch (the early-return shape) is
        provably safe and must stay silent."""
        got = run("""
            import jax

            step = jax.jit(lambda p, s: (p, s), donate_argnums=(0,))

            def train(params, state, cond, norm_of):
                if cond:
                    return step(params, state)
                return norm_of(params)
            """, tmp_path, [DonatedBufferReuse()])
        assert got == []

    def test_negative_sibling_branch_read(self, tmp_path):
        """Assign-in-branch sibling of the early-return shape: the
        else-arm read can never execute after the if-arm's donating
        call in one invocation — silent."""
        got = run("""
            import jax

            step = jax.jit(lambda p: p, donate_argnums=(0,))

            def train(params, cond, f):
                if cond:
                    out = step(params)
                else:
                    out = f(params)
                return out
            """, tmp_path, [DonatedBufferReuse()])
        assert got == []

    def test_positive_sibling_branch_inside_loop(self, tmp_path):
        """The same two arms under a loop ARE a bug: iteration 1 may
        donate, iteration 2 read the stale name."""
        got = run("""
            import jax

            step = jax.jit(lambda p: p, donate_argnums=(0,))

            def train(params, iters, f):
                for i in range(iters):
                    if i % 2 == 0:
                        out = step(params)
                    else:
                        out = f(params)
                return out
            """, tmp_path, [DonatedBufferReuse()])
        assert rule_ids(got) == ["APX103"]

    def test_positive_read_after_exclusive_branch(self, tmp_path):
        """A read BELOW the if/else is reachable after the donating arm
        ran — the exclusive-branch skip must not silence it."""
        got = run("""
            import jax

            step = jax.jit(lambda p: p, donate_argnums=(0,))

            def train(params, cond, f, g):
                if cond:
                    out = step(params)
                else:
                    out = f(params)
                return g(params)
            """, tmp_path, [DonatedBufferReuse()])
        assert rule_ids(got) == ["APX103"]

    def test_negative_rebound_from_the_call(self, tmp_path):
        """`params, state, loss = step(params, state)` — the safe
        idiom every bench section uses — must stay silent, including
        inside loops (the rebind covers the next iteration's read)."""
        got = run("""
            from functools import partial

            import jax

            @partial(jax.jit, donate_argnums=(0, 1))
            def step(params, state):
                return params, state, 0.0

            def train(params, state, iters):
                params, state, loss = step(params, state)
                for _ in range(iters):
                    params, state, loss = step(params, state)
                return params, loss
            """, tmp_path, [DonatedBufferReuse()])
        assert got == []

    def test_negative_read_before_and_rebind_after(self, tmp_path):
        got = run("""
            import jax

            step = jax.jit(lambda p: p, donate_argnums=(0,))

            def train(params, norm_of):
                norm = norm_of(params)      # read BEFORE donation: fine
                out = step(params)
                params = out                # rebound before any read
                return params, norm
            """, tmp_path, [DonatedBufferReuse()])
        assert got == []

    def test_negative_same_name_in_nested_scope(self, tmp_path):
        """A same-named parameter or local of a NESTED scope after the
        donating call is a different variable, not the donated buffer —
        the read search stops at function/class/lambda boundaries (this
        exact shape was a reproduced false positive)."""
        got = run("""
            import jax

            step = jax.jit(lambda p: p, donate_argnums=(0,))
            params = {"w": 1.0}
            out = step(params)

            def helper(params):
                return params["w"] * 2

            scale = lambda params: params["w"] + 1
            """, tmp_path, [DonatedBufferReuse()])
        assert got == []

    def test_negative_nested_scope_inside_function(self, tmp_path):
        """Same boundary one level down: a helper def nested in the
        donating function reuses the name for its own parameter."""
        got = run("""
            import jax

            step = jax.jit(lambda p: p, donate_argnums=(0,))

            def train(params, sink):
                out = step(params)

                def norm_of(params):
                    return params["w"]

                sink(norm_of(out))
                return out
            """, tmp_path, [DonatedBufferReuse()])
        assert got == []

    def test_negative_computed_argnums_and_star_args(self, tmp_path):
        """Non-literal donate_argnums and *args call sites are trusted
        (the models/gpt.py `donate_argnums=donate` shape)."""
        got = run("""
            import jax

            def make(fn, donate_state):
                donate = (0, 1) if donate_state else ()
                return jax.jit(fn, donate_argnums=donate)

            step = jax.jit(lambda p, s: (p, s), donate_argnums=(0, 1))

            def train(step_args, params):
                out = step(*step_args)
                return out, params
            """, tmp_path, [DonatedBufferReuse()])
        assert got == []


# ------------------------------------------ APX104 non-atomic ckpt write
class TestNonAtomicCheckpointWrite:
    def test_positive_direct_wb_on_checkpoint_path(self, tmp_path):
        """The torn-write shape: a checkpoint-named path opened for a
        direct binary write — an interrupted writer publishes a
        truncated file under the final name."""
        got = run("""
            def save(ckpt_path, blob):
                with open(ckpt_path, "wb") as f:
                    f.write(blob)
            """, tmp_path, [NonAtomicCheckpointWrite()])
        assert rule_ids(got) == ["APX104"]
        assert "atomic_output" in got[0].fix_hint

    def test_positive_checkpointish_function_name(self, tmp_path):
        """The function name marks the write even when the path
        expression itself is opaque."""
        got = run("""
            def write_checkpoint(path, blob):
                f = open(path, mode="wb")
                f.write(blob)
                f.close()
            """, tmp_path, [NonAtomicCheckpointWrite()])
        assert rule_ids(got) == ["APX104"]

    def test_positive_append_and_exclusive_binary_modes(self, tmp_path):
        got = run("""
            def save(ckpt_path, blob):
                with open(ckpt_path, "ab") as f:
                    f.write(blob)
                with open(ckpt_path, "xb") as f:
                    f.write(blob)
            """, tmp_path, [NonAtomicCheckpointWrite()])
        assert rule_ids(got) == ["APX104", "APX104"]

    def test_negative_tmp_staged_write(self, tmp_path):
        """Writing to <path>.tmp then renaming IS the atomic idiom —
        the staging write must stay silent."""
        got = run("""
            import os

            def save(ckpt_path, blob):
                with open(str(ckpt_path) + ".tmp", "wb") as f:
                    f.write(blob)
                os.replace(str(ckpt_path) + ".tmp", ckpt_path)
            """, tmp_path, [NonAtomicCheckpointWrite()])
        assert got == []

    def test_negative_atomic_helper_itself(self, tmp_path):
        """The designated helper (atomic_output / _atomic_* wrappers)
        owns the one sanctioned open."""
        got = run("""
            import contextlib, os

            @contextlib.contextmanager
            def atomic_output(path):
                f = open(str(path) + ".stage", "wb")
                yield f
                f.close()
                os.replace(str(path) + ".stage", path)

            def _atomic_write_checkpoint(path, blob):
                f = open(path, "wb")
                f.write(blob)
            """, tmp_path, [NonAtomicCheckpointWrite()])
        assert got == []

    def test_negative_non_checkpoint_writes_and_reads(self, tmp_path):
        """Binary writes to non-checkpoint paths, text-mode writes, and
        checkpoint READS are out of scope."""
        got = run("""
            def dump_log(log_path, text):
                with open(log_path, "wb") as f:      # not a ckpt path
                    f.write(text)
                with open("sections.jsonl", "a") as f:  # text append
                    f.write("{}")

            def load_checkpoint(ckpt_path):
                with open(ckpt_path, "rb") as f:     # read: fine
                    return f.read()
            """, tmp_path, [NonAtomicCheckpointWrite()])
        assert got == []

    def test_negative_computed_mode_trusted(self, tmp_path):
        got = run("""
            def save(ckpt_path, blob, mode):
                with open(ckpt_path, mode) as f:
                    f.write(blob)
            """, tmp_path, [NonAtomicCheckpointWrite()])
        assert got == []


# ---------------------------------- APX109 swallowed recovery-path except
class TestSwallowedExceptionInRecoveryPath:
    """The silent-swallow pattern PR 10's review kept hand-auditing:
    a do-nothing `except` in resilience/io/inference erases the one
    signal a wedged run's postmortem needs."""

    def _run_scoped(self, src, tmp_path, subdir):
        """Fixture placed under a scoped directory: APX109 keys on the
        path's directory segments (resilience/io/inference), not on the
        file name."""
        d = tmp_path / subdir
        d.mkdir(parents=True, exist_ok=True)
        p = d / "fixture.py"
        p.write_text(textwrap.dedent(src))
        return analyze_file(str(p), [SwallowedExceptionInRecoveryPath()],
                            set(AXES))

    def test_positive_except_pass_in_resilience(self, tmp_path):
        """The motivating shape: a drain error swallowed whole — the
        supervisor restarts on a wedge and nobody ever learns the
        flush failed too."""
        got = self._run_scoped("""
            def drain(checkpointer):
                try:
                    checkpointer.wait_until_finished()
                except OSError:
                    pass
            """, tmp_path, "resilience")
        assert rule_ids(got) == ["APX109"]
        assert "OSError" in got[0].message
        assert "log_structured" in got[0].fix_hint

    def test_positive_bare_except_ellipsis_in_io(self, tmp_path):
        got = self._run_scoped("""
            def read_shard(path):
                try:
                    return open(path, "rb").read()
                except:
                    ...
            """, tmp_path, "io")
        assert rule_ids(got) == ["APX109"]
        assert "bare" in got[0].message

    def test_positive_stray_string_body_in_inference(self, tmp_path):
        """A bare string is not a report — it is a comment that
        evaluates to nothing."""
        got = self._run_scoped("""
            def evict(slot, allocator, pages):
                try:
                    allocator.free(pages)
                except ValueError:
                    "double free: already recycled"
            """, tmp_path, "inference")
        assert rule_ids(got) == ["APX109"]

    def test_negative_logging_metrics_reraise_and_defaults(self, tmp_path):
        """Handlers that report (log_structured, a metrics record), re-
        raise, or return a fallback value are the sanctioned shapes."""
        got = self._run_scoped("""
            import logging

            def recover(step, logger, metrics):
                try:
                    step()
                except OSError as e:
                    log_structured(logger, logging.WARNING,
                                   "step.recovered", error=str(e))
                try:
                    step()
                except ValueError:
                    metrics.inc("apex_bad_steps_total")
                try:
                    step()
                except KeyError:
                    raise
                try:
                    return step()
                except RuntimeError:
                    return None
            """, tmp_path, "resilience")
        assert got == []

    def test_negative_out_of_scope_modules_trusted(self, tmp_path):
        """The same swallow OUTSIDE the recovery-path packages (an
        example script, an op) is not this rule's business."""
        src = """
            def cleanup(path):
                try:
                    path.unlink()
                except OSError:
                    pass
            """
        for subdir in ("examples/gpt", "ops", "observability"):
            assert self._run_scoped(src, tmp_path, subdir) == []


# ------------------------------------------ APX113 retry without backoff
class TestRetryWithoutBackoff:
    """The busy-spin retry: `while True:` swallowing the failure and
    immediately re-attempting hammers the failing dependency exactly
    when it needs room to recover."""

    def _run_scoped(self, src, tmp_path, subdir):
        d = tmp_path / subdir
        d.mkdir(parents=True, exist_ok=True)
        p = d / "fixture.py"
        p.write_text(textwrap.dedent(src))
        return analyze_file(str(p), [RetryWithoutBackoff()], set(AXES))

    def test_positive_hot_retry_in_resilience(self, tmp_path):
        got = self._run_scoped("""
            def reconnect(coordinator, log):
                while True:
                    try:
                        return coordinator.connect()
                    except OSError as e:
                        log.warning("retrying: %s", e)
            """, tmp_path, "resilience")
        assert rule_ids(got) == ["APX113"]
        assert "busy-spin" in got[0].message
        assert "retry_after_s" in got[0].fix_hint

    def test_positive_while_one_in_inference(self, tmp_path):
        """`while 1:` is the same loop; logging between attempts is
        reporting, not pacing."""
        got = self._run_scoped("""
            def resubmit(frontend, request):
                while 1:
                    try:
                        frontend.submit(request)
                        break
                    except Overloaded:
                        continue
            """, tmp_path, "inference")
        assert rule_ids(got) == ["APX113"]

    def test_negative_sleep_between_attempts(self, tmp_path):
        got = self._run_scoped("""
            import time

            def reconnect(coordinator):
                while True:
                    try:
                        return coordinator.connect()
                    except OSError:
                        time.sleep(0.5)
            """, tmp_path, "resilience")
        assert got == []

    def test_negative_backoff_helper_and_timeout_wait(self, tmp_path):
        """The supervisor shape: a crash-loop `_backoff_s` helper or a
        `child.wait(timeout=...)` both pace the loop."""
        got = self._run_scoped("""
            def supervise(child, attempt):
                while True:
                    try:
                        child.wait(timeout=0.2)
                        return child.returncode
                    except TimeoutError:
                        attempt += 1
            """, tmp_path, "resilience")
        assert got == []

    def test_negative_handler_escapes_loop(self, tmp_path):
        """A handler that re-raises / breaks / returns is not a retry
        loop — it gives up instead of spinning."""
        got = self._run_scoped("""
            def drain(sched):
                while True:
                    try:
                        sched.step()
                    except RuntimeError:
                        raise
                while True:
                    try:
                        sched.step()
                    except RuntimeError:
                        break
            """, tmp_path, "io")
        assert got == []

    def test_negative_blocking_dequeue_worker(self, tmp_path):
        """The async-checkpoint worker: the loop parks on a no-arg
        `q.get()` each iteration — not a busy-spin over the failure."""
        got = self._run_scoped("""
            def worker(q, errors):
                while True:
                    try:
                        q.get()()
                    except OSError as e:
                        errors.append(e)
            """, tmp_path, "io")
        assert got == []

    def test_negative_out_of_scope_and_bounded_for(self, tmp_path):
        """Outside resilience/io/inference the loop is not this rule's
        business, and a bounded `for` retry is self-limiting."""
        src = """
            def reconnect(coordinator):
                while True:
                    try:
                        return coordinator.connect()
                    except OSError:
                        pass
            """
        assert self._run_scoped(src, tmp_path, "examples/gpt") == []
        got = self._run_scoped("""
            def reconnect(coordinator):
                for _ in range(3):
                    try:
                        return coordinator.connect()
                    except OSError:
                        pass
            """, tmp_path, "resilience")
        assert got == []


# ------------------------------------------- APX201 unknown collective axis
class TestUnknownCollectiveAxis:
    def test_positive_typo_axis(self, tmp_path):
        got = run("""
            import jax

            def allreduce(x):
                return jax.lax.psum(x, "tq")
            """, tmp_path, [UnknownCollectiveAxis()])
        assert rule_ids(got) == ["APX201"]
        assert "'tq'" in got[0].message

    def test_positive_unknown_in_tuple(self, tmp_path):
        got = run("""
            import jax

            def hier(x):
                return jax.lax.psum(x, ("dcn", "dq"))
            """, tmp_path, [UnknownCollectiveAxis()])
        assert rule_ids(got) == ["APX201"]
        assert "'dq'" in got[0].message

    def test_negative_registered_and_dynamic_axes(self, tmp_path):
        got = run("""
            import jax

            def allreduce(x):
                return jax.lax.psum(x, "tp")

            def generic(x, axis_name):
                return jax.lax.pmean(x, axis_name)

            def hier(x):
                return jax.lax.psum(x, ("dcn", "dp"))
            """, tmp_path, [UnknownCollectiveAxis()])
        assert got == []


# ------------------------------------ APX202 collective without spmd context
class TestCollectiveOutsideSpmdContext:
    def test_positive_no_shard_map_in_sight(self, tmp_path):
        got = run("""
            import jax

            def loss(x):
                return jax.lax.pmean(x, "dp")
            """, tmp_path, [CollectiveOutsideSpmdContext()])
        assert rule_ids(got) == ["APX202"]

    def test_negative_module_binds_the_axis(self, tmp_path):
        got = run("""
            import jax
            from jax.sharding import Mesh, PartitionSpec as P

            def loss(x):
                return jax.lax.pmean(x, "dp")

            def train(mesh, x):
                return jax.shard_map(loss, mesh=mesh,
                                     in_specs=P("dp"), out_specs=P())(x)
            """, tmp_path, [CollectiveOutsideSpmdContext()])
        assert got == []


# ------------------------------ APX203 collective unbound under jit/pjit
class TestCollectiveAxisUnboundUnderJit:
    def test_positive_helper_reached_only_from_jit(self, tmp_path):
        """jit binds no axis names: the psum dies with an unbound-axis
        error on the first real trace — which for TPU-gated code is the
        chip, not the CPU suite."""
        got = run("""
            import jax

            def allreduce(x):
                return jax.lax.psum(x, "dp")

            @jax.jit
            def f(x):
                return allreduce(x)
            """, tmp_path, [CollectiveAxisUnboundUnderJit()])
        assert rule_ids(got) == ["APX203"]
        assert got[0].symbol == "allreduce"
        assert "jit auto-sharding binds no axis names" in got[0].message

    def test_positive_inside_jitted_lambda(self, tmp_path):
        got = run("""
            import jax

            g = jax.jit(lambda x: jax.lax.pmean(x, "tp"))
            """, tmp_path, [CollectiveAxisUnboundUnderJit()])
        assert rule_ids(got) == ["APX203"]

    def test_one_hazard_one_finding_with_apx202(self, tmp_path):
        """Reconciliation: where the dataflow pass HAS a verdict, the
        APX202 module heuristic yields — the full rule set reports
        exactly one finding for the jit-only psum."""
        got = run("""
            import jax

            def allreduce(x):
                return jax.lax.psum(x, "dp")

            @jax.jit
            def f(x):
                return allreduce(x)
            """, tmp_path, DEFAULT_RULES)
        assert rule_ids(got) == ["APX203"]

    def test_negative_shard_map_binds_the_axis(self, tmp_path):
        """The same helper additionally reachable through a shard_map
        whose (statically resolvable) mesh carries the axis: one
        binding path acquits the call site."""
        got = run("""
            import jax
            import numpy as np
            from jax.sharding import Mesh, PartitionSpec as P

            def allreduce(x):
                return jax.lax.psum(x, "dp")

            @jax.jit
            def f(x):
                return allreduce(x)

            def train(x):
                mesh = Mesh(np.array(jax.devices()), ("dp",))
                return jax.shard_map(allreduce, mesh=mesh,
                                     in_specs=P("dp"), out_specs=P())(x)
            """, tmp_path, [CollectiveAxisUnboundUnderJit(),
                            CollectiveAxisOutsideShardMapNest()])
        assert got == []

    def test_negative_dynamic_axis_name_never_flags(self, tmp_path):
        """Threading the axis as an argument is the RECOMMENDED fix —
        a dynamic axis name must stay silent even on a jit-only path
        (the caller may pass an axis its own shard_map binds)."""
        got = run("""
            import jax

            def generic(x, axis_name):
                return jax.lax.pmean(x, axis_name)

            @jax.jit
            def f(x):
                return generic(x, "dp")
            """, tmp_path, [CollectiveAxisUnboundUnderJit(),
                            CollectiveAxisOutsideShardMapNest(),
                            CollectiveOutsideSpmdContext()])
        assert got == []

    def test_negative_unregistered_axis_is_apx201_territory(self, tmp_path):
        got = run("""
            import jax

            @jax.jit
            def f(x):
                return jax.lax.psum(x, "tq")
            """, tmp_path, [CollectiveAxisUnboundUnderJit(),
                            UnknownCollectiveAxis()])
        assert rule_ids(got) == ["APX201"]

    def test_cross_module_jit_wrapper_feeds_apx203(self, tmp_path):
        """The collective lives in one file, its ONLY traced entry
        point (a jit wrapper) in another: the linked scope pass still
        proves the axis unbound — per-module analysis could not."""
        (tmp_path / "collective_mod.py").write_text(textwrap.dedent("""
            import jax

            def allreduce(x):
                return jax.lax.psum(x, "dp")
            """))
        (tmp_path / "main.py").write_text(textwrap.dedent("""
            import jax
            from collective_mod import allreduce

            @jax.jit
            def step(x):
                return allreduce(x)
            """))
        got = analyze_paths([str(tmp_path)], DEFAULT_RULES,
                            axis_registry=set(AXES), rel_to=str(tmp_path))
        assert [(f.rule, f.path, f.symbol) for f in got] == \
            [("APX203", "collective_mod.py", "allreduce")]


# --------------------------- APX204 collective outside the shard_map nest
class TestCollectiveAxisOutsideShardMapNest:
    def test_positive_nest_binds_only_other_axes(self, tmp_path):
        """Both axes are on the registry (APX201 is blind), but the
        shard_map's resolvable mesh binds only "tp" — the dp collective
        can never bind on this path."""
        got = run("""
            import jax
            import numpy as np
            from jax.sharding import Mesh, PartitionSpec as P

            def loss(x):
                return jax.lax.pmean(x, "dp")

            def train(x):
                mesh = Mesh(np.array(jax.devices()), ("tp",))
                return jax.shard_map(loss, mesh=mesh, in_specs=P("tp"),
                                     out_specs=P())(x)
            """, tmp_path, [CollectiveAxisOutsideShardMapNest()])
        assert rule_ids(got) == ["APX204"]
        assert "binds only {tp}" in got[0].message

    def test_negative_shadowed_axis_nest_unions(self, tmp_path):
        """The nest case that MUST stay silent: the inner shard_map
        binds only "tp", but the outer one already bound "dp" — axes
        accumulate through the nest, so the dp collective inside the
        inner function is legal."""
        got = run("""
            import jax
            import numpy as np
            from jax.sharding import Mesh, PartitionSpec as P

            def inner(x):
                return jax.lax.psum(x, "dp")

            def mid(x):
                tp_mesh = Mesh(np.array(jax.devices()), ("tp",))
                return jax.shard_map(inner, mesh=tp_mesh,
                                     in_specs=P("tp"), out_specs=P())(x)

            def train(x):
                dp_mesh = Mesh(np.array(jax.devices()), ("dp",))
                return jax.shard_map(mid, mesh=dp_mesh,
                                     in_specs=P("dp"), out_specs=P())(x)
            """, tmp_path, DEFAULT_RULES)
        assert got == []

    def test_negative_dynamic_mesh_is_unknowable(self, tmp_path):
        """A mesh passed in as a parameter may bind ANY axes — the
        scope records unknown and the rule stays quiet (specs are only
        a lower bound: replicated axes never appear in them)."""
        got = run("""
            import jax
            from jax.sharding import PartitionSpec as P

            def loss(x):
                return jax.lax.pmean(x, "dp")

            def train(mesh, x):
                return jax.shard_map(loss, mesh=mesh, in_specs=P("tp"),
                                     out_specs=P())(x)
            """, tmp_path, DEFAULT_RULES)
        assert got == []

    def test_positive_pmap_binds_one_name(self, tmp_path):
        got = run("""
            import jax

            def loss(x):
                return jax.lax.pmean(x, "dp")

            def train(x):
                return jax.pmap(loss, axis_name="tp")(x)
            """, tmp_path, [CollectiveAxisOutsideShardMapNest()])
        assert rule_ids(got) == ["APX204"]

    def test_negative_lambda_under_binding_shard_map(self, tmp_path):
        got = run("""
            import jax
            import numpy as np
            from jax.sharding import Mesh, PartitionSpec as P

            def train(x):
                mesh = Mesh(np.array(jax.devices()), ("dp",))
                return jax.shard_map(
                    lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                    in_specs=P("dp"), out_specs=P())(x)
            """, tmp_path, DEFAULT_RULES)
        assert got == []


# --------------------------- APX205 tuple-of-axes collective with unbound
HIER_AXES_REG = AXES | {"dp_out", "dp_in"}


class TestCollectiveTupleAxisUnbound:
    """APX205: the hierarchical-sync spelling ``psum(x, ("dp_out",
    "dp_in"))`` needs EVERY member bound in the same nest — the scalar
    dataflow rules (203/204) yield tuple spellings here, which judges
    the tuple at once and names exactly the bad members."""

    def test_positive_tuple_under_jit_only(self, tmp_path):
        got = run("""
            import jax

            def hier_mean(x):
                return jax.lax.pmean(x, ("dp_out", "dp_in"))

            @jax.jit
            def f(x):
                return hier_mean(x)
            """, tmp_path, [CollectiveTupleAxisUnbound()],
            axes=HIER_AXES_REG)
        assert rule_ids(got) == ["APX205"]
        assert "'dp_out'" in got[0].message and "'dp_in'" in got[0].message
        assert "jit" in got[0].message

    def test_positive_nest_binds_only_one_member(self, tmp_path):
        """The case neither APX201 nor the scalar rules report as ONE
        hazard: both members are registered, the shard_map binds only
        the inner axis — the tuple collective dies at trace time."""
        got = run("""
            import jax
            import numpy as np
            from jax.sharding import Mesh, PartitionSpec as P

            def loss(x):
                return jax.lax.pmean(x, ("dp_out", "dp_in"))

            def train(x):
                mesh = Mesh(np.array(jax.devices()), ("dp_in",))
                return jax.shard_map(loss, mesh=mesh, in_specs=P("dp_in"),
                                     out_specs=P())(x)
            """, tmp_path, [CollectiveTupleAxisUnbound()],
            axes=HIER_AXES_REG)
        assert rule_ids(got) == ["APX205"]
        assert "['dp_out']" in got[0].message
        assert "binds only {dp_in}" in got[0].message

    def test_one_hazard_one_finding_full_rule_set(self, tmp_path):
        """Reconciliation with the scalar rules: the full set reports
        exactly ONE finding for a jit-only tuple collective — 203/204
        skip tuple spellings, APX205 owns them."""
        got = run("""
            import jax

            def hier_mean(x):
                return jax.lax.pmean(x, ("dp_out", "dp_in"))

            @jax.jit
            def f(x):
                return hier_mean(x)
            """, tmp_path, DEFAULT_RULES, axes=HIER_AXES_REG)
        assert rule_ids(got) == ["APX205"]

    def test_negative_nest_binds_both_members(self, tmp_path):
        got = run("""
            import jax
            import numpy as np
            from jax.sharding import Mesh, PartitionSpec as P

            def loss(x):
                return jax.lax.pmean(x, ("dp_out", "dp_in"))

            def train(x):
                mesh = Mesh(np.array(jax.devices()).reshape(2, 2),
                            ("dp_out", "dp_in"))
                return jax.shard_map(loss, mesh=mesh,
                                     in_specs=P(("dp_out", "dp_in")),
                                     out_specs=P())(x)
            """, tmp_path, DEFAULT_RULES, axes=HIER_AXES_REG)
        assert got == []

    def test_negative_dynamic_member_stays_quiet(self, tmp_path):
        """A dynamically-spelled member may be anything the caller's
        nest binds — the whole tuple stays quiet (the threading-as-
        argument pattern the scalar rules also bless)."""
        got = run("""
            import jax

            def generic(x, outer_axis):
                return jax.lax.pmean(x, (outer_axis, "dp_in"))

            @jax.jit
            def f(x):
                return generic(x, "dp_out")
            """, tmp_path, [CollectiveTupleAxisUnbound()],
            axes=HIER_AXES_REG)
        assert got == []

    def test_unregistered_member_stays_apx201s(self, tmp_path):
        """Registry-tier findings stay APX201's (one per unknown
        member, as its own fixtures pin); APX205 names them only as
        context when an unbound REGISTERED member triggers it."""
        got = run("""
            import jax
            import numpy as np
            from jax.sharding import Mesh, PartitionSpec as P

            def loss(x):
                return jax.lax.pmean(x, ("dp_outer_typo", "dp_in"))

            def train(x):
                mesh = Mesh(np.array(jax.devices()), ("tp",))
                return jax.shard_map(loss, mesh=mesh, in_specs=P("tp"),
                                     out_specs=P())(x)
            """, tmp_path,
            [UnknownCollectiveAxis(), CollectiveTupleAxisUnbound()],
            axes=HIER_AXES_REG)
        assert sorted(rule_ids(got)) == ["APX201", "APX205"]
        apx205 = [f for f in got if f.rule == "APX205"][0]
        assert "'dp_in'" in apx205.message
        assert "dp_outer_typo" in apx205.message  # context, not a dup


# ----------------------------- APX206 sharding-annotation axis unbound
class TestShardingSpecAxisUnbound:
    """APX206: the GSPMD tier of the axis family — PartitionSpec axes
    vs the mesh that actually reaches the annotation."""

    def test_positive_typo_against_own_mesh(self, tmp_path):
        """The one-character-typo class on the annotation side: 'dq'
        is not on the NamedSharding's own mesh — raises at annotation
        construction, which for a TPU-gated builder is on the chip."""
        got = run("""
            import jax
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            mesh = Mesh(devs, ("dp", "tp"))
            spec = NamedSharding(mesh, P("dq", None))
            """, tmp_path, [ShardingSpecAxisUnbound()])
        assert rule_ids(got) == ["APX206"]
        assert "'dq'" in got[0].message
        assert "dp, tp" in got[0].message

    def test_positive_stale_mesh_constraint_under_annotated_jit(
            self, tmp_path):
        """The SILENT-replication class (the fixture
        tests/test_lowered_invariants.py::TestShardingRuleProof runs
        live: jit compiles and runs with zero exceptions): the
        with_sharding_constraint's NamedSharding is self-consistent,
        but it was built on a STALE prod mesh — the mesh reaching this
        jit (its in_shardings) binds only 'dp'."""
        got = run("""
            import jax
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            mesh_ci = Mesh(devs, ("dp",))
            mesh_prod = Mesh(devs2, ("dp", "tp"))

            def f(x):
                return jax.lax.with_sharding_constraint(
                    x * 2, NamedSharding(mesh_prod, P(None, "tp")))

            step = jax.jit(f, in_shardings=NamedSharding(mesh_ci, P("dp")))
            """, tmp_path, [ShardingSpecAxisUnbound()])
        assert rule_ids(got) == ["APX206"]
        assert "silently rematerializes" in got[0].message

    def test_positive_bare_spec_constraint_off_the_reaching_mesh(
            self, tmp_path):
        got = run("""
            import jax
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            mesh = Mesh(devs, ("dp",))

            @functools.partial(jax.jit,
                               in_shardings=NamedSharding(mesh, P("dp")))
            def f(x):
                return jax.lax.with_sharding_constraint(x, P("model"))
            """, tmp_path, [ShardingSpecAxisUnbound()])
        assert rule_ids(got) == ["APX206"]
        assert "'model'" in got[0].message

    def test_negative_bound_axes_and_dynamic_meshes(self, tmp_path):
        """Bound axes pass; a mesh (or spec) out of static reach —
        the threading pattern — stays quiet."""
        got = run("""
            import jax
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            mesh = Mesh(devs, ("dp", "tp"))
            ok = NamedSharding(mesh, P("dp", None, "tp"))

            def make(m, spec):
                return NamedSharding(m, spec)

            def f(x):
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, P("dp")))

            step = jax.jit(f, in_shardings=NamedSharding(mesh, P("dp")))
            """, tmp_path, [ShardingSpecAxisUnbound()])
        assert got == []

    def test_negative_unannotated_jit_has_no_mesh_opinion(self, tmp_path):
        """A wsc under a PLAIN jit (no in_shardings) follows the
        ambient device context the analyzer cannot see — quiet."""
        got = run("""
            import jax
            from jax.sharding import PartitionSpec as P

            @jax.jit
            def f(x):
                return jax.lax.with_sharding_constraint(x, P("dp"))
            """, tmp_path, [ShardingSpecAxisUnbound()])
        assert got == []

    def test_rides_default_rules(self, tmp_path):
        got = run("""
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            mesh = Mesh(devs, ("dp", "tp"))
            s = NamedSharding(mesh, P("dq"))
            """, tmp_path, DEFAULT_RULES)
        assert "APX206" in rule_ids(got)


# ------------------------------------ APX207 spec rank vs array rank
class TestShardingSpecRankMismatch:
    def test_positive_constraint_longer_than_creation_rank(self, tmp_path):
        """The refactor wound: the tensor lost a dim, the annotation
        kept it — a trace-time error deferred to the chip for
        TPU-gated paths."""
        got = run("""
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P

            x = jnp.zeros((8, 128))
            y = jax.lax.with_sharding_constraint(x, P("dp", None, "tp"))
            """, tmp_path, [ShardingSpecRankMismatch()])
        assert rule_ids(got) == ["APX207"]
        assert "3 dimensions" in got[0].message
        assert "rank 2" in got[0].message

    def test_positive_device_put_and_aliased_dims(self, tmp_path):
        """device_put sites count too, and dims thread through the
        one-hop local lattice (`bn = 8`)."""
        got = run("""
            import jax, jax.numpy as jnp
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            mesh = Mesh(devs, ("dp", "tp"))
            bn = 8
            x = jnp.ones((bn, 128))
            y = jax.device_put(x, NamedSharding(mesh, P("dp", "tp", None)))
            """, tmp_path, [ShardingSpecRankMismatch()])
        assert rule_ids(got) == ["APX207"]

    def test_negative_numpy_random_signature_not_conflated(self, tmp_path):
        """Review finding: np.random.normal(loc, SCALE, size) puts a
        scalar where jax.random.normal puts the shape — claiming the
        array is rank 1 there was a confirmed false positive.  Scalar
        shapes only count for the zeros/ones (position-0) family."""
        got = run("""
            import jax
            import numpy as np
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            mesh = Mesh(devs, ("dp", "tp"))
            x = np.random.normal(0, 1, (8, 128))
            y = jax.device_put(x, NamedSharding(mesh, P("dp", None)))
            """, tmp_path, [ShardingSpecRankMismatch()])
        assert got == []

    def test_negative_shorter_spec_and_unknown_ranks(self, tmp_path):
        """Shorter specs are legal (trailing dims replicate); arrays
        whose rank is out of static reach are trusted."""
        got = run("""
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P

            x = jnp.zeros((8, 128, 4))
            ok = jax.lax.with_sharding_constraint(x, P("dp"))
            exact = jax.lax.with_sharding_constraint(x, P("dp", None, "tp"))
            dyn = jax.lax.with_sharding_constraint(load(), P("a", "b", "c"))
            """, tmp_path, [ShardingSpecRankMismatch()])
        assert got == []

    def test_rides_default_rules(self, tmp_path):
        got = run("""
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P

            x = jnp.zeros((16,))
            y = jax.lax.with_sharding_constraint(x, P("dp", "tp"))
            """, tmp_path, DEFAULT_RULES)
        assert "APX207" in rule_ids(got)


# -------------------------- APX208 donated in/out sharding mismatch
class TestDonatedShardingMismatch:
    def test_positive_donated_arg_can_never_alias(self, tmp_path):
        """The silent-drop class: in P('dp', None) matches no output
        sharding, so XLA keeps the input AND the output alive — a
        UserWarning nobody reads in CI logs."""
        got = run("""
            import jax
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            mesh = Mesh(devs, ("dp", "tp"))
            step = jax.jit(f, donate_argnums=(0,),
                           in_shardings=(NamedSharding(mesh, P("dp", None)),
                                         NamedSharding(mesh, P())),
                           out_shardings=(NamedSharding(mesh, P(None, "tp")),))
            """, tmp_path, [DonatedShardingMismatch()])
        assert rule_ids(got) == ["APX208"]
        assert "argument 0 is donated" in got[0].message

    def test_positive_partial_jit_decorator_spelling(self, tmp_path):
        """Review finding: the ``@functools.partial(jax.jit, ...)``
        decorator spelling carries the same three kwargs on the
        partial call — the most common step-builder shape must not
        dodge the rule."""
        got = run("""
            import functools

            import jax
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            mesh = Mesh(devs, ("dp", "tp"))

            @functools.partial(
                jax.jit, donate_argnums=(0,),
                in_shardings=(NamedSharding(mesh, P("dp", None)),),
                out_shardings=(NamedSharding(mesh, P(None, "tp")),))
            def step(state):
                return state * 2
            """, tmp_path, [DonatedShardingMismatch()])
        assert rule_ids(got) == ["APX208"]

    def test_negative_matching_modulo_trailing_nones(self, tmp_path):
        """P('dp') and P('dp', None) are the SAME sharding — trailing
        Nones replicate; flagging them was a false positive waiting to
        happen.  Undonated args and unresolvable specs stay quiet."""
        got = run("""
            import jax
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            mesh = Mesh(devs, ("dp", "tp"))
            ok = jax.jit(f, donate_argnums=(0,),
                         in_shardings=(NamedSharding(mesh, P("dp", None)),),
                         out_shardings=(NamedSharding(mesh, P("dp")),))
            free = jax.jit(f, donate_argnums=(0,),
                           in_shardings=(NamedSharding(mesh, P("dp")),),
                           out_shardings=(make_out_spec(),))
            undonated = jax.jit(f,
                                in_shardings=(NamedSharding(mesh, P("dp")),),
                                out_shardings=(NamedSharding(mesh, P("tp")),))
            """, tmp_path, [DonatedShardingMismatch()])
        assert got == []

    def test_negative_no_out_shardings_means_xla_chooses(self, tmp_path):
        got = run("""
            import jax
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            mesh = Mesh(devs, ("dp",))
            step = jax.jit(f, donate_argnums=(0,),
                           in_shardings=(NamedSharding(mesh, P("dp")),))
            """, tmp_path, [DonatedShardingMismatch()])
        assert got == []

    def test_rides_default_rules(self, tmp_path):
        got = run("""
            import jax
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            mesh = Mesh(devs, ("dp", "tp"))
            step = jax.jit(f, donate_argnums=(0,),
                           in_shardings=(NamedSharding(mesh, P("dp")),),
                           out_shardings=(NamedSharding(mesh, P("tp")),))
            """, tmp_path, DEFAULT_RULES)
        assert "APX208" in rule_ids(got)


# ------------------------------- APX303 scratch/accumulator dtype vs dot
class TestScratchAccumDtypeMismatch:
    def test_positive_bf16_scratch_fp32_preferred(self, tmp_path):
        """The hazard class: preferred_element_type asks the MXU for
        fp32 partials, the bf16 scratch re-rounds every accumulation
        step — the precision was paid for and silently discarded."""
        got = run("""
            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            def _kernel(x_ref, o_ref, acc_ref):
                acc_ref[:] += jax.lax.dot_general(
                    x_ref[:], x_ref[:], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

            def launch(x, bn, H):
                return pl.pallas_call(
                    _kernel, grid=(4,),
                    in_specs=[pl.BlockSpec((bn, H), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((bn, H), lambda i: (i, 0)),
                    scratch_shapes=[pltpu.VMEM((bn, H), jnp.bfloat16)],
                )(x)
            """, tmp_path, [ScratchAccumDtypeMismatch()])
        assert rule_ids(got) == ["APX303"]
        assert got[0].symbol == "_kernel"
        assert "preferred_element_type=float32" in got[0].message

    def test_positive_dtype_through_lattice_and_repeat_list(self, tmp_path):
        """The dtype rides a local assignment (``acc_dtype = jnp.
        bfloat16``) and the scratch list uses the ``[...] * 2`` repeat
        spelling — both resolved by the dataflow lattice."""
        got = run("""
            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            acc_dtype = jnp.bfloat16

            def _kernel(x_ref, o_ref, a_ref, b_ref):
                b_ref[:] = b_ref[:] + jax.lax.dot_general(
                    x_ref[:], x_ref[:], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

            def launch(x, bn, H):
                return pl.pallas_call(
                    _kernel, grid=(4,),
                    in_specs=[pl.BlockSpec((bn, H), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((bn, H), lambda i: (i, 0)),
                    scratch_shapes=[pltpu.VMEM((bn, H), acc_dtype)] * 2,
                )(x)
            """, tmp_path, [ScratchAccumDtypeMismatch()])
        assert rule_ids(got) == ["APX303"]

    def test_positive_local_accumulator(self, tmp_path):
        """The non-Pallas spelling: a bf16 ``jnp.zeros`` accumulator
        fed by fp32-preferred dots in a scan-style loop."""
        got = run("""
            import jax
            import jax.numpy as jnp

            def chunked_matmul(a, b):
                acc = jnp.zeros((128, 128), dtype=jnp.bfloat16)
                for i in range(4):
                    acc += jax.lax.dot_general(
                        a[i], b[i], (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                return acc
            """, tmp_path, [ScratchAccumDtypeMismatch()])
        assert rule_ids(got) == ["APX303"]
        assert "accumulator `acc`" in got[0].message

    def test_negative_fp32_scratch_fp32_preferred(self, tmp_path):
        """The repo's own fused-CE shape: fp32 scratch, fp32 preferred
        — the contract this rule exists to protect."""
        got = run("""
            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            def _kernel(x_ref, o_ref, acc_ref):
                acc_ref[:] += jax.lax.dot_general(
                    x_ref[:], x_ref[:], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

            def launch(x, bn, H):
                return pl.pallas_call(
                    _kernel, grid=(4,),
                    in_specs=[pl.BlockSpec((bn, H), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((bn, H), lambda i: (i, 0)),
                    scratch_shapes=[pltpu.VMEM((bn, H), jnp.float32)],
                )(x)
            """, tmp_path, [ScratchAccumDtypeMismatch()])
        assert got == []

    def test_negative_deliberate_narrow_accumulation(self, tmp_path):
        """bf16 scratch with bf16 preferred is self-consistent: the
        author CHOSE narrow accumulation, nothing is discarded."""
        got = run("""
            import jax
            import jax.numpy as jnp

            def f(a, b):
                acc = jnp.zeros((128, 128), dtype=jnp.bfloat16)
                acc += jax.lax.dot_general(
                    a, b, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.bfloat16)
                return acc
            """, tmp_path, [ScratchAccumDtypeMismatch()])
        assert got == []

    def test_negative_unresolvable_dtype_stays_quiet(self, tmp_path):
        got = run("""
            import jax
            import jax.numpy as jnp

            def f(a, b, out_dtype):
                acc = jnp.zeros((128, 128), dtype=out_dtype)
                acc += jax.lax.dot_general(
                    a, b, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return acc
            """, tmp_path, [ScratchAccumDtypeMismatch()])
        assert got == []

    def test_conflicting_dtype_names_terminate_and_poison(self, tmp_path):
        """Review finding: two functions reusing one dtype name with
        different values made the old dtype_env fixpoint flip forever
        (the analyzer HUNG on any module reusing the name ``dtype``).
        Now the module layer reads only top-level statements and a
        conflicting name poisons to UNKNOWN — terminates, stays
        quiet."""
        got = run("""
            import jax
            import jax.numpy as jnp

            def a():
                dt = jnp.bfloat16
                return dt

            def b():
                dt = jnp.float32
                return dt

            def f(x, y):
                acc = jnp.zeros((128, 128), dtype=jnp.bfloat16)
                acc += jax.lax.dot_general(
                    x, y, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return acc
            """, tmp_path, [ScratchAccumDtypeMismatch()])
        assert rule_ids(got) == ["APX303"]  # f still judged; no hang

    def test_dtype_locals_do_not_leak_across_functions(self, tmp_path):
        """Review finding: one function's ``dt = jnp.bfloat16`` must
        not resolve another function's unrelated ``dt`` (a parameter
        there) — the module layer is top-level-only now."""
        got = run("""
            import jax
            import jax.numpy as jnp

            def other():
                dt = jnp.bfloat16
                return dt

            def f(x, y, dt):
                acc = jnp.zeros((128, 128), dtype=dt)
                acc += jax.lax.dot_general(
                    x, y, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return acc
            """, tmp_path, [ScratchAccumDtypeMismatch()])
        assert got == []

    def test_branch_conflicting_accumulator_dtype_stays_quiet(self, tmp_path):
        """A name carrying fp32 on one branch and bf16 on the other
        must poison, not last-win into a wrong finding."""
        got = run("""
            import jax
            import jax.numpy as jnp

            def f(x, y, wide):
                dt = jnp.float32
                if not wide:
                    dt = jnp.bfloat16
                acc = jnp.zeros((128, 128), dtype=dt)
                acc += jax.lax.dot_general(
                    x, y, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return acc
            """, tmp_path, [ScratchAccumDtypeMismatch()])
        assert got == []


# ------------------------------- APX107 page-table gathers (decode path)
class TestPageTableGatherUnclamped:
    """The APX401 unclamped-gather family extended to the serving
    path's mutable page indirection: page-table reads and table-valued
    pool indexing must clamp (or choose an explicit mode)."""

    def test_positive_take_through_page_table(self, tmp_path):
        got = run("""
            import jax.numpy as jnp

            def gather_pages(page_table_row, page_ix):
                return jnp.take(page_table_row, page_ix)
            """, tmp_path, [PageTableGatherUnclamped()])
        assert rule_ids(got) == ["APX107"]
        assert "page_table_row" in got[0].message

    def test_positive_table_values_index_the_pool(self, tmp_path):
        """The vLLM-shaped hazard: the table's VALUES address the pool;
        a stale entry wraps into a live sequence's page."""
        got = run("""
            def gather(k_pool, page_tables):
                return k_pool[page_tables]
            """, tmp_path, [PageTableGatherUnclamped()])
        assert rule_ids(got) == ["APX107"]
        assert "LIVE sequence" in got[0].message

    def test_positive_scatter_through_at(self, tmp_path):
        got = run("""
            def write(k_pool, page_tables, slot, k_new):
                return k_pool.at[page_tables, slot].set(k_new)
            """, tmp_path, [PageTableGatherUnclamped()])
        assert rule_ids(got) == ["APX107"]

    def test_negative_clipped_index(self, tmp_path):
        """The kv_cache.py contract shape: indices clipped (directly
        or through a clipped local) are clean."""
        got = run("""
            import jax.numpy as jnp

            def gather_pages(page_table_row, s, P, num_pages):
                page_ix = jnp.clip(s // 4, 0, P - 1)
                rows = jnp.take(page_table_row, page_ix)
                return jnp.clip(rows, 0, num_pages - 1)

            def gather(k_pool, page_table, num_pages):
                pt = jnp.clip(page_table, 0, num_pages - 1)
                return k_pool[pt]
            """, tmp_path, [PageTableGatherUnclamped()])
        assert got == []

    def test_negative_explicit_mode(self, tmp_path):
        got = run("""
            import jax.numpy as jnp

            def gather_pages(page_table_row, ix):
                return jnp.take(page_table_row, ix, mode="clip")
            """, tmp_path, [PageTableGatherUnclamped()])
        assert got == []

    def test_negative_at_scatter_with_explicit_mode(self, tmp_path):
        """``.at[...].set(..., mode=...)`` chose its out-of-bounds
        semantic explicitly — the mode lives on the ENCLOSING set/get
        call, and must acquit like take's mode= does."""
        got = run("""
            def write(k_pool, page_tables, slot, k_new):
                return k_pool.at[page_tables, slot].set(k_new, mode="drop")

            def read(k_pool, page_tables):
                return k_pool.at[page_tables].get(mode="fill", fill_value=0)
            """, tmp_path, [PageTableGatherUnclamped()])
        assert got == []

    def test_negative_non_page_table_names_quiet(self, tmp_path):
        """Ordinary gathers (embedding lookups, host bookkeeping) stay
        out of reach — the rule is scoped to page-table names."""
        got = run("""
            import jax.numpy as jnp

            def embed(table, tokens):
                return jnp.take(table, tokens, axis=0)

            def host_side(slots, i):
                return slots[i]
            """, tmp_path, [PageTableGatherUnclamped()])
        assert got == []


# ----------------------------- APX110 kv/pool scatter bypassing the seam
class TestKvPoolScatterBypassesSeam:
    """The COW-bypass hazard class: ``.at[...].set`` into a pool-named
    buffer whose page index is neither clamped/garbage-routed device
    data nor an allocator-normalized host int — with refcounted shared
    pages, a write the scheduler's COW pass cannot see mutates pages
    other sequences still read."""

    def test_positive_raw_index_scatter(self, tmp_path):
        got = run("""
            def poison(pools, page, slot, val):
                return pools["k"].at[page, slot].set(val)
            """, tmp_path, [KvPoolScatterBypassesSeam()])
        assert rule_ids(got) == ["APX110"]
        assert "copy-on-write" in got[0].message

    def test_positive_arithmetic_on_unrouted_index(self, tmp_path):
        got = run("""
            def poison(k_pool, positions, page_size, val):
                dest = positions // page_size
                return k_pool.at[dest].add(val)
            """, tmp_path, [KvPoolScatterBypassesSeam()])
        assert rule_ids(got) == ["APX110"]

    def test_negative_garbage_routed_seam_shape(self, tmp_path):
        """The write_decode_kv contract shape: dest built from
        where(clip(...), GARBAGE_PAGE) is the seam itself."""
        got = run("""
            import jax.numpy as jnp
            GARBAGE_PAGE = 0

            def write(k_pool, rows, slot, active, num_pages, k_new):
                dest = jnp.where(active,
                                 jnp.clip(rows, 0, num_pages - 1),
                                 GARBAGE_PAGE)
                return k_pool.at[dest, slot].set(k_new)
            """, tmp_path, [KvPoolScatterBypassesSeam()])
        assert got == []

    def test_negative_allocator_host_int(self, tmp_path):
        """copy_page's shape: allocator-issued ids normalized through
        int(...) — including the tuple-assignment spelling."""
        got = run("""
            def copy_page(pools, src, dst):
                src, dst = int(src), int(dst)
                k = pools["k"].at[:, dst].set(pools["k"][:, src])
                return k
            """, tmp_path, [KvPoolScatterBypassesSeam()])
        assert got == []

    def test_negative_non_pool_buffers_quiet(self, tmp_path):
        """Ordinary functional updates (grads, params, stats) stay out
        of reach — the rule is scoped to kv/pool names."""
        got = run("""
            def bump(stats, i, g):
                return stats.at[i].add(g)

            def read_only(pools, page):
                return pools["k"].at[page].get(mode="fill", fill_value=0)
            """, tmp_path, [KvPoolScatterBypassesSeam()])
        assert rule_ids(got) == []

    def test_negative_static_literal_index(self, tmp_path):
        got = run("""
            def reset_garbage(k_pool):
                return k_pool.at[0].set(0.0)
            """, tmp_path, [KvPoolScatterBypassesSeam()])
        assert got == []

    def test_finding_says_why_a_raw_write_costs_a_pool_copy(self, tmp_path):
        """The message carries the second reason (the chip's): a raw
        XLA write makes XLA re-lay out the pool; the fix names the
        in-place kernel write."""
        got = run("""
            def poison(pools, page, slot, val):
                return pools["k"].at[page, slot].set(val)
            """, tmp_path, [KvPoolScatterBypassesSeam()])
        assert "layout" in got[0].message
        assert "apex_kv_write" in got[0].fix_hint

    @pytest.mark.parametrize("call", [
        "jax.lax.dynamic_update_slice(k_pool, row, (layer, page, 0, 0, 0))",
        "lax.dynamic_update_slice_in_dim(pools['k'], row, page, 1)",
        "jax.lax.dynamic_update_index_in_dim(kv_cache, row, page, 1)"])
    def test_positive_dynamic_update_slice_on_a_pool(self, tmp_path, call):
        """No routed spelling exists for the slice-write family: on a
        pool it is always a finding, clamped index or not."""
        got = run(f"""
            import jax
            from jax import lax
            import jax.numpy as jnp

            def write(k_pool, pools, kv_cache, row, layer, page, n):
                page = jnp.clip(page, 0, n - 1)
                return {call}
            """, tmp_path, [KvPoolScatterBypassesSeam()])
        assert rule_ids(got) == ["APX110"]
        assert "layout" in got[0].message

    def test_negative_dynamic_update_slice_elsewhere(self, tmp_path):
        got = run("""
            import jax

            def bump(acc, row, i):
                return jax.lax.dynamic_update_slice(acc, row, (i, 0))
            """, tmp_path, [KvPoolScatterBypassesSeam()])
        assert got == []

    def test_negative_targets_from_the_seam_helpers(self, tmp_path):
        """kv_cache's own XLA twin: (dest, slot) unpacked from
        ``_row_targets`` are the seam's output."""
        got = run("""
            def write(k_pool, tables, positions, active, k_new, layer):
                dest, slot = _row_targets(tables, positions, active, 16, 9)
                return k_pool.at[layer, dest, :, :, slot].set(k_new)
            """, tmp_path, [KvPoolScatterBypassesSeam()])
        assert got == []


# ------------------------------ APX306 kv-cache read dtype (decode path)
class TestKvCacheReadDtypeMismatch:
    """Narrow (bf16) cache storage feeding a wider-accumulator dot
    needs the widen SPELLED at the read."""

    def test_positive_bf16_pool_into_f32_dot(self, tmp_path):
        got = run("""
            import jax
            import jax.numpy as jnp

            def attend(q, i):
                k_cache = jnp.zeros((8, 16, 64), dtype=jnp.bfloat16)
                return jax.lax.dot_general(
                    q, k_cache[i], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            """, tmp_path, [KvCacheReadDtypeMismatch()])
        assert rule_ids(got) == ["APX306"]
        assert "k_cache" in got[0].message and "bfloat16" in got[0].message

    def test_positive_via_dtype_lattice(self, tmp_path):
        """Storage dtype resolved through a local alias
        (``store = jnp.bfloat16``) — the APX303-style lattice hop."""
        got = run("""
            import jax
            import jax.numpy as jnp

            store = jnp.bfloat16

            def attend(q, pages):
                kv_pool = pages.astype(store)
                return jax.lax.dot_general(
                    q, kv_pool, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            """, tmp_path, [KvCacheReadDtypeMismatch()])
        assert rule_ids(got) == ["APX306"]

    def test_negative_widened_at_the_read(self, tmp_path):
        """The decode kernels' contract shape: the cache operand is
        astype-widened where it meets the dot."""
        got = run("""
            import jax
            import jax.numpy as jnp

            def attend(q, i):
                k_cache = jnp.zeros((8, 16, 64), dtype=jnp.bfloat16)
                return jax.lax.dot_general(
                    q, k_cache[i].astype(jnp.float32),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            """, tmp_path, [KvCacheReadDtypeMismatch()])
        assert got == []

    def test_negative_wide_storage(self, tmp_path):
        got = run("""
            import jax
            import jax.numpy as jnp

            def attend(q, i):
                k_cache = jnp.zeros((8, 16, 64), dtype=jnp.float32)
                return jax.lax.dot_general(
                    q, k_cache[i], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            """, tmp_path, [KvCacheReadDtypeMismatch()])
        assert got == []

    def test_negative_unresolvable_astype_at_read_stays_quiet(
            self, tmp_path):
        """An explicit cast at the read whose dtype the lattice cannot
        resolve (a parameter, a config attribute) is still the SPELLED
        widen the rule demands — quiet-when-unprovable applies to the
        cast too, not just the buffer."""
        got = run("""
            import jax
            import jax.numpy as jnp

            def attend(q, pages, acc_dtype, i):
                kv_pool = pages.astype(jnp.bfloat16)
                return jax.lax.dot_general(
                    q, kv_pool[i].astype(acc_dtype),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            """, tmp_path, [KvCacheReadDtypeMismatch()])
        assert got == []

    def test_negative_unresolvable_dtype_stays_quiet(self, tmp_path):
        """A pool whose dtype the lattice cannot prove (the real
        kernels: the ref's dtype is whatever the caller allocated)
        must not be guessed at."""
        got = run("""
            import jax

            def attend(q, k_pool, i):
                return jax.lax.dot_general(
                    q, k_pool[i], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            """, tmp_path, [KvCacheReadDtypeMismatch()])
        assert got == []


# ---------------------------------- APX305 quantized-sync state dtypes
class TestQuantizedSyncStateDtype:
    """Scale/residual buffers of the compressed grad-sync idiom —
    scoped to functions that cast to a quantized WIRE dtype, so the
    repo's many ``loss_scale``-style names stay out of reach."""

    def test_positive_narrow_scales(self, tmp_path):
        got = run("""
            import jax
            import jax.numpy as jnp

            def quantized_sync(h, amax_sum):
                scales = (amax_sum / 127.0).astype(jnp.bfloat16)
                q = (h / scales).astype(jnp.int8)
                return jax.lax.psum_scatter(q, "dp", scatter_dimension=0,
                                            tiled=True)
            """, tmp_path, [QuantizedSyncStateDtype()])
        assert rule_ids(got) == ["APX305"]
        assert "scale" in got[0].message and "float32" in got[0].message

    def test_positive_wire_width_residual_via_lattice(self, tmp_path):
        """The residual narrowed to the WIRE dtype (through a dtype
        alias) — the error-feedback information re-rounded away."""
        got = run("""
            import jax.numpy as jnp

            wire = jnp.float8_e4m3fn

            def quantize_with_feedback(h, scales):
                q = (h / scales).astype(wire)
                residual = (h - q.astype(jnp.float32) * scales).astype(wire)
                return q, residual
            """, tmp_path, [QuantizedSyncStateDtype()])
        assert rule_ids(got) == ["APX305"]
        assert "residual" in got[0].message

    def test_negative_contract_shapes(self, tmp_path):
        """fp32 scales + storage-dtype residual (the
        ``_quantized_sync`` contract itself) are clean."""
        got = run("""
            import jax.numpy as jnp

            def quantize_with_feedback(h, scales):
                scales = scales.astype(jnp.float32)
                q = (h / scales).astype(jnp.int8)
                residual = (h - q.astype(jnp.float32) * scales).astype(
                    jnp.bfloat16)
                return q, residual
            """, tmp_path, [QuantizedSyncStateDtype()])
        assert got == []

    def test_negative_wire_cast_in_nested_def_does_not_mark_outer(
            self, tmp_path):
        """The marker is per-function: a nested helper's int8 cast must
        not put the OUTER function's ``loss_scale``-style names in
        APX305's reach."""
        got = run("""
            import jax.numpy as jnp

            def train_step(grads, scaler_state):
                new_scale = scaler_state.loss_scale.astype(jnp.bfloat16)

                def _quantize(x):
                    return x.astype(jnp.int8)

                return _quantize(grads), new_scale
            """, tmp_path, [QuantizedSyncStateDtype()])
        assert got == []

    def test_negative_loss_scale_outside_quantized_code(self, tmp_path):
        """A half-precision ``loss_scale`` in ordinary amp code — no
        wire cast in the function, so APX305 must stay quiet."""
        got = run("""
            import jax.numpy as jnp

            def scale_loss(loss, scaler_state):
                loss_scale = scaler_state.loss_scale.astype(jnp.float16)
                return loss * loss_scale
            """, tmp_path, [QuantizedSyncStateDtype()])
        assert got == []

    def test_negative_unresolvable_dtype_stays_quiet(self, tmp_path):
        """A residual cast to a dynamically-chosen dtype (the engine's
        ``.astype(jnp.dtype(b.dtype))``) is UNKNOWN — no finding."""
        got = run("""
            import jax.numpy as jnp

            def quantize(h, scales, storage_dtype):
                q = (h / scales).astype(jnp.int8)
                residual = (h - q.astype(jnp.float32) * scales).astype(
                    storage_dtype)
                return q, residual
            """, tmp_path, [QuantizedSyncStateDtype()])
        assert got == []


# ----------------------------------------- APX304 VMEM footprint budget
class TestVmemFootprintOverBudget:
    def test_positive_literal_blocks_over_budget(self, tmp_path):
        """2048x1024 fp32 blocks x 3 ≈ 24 MiB — fine in interpret
        mode, a Mosaic allocation failure on the chip."""
        got = run("""
            import jax.numpy as jnp
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            def launch(x):
                return pl.pallas_call(
                    _body, grid=(4,),
                    in_specs=[pl.BlockSpec((2048, 1024),
                                           lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((2048, 1024),
                                           lambda i: (i, 0)),
                    scratch_shapes=[pltpu.VMEM((2048, 1024),
                                               jnp.float32)],
                )(x)
            """, tmp_path, [VmemFootprintOverBudget()])
        assert rule_ids(got) == ["APX304"]
        assert got[0].severity == "warning"
        assert "24.0 MiB" in got[0].message

    def test_positive_dims_through_local_aliases(self, tmp_path):
        """``bn = 2048`` resolves through the assignment lattice —
        the spelling real kernels use."""
        got = run("""
            import jax.numpy as jnp
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            def launch(x):
                bn = 2048
                hidden = 1024
                spec = pl.BlockSpec((bn, hidden), lambda i: (i, 0))
                return pl.pallas_call(
                    _body, grid=(4,),
                    in_specs=[spec],
                    out_specs=pl.BlockSpec((bn, hidden),
                                           lambda i: (i, 0)),
                    scratch_shapes=[pltpu.VMEM((bn, hidden),
                                               jnp.float32)],
                )(x)
            """, tmp_path, [VmemFootprintOverBudget()])
        assert rule_ids(got) == ["APX304"]

    def test_negative_dynamic_dims_unpriceable(self, tmp_path):
        """Runtime-sized blocks (the repo's ``_ceil_block`` pattern)
        cannot be priced — the rule only speaks on provable sums."""
        got = run("""
            import jax.numpy as jnp
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            def launch(x, block_n):
                bn = _ceil_block(x.shape[0], block_n, 8)
                return pl.pallas_call(
                    _body, grid=(4,),
                    in_specs=[pl.BlockSpec((bn, 4096), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((bn, 4096), lambda i: (i, 0)),
                    scratch_shapes=[pltpu.VMEM((bn, 4096), jnp.float32)],
                )(x)
            """, tmp_path, [VmemFootprintOverBudget()])
        assert got == []

    def test_negative_small_blocks_under_budget(self, tmp_path):
        got = run("""
            import jax.numpy as jnp
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            def launch(x):
                return pl.pallas_call(
                    _body, grid=(4,),
                    in_specs=[pl.BlockSpec((256, 512), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((256, 512), lambda i: (i, 0)),
                    scratch_shapes=[pltpu.VMEM((256, 128), jnp.float32)],
                )(x)
            """, tmp_path, [VmemFootprintOverBudget()])
        assert got == []

    def test_positive_bwd_score_dots_price_temporaries(self, tmp_path):
        """The backward-kernel class: declared buffers well under
        budget (~2.5 MiB), but two last-dim-contracting dots (the
        s = q·kᵀ / dp = do·vᵀ score pattern) keep four
        (2048 × 1024) f32 temporaries live — 32 MiB the spec sum never
        sees.  The kernel resolves through the functools.partial
        binding idiom."""
        got = run("""
            import functools

            import jax
            from jax.experimental import pallas as pl

            def _bwd_body(q_ref, k_ref, dq_ref, *, scale):
                s = jax.lax.dot_general(
                    q_ref[...], k_ref[...], (((1,), (1,)), ((), ())))
                dp = jax.lax.dot_general(
                    dq_ref[...], k_ref[...], (((1,), (1,)), ((), ())))
                dq_ref[...] = (s * dp) * scale

            def launch(q, k, dq):
                kernel = functools.partial(_bwd_body, scale=0.125)
                return pl.pallas_call(
                    kernel, grid=(4,),
                    in_specs=[pl.BlockSpec((2048, 128), lambda i: (i, 0)),
                              pl.BlockSpec((1024, 128), lambda i: (0, 0))],
                    out_specs=pl.BlockSpec((2048, 128), lambda i: (i, 0)),
                )(q, k, dq)
            """, tmp_path, [VmemFootprintOverBudget()])
        assert rule_ids(got) == ["APX304"]
        assert "4 score-sized f32 kernel temporaries" in got[0].message

    def test_negative_non_score_dots_not_priced(self, tmp_path):
        """pv/dv-style ``(1,)×(0,)`` dots produce block-shaped results
        the specs already price — the same launch stays clean."""
        got = run("""
            import functools

            import jax
            from jax.experimental import pallas as pl

            def _pv_body(p_ref, v_ref, o_ref, *, scale):
                o_ref[...] = jax.lax.dot_general(
                    p_ref[...], v_ref[...],
                    (((1,), (0,)), ((), ()))) * scale

            def launch(p, v, o):
                kernel = functools.partial(_pv_body, scale=0.125)
                return pl.pallas_call(
                    kernel, grid=(4,),
                    in_specs=[pl.BlockSpec((2048, 128), lambda i: (i, 0)),
                              pl.BlockSpec((1024, 128), lambda i: (0, 0))],
                    out_specs=pl.BlockSpec((2048, 128), lambda i: (i, 0)),
                )(p, v, o)
            """, tmp_path, [VmemFootprintOverBudget()])
        assert got == []

    def test_budget_is_configurable(self, tmp_path):
        """The same small kernel flags under a 128 KiB budget — the
        constructor knob the CLI's --vmem-budget-mib drives."""
        got = run("""
            from jax.experimental import pallas as pl

            def launch(x):
                return pl.pallas_call(
                    _body, grid=(4,),
                    in_specs=[pl.BlockSpec((256, 512), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((256, 512), lambda i: (i, 0)),
                )(x)
            """, tmp_path,
            [VmemFootprintOverBudget(budget_bytes=128 * 1024)])
        assert rule_ids(got) == ["APX304"]


# ----------------------------------------------- APX301 BlockSpec tiling
class TestBlockShapeTilingViolation:
    def test_positive_bad_lane_and_sublane(self, tmp_path):
        got = run("""
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            def specs(H):
                a = pl.BlockSpec((8, 64), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM)
                b = pl.BlockSpec((7, 128), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM)
                return a, b
            """, tmp_path, [BlockShapeTilingViolation()])
        assert rule_ids(got) == ["APX301", "APX301"]
        assert "lane dim 64" in got[0].message
        assert "sublane dim 7" in got[1].message

    def test_negative_tiled_scalar_column_and_dynamic(self, tmp_path):
        got = run("""
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            def specs(bn, H):
                a = pl.BlockSpec((16, 256), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM)
                b = pl.BlockSpec((bn, 1), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM)
                c = pl.BlockSpec((256, H), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM)
                return a, b, c
            """, tmp_path, [BlockShapeTilingViolation()])
        assert got == []


# ------------------------------- APX105 BlockSpec index_map arity vs grid
class TestBlockSpecIndexMapArity:
    def test_positive_arity_mismatch_direct_and_aliased(self, tmp_path):
        """The refactor hazard: a grid grown to rank 3 while the
        lambdas still take 2 ids — both the inline spec and one built
        through a local alias (the flash-kernel idiom)."""
        got = run("""
            import functools
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            def kernel(x):
                kv_spec = pl.BlockSpec((1, 128, 64), lambda b, j: (b, j, 0),
                                       memory_space=pltpu.VMEM)
                grid = (4, 8, 2)
                return pl.pallas_call(
                    functools.partial(_body),
                    grid=grid,
                    in_specs=[
                        pl.BlockSpec((1, 128, 64), lambda b, i: (b, i, 0),
                                     memory_space=pltpu.VMEM),
                        kv_spec,
                    ],
                    out_specs=pl.BlockSpec((1, 128, 64),
                                           lambda b, i, j: (b, i, 0),
                                           memory_space=pltpu.VMEM),
                )(x)
            """, tmp_path, [BlockSpecIndexMapArity()])
        assert rule_ids(got) == ["APX105", "APX105"]
        assert "takes 2 argument(s)" in got[0].message
        assert "rank 3" in got[0].message

    def test_shadowed_alias_last_assignment_wins(self, tmp_path):
        """``grid = (4, 8)`` rebound to ``(4, 8, 2)`` before the call:
        the lexically LAST assignment is the one the call sees, so
        rank-3 lambdas are clean and a rank-2 lambda is flagged (the
        reverse-visit-order bug flagged the correct ones instead)."""
        got = run("""
            from jax.experimental import pallas as pl

            def kernel(x):
                grid = (4, 8)
                grid = (4, 8, 2)
                return pl.pallas_call(
                    _body, grid=grid,
                    in_specs=[
                        pl.BlockSpec((8, 128), lambda b, i, j: (b, i, 0)),
                        pl.BlockSpec((8, 128), lambda b, i: (b, i)),
                    ],
                    out_specs=pl.BlockSpec((8, 128),
                                           lambda b, i, j: (b, i, 0)),
                )(x)
            """, tmp_path, [BlockSpecIndexMapArity()])
        assert rule_ids(got) == ["APX105"]
        assert "takes 2 argument(s)" in got[0].message

    def test_positive_int_grid_is_rank_one(self, tmp_path):
        got = run("""
            from jax.experimental import pallas as pl

            def kernel(x):
                return pl.pallas_call(
                    _body, grid=8,
                    in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                )(x)
            """, tmp_path, [BlockSpecIndexMapArity()])
        assert rule_ids(got) == ["APX105"]

    def test_negative_matching_named_default_and_dynamic(self, tmp_path):
        """Matching lambdas, a named index_map def of the right arity,
        a default index_map, a *args lambda, and a dynamic grid are
        all silent — the rule only speaks when the mismatch is
        provable."""
        got = run("""
            from jax.experimental import pallas as pl

            def imap(b, i, j):
                return (b, i, 0)

            def kernel(x, grid_from_caller):
                inline = pl.BlockSpec((1, 128, 64),
                                      lambda b, i, j: (b, j, 0))
                return pl.pallas_call(
                    _body,
                    grid=(4, 8, 2),
                    in_specs=[
                        inline,
                        pl.BlockSpec((1, 128, 64), imap),
                        pl.BlockSpec((1, 128, 64)),
                        pl.BlockSpec((1, 128, 64), lambda *ids: ids),
                    ],
                    out_specs=pl.BlockSpec((1, 128, 64), index_map=imap),
                )(x) + pl.pallas_call(
                    _body,
                    grid=grid_from_caller,
                    in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                )(x)
            """, tmp_path, [BlockSpecIndexMapArity()])
        assert got == []


# ------------------------------------- APX302 hard-coded sublane alignment
class TestHardCodedSublaneAlignment:
    def test_positive_advice_r5_fused_ce_pallas_58(self, tmp_path):
        """The literal pre-fix fused_ce_pallas.py:58 shape (ADVICE r5):
        ceil-rounding row blocks to fp32's sublane 8 in a kernel whose
        MXU dots run bf16."""
        got = run("""
            import jax.numpy as jnp

            def _ceil_block(n, target, align):
                if n >= target:
                    return target
                return -(-n // align) * align

            def fused_ce_fwd_pallas(x2, embed, t, block_n=256):
                dot_dtype = jnp.bfloat16
                bn = _ceil_block(x2.shape[0], block_n, align=8)
                return bn
            """, tmp_path, [HardCodedSublaneAlignment()])
        assert rule_ids(got) == ["APX302"]
        assert "align=8" in got[0].message

    def test_positive_positional_spelling(self, tmp_path):
        """The same constant passed positionally must not slip through."""
        got = run("""
            import jax.numpy as jnp

            def _ceil_block(n, target, align):
                return -(-n // align) * align

            def launch(x, block_n=256):
                dot_dtype = jnp.bfloat16
                return _ceil_block(x.shape[0], block_n, 8)
            """, tmp_path, [HardCodedSublaneAlignment()])
        assert rule_ids(got) == ["APX302"]

    def test_negative_dtype_derived_alignment(self, tmp_path):
        got = run("""
            import jax.numpy as jnp

            def _sublane(dtype):
                return {4: 8, 2: 16, 1: 32}[jnp.dtype(dtype).itemsize]

            def _ceil_block(n, target, align):
                if n >= target:
                    return target
                return -(-n // align) * align

            def fused_ce_fwd_pallas(x2, embed, t, block_n=256):
                dot_dtype = jnp.bfloat16
                bn = _ceil_block(x2.shape[0], block_n,
                                 align=_sublane(x2.dtype))
                return bn
            """, tmp_path, [HardCodedSublaneAlignment()])
        assert got == []

    def test_negative_fp32_only_module(self, tmp_path):
        """align=8 is correct when no bf16 can reach the kernel."""
        got = run("""
            def _ceil_block(n, target, align):
                return -(-n // align) * align

            def launch(x, block_n=256):
                bn = _ceil_block(x.shape[0], block_n, align=8)
                return bn
            """, tmp_path, [HardCodedSublaneAlignment()])
        assert got == []


# ---------------------------------------- APX401 unclamped take_along_axis
class TestUnclampedTakeAlongAxis:
    def test_positive_advice_r5_gpt_py_447(self, tmp_path):
        """The literal pre-fix gpt.py:447 dense-head shape (ADVICE r5)."""
        got = run("""
            import jax
            import jax.numpy as jnp

            def lm_head_loss(x, embed, targets):
                logits = jnp.matmul(x.astype(jnp.float32),
                                    embed.T.astype(jnp.float32))
                lse = jax.scipy.special.logsumexp(logits, axis=-1)
                tgt = jnp.take_along_axis(
                    logits, targets[..., None], axis=-1)[..., 0]
                return lse - tgt
            """, tmp_path, [UnclampedTakeAlongAxis()])
        assert rule_ids(got) == ["APX401"]

    def test_negative_clamped_through_a_name(self, tmp_path):
        got = run("""
            import jax.numpy as jnp

            def lm_head_loss(logits, targets):
                t_cl = jnp.clip(targets, 0, logits.shape[-1] - 1)
                tgt = jnp.take_along_axis(
                    logits, t_cl[..., None], axis=-1)[..., 0]
                return tgt
            """, tmp_path, [UnclampedTakeAlongAxis()])
        assert got == []

    def test_negative_explicit_mode(self, tmp_path):
        got = run("""
            import jax.numpy as jnp

            def gather(logits, t):
                return jnp.take_along_axis(
                    logits, t[..., None], axis=-1, mode="fill")
            """, tmp_path, [UnclampedTakeAlongAxis()])
        assert got == []


# ------------------------------------------ APX402 fp32 constant in bf16
class TestFp32ConstantInBf16Path:
    def test_positive_materialized_f32_meets_bf16(self, tmp_path):
        got = run("""
            import jax.numpy as jnp

            def scale(x, shape):
                return x.astype(jnp.bfloat16) * jnp.ones(
                    shape, dtype=jnp.float32)
            """, tmp_path, [Fp32ConstantInBf16Path()])
        assert rule_ids(got) == ["APX402"]
        assert "upcasts" in got[0].message

    def test_negative_constant_in_compute_dtype(self, tmp_path):
        got = run("""
            import jax.numpy as jnp

            def scale(x, shape):
                return x.astype(jnp.bfloat16) * jnp.ones(
                    shape, dtype=jnp.bfloat16)
            """, tmp_path, [Fp32ConstantInBf16Path()])
        assert got == []


# ------------------------------------------------------------ engine bits
class TestEngine:
    def test_axis_registry_discovered_from_parallel_state(self, tmp_path):
        ps = tmp_path / "parallel_state.py"
        ps.write_text('WEIRD_AXIS = "zz"\nOTHER = 3\n')
        assert discover_axis_registry([str(tmp_path)]) == {"zz"}

    def test_axis_registry_falls_back_to_defaults(self, tmp_path):
        assert "tp" in discover_axis_registry([str(tmp_path)])

    def test_syntax_error_is_a_finding_not_a_crash(self, tmp_path):
        got = run("def broken(:\n", tmp_path, DEFAULT_RULES)
        assert rule_ids(got) == ["APX000"]

    def test_findings_are_sorted_and_relative(self, tmp_path):
        (tmp_path / "b.py").write_text(
            "import os\n\ndef f():\n    os.environ['X'] = '1'\n")
        (tmp_path / "a.py").write_text(
            "import os\n\ndef f():\n    os.environ['X'] = '1'\n")
        got = analyze_paths([str(tmp_path)], DEFAULT_RULES,
                            axis_registry=set(AXES), rel_to=str(tmp_path))
        assert [f.path for f in got] == ["a.py", "b.py"]


# ------------------------------------- cross-module trace reachability
class TestCrossModuleReachability:
    """The traced-function index was per-module, so a helper whose only
    traced caller lives in ANOTHER module escaped APX101 — the exact
    ROADMAP case: ``fused_ce_pallas._default_dot_dtype``'s env read
    reached from ``fused_ce._fwd``.  ``analyze_paths`` now links the
    indexes through import-resolved calls; single-file
    ``analyze_file`` stays per-module (no imports to resolve)."""

    HELPER = textwrap.dedent("""
        import os

        def helper():
            return os.environ.get("APEX_TPU_X", "auto")
        """)

    def _scan(self, tmp_path):
        return analyze_paths([str(tmp_path)], DEFAULT_RULES,
                             axis_registry=set(AXES),
                             rel_to=str(tmp_path))

    def test_from_import_reached_from_jit(self, tmp_path):
        (tmp_path / "helper_mod.py").write_text(self.HELPER)
        (tmp_path / "main.py").write_text(textwrap.dedent("""
            import jax
            from helper_mod import helper

            @jax.jit
            def f(x):
                if helper() == "on":
                    return x * 2
                return x
            """))
        got = self._scan(tmp_path)
        assert [(f.rule, f.path, f.symbol) for f in got] == \
            [("APX101", "helper_mod.py", "helper")]
        assert "cross-module" in got[0].message or "main" in got[0].message

    def test_function_local_import_and_alias(self, tmp_path):
        """The fused_ce shape: the import lives INSIDE the traced
        closure; and the `import m as alias` dotted-call spelling."""
        (tmp_path / "helper_mod.py").write_text(self.HELPER)
        (tmp_path / "main.py").write_text(textwrap.dedent("""
            import jax
            import helper_mod as hm

            @jax.jit
            def f(x):
                from helper_mod import helper
                return x if helper() else x * hm.helper()
            """))
        got = self._scan(tmp_path)
        assert [(f.rule, f.path) for f in got] == \
            [("APX101", "helper_mod.py")]

    def test_package_relative_import(self, tmp_path):
        """Packages resolve: `from .kernels import helper` inside
        pkg/api.py marks pkg/kernels.py's helper traced."""
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "kernels.py").write_text(self.HELPER)
        (pkg / "api.py").write_text(textwrap.dedent("""
            import jax
            from .kernels import helper

            @jax.jit
            def f(x):
                return x * helper()
            """))
        got = self._scan(tmp_path)
        assert [(f.rule, f.path, f.symbol) for f in got] == \
            [("APX101", str(Path("pkg") / "kernels.py"), "helper")]

    def test_package_init_relative_import(self, tmp_path):
        """Relative imports in a package __init__.py resolve against
        the package ITSELF (python semantics) — review finding: the
        parent-of-module rule resolved one level too shallow and the
        seed was silently dropped."""
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "kernels.py").write_text(self.HELPER)
        (pkg / "__init__.py").write_text(textwrap.dedent("""
            import jax
            from .kernels import helper

            @jax.jit
            def f(x):
                return x * helper()
            """))
        got = self._scan(tmp_path)
        assert [(f.rule, f.path, f.symbol) for f in got] == \
            [("APX101", str(Path("pkg") / "kernels.py"), "helper")]

    def test_colliding_module_names_never_mislink(self, tmp_path):
        """Two bare roots both holding utils.py: the dotted name is
        ambiguous, so NO cross-module seed may land through it (a wrong
        -file APX101 is worse than a missed link)."""
        for d in ("libA", "libB"):
            (tmp_path / d).mkdir()
            (tmp_path / d / "utils.py").write_text(self.HELPER)
        (tmp_path / "libB" / "main.py").write_text(textwrap.dedent("""
            import jax
            from utils import helper

            @jax.jit
            def f(x):
                return x * helper()
            """))
        got = analyze_paths(
            [str(tmp_path / "libA"), str(tmp_path / "libB")],
            DEFAULT_RULES, axis_registry=set(AXES), rel_to=str(tmp_path))
        assert got == []

    def test_untraced_cross_module_call_not_flagged(self, tmp_path):
        """A helper reached only from plain (untraced) code stays
        clean — reachability, not mere import, is the trigger."""
        (tmp_path / "helper_mod.py").write_text(self.HELPER)
        (tmp_path / "main.py").write_text(textwrap.dedent("""
            from helper_mod import helper

            def plain():
                return helper()
            """))
        assert self._scan(tmp_path) == []

    def test_local_binding_shadows_import(self, tmp_path):
        """A module-local def with the imported name wins resolution —
        the other module must not be marked through the shadowed
        name."""
        (tmp_path / "helper_mod.py").write_text(self.HELPER)
        (tmp_path / "main.py").write_text(textwrap.dedent("""
            import jax
            from helper_mod import helper

            def helper():
                return 1

            @jax.jit
            def f(x):
                return x * helper()
            """))
        assert self._scan(tmp_path) == []


# ------------------------------------------------------------- baseline
class TestBaseline:
    def _write(self, tmp_path, entries):
        p = tmp_path / "baseline.json"
        p.write_text(json.dumps({"entries": entries}))
        return str(p)

    def test_suppression_and_stale_reporting(self, tmp_path):
        findings = run("""
            import os

            def f():
                os.environ["X"] = "1"
            """, tmp_path, [ProcessGlobalEnvMutation()])
        entries = load_baseline(self._write(tmp_path, [
            {"rule": "APX102", "path": "fixture.py", "symbol": "f",
             "contains": "os.environ", "justification": "test fixture"},
            {"rule": "APX102", "path": "nonexistent.py",
             "justification": "stale on purpose"},
        ]))
        kept, suppressed, stale = apply_baseline(findings, entries)
        assert kept == []
        assert len(suppressed) == 1
        assert len(stale) == 1 and stale[0].path == "nonexistent.py"

    def test_justification_is_mandatory(self, tmp_path):
        with pytest.raises(BaselineError, match="justification"):
            load_baseline(self._write(tmp_path, [
                {"rule": "APX102", "path": "x.py", "justification": "  "}]))

    def test_missing_fields_rejected(self, tmp_path):
        with pytest.raises(BaselineError, match="missing"):
            load_baseline(self._write(tmp_path, [{"rule": "APX102"}]))

    @pytest.mark.parametrize("placeholder", ["TODO", "todo", "TODO: later"])
    def test_todo_placeholder_rejected(self, tmp_path, placeholder):
        """--update-baseline's placeholder must never LOAD — a refresh
        is mechanical, signing off on it is not."""
        with pytest.raises(BaselineError, match="placeholder"):
            load_baseline(self._write(tmp_path, [
                {"rule": "APX102", "path": "x.py",
                 "justification": placeholder}]))

    def test_todo_allowed_only_for_the_update_path(self, tmp_path):
        entries = load_baseline(self._write(tmp_path, [
            {"rule": "APX102", "path": "x.py", "justification": "TODO"}]),
            allow_todo=True)
        assert len(entries) == 1

    def test_write_baseline_keeps_drops_adds(self, tmp_path):
        """Regeneration semantics: matched entries survive VERBATIM
        (their justifications are reviewed text), stale entries drop,
        new findings land with the rejected TODO placeholder."""
        findings = run("""
            import os

            def f():
                os.environ["X"] = "1"

            def g():
                os.environ.pop("Y", None)
            """, tmp_path, [ProcessGlobalEnvMutation()])
        entries = load_baseline(self._write(tmp_path, [
            {"rule": "APX102", "path": "fixture.py", "symbol": "f",
             "contains": "assignment", "justification": "reviewed: test"},
            {"rule": "APX102", "path": "gone.py",
             "justification": "stale on purpose"},
        ]))
        out = tmp_path / "new_baseline.json"
        kept, dropped, added = write_baseline(str(out), findings, entries)
        assert (kept, dropped, added) == (1, 1, 1)
        data = json.loads(out.read_text())
        justs = [e["justification"] for e in data["entries"]]
        assert justs == ["reviewed: test", "TODO"]
        assert data["entries"][1]["symbol"] == "g"
        # the regenerated file round-trips ONLY through the update path
        with pytest.raises(BaselineError, match="placeholder"):
            load_baseline(str(out))
        reloaded = load_baseline(str(out), allow_todo=True)
        k2, s2, _ = apply_baseline(findings, reloaded)
        assert k2 == [] and len(s2) == 2  # every finding now matched


# ----------------------------------------- CLI: --update-baseline, SARIF
class TestCliUpdateBaselineAndSarif:
    FIXTURE = textwrap.dedent("""
        import os
        import jax

        @jax.jit
        def f(x):
            return x if os.environ.get("FLAG") else -x
        """)

    def _run_cli(self, args, cwd):
        import os as _os

        env = dict(_os.environ, PYTHONPATH=str(REPO))
        return subprocess.run(
            [sys.executable, "-m", "apex_tpu.analysis", *args],
            cwd=str(cwd), env=env, capture_output=True, text=True,
            timeout=600)

    def test_update_baseline_is_mechanical_but_loud(self, tmp_path):
        """The full loop: findings -> --update-baseline exits 0 and
        writes TODO entries -> a normal run REFUSES the file (exit 2)
        -> filling the justification in makes the run clean (exit 0)."""
        (tmp_path / "mod.py").write_text(self.FIXTURE)
        r = self._run_cli(["mod.py"], tmp_path)
        assert r.returncode == 1  # the APX101 finding, unsuppressed

        r = self._run_cli(["mod.py", "--update-baseline"], tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "added 1" in r.stderr
        baseline = tmp_path / "analysis_baseline.json"
        data = json.loads(baseline.read_text())
        assert data["entries"][0]["justification"] == "TODO"
        assert data["entries"][0]["rule"] == "APX101"

        r = self._run_cli(["mod.py"], tmp_path)
        assert r.returncode == 2, r.stdout + r.stderr
        assert "placeholder" in r.stderr

        data["entries"][0]["justification"] = "reviewed: test fixture"
        baseline.write_text(json.dumps(data))
        r = self._run_cli(["mod.py"], tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "1 baselined" in r.stderr

    def test_sarif_schema_shape(self, tmp_path):
        """--format sarif emits a SARIF 2.1.0 log whose runs/tool/
        driver/rules/results shape CI consumers (GitHub code scanning,
        the VS Code viewer) require; baselined findings carry
        ``suppressions`` instead of disappearing."""
        (tmp_path / "mod.py").write_text(self.FIXTURE)
        (tmp_path / "analysis_baseline.json").write_text(json.dumps({
            "entries": [{"rule": "APX101", "path": "mod.py",
                         "justification": "reviewed: test fixture"}]}))
        r = self._run_cli(["mod.py", "--format", "sarif"], tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr
        log = json.loads(r.stdout)
        assert log["version"] == "2.1.0"
        assert log["$schema"].endswith("sarif-schema-2.1.0.json")
        (run_obj,) = log["runs"]
        driver = run_obj["tool"]["driver"]
        assert driver["name"] == "apex_tpu.analysis"
        rule_d = {d["id"]: d for d in driver["rules"]}
        assert "APX101" in rule_d
        assert rule_d["APX101"]["defaultConfiguration"]["level"] == "error"
        (result,) = run_obj["results"]
        assert result["ruleId"] == "APX101"
        assert result["level"] == "error"
        loc = result["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == "mod.py"
        assert loc["region"]["startLine"] >= 1
        assert loc["region"]["startColumn"] >= 1
        assert result["suppressions"][0]["kind"] == "external"

    def test_update_baseline_bootstraps_an_explicit_path(self, tmp_path):
        """Review finding: --baseline pointing at a not-yet-existing
        file must BOOTSTRAP it under --update-baseline, not die with
        'cannot read baseline' before write_baseline runs."""
        (tmp_path / "mod.py").write_text(self.FIXTURE)
        target = tmp_path / "fresh" "_baseline.json"
        r = self._run_cli(
            ["mod.py", "--baseline", str(target), "--update-baseline"],
            tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr
        assert json.loads(target.read_text())["entries"]
        # a normal run against a MISSING explicit baseline still errors
        r = self._run_cli(
            ["mod.py", "--baseline", str(tmp_path / "nope.json")],
            tmp_path)
        assert r.returncode == 2

    def test_update_baseline_rejects_no_baseline(self, tmp_path):
        """Review finding: the combination would rewrite the file from
        an EMPTY entry list, silently discarding every reviewed
        justification — refuse it."""
        (tmp_path / "mod.py").write_text(self.FIXTURE)
        (tmp_path / "analysis_baseline.json").write_text(json.dumps({
            "entries": [{"rule": "APX101", "path": "mod.py",
                         "justification": "reviewed: keep me"}]}))
        r = self._run_cli(
            ["mod.py", "--update-baseline", "--no-baseline"], tmp_path)
        assert r.returncode == 2
        assert "discard" in r.stderr
        kept = json.loads(
            (tmp_path / "analysis_baseline.json").read_text())
        assert kept["entries"][0]["justification"] == "reviewed: keep me"

    def test_sarif_unsuppressed_finding_has_no_suppressions(self, tmp_path):
        (tmp_path / "mod.py").write_text(self.FIXTURE)
        r = self._run_cli(
            ["mod.py", "--format", "sarif", "--no-baseline"], tmp_path)
        assert r.returncode == 1  # findings still drive the exit code
        log = json.loads(r.stdout)
        (result,) = log["runs"][0]["results"]
        assert "suppressions" not in result

    def test_vmem_budget_flag_reaches_apx304(self, tmp_path):
        (tmp_path / "mod.py").write_text(textwrap.dedent("""
            from jax.experimental import pallas as pl

            def launch(x):
                return pl.pallas_call(
                    _body, grid=(4,),
                    in_specs=[pl.BlockSpec((256, 512), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((256, 512), lambda i: (i, 0)),
                )(x)
            """))
        assert self._run_cli(["mod.py"], tmp_path).returncode == 0
        r = self._run_cli(
            ["mod.py", "--vmem-budget-mib", "0.125"], tmp_path)
        assert r.returncode == 1
        assert "APX304" in r.stdout


# --------------------------------------- APX108 host sync in step loops
class TestBlockingHostSyncInStepLoop:
    """APX108: float()/.item()/np.asarray/f-string of a proven device
    array inside a loop that dispatches a compiled step — the per-step
    sync barrier the observability async-fetch seam exists to remove."""

    def test_positive_float_of_jit_result_in_loop(self, tmp_path):
        got = run("""
            import jax
            step = jax.jit(lambda p: (p, p.sum()))
            def train(params):
                for i in range(10):
                    params, loss = step(params)
                    print(float(loss))
            """, tmp_path, [BlockingHostSyncInStepLoop()])
        assert rule_ids(got) == ["APX108"]
        assert "float()" in got[0].message

    def test_positive_builder_and_run_step_indirection(self, tmp_path):
        """The pre-fix pretrain_gpt shape: the step comes from a
        builder (`step = build_step()`), dispatch goes through a local
        retry wrapper (`run_step`), and the f-string formats the
        wrapper's result — still proven, still flagged."""
        got = run("""
            from apex_tpu.models.gpt import make_train_step

            def main():
                def build_step():
                    return make_train_step(None, None, None)

                step = build_step()

                def run_step(t):
                    return step(t)

                for i in range(8):
                    params, state, loss = run_step(i)
                    print(f"step {i}: loss={loss:.4f}")
            """, tmp_path, [BlockingHostSyncInStepLoop()])
        assert rule_ids(got) == ["APX108"]
        assert "f-string" in got[0].message

    def test_positive_item_and_np_asarray_in_while(self, tmp_path):
        got = run("""
            import jax
            import numpy as np
            f = jax.jit(lambda x: x)
            def loop():
                out = None
                while True:
                    out = f(1)
                    a = out.item()
                    b = np.asarray(out)
            """, tmp_path, [BlockingHostSyncInStepLoop()])
        assert rule_ids(got) == ["APX108", "APX108"]
        assert {".item()" in f.message or "np.asarray" in f.message
                for f in got} == {True}

    def test_positive_attribute_off_device_tuple(self, tmp_path):
        """float(scaler_state.loss_scale): the base name is the step
        result, the attribute read still materializes on host."""
        got = run("""
            from apex_tpu.models.gpt import make_train_step
            step = make_train_step(1, 2, 3)
            def train(p, s, sc, t):
                for i in range(4):
                    p, s, sc, loss = step(p, s, sc, t)
                    print(float(sc.loss_scale))
            """, tmp_path, [BlockingHostSyncInStepLoop()])
        assert rule_ids(got) == ["APX108"]

    def test_negative_conversion_after_loop_and_async_seam(self, tmp_path):
        """The allowed spellings: hand the array to the fetch seam in
        the loop, convert AFTER the loop, format only harvested host
        values."""
        got = run("""
            import jax
            step = jax.jit(lambda p: (p, p))
            def train(params, fetcher):
                loss = None
                for i in range(10):
                    params, loss = step(params)
                    fetcher.put("loss", i, {"loss": loss})
                    for kind, s, tree in fetcher.ready():
                        print(f"step {s}: loss={float(tree['loss']):.4f}")
                print(float(loss))
            """, tmp_path, [BlockingHostSyncInStepLoop()])
        assert got == []

    def test_negative_jnp_asarray_and_non_device_values(self, tmp_path):
        """jnp.asarray stays on device; float() of a plain loop index
        or of an unproven name is not flagged."""
        got = run("""
            import jax
            import jax.numpy as jnp
            step = jax.jit(lambda p: p)
            def train(params, mystery):
                for i in range(10):
                    params = step(params)
                    x = jnp.asarray(params)
                    y = float(i)
                    z = float(mystery)
            """, tmp_path, [BlockingHostSyncInStepLoop()])
        assert got == []

    def test_negative_loop_without_step_dispatch(self, tmp_path):
        """A conversion in a loop that does NOT dispatch a step is not
        a per-step sync barrier (the post-run report loop shape)."""
        got = run("""
            import jax
            step = jax.jit(lambda p: p)
            def report(params):
                out = step(params)
                for i in range(10):
                    print(float(out))
            """, tmp_path, [BlockingHostSyncInStepLoop()])
        assert got == []

    def test_rides_default_rules(self, tmp_path):
        got = run("""
            import jax
            step = jax.jit(lambda p: p)
            def train(p):
                for i in range(4):
                    p = step(p)
                    print(float(p))
            """, tmp_path, DEFAULT_RULES)
        assert "APX108" in rule_ids(got)


# ------------------------------------ APX112 unseamed dispatch timing
class TestUnseamedDispatchTiming:
    """APX112: a wall-clock delta spanning a proven step dispatch with
    no block_until_ready/host-read/async-fetch seam — async dispatch
    makes such timings enqueue measurements, not step times."""

    def test_positive_delta_around_dispatch(self, tmp_path):
        got = run("""
            import time
            import jax
            step = jax.jit(lambda p: p)
            def bench(p):
                t0 = time.perf_counter()
                p = step(p)
                dt = time.perf_counter() - t0
                return dt
            """, tmp_path, [UnseamedDispatchTiming()])
        assert rule_ids(got) == ["APX112"]
        assert "enqueue" in got[0].message

    def test_positive_two_stamp_spelling_and_from_import(self, tmp_path):
        """t1 = perf_counter(); dt = t1 - t0 — the second stamp, not
        the subtraction, is the read that lies."""
        got = run("""
            from time import perf_counter
            from apex_tpu.models.gpt import make_train_step
            step = make_train_step(1, 2, 3)
            def bench(p, s, t, y):
                t0 = perf_counter()
                p, s, loss = step(p, s, t, y)
                t1 = perf_counter()
                print(float(loss))  # AFTER t1: does not unlie it
                dt = t1 - t0
            """, tmp_path, [UnseamedDispatchTiming()])
        assert rule_ids(got) == ["APX112"]

    def test_positive_dispatch_loop_between_stamps(self, tmp_path):
        got = run("""
            import time
            import jax
            step = jax.jit(lambda p: p)
            def bench(p, iters):
                t0 = time.time()
                for _ in range(iters):
                    p = step(p)
                dt = time.time() - t0
            """, tmp_path, [UnseamedDispatchTiming()])
        assert rule_ids(got) == ["APX112"]

    def test_positive_warmup_seam_does_not_acquit_timed_loop(self,
                                                            tmp_path):
        """A seam after the WARMUP dispatch must not acquit the timed
        loop's own (later, unseamed) dispatches."""
        got = run("""
            import time
            import jax
            step = jax.jit(lambda p: p)
            def bench(p, iters):
                t0 = time.perf_counter()
                p = step(p)                 # warmup
                jax.block_until_ready(p)    # seam covers ONLY warmup
                for _ in range(iters):
                    p = step(p)             # the timed dispatches
                dt = time.perf_counter() - t0
            """, tmp_path, [UnseamedDispatchTiming()])
        assert rule_ids(got) == ["APX112"]

    def test_negative_rebound_stamp_is_data_not_timing(self, tmp_path):
        """Reusing a stamp name for NON-clock data invalidates the
        stamp: the later delta is arithmetic, not a dispatch timing —
        flagging it would turn the gate red on clean code."""
        got = run("""
            import time
            import jax
            step = jax.jit(lambda p: p)
            def bench(p, offsets):
                t0 = time.time()
                p = step(p)
                jax.block_until_ready(p)
                warm = time.time() - t0     # properly seamed
                t0 = offsets[0]             # name reused for DATA
                p = step(p)
                shifted = time.time() - t0  # data math, not timing
                return warm, shifted
            """, tmp_path, [UnseamedDispatchTiming()])
        assert got == []

    def test_negative_block_until_ready_seam(self, tmp_path):
        got = run("""
            import time
            import jax
            step = jax.jit(lambda p: p)
            def bench(p, iters):
                t0 = time.perf_counter()
                for _ in range(iters):
                    p = step(p)
                jax.block_until_ready(p)
                dt = time.perf_counter() - t0
            """, tmp_path, [UnseamedDispatchTiming()])
        assert got == []

    def test_negative_host_read_and_local_seam_wrapper(self, tmp_path):
        """float(loss) is a sync; so is calling a local def that wraps
        block_until_ready (a harness's `block(tree)` idiom)."""
        got = run("""
            import time
            import jax
            step = jax.jit(lambda p: (p, p.sum()))

            def block(tree):
                for x in jax.tree.leaves(tree):
                    jax.block_until_ready(x)

            def bench(p, iters):
                t0 = time.perf_counter()
                p, loss = step(p)
                host = float(loss)
                dt1 = time.perf_counter() - t0
                t2 = time.perf_counter()
                p, loss = step(p)
                block(loss)
                dt2 = time.perf_counter() - t2
            """, tmp_path, [UnseamedDispatchTiming()])
        assert got == []

    def test_negative_no_dispatch_between_stamps(self, tmp_path):
        """Deltas around host work, or taken before the dispatch, and
        unproven callees between stamps are all trusted."""
        got = run("""
            import time
            import jax
            step = jax.jit(lambda p: p)
            def bench(p, mystery):
                t0 = time.time()
                q = mystery(p)
                setup = time.time() - t0
                p = step(p)
                t1 = time.time()
                host_only = sum(range(100))
                dt = time.time() - t1
            """, tmp_path, [UnseamedDispatchTiming()])
        assert got == []

    def test_negative_nonclock_subtraction_names(self, tmp_path):
        got = run("""
            import time
            import jax
            step = jax.jit(lambda p: p)
            def bench(p, a, b):
                t0 = a  # not a clock read
                p = step(p)
                dt = b - t0
            """, tmp_path, [UnseamedDispatchTiming()])
        assert got == []

    def test_rides_default_rules(self, tmp_path):
        got = run("""
            import time
            import jax
            step = jax.jit(lambda p: p)
            def bench(p):
                t0 = time.time()
                p = step(p)
                return time.time() - t0
            """, tmp_path, DEFAULT_RULES)
        assert "APX112" in rule_ids(got)


# ------------------------------------------------- the repo-wide rider
class TestRepoIsClean:
    """The tier-1 rider: the shipped tree stays clean modulo the
    committed baseline, and every baseline entry still bites."""

    def _repo_findings(self):
        paths = [str(REPO / "apex_tpu"), str(REPO / "examples")]
        return analyze_paths(paths, DEFAULT_RULES, rel_to=str(REPO))

    def test_repo_clean_modulo_baseline(self):
        entries = load_baseline(str(REPO / "analysis_baseline.json"))
        kept, _, stale = apply_baseline(self._repo_findings(), entries)
        assert not kept, "new analyzer findings:\n" + "\n".join(
            f.render() for f in kept)
        assert not stale, "stale baseline entries (fixed code? remove " \
            "them): " + ", ".join(f"{e.rule} {e.path}" for e in stale)

    def test_advice_r5_fixes_are_in_the_tree(self):
        """The three ADVICE r5 findings must stay FIXED (their pre-fix
        shapes are pinned by the fixture tests above): no APX102 anywhere
        in the scanned tree (the file that held it is gone), no APX302
        in the Pallas ops, no APX401 in gpt.py."""
        by_rule = {}
        for f in self._repo_findings():
            by_rule.setdefault(f.rule, []).append(f.path)
        assert not by_rule.get("APX102")
        assert not [p for p in by_rule.get("APX302", [])
                    if p.startswith("apex_tpu/ops/")]
        assert "apex_tpu/models/gpt.py" not in by_rule.get("APX401", [])

    def test_cli_acceptance_command(self):
        """`python -m apex_tpu.analysis apex_tpu examples` exits 0."""
        r = subprocess.run(
            [sys.executable, "-m", "apex_tpu.analysis",
             "apex_tpu", "examples"],
            cwd=str(REPO), capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_cli_from_foreign_cwd_finds_baseline(self, tmp_path):
        """The committed baseline must be picked up when the CLI runs
        from another directory with absolute paths (pre-commit hooks,
        CI jobs) — review finding: CWD-relative default dropped it."""
        import os

        env = dict(os.environ, PYTHONPATH=str(REPO))
        r = subprocess.run(
            [sys.executable, "-m", "apex_tpu.analysis",
             str(REPO / "apex_tpu"), str(REPO / "examples")],
            cwd=str(tmp_path), env=env, capture_output=True, text=True,
            timeout=600)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "baselined" in r.stderr


# ------------------------------------------------ rule-hygiene meta-lint
class TestRuleHygieneMetaLint:
    """Every registered APX rule must ship documented and fixtured:
    a docs/static_analysis.md table row, and a Test<RuleClass> class
    here with at least one test_positive* and one test_negative*
    method.  The next rule someone lands undocumented or untested
    fails THIS test, not a review comment."""

    def _rule_classes(self):
        return {type(r).__name__: r.rule_id for r in DEFAULT_RULES}

    def test_every_rule_has_a_docs_row(self):
        docs = (REPO / "docs" / "static_analysis.md").read_text()
        import re as _re

        documented = set(_re.findall(r"^\|\s*(APX\d+)\s*\|", docs,
                                     _re.M))
        missing = {rid for rid in self._rule_classes().values()
                   if rid not in documented}
        assert not missing, (
            f"rules with no docs/static_analysis.md table row: "
            f"{sorted(missing)} — add the row (what it catches / why "
            f"it only fails on the chip)")

    def test_every_rule_has_positive_and_negative_fixtures(self):
        import ast as _ast

        tree = _ast.parse(Path(__file__).read_text())
        classes = {
            n.name: [m.name for m in n.body
                     if isinstance(m, _ast.FunctionDef)]
            for n in tree.body if isinstance(n, _ast.ClassDef)
        }
        problems = []
        for cls, rid in self._rule_classes().items():
            test_cls = f"Test{cls}"
            methods = classes.get(test_cls)
            if methods is None:
                problems.append(f"{rid}: no {test_cls} class")
                continue
            if not any(m.startswith("test_positive") for m in methods):
                problems.append(f"{rid}: {test_cls} has no "
                                f"test_positive* fixture")
            if not any(m.startswith("test_negative") for m in methods):
                problems.append(f"{rid}: {test_cls} has no "
                                f"test_negative* fixture")
        assert not problems, "\n".join(problems)


# ------------------------------------------- CLI performance and hygiene
class TestCliPerformanceAndHygiene:
    def test_repo_scan_stays_fast(self):
        """The analyzer rides tier-1 AND pre-commit: the full repo scan
        must stay interactive.  Measured ~9 s CPU on this 1-core box
        WITH the divergence tier (the taint lattice adds its per-module
        event replay and the link_taint cross-module fixpoint — ~1 s
        over the pre-APX209 scan); the 30 s budget is ~3x headroom
        while still catching an accidentally-quadratic rule or
        fixpoint.  CPU time, not wall time: this box's wall-clock
        tests false-fire under CPU contention (the gpt_example
        watchdog class), and the hazard this test guards is
        algorithmic, not scheduling."""
        import time

        paths = [str(REPO / "apex_tpu"), str(REPO / "examples")]
        t0 = time.process_time()
        analyze_paths(paths, DEFAULT_RULES, rel_to=str(REPO))
        dt = time.process_time() - t0
        assert dt < 30.0, f"repo scan took {dt:.1f}s CPU (budget 30s)"

    def test_jobs_results_identical(self):
        """--jobs may change wall time, never findings: the parallel
        parse/index pass over a real subtree must produce byte-equal
        findings to the serial one."""
        paths = [str(REPO / "apex_tpu" / "ops"),
                 str(REPO / "chip_smoke.py")]
        serial = analyze_paths(paths, DEFAULT_RULES, rel_to=str(REPO))
        parallel = analyze_paths(paths, DEFAULT_RULES, rel_to=str(REPO),
                                 jobs=2)
        assert [f.to_json() for f in serial] \
            == [f.to_json() for f in parallel]

    def test_timing_collects_per_rule_walltime(self):
        timings = {}
        analyze_paths([str(REPO / "apex_tpu" / "analysis")],
                      DEFAULT_RULES, timings=timings)
        assert "<load>" in timings and "<link>" in timings
        ids = {r.rule_id for r in DEFAULT_RULES}
        assert ids <= set(timings), ids - set(timings)
        assert all(v >= 0 for v in timings.values())

    def test_cli_check_baseline_fails_on_stale_entry(self, tmp_path):
        """--check-baseline turns a stale suppression into exit 1 —
        without it the note on stderr scrolls past and the entry rots
        (matching the next unrelated finding that drifts into its
        substring)."""
        import os

        (tmp_path / "mod.py").write_text("import os\n")
        (tmp_path / "analysis_baseline.json").write_text(json.dumps({
            "entries": [{"rule": "APX101", "path": "never.py",
                         "symbol": "*", "contains": "",
                         "justification": "covers deleted code"}]}))
        env = dict(os.environ, PYTHONPATH=str(REPO))
        base = [sys.executable, "-m", "apex_tpu.analysis", "mod.py"]
        clean = subprocess.run(base, cwd=str(tmp_path), env=env,
                               capture_output=True, text=True, timeout=120)
        assert clean.returncode == 0, clean.stderr
        checked = subprocess.run(base + ["--check-baseline"],
                                 cwd=str(tmp_path), env=env,
                                 capture_output=True, text=True,
                                 timeout=120)
        assert checked.returncode == 1
        assert "stale baseline entry" in checked.stderr
        assert "--check-baseline" in checked.stderr

    def test_cli_sarif_failure_prints_human_summary(self, tmp_path):
        """The red-CI-log fix: --format sarif on a failing tree must
        name the findings count and rule ids on stderr, not just dump
        the SARIF document."""
        import os

        (tmp_path / "bad.py").write_text(textwrap.dedent("""
            import jax, os

            @jax.jit
            def f(x):
                return x if os.environ.get("FLAG") else -x
            """))
        env = dict(os.environ, PYTHONPATH=str(REPO))
        r = subprocess.run(
            [sys.executable, "-m", "apex_tpu.analysis", "bad.py",
             "--no-baseline", "--format", "sarif"],
            cwd=str(tmp_path), env=env, capture_output=True, text=True,
            timeout=120)
        assert r.returncode == 1
        assert "APX101" in r.stderr and "finding(s)" in r.stderr
        doc = json.loads(r.stdout)   # the SARIF document stays valid
        assert doc["runs"][0]["results"]

    def test_repo_scan_has_no_stale_baseline_via_cli_flag(self):
        """The repo-level --check-baseline run the CI target uses."""
        r = subprocess.run(
            [sys.executable, "-m", "apex_tpu.analysis", "apex_tpu",
             "examples", "--check-baseline"],
            cwd=str(REPO), capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stdout + r.stderr


# --------------------------------------- APX209 rank-gated collective launch
#: the shared scaffolding of the divergence fixtures: a registered-axis
#: collective inside a shard_map step
_STEP_PRELUDE = textwrap.dedent("""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def grad_sync(g):
        return jax.lax.psum(g, "dp")

    step = shard_map(grad_sync, mesh=mesh, in_specs=P("dp"),
                     out_specs=P("dp"))
""")


def run_div(src, tmp_path, rules, axes=AXES):
    """``run`` with the shard_map step prelude prepended (both parts
    dedented independently — the fixture bodies sit at test-method
    indentation, the prelude at module level)."""
    return run(_STEP_PRELUDE + textwrap.dedent(src), tmp_path, rules,
               axes)


class TestTaintedPredicateGuardsCollective:
    def test_positive_rank_zero_probe(self, tmp_path):
        """The canonical bug: only rank 0 launches the collective-
        bearing step — its peers block in the psum forever."""
        got = run_div("""
            def maybe_probe(x):
                if jax.process_index() == 0:
                    return step(x)
                return x
            """, tmp_path, [TaintedPredicateGuardsCollective()])
        assert rule_ids(got) == ["APX209"]
        assert "wedges" in got[0].message
        assert "process_index" in got[0].message

    def test_positive_taint_through_partial_and_conditional_join(
            self, tmp_path):
        """The value survives a functools.partial alias AND a
        conditional clean rebind (the branch may not execute, so the
        taint only joins — it never clears)."""
        got = run_div("""
            import functools

            who = functools.partial(jax.process_index)

            def maybe_probe(x, flag):
                r = who()
                if flag:
                    r = 0
                if r == 0:
                    return step(x)
                return x
            """, tmp_path, [TaintedPredicateGuardsCollective()])
        assert rule_ids(got) == ["APX209"]

    def test_negative_both_branches_launch(self, tmp_path):
        """Branching on rank is fine when EVERY path launches the same
        traced step — per-rank logging around a uniform launch."""
        got = run_div("""
            def maybe_probe(x):
                if jax.process_index() == 0:
                    return step(x * 2)
                return step(x)
            """, tmp_path, [TaintedPredicateGuardsCollective()])
        assert got == []

    def test_negative_straight_line_rebind_clears(self, tmp_path):
        """An unconditional clean rebind kills the taint — the value
        the predicate reads no longer depends on the rank."""
        got = run_div("""
            def maybe_probe(x):
                rank = jax.process_index()
                rank = 0
                if rank == 0:
                    return step(x)
                return x
            """, tmp_path, [TaintedPredicateGuardsCollective()])
        assert got == []

    def test_negative_acquitted_by_uniformity_seam(self, tmp_path):
        """A function that routes the decision through the runtime
        uniformity seam has DECLARED the divergence risk — the runtime
        tier owns it from there."""
        got = run_div("""
            from apex_tpu.resilience.uniformity import assert_uniform

            def maybe_probe(x):
                probe = jax.process_index() == 0
                assert_uniform("probe.rank0", bool(probe))
                if probe:
                    return step(x)
                return x
            """, tmp_path, [TaintedPredicateGuardsCollective()])
        assert got == []


# ------------------------------------------- APX210 tainted compiled shapes
class TestTaintedValueShapesCompiledProgram:
    def test_positive_rank_into_jit_static_arg(self, tmp_path):
        got = run("""
            import jax

            def f(x, variant):
                return x * variant

            step = jax.jit(f, static_argnums=(1,))

            def launch(x):
                return step(x, jax.process_index())
            """, tmp_path, [TaintedValueShapesCompiledProgram()])
        assert rule_ids(got) == ["APX210"]
        assert "static argument" in got[0].message

    def test_positive_env_into_mesh_construction(self, tmp_path):
        got = run("""
            import os
            import jax
            from jax.sharding import Mesh

            def build():
                n = int(os.getenv("APEX_DP", "8"))
                return Mesh(jax.devices()[:n], ("dp",))
            """, tmp_path, [TaintedValueShapesCompiledProgram()])
        assert rule_ids(got) == ["APX210"]
        assert "mesh construction" in got[0].message

    def test_positive_env_into_bucket_plan_shape(self, tmp_path):
        got = run("""
            import os
            from apex_tpu.optimizers import bucketing

            def build(treedef, shapes):
                cap = int(os.getenv("APEX_CAP", "0")) or None
                return bucketing.plan_of_shapes(treedef, shapes,
                                                cap_bytes=cap)
            """, tmp_path, [TaintedValueShapesCompiledProgram()])
        assert rule_ids(got) == ["APX210"]
        assert "plan" in got[0].message

    def test_negative_threaded_config_is_clean(self, tmp_path):
        """Parameters are always clean: threading the value IN is the
        blessed pattern the fix hint prescribes."""
        got = run("""
            import jax
            from jax.sharding import Mesh

            def build(n, cap_bytes):
                return Mesh(jax.devices()[:n], ("dp",))

            def launch(step, x, variant):
                return step(x, variant)
            """, tmp_path, [TaintedValueShapesCompiledProgram()])
        assert got == []


# --------------------------------------- APX211 rank-divergent dispatch
class TestTaintedEngineDispatchDivergence:
    def test_positive_env_gated_kernel_impl(self, tmp_path):
        got = run("""
            import os
            import jax

            def n_shards():
                return jax.process_count()

            def forward(x):
                impl = os.getenv("APEX_ATTN", "auto")
                if impl == "pallas":
                    return pallas_attention(x)
                return xla_attention(x)
            """, tmp_path, [TaintedEngineDispatchDivergence()])
        assert rule_ids(got) == ["APX211"]
        assert "divergent SPMD programs" in got[0].message

    def test_negative_module_without_multiprocess_reach(self, tmp_path):
        """No mention of process_count: nothing scopes this module
        into multi-process reachability — single-host env dispatch is
        the supported configuration surface."""
        got = run("""
            import os

            def forward(x):
                impl = os.getenv("APEX_ATTN", "auto")
                if impl == "pallas":
                    return pallas_attention(x)
                return xla_attention(x)
            """, tmp_path, [TaintedEngineDispatchDivergence()])
        assert got == []

    def test_negative_acquitted_by_uniformity_seam(self, tmp_path):
        got = run("""
            import os
            import jax
            from apex_tpu.resilience.uniformity import assert_uniform

            def n_shards():
                return jax.process_count()

            def forward(x):
                impl = os.getenv("APEX_ATTN", "auto")
                assert_uniform("attn.impl", impl)
                if impl == "pallas":
                    return pallas_attention(x)
                return xla_attention(x)
            """, tmp_path, [TaintedEngineDispatchDivergence()])
        assert got == []

    def test_negative_registry_engaged_shape_stays_quiet(self, tmp_path):
        """The fail-fast spelling the repo itself uses: branch on the
        topology, return a constant — no dispatch in the branch."""
        got = run("""
            import jax

            def registry_engaged(forced):
                if jax.process_count() > 1:
                    return False
                return not forced
            """, tmp_path, [TaintedEngineDispatchDivergence()])
        assert got == []


# ------------------------------------------------ taint-lattice edge cases
class TestTaintLatticeEdgeCases:
    """The dataflow semantics the three rules rest on, probed directly
    through rule behavior: event ordering, aliasing, and the
    cross-module fixpoint (including cycles)."""

    def test_shadowed_rebind_inside_nested_function_is_clean(
            self, tmp_path):
        """A parameter shadows an outer tainted name — parameters are
        always clean, even when the caller passes rank in."""
        got = run_div("""
            rank = jax.process_index()

            def probe(rank, x):
                if rank == 0:
                    return step(x)
                return x
            """, tmp_path, [TaintedPredicateGuardsCollective()])
        assert got == []

    def test_outer_tainted_name_reaches_nested_function(self, tmp_path):
        """...but WITHOUT the shadowing parameter, the module-level
        tainted binding flows in through the enclosing scope."""
        got = run_div("""
            rank = jax.process_index()

            def probe(x):
                if rank == 0:
                    return step(x)
                return x
            """, tmp_path, [TaintedPredicateGuardsCollective()])
        assert rule_ids(got) == ["APX209"]

    def test_partial_of_clean_function_is_clean(self, tmp_path):
        got = run_div("""
            import functools

            def fixed():
                return 0

            who = functools.partial(fixed)

            def probe(x):
                if who() == 0:
                    return step(x)
                return x
            """, tmp_path, [TaintedPredicateGuardsCollective()])
        assert got == []

    def test_cross_module_taint_cycle_converges_and_flags(self, tmp_path):
        """Two modules whose taint-returning helpers call ACROSS the
        module boundary in a cycle: the link_taint fixpoint must
        terminate and still carry process_index's taint around the
        loop into the guarded launch."""
        from apex_tpu.analysis import analyze_paths

        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "ident.py").write_text(textwrap.dedent("""
            import jax

            from pkg.roles import role_of

            def rank():
                return jax.process_index()

            def rank_or_role(named):
                if named:
                    return role_of()
                return rank()
            """))
        (pkg / "roles.py").write_text(_STEP_PRELUDE + textwrap.dedent("""
            from pkg.ident import rank_or_role

            def role_of():
                return rank_or_role(False)

            def probe(x):
                if role_of() == 0:
                    return step(x)
                return x
            """))
        got = analyze_paths([str(pkg)],
                            [TaintedPredicateGuardsCollective()], {"dp"})
        assert rule_ids(got) == ["APX209"]
        assert got[0].path.endswith("roles.py")


# ---------------------------------------- CLI: --only-rules / --skip-rules
class TestCliRuleSelection:
    FIXTURE = textwrap.dedent("""
        import os
        import jax

        @jax.jit
        def f(x):
            return x if os.environ.get("FLAG") else -x
        """)

    def _run_cli(self, args, cwd):
        import os as _os

        env = dict(_os.environ, PYTHONPATH=str(REPO))
        return subprocess.run(
            [sys.executable, "-m", "apex_tpu.analysis", *args],
            cwd=str(cwd), env=env, capture_output=True, text=True,
            timeout=600)

    def test_only_rules_scopes_the_run(self, tmp_path):
        (tmp_path / "mod.py").write_text(self.FIXTURE)
        r = self._run_cli(["mod.py", "--no-baseline",
                           "--only-rules", "APX101"], tmp_path)
        assert r.returncode == 1 and "APX101" in r.stdout
        # scoped AWAY from the finding's rule: clean exit
        r = self._run_cli(["mod.py", "--no-baseline",
                           "--only-rules", "APX104"], tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_skip_rules_drops_the_finding(self, tmp_path):
        (tmp_path / "mod.py").write_text(self.FIXTURE)
        r = self._run_cli(["mod.py", "--no-baseline",
                           "--skip-rules", "APX101"], tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_unknown_rule_id_is_a_usage_error(self, tmp_path):
        (tmp_path / "mod.py").write_text(self.FIXTURE)
        for flag in ("--only-rules", "--skip-rules"):
            r = self._run_cli(["mod.py", flag, "APX999"], tmp_path)
            assert r.returncode == 2
            assert "unknown rule id" in r.stderr

    def test_selecting_everything_away_is_an_error(self, tmp_path):
        (tmp_path / "mod.py").write_text(self.FIXTURE)
        r = self._run_cli(["mod.py", "--only-rules", "APX101",
                           "--skip-rules", "APX101"], tmp_path)
        assert r.returncode == 2
        assert "nothing to run" in r.stderr

    def test_timing_json_artifact_and_family_rollup(self, tmp_path):
        (tmp_path / "mod.py").write_text(self.FIXTURE)
        out = tmp_path / "timing.json"
        r = self._run_cli(["mod.py", "--no-baseline", "--timing",
                           "--timing-json", str(out)], tmp_path)
        assert r.returncode == 1
        timings = json.loads(out.read_text())
        assert "<load>" in timings and "<link>" in timings
        assert "APX101" in timings
        assert "timing: family" in r.stderr
        assert "distributed" in r.stderr


# ----------------------------------------------- SARIF partialFingerprints
class TestSarifPartialFingerprints:
    SRC = textwrap.dedent("""
        import os
        import jax

        @jax.jit
        def f(x):
            return x if os.environ.get("FLAG") else -x
        """)

    def _fingerprints(self, tmp_path, src, name):
        p = tmp_path / name
        p.write_text(src)
        got = analyze_file(str(p), [TraceTimeHostStateRead()], set())
        log = sarif.render(got, [], [TraceTimeHostStateRead()])
        return [(r["partialFingerprints"]["apexContextHash/v1"],
                 r["locations"][0]["physicalLocation"]["region"]
                  ["startLine"]) for r in log["runs"][0]["results"]]

    def test_fingerprint_survives_line_shift(self, tmp_path):
        """The round-trip code scanning depends on: shifting a finding
        down the file (the every-commit event) keeps its fingerprint —
        keying on the line would re-open the alert each time."""
        base = self._fingerprints(tmp_path, self.SRC, "a.py")
        shifted = self._fingerprints(
            tmp_path, "\n# padding\n# padding\n\n" + self.SRC, "a.py")
        (fp1, line1), (fp2, line2) = base[0], shifted[0]
        assert line2 > line1          # the finding really moved
        assert fp1 == fp2             # ...and the identity did not

    def test_distinct_findings_get_distinct_fingerprints(self, tmp_path):
        fps = self._fingerprints(tmp_path, self.SRC + textwrap.dedent("""
            @jax.jit
            def g(x):
                return x if os.environ.get("OTHER") else -x
            """), "b.py")
        assert len(fps) == 2
        assert fps[0][0] != fps[1][0]


# -------------------------------------- APX114 thread-unsafe shared writes
class TestSharedMutationWithoutLock:
    def test_positive_thread_target_mutates_locked_attr(self, tmp_path):
        got = run("""
            import threading

            class Acc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._tokens = 0
                    threading.Thread(target=self._persist).start()

                def add(self, n):
                    with self._lock:
                        self._tokens += n

                def _persist(self):
                    self._tokens = 0
            """, tmp_path, [SharedMutationWithoutLock()])
        assert rule_ids(got) == ["APX114"]
        assert "_tokens" in got[0].message
        assert "Acc.add" in got[0].message       # the locked other site
        assert "_persist" in got[0].symbol

    def test_positive_prefix_goodput_accountant_shape(self, tmp_path):
        """The literal PR 10 review finding, as a regression fixture:
        the main-thread mutators take ``self._lock``, but ``finalize``
        — reachable from the watchdog's ``on_wedge=`` callback seam,
        i.e. the monitor thread — writes the same accumulators bare.
        The rule must flag the pre-fix spelling forever (the post-fix
        live tree stays clean via TestRepoIsClean)."""
        got = run("""
            import threading

            class StepWatchdog:
                def check(self):
                    pass

            class GoodputAccountant:
                def __init__(self, path):
                    self._lock = threading.RLock()
                    self._path = path
                    self._productive_s = 0.0
                    self._lost_s = 0.0
                    self._events = []

                def record_step(self, seconds):
                    with self._lock:
                        self._productive_s += seconds
                        self._persist()

                def record_loss(self, seconds, why):
                    with self._lock:
                        self._lost_s += seconds
                        self._events.append(why)
                        self._persist()

                def _persist(self):
                    pass

                def finalize(self, why):
                    # pre-fix: no lock — but this runs on the WATCHDOG
                    # thread via on_wedge while record_step runs on main
                    self._lost_s += 1.0
                    self._events.append(why)
                    self._persist()

            def install(acc):
                wd = StepWatchdog()
                wd.on_wedge = lambda info: acc.finalize("wedge")
                threading.Thread(target=wd.check).start()
                return wd
            """, tmp_path, [SharedMutationWithoutLock()])
        assert "APX114" in rule_ids(got)
        assert any("finalize" in f.symbol for f in got)

    def test_positive_prefix_flightrec_dump_shape(self, tmp_path):
        """The PR 14 review finding: ``record_event`` appends to the
        ring under ``self._lock`` on the main thread, while the dump
        path — reached from the watchdog's ``on_wedge`` — drained the
        same ring with NO lock (the dump-vs-checkpoint torn-read/lost-
        event race, fixed by copying under the lock in ``snapshot``)."""
        got = run("""
            import threading

            class FlightRecorder:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._events = []

                def record_event(self, e):
                    with self._lock:
                        self._events.append(e)

                def dump(self, reason):
                    # pre-fix: read+clear outside the lock, on the
                    # watchdog thread, racing main-thread record_event
                    rec = list(self._events)
                    self._events.clear()
                    return rec

            def install(rec, watchdog):
                watchdog.arm(on_wedge=lambda info: rec.dump("wedge"))
            """, tmp_path, [SharedMutationWithoutLock()])
        assert "APX114" in rule_ids(got)
        assert any("dump" in f.symbol for f in got)

    def test_positive_cross_module_thread_target(self, tmp_path):
        """The thread entry lives in ANOTHER module: main.py starts a
        Thread on worker.Acc._persist's bound method via the instance
        it builds — the link_threads fixpoint must carry thread-
        reachability across the import edge."""
        (tmp_path / "worker.py").write_text(textwrap.dedent("""
            import threading

            class Acc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def add(self):
                    with self._lock:
                        self._n += 1

                def spill(self):
                    self._n = 0
            """))
        (tmp_path / "main.py").write_text(textwrap.dedent("""
            import threading
            from worker import Acc

            def launch():
                acc = Acc()
                threading.Thread(target=acc.spill).start()
            """))
        got = analyze_paths([str(tmp_path / "worker.py"),
                             str(tmp_path / "main.py")],
                            [SharedMutationWithoutLock()], set(AXES))
        assert "APX114" in rule_ids(got)

    def test_negative_all_sites_locked(self, tmp_path):
        got = run("""
            import threading

            class Acc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._tokens = 0
                    threading.Thread(target=self._persist).start()

                def add(self, n):
                    with self._lock:
                        self._tokens += n

                def _persist(self):
                    with self._lock:
                        self._tokens = 0
            """, tmp_path, [SharedMutationWithoutLock()])
        assert got == []

    def test_negative_no_lock_discipline_declared(self, tmp_path):
        """A class with NO locked site for the attribute is a design
        choice (maybe GIL-atomic, maybe wrong — but there is no
        declared discipline being violated): quiet."""
        got = run("""
            import threading

            class Flag:
                def __init__(self):
                    self.hit = False
                    threading.Thread(target=self._mark).start()

                def _mark(self):
                    self.hit = True
            """, tmp_path, [SharedMutationWithoutLock()])
        assert got == []

    def test_negative_acquitted_by_assert_lock_held(self, tmp_path):
        """The assert_lock_held seam: the mutator's contract is "my
        caller holds the lock", checked at runtime — acquitted."""
        got = run("""
            import threading
            from apex_tpu.resilience.locks import assert_lock_held

            class Acc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._tokens = 0
                    threading.Thread(target=self._persist).start()

                def add(self, n):
                    with self._lock:
                        self._tokens += n

                def _persist(self):
                    assert_lock_held(self._lock)
                    self._tokens = 0
            """, tmp_path, [SharedMutationWithoutLock()])
        assert got == []

    def test_negative_acquire_release_pairing_counts_as_locked(
            self, tmp_path):
        got = run("""
            import threading

            class Acc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._tokens = 0
                    threading.Thread(target=self._persist).start()

                def add(self, n):
                    with self._lock:
                        self._tokens += n

                def _persist(self):
                    self._lock.acquire()
                    try:
                        self._tokens = 0
                    finally:
                        self._lock.release()
            """, tmp_path, [SharedMutationWithoutLock()])
        assert got == []

    def test_negative_main_thread_only_class(self, tmp_path):
        """No thread entry anywhere in the module: quiet even with
        asymmetric locking (single-threaded code may lock for re-use
        from threaded callers it does not itself create)."""
        got = run("""
            import threading

            class Acc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._tokens = 0

                def add(self, n):
                    with self._lock:
                        self._tokens += n

                def reset(self):
                    self._tokens = 0
            """, tmp_path, [SharedMutationWithoutLock()])
        assert got == []


# ------------------------------------------- APX115 lock-order inversions
class TestLockOrderInversion:
    def test_positive_abba_names_both_sites(self, tmp_path):
        got = run("""
            import threading
            A = threading.Lock()
            B = threading.Lock()

            def forward():
                with A:
                    with B:
                        pass

            def backward():
                with B:
                    with A:
                        pass
            """, tmp_path, [LockOrderInversion()])
        assert rule_ids(got) == ["APX115"]
        msg = got[0].message
        assert "`A`" in msg and "`B`" in msg
        assert "backward" in msg or "forward" in msg  # the other site

    def test_positive_inversion_through_helper_call(self, tmp_path):
        """One side never spells both with-statements: it calls a
        module-local helper whose body takes the second lock — the
        acquisition graph must follow the call edge."""
        got = run("""
            import threading

            class Pair:
                def __init__(self):
                    self._alock = threading.Lock()
                    self._block = threading.Lock()

                def _grab_a(self):
                    with self._alock:
                        return 1

                def one(self):
                    with self._block:
                        return self._grab_a()

                def two(self):
                    with self._alock:
                        with self._block:
                            return 2
            """, tmp_path, [LockOrderInversion()])
        assert rule_ids(got) == ["APX115"]

    def test_negative_consistent_order(self, tmp_path):
        got = run("""
            import threading
            A = threading.Lock()
            B = threading.Lock()

            def one():
                with A:
                    with B:
                        pass

            def two():
                with A:
                    with B:
                        pass
            """, tmp_path, [LockOrderInversion()])
        assert got == []

    def test_negative_rlock_reentry_is_not_a_cycle(self, tmp_path):
        got = run("""
            import threading

            class R:
                def __init__(self):
                    self._lock = threading.RLock()

                def outer(self):
                    with self._lock:
                        self.inner()

                def inner(self):
                    with self._lock:
                        pass
            """, tmp_path, [LockOrderInversion()])
        assert got == []


# --------------------------------- APX116 blocking under a contended lock
class TestBlockingCallUnderContendedLock:
    def test_positive_queue_get_under_signal_contended_lock(
            self, tmp_path):
        got = run("""
            import signal
            import threading

            class H:
                def __init__(self, q):
                    self._lock = threading.Lock()
                    self._q = q
                    signal.signal(signal.SIGTERM, self._on_sig)

                def _on_sig(self, signum, frame):
                    with self._lock:
                        pass

                def drain(self):
                    with self._lock:
                        return self._q.get()
            """, tmp_path, [BlockingCallUnderContendedLock()])
        assert rule_ids(got) == ["APX116"]
        assert "_on_sig" in got[0].message
        assert "signal" in got[0].message

    def test_positive_checkpoint_io_under_watchdog_callback_lock(
            self, tmp_path):
        got = run("""
            import threading

            def save_checkpoint(path, state):
                pass

            class Saver:
                def __init__(self, wd):
                    self._lock = threading.Lock()
                    self.state = {}
                    wd.arm(on_wedge=self._note)

                def _note(self, info):
                    with self._lock:
                        self.state["wedged"] = info

                def save(self, path):
                    with self._lock:
                        save_checkpoint(path, self.state)
            """, tmp_path, [BlockingCallUnderContendedLock()])
        assert rule_ids(got) == ["APX116"]

    def test_negative_timeout_bounded_wait(self, tmp_path):
        got = run("""
            import signal
            import threading

            class H:
                def __init__(self, q):
                    self._lock = threading.Lock()
                    self._q = q
                    signal.signal(signal.SIGTERM, self._on_sig)

                def _on_sig(self, signum, frame):
                    with self._lock:
                        pass

                def drain(self):
                    with self._lock:
                        return self._q.get(timeout=5.0)
            """, tmp_path, [BlockingCallUnderContendedLock()])
        assert got == []

    def test_negative_uncontended_lock_is_merely_slow(self, tmp_path):
        """Blocking under a lock NO async path acquires: not a
        deadlock, stays quiet."""
        got = run("""
            import threading

            class H:
                def __init__(self, q):
                    self._lock = threading.Lock()
                    self._q = q

                def drain(self):
                    with self._lock:
                        return self._q.get()
            """, tmp_path, [BlockingCallUnderContendedLock()])
        assert got == []

    def test_negative_dict_get_is_not_blocking(self, tmp_path):
        got = run("""
            import signal
            import threading

            class H:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._d = {}
                    signal.signal(signal.SIGTERM, self._on_sig)

                def _on_sig(self, signum, frame):
                    with self._lock:
                        pass

                def read(self, k):
                    with self._lock:
                        return self._d.get(k)
            """, tmp_path, [BlockingCallUnderContendedLock()])
        assert got == []

    def test_negative_acquitted_by_assert_lock_held(self, tmp_path):
        got = run("""
            import signal
            import threading
            from apex_tpu.resilience.locks import assert_lock_held

            class H:
                def __init__(self, q):
                    self._lock = threading.Lock()
                    self._q = q
                    signal.signal(signal.SIGTERM, self._on_sig)

                def _on_sig(self, signum, frame):
                    with self._lock:
                        pass

                def drain(self):
                    with self._lock:
                        assert_lock_held(self._lock)
                        return self._q.get()
            """, tmp_path, [BlockingCallUnderContendedLock()])
        assert got == []


# ------------------------------------------ concurrency-tier CLI plumbing
class TestConcurrencyTierCli:
    FIXTURE = textwrap.dedent("""
        import threading
        A = threading.Lock()
        B = threading.Lock()

        def one():
            with A:
                with B:
                    pass

        def two():
            with B:
                with A:
                    pass
        """)

    def _run_cli(self, args, cwd):
        import os as _os

        env = dict(_os.environ, PYTHONPATH=str(REPO))
        return subprocess.run(
            [sys.executable, "-m", "apex_tpu.analysis", *args],
            cwd=str(cwd), env=env, capture_output=True, text=True,
            timeout=600)

    def test_only_rules_scopes_to_the_concurrency_tier(self, tmp_path):
        (tmp_path / "mod.py").write_text(self.FIXTURE)
        r = self._run_cli(
            ["mod.py", "--no-baseline",
             "--only-rules", "APX114,APX115,APX116"], tmp_path)
        assert r.returncode == 1 and "APX115" in r.stdout
        r = self._run_cli(["mod.py", "--no-baseline",
                           "--only-rules", "APX101"], tmp_path)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_timing_rollup_has_a_concurrency_family(self, tmp_path):
        (tmp_path / "mod.py").write_text(self.FIXTURE)
        out = tmp_path / "timing.json"
        r = self._run_cli(["mod.py", "--no-baseline", "--timing",
                           "--timing-json", str(out)], tmp_path)
        assert r.returncode == 1
        timings = json.loads(out.read_text())
        for rid in ("APX114", "APX115", "APX116"):
            assert rid in timings
        assert "timing: family concurrency" in r.stderr
        # APX11x must NOT also be double-counted under trace/io
        concurrency = sum(timings[r] for r in
                          ("APX114", "APX115", "APX116"))
        assert concurrency >= 0.0
