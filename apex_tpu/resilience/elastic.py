"""Elastic fault-tolerant run control: cross-world resume + watchdog.

The resilience runtime (PR 2) survives preemptions and bad steps but
could only resume at the SAME world size, and a wedged collective hung
forever.  This module closes both gaps — the TorchTitan
production-readiness recipe (PAPERS.md, arxiv 2410.06511) on top of the
cross-replica-sharded state layout of arxiv 2004.13336:

- **Elastic checkpoints** (:func:`save_elastic_checkpoint` /
  :func:`restore_elastic_checkpoint`): one ``step_<N>/`` directory per
  save holding per-dp-rank shard files plus an ``index.json`` that
  records the saved world layout.  On restart the live world size is
  compared against the index; a ZeRO state saved at dp=4 reshards for
  dp=2 (or dp=8) through the ONE pad formula the bucket plan itself
  uses (:func:`apex_tpu.optimizers.bucketing.padded_total`, via
  :meth:`~apex_tpu.contrib.optimizers._zero_engine.ZeroOptimizerBase
  .load_sharded_state_dicts`) — m/v, fp32 masters or uint16
  remainders, and int8/fp8 error-feedback residuals all reshard;
  params, loss-scaler state, StepGuard counts, and the RNG tracker are
  dp-replicated and ride rank 0's shard.  Only the data axis is
  elastic: the model layout (tp/pp) is part of the state's shape and a
  mismatch fails loudly.
- **Step watchdog** (:class:`StepWatchdog`): a heartbeat thread that
  notices a step exceeding its deadline (wedged collective, hung
  Pallas compile), emits a structured
  ``watchdog.step_wedged`` record, drains the async checkpointer (so
  every ACCEPTED save is durable — the wedged step itself is lost by
  definition), and exits with :data:`EXIT_WEDGED` so a supervisor
  restarts with backoff (:func:`restart_backoff`).
- **Run controller** (:class:`ElasticRunController`): the loop-facing
  composition — restore-or-fresh, per-step heartbeat + chaos delivery
  (per-rank kill plans, wedged steps), bounded-disk saves.

Exit-code contract (what a supervisor keys restart policy on)::

    0            clean finish, or preemption save+drain (resume freely)
    EXIT_WEDGED  (75, EX_TEMPFAIL) watchdog killed a wedged step —
                 restart with backoff; the run resumes elastically
    EXIT_KILLED  (137, 128+SIGKILL) chaos hard-kill stand-in — the
                 supervisor restarts the survivors at the smaller world
    anything else: a real crash; do not blindly restart
"""

import os
import threading
import time
from typing import Any, Dict, Mapping, NamedTuple, Optional

import numpy as np

from apex_tpu.observability import flightrec as _flightrec
from apex_tpu.observability import metrics as _metrics
from apex_tpu.utils.logging import get_logger, log_structured

import logging

__all__ = [
    "EXIT_KILLED", "EXIT_WEDGED", "ElasticRestore", "ElasticRunController",
    "StepWatchdog", "restart_backoff", "restore_elastic_checkpoint",
    "save_elastic_checkpoint",
]

_logger = get_logger("apex_tpu.resilience")

#: sysexits EX_TEMPFAIL: "temporary failure, retry later" — the
#: watchdog's exit code.  Distinct from 0 (clean/preempted) and from
#: Python's generic 1 so a supervisor can apply restart-with-backoff to
#: exactly the wedged-step case.
EXIT_WEDGED = 75

#: 128+SIGKILL — what a hard-killed process reports; the chaos
#: harness's :class:`~apex_tpu.resilience.chaos.ChaosHostKilled` carries
#: it so the simulated death is indistinguishable to a supervisor.
EXIT_KILLED = 137


def restart_backoff(attempt: int, base: float = 2.0, cap: float = 300.0,
                    seed: int = 0, rng=None) -> float:
    """The documented supervisor backoff contract: full-jitter
    exponential — attempt ``k`` sleeps ``uniform(0, min(cap, base·2^k))``
    seconds.  Deterministic per ``(seed, attempt)`` so the chaos matrix
    can assert the schedule; a real supervisor seeds per host (rank) so
    a pod's restarts don't re-land in lockstep.

    ``rng`` (anything with ``uniform(a, b)``) overrides the per-(seed,
    attempt) derivation — the :class:`~apex_tpu.resilience.supervisor
    .Supervisor` tests pin exact jittered delays through it; when
    omitted the historical seeded behavior is unchanged."""
    import random

    if attempt < 0:
        raise ValueError(f"attempt must be >= 0, got {attempt}")
    hi = min(float(cap), float(base) * (2.0 ** int(attempt)))
    if rng is None:
        # int seed (not a tuple): tuple seeding is hash-based + deprecated
        rng = random.Random(int(seed) * 1000003 + int(attempt))
    return rng.uniform(0.0, hi)


# ---------------------------------------------------------- step watchdog
class StepWatchdog:
    """Heartbeat-deadline watchdog for the training loop.

    The loop calls :meth:`beat` once per step (host-side, a couple of
    float stores).  A background thread checks the time since the last
    beat; past ``deadline_sec`` the step is declared WEDGED: one
    structured ``watchdog.step_wedged`` record, a bounded drain of the
    async checkpointer (``drain_timeout_sec`` — the wedged thing may BE
    the filesystem), then ``os._exit(exit_code)`` so the supervisor
    restarts with backoff.  ``os._exit`` (not ``sys.exit``): the main
    thread is blocked inside a C dispatch holding the GIL-adjacent
    runtime; only a hard exit reliably escapes a wedged collective.

    ``first_deadline_sec`` covers the first interval (jit compiles make
    step 0 legitimately slow); defaults to ``deadline_sec``.
    ``on_fire`` replaces the exit for tests: called with the fire-info
    dict instead of terminating.  ``on_wedge`` is a best-effort
    pre-exit hook called with the fire-info dict BEFORE the drain (the
    goodput accountant's ``finalize("wedge")`` rides it, so the wedged
    tail is attributable after the ``os._exit``); its failures are
    swallowed — the watchdog must still exit.  ``preemption`` (a
    :class:`~apex_tpu.resilience.preemption.PreemptionHandler`) routes
    the drain through its re-entrancy guard so a watchdog firing while
    the loop already drains cannot double-enter the flush.
    """

    def __init__(self, deadline_sec: float, checkpointer=None,
                 exit_code: int = EXIT_WEDGED, poll_sec: Optional[float] = None,
                 first_deadline_sec: Optional[float] = None,
                 drain_timeout_sec: float = 60.0, on_fire=None,
                 preemption=None, on_wedge=None):
        if deadline_sec <= 0:
            raise ValueError(f"deadline_sec must be > 0, got {deadline_sec}")
        self.deadline_sec = float(deadline_sec)
        self.first_deadline_sec = float(
            first_deadline_sec if first_deadline_sec is not None
            else deadline_sec)
        self.exit_code = int(exit_code)
        self._checkpointer = checkpointer
        self._preemption = preemption
        self._drain_timeout = float(drain_timeout_sec)
        self._on_fire = on_fire
        self.on_wedge = on_wedge
        self._poll = float(poll_sec) if poll_sec is not None else min(
            1.0, self.deadline_sec / 4.0)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last_beat: Optional[float] = None
        self._armed_at: Optional[float] = None
        self._step: Optional[int] = None
        self._interval_deadline = self.deadline_sec
        self.fired = False
        self.fire_info: Optional[dict] = None

    # ------------------------------------------------------- lifecycle
    def start(self) -> "StepWatchdog":
        if self._thread is not None:
            return self
        self._stop.clear()
        self._armed_at = time.monotonic()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="apex_tpu-step-watchdog")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=max(2.0, 4 * self._poll))
        self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # ------------------------------------------------------- heartbeat
    def beat(self, step: Optional[int] = None,
             deadline: Optional[float] = None) -> None:
        """Record progress: the loop reached (the top of) ``step``.
        ``deadline`` overrides the allowance for THIS interval only —
        the loop grants the first step its jit-compile grace
        (``watchdog.beat(0, deadline=compile_grace)``) without
        loosening the steady-state deadline."""
        with self._lock:
            self._last_beat = time.monotonic()
            self._interval_deadline = (float(deadline) if deadline is not None
                                       else self.deadline_sec)
            if step is not None:
                self._step = int(step)

    # --------------------------------------------------------- monitor
    def _expired(self, now: float):
        with self._lock:
            last, step = self._last_beat, self._step
            interval = self._interval_deadline
        if last is None:
            # never beaten: the first interval covers startup + compile
            start = self._armed_at if self._armed_at is not None else now
            elapsed, deadline = now - start, self.first_deadline_sec
        else:
            elapsed, deadline = now - last, interval
        return (elapsed, deadline, step) if elapsed >= deadline else None

    def _run(self) -> None:
        while not self._stop.wait(self._poll):
            hit = self._expired(time.monotonic())
            if hit is None:
                continue
            elapsed, deadline, step = hit
            self._fire(elapsed, deadline, step)
            return

    def _drain_bounded(self) -> str:
        """Drain the async checkpointer from a helper thread with a
        timeout: the wedge may be the filesystem itself, and a watchdog
        that hangs in its own cleanup protects nothing."""
        if self._checkpointer is None:
            return "no_checkpointer"
        done = threading.Event()
        outcome = {"state": "drain_timeout"}

        def flush():
            try:
                if self._preemption is not None:
                    self._preemption.drain(self._checkpointer)
                else:
                    self._checkpointer.wait_until_finished()
                outcome["state"] = "drained"
            except BaseException as e:  # noqa: BLE001 — report, then exit anyway
                outcome["state"] = f"drain_error:{type(e).__name__}"
            finally:
                done.set()

        threading.Thread(target=flush, daemon=True,
                         name="apex_tpu-watchdog-drain").start()
        done.wait(self._drain_timeout)
        return outcome["state"]

    def _fire(self, elapsed: float, deadline: float,
              step: Optional[int]) -> None:
        info = {"step": step, "elapsed_s": round(elapsed, 3),
                "deadline_s": deadline, "exit_code": self.exit_code}
        log_structured(_logger, logging.ERROR, "watchdog.step_wedged",
                       **info)
        # two SEPARATE best-effort blocks: a metrics registration clash
        # must not also rob the goodput accountant of its wedge stamp
        # (the attribution the report exists to make)
        _metrics.inc("apex_watchdog_wedges_total",
                     help="steps the watchdog declared wedged")
        try:
            if self.on_wedge is not None:
                self.on_wedge(info)
        except Exception as e:  # noqa: BLE001 — the hook is best-effort;
            # the watchdog's one job is to exit, so a broken accountant
            # must never wedge the wedge handler
            log_structured(_logger, logging.WARNING,
                           "watchdog.on_wedge_failed",
                           error=f"{type(e).__name__}: {e}")
        # flight-recorder dump AFTER the on_wedge hook (so the hook's
        # own records — the goodput wedge stamp, the forced anomaly
        # alert — are IN the dump) and BEFORE the drain (the wedged
        # thing may be the filesystem the drain is about to wait on).
        # dump_active is best-effort and a no-op without a recorder.
        info["flight_dump"] = _flightrec.dump_active(
            "wedge", wedged_step=step,
            elapsed_s=info["elapsed_s"], deadline_s=deadline)
        info["drain"] = self._drain_bounded()
        log_structured(_logger, logging.ERROR, "watchdog.exiting",
                       **info)
        self.fired = True
        self.fire_info = info
        if self._on_fire is not None:
            self._on_fire(info)
            return
        os._exit(self.exit_code)


# ------------------------------------------------------ elastic checkpoints
#: index.json metadata kinds — which restore path owns the state
ELASTIC_KIND_ZERO = "zero2"
ELASTIC_KIND_REPLICATED = "replicated"


class ElasticRestore(NamedTuple):
    """What :func:`restore_elastic_checkpoint` hands the loop."""

    step: int                    # steps already taken (resume here)
    params: Any
    opt_state: Any               # resharded for the LIVE world
    scaler: Optional[dict]       # DynamicLossScaler.state_dict payload
    guard: Optional[dict]        # StepGuard.state_dict payload
    rng: Optional[dict]          # rng_tracker_state_dict payload
    saved_world: int             # dp world the checkpoint was written at
    resharded: bool              # saved_world != live world


def _is_zero(optimizer) -> bool:
    return hasattr(optimizer, "sharded_state_dict")


def _step_dir(dir_path, step: int):
    from pathlib import Path

    return Path(dir_path) / f"step_{int(step):08d}"


def save_elastic_checkpoint(dir_path, step: int, *, params, opt_state,
                            optimizer, world_size: int,
                            mesh_axes: Optional[Mapping[str, int]] = None,
                            scaler_state: Optional[dict] = None,
                            guard_state: Optional[dict] = None,
                            rng_state: Optional[dict] = None,
                            checkpointer=None) -> str:
    """Publish the FULL train state as an elastic ``step_<N>/`` dir.

    ZeRO optimizers write one shard file per dp rank
    (:meth:`sharded_state_dict` slices the resident bucket state);
    replicated optimizers write a single world-size-1 shard (their
    state is dp-invariant — elastic by construction).  Rank 0's shard
    additionally carries the dp-replicated pieces: params, the step
    counter, loss-scaler / StepGuard / RNG-tracker state dicts.  The
    ``index.json`` (written FIRST — an interrupted save leaves an
    incomplete dir that ``latest_distributed_step`` skips as torn)
    records the world layout under the ``"elastic"`` key.

    ``scaler_state``/``guard_state``/``rng_state`` are the PLAIN DICTS
    from the owners' ``state_dict()`` methods, not live objects.  With
    a ``checkpointer`` (:class:`apex_tpu.io.AsyncCheckpointer`) shard
    writes are queued after a synchronous host snapshot; otherwise the
    write is synchronous.  Returns the step dir path."""
    from apex_tpu import io
    from apex_tpu.io.checkpoint import _shard_name, _write_index

    zero = _is_zero(optimizer)
    world = int(world_size) if zero else 1
    sd = _step_dir(dir_path, step)
    meta = {"elastic": {
        "kind": ELASTIC_KIND_ZERO if zero else ELASTIC_KIND_REPLICATED,
        "step": int(step),
        "dp_world": world,
        "mesh_axes": {k: int(v) for k, v in (mesh_axes or {}).items()},
    }}

    def rank_tree(r: int) -> Dict[str, Any]:
        if zero:
            tree: Dict[str, Any] = {
                "opt": optimizer.sharded_state_dict(opt_state, r, world)}
        else:
            tree = {"opt": opt_state if r == 0 else None}
        if r == 0:
            tree.update({
                "params": params,
                "step": np.int64(step),
                "scaler": scaler_state,
                "guard": guard_state,
                "rng": rng_state,
            })
        return tree

    if checkpointer is not None:
        # index first (synchronous, tiny) so a crash mid-queue leaves an
        # incomplete dir, then the shard snapshots ride the async queue
        _write_index(sd, world, extra=meta)
        for r in range(world):
            checkpointer.save(sd / _shard_name(r, world), rank_tree(r))
    else:
        for r in range(world):
            io.save_sharded_checkpoint(sd, rank_tree(r), r, world,
                                       index_extra=meta)
    log_structured(_logger, logging.INFO, "elastic.saved", step=int(step),
                   dp_world=world, path=str(sd))
    return str(sd)


def restore_elastic_checkpoint(dir_path, *, optimizer, world_size: int,
                               mesh_axes: Optional[Mapping[str, int]] = None,
                               step: Optional[int] = None
                               ) -> Optional[ElasticRestore]:
    """Resume the full train state from the newest complete elastic
    ``step_<N>/`` dir, RESHARDING for the live ``world_size`` when it
    differs from the saved one.

    Returns ``None`` when no ``step_*`` dirs exist (a legitimate fresh
    start) and propagates :class:`apex_tpu.io.AllCheckpointsTornError`
    when dirs exist but none is complete.  Fails loudly on a model-
    layout change (``mesh_axes`` vs the saved record — only the dp axis
    is elastic), on a replicated/ZeRO kind mismatch, and on the ZeRO
    engine's own state-compat checks (master precision, residual kind,
    incomplete shard sets).  ZeRO resharding routes through
    ``load_sharded_state_dicts`` — the one
    :func:`~apex_tpu.optimizers.bucketing.padded_total` pad formula —
    so a same-world resume is bitwise and a cross-world resume is
    payload-exact with re-derived padding."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import io

    if step is None:
        step = io.latest_distributed_step(dir_path)
        if step < 0:
            return None
    sd = _step_dir(dir_path, step)
    index = io.read_index(sd)
    meta = index.get("elastic")
    if meta is None:
        raise ValueError(
            f"{sd} is a sharded checkpoint but not an ELASTIC one (no "
            "'elastic' index metadata): it cannot be world-size-checked "
            "— load it with io.load_sharded_checkpoint directly")
    zero = _is_zero(optimizer)
    want_kind = ELASTIC_KIND_ZERO if zero else ELASTIC_KIND_REPLICATED
    if meta.get("kind") != want_kind:
        raise ValueError(
            f"elastic checkpoint kind {meta.get('kind')!r} does not match "
            f"this optimizer ({want_kind!r}): a replicated state cannot "
            "restore into a ZeRO optimizer or vice versa — construct the "
            "matching optimizer (the --zero flag must agree between save "
            "and resume)")
    saved_axes = {k: int(v) for k, v in (meta.get("mesh_axes") or {}).items()}
    live_axes = {k: int(v) for k, v in (mesh_axes or {}).items()}
    if saved_axes != live_axes:
        raise ValueError(
            f"elastic resume is data-parallel-only: checkpoint was saved "
            f"with model axes {saved_axes} but the live mesh has "
            f"{live_axes} — tp/pp reshape is a state-layout change this "
            "controller refuses to guess at")
    saved_world = int(meta.get("dp_world", index["world_size"]))
    shards = io.load_sharded_checkpoint(sd)
    r0 = shards[0]
    if zero:
        opt_world = getattr(optimizer, "world_size", None)
        if opt_world is not None and int(opt_world) != int(world_size):
            raise ValueError(
                f"optimizer was init'd for dp={opt_world} but the live "
                f"world is {world_size}: call init(params, world_size="
                f"{world_size}, ...) before restore so the bucket plan "
                "matches the resharded state")
        opt_state = type(optimizer).load_sharded_state_dicts(
            [d["opt"] for d in shards], world_size=int(world_size),
            store_param_remainders=optimizer.store_param_remainders,
            grad_sync_dtype=optimizer.grad_sync_dtype)
    else:
        opt_state = jax.tree.map(jnp.asarray, r0["opt"])
    params = jax.tree.map(jnp.asarray, r0["params"])
    resharded = zero and saved_world != int(world_size)
    log_structured(_logger, logging.INFO, "elastic.restored",
                   step=int(step), saved_world=saved_world,
                   live_world=int(world_size), resharded=resharded,
                   path=str(sd))
    return ElasticRestore(
        step=int(np.asarray(r0["step"])),
        params=params, opt_state=opt_state,
        scaler=r0.get("scaler"), guard=r0.get("guard"), rng=r0.get("rng"),
        saved_world=saved_world, resharded=resharded)


# ---------------------------------------------------------- run controller
class ElasticRunController:
    """Loop-facing composition of elastic checkpoints, the step
    watchdog, and the chaos pod faults.

    Usage (see ``examples/gpt/pretrain_gpt.py`` and
    ``tests/test_elastic.py``)::

        ctl = ElasticRunController(ckdir, optimizer, world_size=dp,
                                   mesh_axes={"tp": tp}, checkpointer=ckpt,
                                   watchdog=StepWatchdog(60, ckpt))
        restored = ctl.restore()          # None on a fresh start
        with ctl:                         # arms the watchdog
            for step in range(start, end):
                ctl.on_step(step)         # heartbeat + chaos delivery
                ...train...
                ctl.save(step + 1, params, state, ...)   # bounded disk

    ``rank`` is this host's index for the per-rank chaos kill plans —
    on a real pod ``jax.process_index()``, in the CPU matrix whatever
    simulated host the test is playing."""

    def __init__(self, checkpoint_dir, optimizer, world_size: int,
                 mesh_axes: Optional[Mapping[str, int]] = None,
                 checkpointer=None, watchdog: Optional[StepWatchdog] = None,
                 keep: int = 3, chaos=None, rank: int = 0):
        self.dir = checkpoint_dir
        self.optimizer = optimizer
        self.world_size = int(world_size)
        self.mesh_axes = dict(mesh_axes or {})
        self.checkpointer = checkpointer
        self.watchdog = watchdog
        self.keep = max(int(keep), 1)
        self.chaos = chaos
        self.rank = int(rank)

    # ------------------------------------------------------- lifecycle
    def __enter__(self):
        if self.watchdog is not None:
            self.watchdog.start()
        return self

    def __exit__(self, *exc):
        if self.watchdog is not None:
            self.watchdog.stop()
        return False

    # ------------------------------------------------------------ loop
    def on_step(self, step: int, deadline: Optional[float] = None) -> None:
        """Top-of-iteration hook: heartbeat the watchdog (``deadline``
        overrides this interval's allowance — the first step's compile
        grace), then deliver any chaos faults planned for ``step`` (a
        wedged dispatch the watchdog should catch; a hard host kill)."""
        if self.watchdog is not None:
            self.watchdog.beat(step, deadline=deadline)
        if self.chaos is not None:
            self.chaos.maybe_wedge_step(step)
            self.chaos.maybe_kill(step, rank=self.rank)

    def restore(self) -> Optional[ElasticRestore]:
        return restore_elastic_checkpoint(
            self.dir, optimizer=self.optimizer, world_size=self.world_size,
            mesh_axes=self.mesh_axes)

    def save(self, step: int, params, opt_state, scaler_state=None,
             guard_state=None, rng_state=None) -> str:
        path = save_elastic_checkpoint(
            self.dir, step, params=params, opt_state=opt_state,
            optimizer=self.optimizer, world_size=self.world_size,
            mesh_axes=self.mesh_axes, scaler_state=scaler_state,
            guard_state=guard_state, rng_state=rng_state,
            checkpointer=self.checkpointer)
        self.prune()
        return path

    def prune(self) -> None:
        """Bounded disk: drop step dirs older than the newest ``keep``
        (min 3 when async — the queue holds ≤2 pending saves, so the 3
        newest can still be in flight; a prune can never race a
        write)."""
        import shutil
        from pathlib import Path

        keep = max(self.keep, 3) if self.checkpointer is not None \
            else self.keep
        old = sorted(Path(self.dir).glob("step_*"))
        for d in old[:-keep]:
            shutil.rmtree(d, ignore_errors=True)
