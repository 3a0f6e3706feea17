"""apex_tpu.analysis — JAX/Pallas-aware static linter for TPU hazards.

Catches, before code ever reaches the chip, the failure classes that
are silent and deferred on TPU (each found at least once by a human
reviewer in this repo's history — the rules scale those findings into
machine-checked invariants):

- **APX101/102** trace-time host-state capture and process-global env
  mutation (``rules_trace``) — the trace-time host-state class.
- **APX103** donated-buffer reuse: a ``donate_argnums`` argument read
  after the donating call without a rebind (``rules_donation``) — a
  no-op on CPU, garbage or a deleted-array error on TPU.
- **APX104** non-atomic checkpoint write (``rules_io``): a direct
  ``open(..., "wb")`` on a checkpoint path bypassing the
  ``io.native.atomic_output`` tmp+fsync+rename helper — the
  torn-write class ``io.validate_checkpoint`` exists to detect.
- **APX109** swallowed exception in a recovery path
  (``rules_resilience``): an ``except`` whose body is only
  ``pass``/``...`` inside resilience/io/inference modules — no
  re-raise, no ``log_structured``, no metrics record, so the failure
  is invisible to the supervisor and the postmortem.
- **APX113** retry without backoff (``rules_resilience``): a
  ``while True:`` in the same recovery-path modules whose ``try``
  swallows the failure and re-attempts with no sleep/backoff/wait
  anywhere in the loop — a persistent fault becomes a busy-spin
  against the dependency that needs room to recover (the serving
  fleet's typed ``Overloaded.retry_after_s`` is the paced spelling).
- **APX201/202** collective-axis consistency against the
  ``parallel_state.py`` mesh registry (``rules_collectives``).
- **APX203/204/205** axis-scope dataflow (``dataflow`` + ``rules_collectives``):
  a registered-axis collective reachable only from ``jit``/``pjit``
  (no axis bound), or under a ``shard_map`` nest that binds only OTHER
  axes.
- **APX209/210/211** multi-process divergence (``rules_divergence`` +
  the ``dataflow`` host-divergence taint lattice): a rank-divergent
  predicate (``process_index``, env/hostname/clock/RNG/filesystem
  reads, per-rank branches) guarding the launch of a collective-
  bearing traced step (a static pod-deadlock proof), a rank-divergent
  value baked into a jit static arg / ``Mesh`` / bucket plan
  (divergent compiled programs), and rank-divergent engine/fallback
  dispatch in multi-process-aware code (the ``registry_engaged``
  class, generalized).  Acquittal seam:
  ``apex_tpu.resilience.uniformity.assert_uniform``.
- **APX206/207/208** sharding-annotation consistency
  (``rules_sharding`` — the GSPMD tier): a ``PartitionSpec`` axis no
  reaching mesh binds (a ``with_sharding_constraint`` from a STALE
  mesh object compiles and silently replicates; a typo'd axis against
  the annotation's own mesh raises only when the TPU-gated builder
  first runs — on the chip), a spec provably longer than the annotated
  array's rank, and a donated jit argument whose in/out shardings can
  never alias (XLA drops the donation with only a UserWarning).
- **APX301/302** Mosaic dtype-dependent tiling contracts for Pallas
  block shapes (``rules_tiling``) — the ``_ceil_block(..., 8)``-on-bf16
  class.
- **APX303** scratch/accumulator dtype narrower than the dot's
  ``preferred_element_type`` (``rules_precision`` + the ``dataflow``
  dtype lattice) — fp32 accumulation silently re-rounded to bf16.
- **APX304** provable per-``pallas_call`` VMEM footprint over budget
  (``rules_tiling``, warning).
- **APX305** quantized-sync state dtype (``rules_precision`` + the
  dtype lattice): in int8/fp8 wire-cast code, a ``scale`` buffer
  narrower than fp32 or a ``residual`` buffer at wire width — the
  compressed-grad-sync contract of
  ``contrib.optimizers._quantized_sync``.
- **APX401/402** indexing/precision hygiene: unclamped vocab gathers
  and fp32 constants in bf16 paths (``rules_precision``) — the
  ``gpt.py:447`` class.
- **APX107/306** decode-path hygiene (``rules_precision``): a
  page-table ``take``/subscript gather with no clamp (the APX401
  family extended to the serving path's mutable page indirection),
  and a KV-cache buffer provably narrower than the
  ``preferred_element_type`` of a dot it feeds with no explicit widen
  at the read (the ``inference.kv_cache`` storage-dtype contract).
- **APX110** kv/pool scatter bypassing the allocator/clamp seam
  (``rules_inference``): an ``.at[...].set`` into a pool-named buffer
  whose page index is neither clamped/garbage-routed device data nor
  an allocator-normalized host int — with refcounted prefix-shared
  pages, a write the copy-on-write pass cannot see mutates pages OTHER
  sequences still read.
- **APX108** blocking host sync in a step loop (``rules_host_sync``):
  ``float()``/``.item()``/``np.asarray``/f-string formatting of a
  proven device array inside a ``for``/``while`` loop that dispatches
  a compiled step — the per-step sync barrier
  ``apex_tpu.observability.stepstats`` (the allowed async-fetch
  spelling) exists to remove.
- **APX114/115/116** host-concurrency races (``rules_threading`` +
  the ``dataflow.ThreadIndex`` thread-reachability fixpoint): a
  shared attribute mutated lock-free from a thread-reachable method
  while another site holds the lock (the GoodputAccountant persist
  race), a lock-order inversion in the static acquisition graph
  (ABBA deadlock naming both sites), and a timeout-less blocking
  call under a lock a signal-/watchdog-reachable path also acquires
  (the drain-deadlock class).  Acquittal seam:
  ``apex_tpu.resilience.locks.assert_lock_held``; runtime sanitizer:
  ``instrument_locks()``.
- **APX112** unseamed dispatch timing (``rules_host_sync``): a
  ``time.time()``/``perf_counter()``/``monotonic()`` delta spanning a
  proven step dispatch with no ``block_until_ready``/host-read/
  async-fetch seam in between — async dispatch makes the delta an
  enqueue time, not a step time (host-side tracing spans say so
  explicitly: see ``apex_tpu.observability.tracing``).

CLI: ``python -m apex_tpu.analysis [paths] [--baseline FILE]`` — see
``docs/static_analysis.md`` for rule details, the baseline format, and
how to add a rule.  This package imports NO jax: it must run in
containers where jax is broken and over trees that do not import.
(The jax-importing lowered-artifact tier lives in
``apex_tpu.analysis.lowered`` and is deliberately NOT imported here —
``import apex_tpu.analysis.lowered`` is an explicit, test-suite-side
opt-in.)
"""

from apex_tpu.analysis.baseline import (
    BaselineEntry, BaselineError, apply_baseline, load_baseline,
    write_baseline,
)
from apex_tpu.analysis.core import (
    Finding, ModuleContext, Rule, analyze_file, analyze_paths,
    discover_axis_registry,
)
from apex_tpu.analysis.rules_collectives import (
    CollectiveAxisOutsideShardMapNest, CollectiveAxisUnboundUnderJit,
    CollectiveOutsideSpmdContext, CollectiveTupleAxisUnbound,
    UnknownCollectiveAxis,
)
from apex_tpu.analysis.rules_divergence import (
    TaintedEngineDispatchDivergence, TaintedPredicateGuardsCollective,
    TaintedValueShapesCompiledProgram,
)
from apex_tpu.analysis.rules_donation import DonatedBufferReuse
from apex_tpu.analysis.rules_sharding import (
    DonatedShardingMismatch, ShardingSpecAxisUnbound,
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
    Fp32ConstantInBf16Path, KvCacheReadDtypeMismatch,
    PageTableGatherUnclamped, QuantizedSyncStateDtype,
    ScratchAccumDtypeMismatch, UnclampedTakeAlongAxis,
)
from apex_tpu.analysis.rules_threading import (
    BlockingCallUnderContendedLock, LockOrderInversion,
    SharedMutationWithoutLock,
)
from apex_tpu.analysis.rules_tiling import (
    BlockShapeTilingViolation, BlockSpecIndexMapArity,
    HardCodedSublaneAlignment, VmemFootprintOverBudget,
)
from apex_tpu.analysis.rules_trace import (
    ProcessGlobalEnvMutation, TraceTimeHostStateRead,
)


def default_rules(vmem_budget_bytes=None):
    """Every shipped rule, instantiated — the one place that knows the
    full set.  ``vmem_budget_bytes`` overrides APX304's 16 MiB default
    (the CLI's ``--vmem-budget-mib``)."""
    vmem = VmemFootprintOverBudget() if vmem_budget_bytes is None \
        else VmemFootprintOverBudget(budget_bytes=vmem_budget_bytes)
    return (
        TraceTimeHostStateRead(),
        ProcessGlobalEnvMutation(),
        DonatedBufferReuse(),
        NonAtomicCheckpointWrite(),
        SwallowedExceptionInRecoveryPath(),
        RetryWithoutBackoff(),
        BlockingHostSyncInStepLoop(),
        UnseamedDispatchTiming(),
        UnknownCollectiveAxis(),
        CollectiveOutsideSpmdContext(),
        CollectiveAxisUnboundUnderJit(),
        CollectiveAxisOutsideShardMapNest(),
        CollectiveTupleAxisUnbound(),
        ShardingSpecAxisUnbound(),
        ShardingSpecRankMismatch(),
        DonatedShardingMismatch(),
        TaintedPredicateGuardsCollective(),
        TaintedValueShapesCompiledProgram(),
        TaintedEngineDispatchDivergence(),
        BlockShapeTilingViolation(),
        BlockSpecIndexMapArity(),
        HardCodedSublaneAlignment(),
        vmem,
        ScratchAccumDtypeMismatch(),
        QuantizedSyncStateDtype(),
        KvCacheReadDtypeMismatch(),
        UnclampedTakeAlongAxis(),
        PageTableGatherUnclamped(),
        KvPoolScatterBypassesSeam(),
        Fp32ConstantInBf16Path(),
        SharedMutationWithoutLock(),
        LockOrderInversion(),
        BlockingCallUnderContendedLock(),
    )


#: The default instantiation — the CLI's and the test suite's single
#: source of truth for "what does a full run check".
DEFAULT_RULES = default_rules()

__all__ = [
    "BaselineEntry", "BaselineError", "DEFAULT_RULES", "Finding",
    "ModuleContext", "Rule", "analyze_file", "analyze_paths",
    "apply_baseline", "default_rules", "discover_axis_registry",
    "load_baseline", "write_baseline",
]
