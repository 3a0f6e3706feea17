"""Continuous-batching scheduler: admit, prefill, decode, evict.

The serving loop's control plane (Orca-style continuous batching): the
jitted decode step always runs at the STATIC ``max_batch`` shape, and
this scheduler fills its slots —

- **admit**: between decode steps, queued requests move into free slots
  FIFO *within their lane*.  Two lanes (``Request.lane``):
  ``interactive`` is admitted strictly FIFO with worst-case page
  reservation (``ceil((prompt + max_new [+ draft]) / page_size)``) so a
  resident sequence can never hit a mid-generation allocation failure;
  ``best_effort`` fills leftover capacity only while the interactive
  queue is empty, and is PREEMPTIBLE — when the interactive head does
  not fit, the youngest best-effort resident is evicted through the
  ordinary evict→recycle path and requeued (continuation: prompt +
  tokens generated so far, remaining budget) at its lane's head.
- **prefill**: an admitted prompt runs through the model's full forward
  at a static padded shape (``DecodeConfig.max_prompt_len``, or the
  shortest of ``prefill_buckets`` that holds it) — or, with
  ``prefill_chunk`` set, as fixed-size CHUNKS through the
  multi-position decode forward, one chunk per scheduler step,
  interleaved with resident streams' decode steps (arbitrary prompt
  lengths, no TTFT spike for the streams).
- **prefix sharing** (``prefix_sharing``): admission matches the
  prompt against the refcounted page trie
  (:mod:`apex_tpu.inference.prefix`); matched full pages map straight
  into the page table (one physical copy, N tables), the prefill write
  window starts past them, and chunked prefill skips their compute.  A
  shared partial TAIL page is copy-on-written
  (:func:`~apex_tpu.inference.kv_cache.copy_page`) before the first
  divergent write, paid from a reserve page allocated at admission —
  COW can never fail mid-generation.
- **decode**: one fused step advances every active slot; inactive
  slots ride along masked.  The plain step is PIPELINED, one step in
  flight: a call of ``step()`` launches step n+1 and only then reads
  step n's tokens back and emits them, so the device computes while the
  host does its bookkeeping (see "The decode loop" below).  With
  ``draft_len`` k > 0 the step is the VERIFY step (synchronous:
  acceptance needs the tokens on the host): per slot, an n-gram proposer
  (:class:`~apex_tpu.inference.spec.NGramProposer`) drafts up to k
  tokens, one batched pass scores all k+1 positions, and the host
  accepts the longest matching prefix — the emitted stream is bitwise
  the non-speculative stream (greedy AND sampled: each emission spends
  its own (slot, draw) seed), it just arrives up to k+1 tokens per
  step.
- **block generation** (a served model that declares ``block_length``
  W: generation by diffusion over blocks): the third kind of step.  A
  slot stays at one BLOCK of W positions for several steps: each step
  forwards the block's W ids (some of them the model's mask id) as a
  DENOISING pass, which unmasks some positions and yields no token.
  The pass that leaves the block without a mask moves the slot on to
  its next block on the device, and the clean block's COMMIT, the
  forward that stores its clean tokens' keys and values, rides the next
  step beside the new block's first denoising pass (``2W`` rows a slot
  a step; a request's last block commits in a step of its own).  The
  commit's readback yields the block's tokens at once (``W``, or ``W -
  r`` for the first block of a prompt that ends ``r`` positions into a
  block): a token is emitted only once its keys and values are the
  cache's.  The block's state lives on the device, so the one step in
  flight survives: under the static strategies the host knows every
  slot's steps in advance (its blocks' denoising passes and ONE more),
  under ``low_confidence_dynamic`` it learns of a block's end at the
  readback and may have launched one slot-step too many
  (``stats["wasted_slot_steps"]``; the device drops it).
  Admission reserves ``prompt + max_new_tokens`` rounded up to a block,
  the prefill stores the prompt's whole blocks and samples nothing, and
  a request is evicted at the commit of its last block, having emitted
  exactly ``max_new_tokens`` tokens.  ``Request.denoising_steps`` (T,
  default the model's) is the request's own: fewer passes a block,
  faster and worse.
- **evict**: finished sequences free (decref) their pages back to the
  allocator — the next ``step()`` can admit into them — and register
  their quiesced tail page into the prefix trie.

The decode loop (``draft_len == 0``).  Step n+1 needs nothing of the
host that step n's tokens decide: its ``tokens`` argument IS step n's
output and stays on the device (a prefill writes its first token into
that vector once, at admission); positions, liveness, page tables and
seeds are known before step n ends.  So ``step()`` runs: LAUNCH step
n+1, READ step n BACK, EMIT it (tokens, their times, evictions), ADMIT
(the synchronous prefills queue on the device behind n+1).  Positions,
seeds and the COW pass advance at launch; tokens and their times at
emit — a token's time is the moment it is on the host.  A sequence
that fills its ``max_new_tokens`` with step n is left out of step n+1,
so length-terminated traffic wastes no slot-step; one that ends on
``eos_id`` is found a step late: its slot's part of the step in flight
is dropped (``stats["wasted_slot_steps"]``; the write landed in pages
the sequence had reserved or in its own per-slot rows, which the next
prefill installs whole) and its draw is handed back, so every stream is
bitwise what a lockstep loop serves.  READERS SEE A SETTLED SCHEDULER:
``drain_manifest``, ``begin_drain``, ``slot_state``, ``read_counters``
and ``cancel`` first read the step in flight back and emit it
(``stats["decode_settles"]``), so between two calls a caller sees every
launched token emitted and the caches holding all emitted tokens but
the last; a caller that polls one of them every step runs in lockstep.
Only the watchdog's hook (another thread, the device hung) reads host
state unsettled.  ``idle()``, ``num_active``, ``completed`` and
``stats`` are host reads: they show what has been EMITTED.

What the loop says of its own pace.  Every iteration reads
``time.perf_counter`` six times, tracer or none: ``stats["device_wait_s"]``
sums the seconds blocked in a readback, ``stats["loop_host_s"]`` the
seconds inside ``step()`` less that wait.  With a tracer on,
``serve.decode_step`` and ``serve.prefill`` carry the same reads as
attributes (``prep_us``, ``upload_us``, ``enqueue_us``, ``wait_us``:
docs/observability.md), and a stretch of ``step()`` calls that found
nothing resident, queued or in flight is ONE span, ``serve.idle``.

The scheduler is time-agnostic (drivers decide when to ``submit``;
tests replay seeded traces step-by-step, the load-generator example
submits on wall-clock Poisson arrivals) and deterministic: sampling
seeds derive from ``(base_seed, slot, per-slot draw counter)``, and the
draw counter advances MONOTONICALLY across every generation a slot
serves (drain-and-resubmit, preemption re-admission) — it never
resets, so the same trace of submits produces the same tokens and two
generations can never replay one seed.

Kernel resilience: trace-time kernel failures already degrade through
the fallback registry inside the step build; a DEFERRED jit-compile
failure surfaces on the first call, is attributed via
``resilience.fallback.trip_from_exception``, and the steps are rebuilt
once — the fresh trace lowers the XLA reference and the server keeps
serving (the same recovery ``examples/gpt/pretrain_gpt.py`` wires for
training).

Wedge resilience: a ``watchdog=`` (:class:`apex_tpu.resilience
.StepWatchdog`) gets a heartbeat per scheduler step; a decode step that
never returns (hung compile, hung collective) fires it — the scheduler's
``on_wedge`` hook logs every queued and in-flight request id
(``serve.step_wedged`` — the requeue manifest for the layer above) and
records ``apex_serve_wedges_total``, then the watchdog drains and exits
75 so a :class:`~apex_tpu.resilience.supervisor.Supervisor` restarts
the server (``serve_gpt.py --supervise --watchdog-secs``).
"""

import dataclasses
import logging
import time
from collections import deque
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from apex_tpu.inference.decode import (
    BLOCK_COMMIT, BLOCK_DENOISE, DecodeConfig, block_passes, init_block_state,
    make_block_prefill, make_block_step, make_decode_step, make_prefill,
    make_prefill_chunk, make_sample_head, make_verify_step, served,
)
from apex_tpu.inference.kv_cache import (
    COUNTERS, GARBAGE_PAGE, PageAllocator, alloc_named_pools, copy_page,
    page_positions, pages_needed, per_slot_names, windowed_entry,
)
from apex_tpu.inference.prefix import PrefixCache, PrefixMatch
from apex_tpu.inference.spec import NGramProposer, accepted_tokens
from apex_tpu.observability import metrics as _metrics
from apex_tpu.observability import tracing as _tracing
from apex_tpu.resilience.chaos import active_monkey
from apex_tpu.resilience.uniformity import assert_uniform
from apex_tpu.utils.logging import get_logger, log_structured

__all__ = ["LANES", "Completion", "ContinuousBatchingScheduler",
           "ManifestEntry", "Request"]

_logger = get_logger("apex_tpu.inference")

_MASK32 = (1 << 32) - 1

#: admission lanes, in priority order: ``interactive`` requests carry
#: the latency SLO (strict FIFO, worst-case reservation, may preempt);
#: ``best_effort`` fills leftover capacity and is preemptible
LANES = ("interactive", "best_effort")


@dataclasses.dataclass
class Request:
    """One generation request: ``prompt`` token ids, ``max_new_tokens``
    to generate, optional ``eos_id`` early stop, and the admission
    ``lane`` (see :data:`LANES`).  ``trace_id`` is assigned at
    ``submit`` when the caller did not bring one — it is stamped on
    every span and latency-histogram exemplar the request produces, so
    a p99 outlier in ``apex_serve_ttft_seconds`` joins back to this
    request's admission-wait/prefill/decode spans.  ``blocked_on`` is
    the scheduler's: why this request last blocked at the head of its
    queue (``"slot"``, ``"pages"``, or None when it never waited as
    head).  ``denoising_steps`` is for a block-generating model only
    (refused elsewhere): the denoising passes a block of this request
    gets, 1 to the model's ``block_length``; None: the model's own.
    ``record_passes`` asks such a model to keep every pass of this
    request's slot for ``Completion.block_trace`` (a caller that checks
    what each pass did; nothing is kept for a request that does not
    ask)."""

    rid: int
    prompt: List[int]
    max_new_tokens: int
    eos_id: Optional[int] = None
    lane: str = "interactive"
    trace_id: Optional[str] = None
    blocked_on: Optional[str] = None
    denoising_steps: Optional[int] = None
    record_passes: bool = False


@dataclasses.dataclass
class Completion:
    """Finished request: ``submit_time`` at submit(), ``admit_time`` at admission.

    ``submit_time`` is when ``submit()`` took the request,
    ``admit_time`` when a slot and its pages were reserved (for a
    preempted request, both of the FIRST leg), and ``token_times[i]``
    when ``tokens[i]`` was on the host.
    So ``admit_time - submit_time`` is the queue, ``token_times[0] -
    admit_time`` the prefill, and ``token_times[0] - submit_time`` the
    time to first token.  ``preemptions`` counts how often a
    best-effort generation was evicted-and-requeued on the way.

    Of a block-generating model the tokens of one block come out
    together, at the readback of the block's commit: they SHARE one
    stamp in ``token_times`` (so the gaps inside a block are 0 and the
    gap between blocks is the next block's denoising passes),
    ``token_times[0]`` is the first block's commit (its prefill samples
    nothing).  Where the request asked (``Request.record_passes``),
    ``block_trace`` holds every block-forward of the request, in order
    (a commit that rode the next block's pass comes before that pass):
    ``(block start, row)``, ``row`` the W ids after the pass, then
    the pass's kind
    (``inference.decode.BLOCK_DENOISE``/``BLOCK_COMMIT``) and how many
    positions it unmasked.  None otherwise, and for any other model."""

    rid: int
    prompt: List[int]
    tokens: List[int]
    submit_time: float
    admit_time: float
    finish_time: float
    token_times: List[float]
    lane: str = "interactive"
    preemptions: int = 0
    trace_id: Optional[str] = None
    block_trace: Optional[List[tuple]] = None


@dataclasses.dataclass
class ManifestEntry:
    """One unfinished request in a :meth:`drain_manifest` snapshot —
    everything a frontend needs to RESUBMIT it elsewhere and splice the
    continuation into the caller's stream: the ORIGINAL prompt (not the
    current continuation leg's), every token already emitted across all
    legs (``emitted`` — the splice point), and the tokens still owed
    (``remaining``).  The replay request is
    ``Request(rid, prompt + emitted, remaining, eos_id, lane,
    trace_id)`` — prefix sharing makes the re-prefill cheap on a
    replica that has served the prompt, and monotonic per-slot draw
    seeds make the resubmission seed-safe."""

    rid: int
    lane: str
    phase: str                     # "queued" | "in_flight"
    prompt: List[int]              # original prompt (all legs)
    emitted: List[int]             # tokens already emitted, in order
    remaining: int                 # new tokens still owed
    eos_id: Optional[int] = None
    trace_id: Optional[str] = None
    denoising_steps: Optional[int] = None


@dataclasses.dataclass
class _Carry:
    """Cross-preemption continuation state for one rid: the ORIGINAL
    prompt, submit and admit times, plus tokens/times already emitted
    by earlier residency legs."""

    prompt: List[int]
    tokens: List[int]
    times: List[float]
    submit_time: float
    admit_time: float
    preemptions: int = 0
    block_trace: Optional[List[tuple]] = None


@dataclasses.dataclass
class _BlockPlan:
    """A block-generating slot's progress, as the host knows it:
    ``start`` the first block's first position, ``blocks`` how many the
    request has, ``opened`` how many of them a readback has shown clean
    (the open block is the next), ``committed`` how many committed,
    ``to_launch`` the steps the whole request takes where the strategy
    fixes them in advance (None: learnt at the readback;
    ``_Slot.launched`` counts the steps launched), ``trace`` every
    block-forward read back of a request that asked
    (``Request.record_passes``, for ``Completion.block_trace``), else
    None."""

    start: int
    blocks: int
    to_launch: Optional[int]
    opened: int = 0
    committed: int = 0
    trace: Optional[List[tuple]] = None


@dataclasses.dataclass
class _Slot:
    request: Request
    pages: List[int]               # page-table entries, in index order
    generated: List[int]
    token_times: List[float]
    submit_time: float             # when submit() took it (TTFT base)
    admit_time: float              # slot and pages reserved
    admit_seq: int = 0             # admission order (preemption picks max)
    shared_len: int = 0            # prompt positions served by shared pages
    cow_reserve: Optional[int] = None
    chunk_next: Optional[int] = None  # next prompt position to chunk-prefill
    proposer: Optional[NGramProposer] = None
    launched: int = 0              # tokens emitted or owed by the step in
                                   # flight (a block slot: passes launched)
    block: Optional[_BlockPlan] = None  # a block-generating model's slot


@dataclasses.dataclass
class _InFlight:
    """A plain decode step launched and not read back: its
    ``next_tokens`` as the device holds them, and the slots it owes a
    token (a slot that ends on ``eos_id`` meanwhile is struck).  Of a
    block step ``tokens`` is its (B, 2W + 3) readback and ``slots`` the
    slots it was launched for."""

    tokens: Any
    slots: np.ndarray              # (B,) bool


def _launch_us(t_open: float, t_up: float, t_enq: float,
               t_read: float) -> Dict[str, int]:
    """A device call's host time taken apart (``time.perf_counter``
    reads, in µs): the host arguments made device arrays, the compiled
    call returned, their sum (``dispatch_us``), and the wait for the
    result that is read back."""
    return {"upload_us": int((t_up - t_open) * 1e6),
            "enqueue_us": int((t_enq - t_up) * 1e6),
            "dispatch_us": int((t_enq - t_open) * 1e6),
            "wait_us": int((t_read - t_enq) * 1e6)}


@jax.jit
def _set_token(tokens, slot, token):
    """A prefill's first token into the device's token vector."""
    return tokens.at[slot].set(token)


@partial(jax.jit, donate_argnums=(0,))
def _set_block(blocks, slot, ids, pos):
    """An admitted request's first block into the device's block state:
    what is left of the prompt past its whole blocks, then masks; no
    block is held (what the slot's last tenant left is dropped)."""
    return dict(blocks,
                ids=blocks["ids"].at[slot].set(ids),
                passes=blocks["passes"].at[slot].set(0),
                pos=blocks["pos"].at[slot].set(pos),
                held_live=blocks["held_live"].at[slot].set(False))


class ContinuousBatchingScheduler:
    """The serve loop's control plane: lane-aware admission into freed
    KV pages between steps, static-shape slot management, chunked
    prefill, prefix sharing with COW, eviction with refcounted page
    recycling, deterministic per-slot sampling seeds, degrade-once step
    rebuild on deferred kernel failures, and ONE of three kinds of step
    by what the model and the configuration say: the plain decode step
    (a token a slot, one step in flight), the speculative verify step
    (``draft_len`` > 0: up to ``draft_len + 1`` tokens a slot,
    synchronous), or the block step of a model that generates by
    diffusion over blocks (it declares ``block_length``: no token or a
    whole block a slot, one step in flight).  See the module docstring
    for the full semantics."""

    def __init__(self, params, model, dcfg: DecodeConfig,
                 time_fn=time.monotonic, watchdog=None, anomaly=None):
        """``model``: the served model (docs/inference.md: cache spec,
        prefill, decode forward, head matrix, serving tree), or a model
        configuration whose ``served_model()`` builds it.  ``params``:
        the model's parameters, or what its ``serving_params`` made of
        them before (several schedulers then share one prepared
        tree)."""
        cache = dcfg.cache
        self.model = served(model)
        config = self.model.config
        if (dcfg.draft_len > 0 or dcfg.prefill_chunk is not None) \
                and not self.model.multi_position:
            raise NotImplementedError(
                f"{type(self.model).__name__}'s decode forward scores one "
                "position a slot: speculative verify (draft_len) and "
                "chunked prefill (prefill_chunk) are not built for it")
        #: the block length of a model that generates by diffusion over
        #: blocks (it declares it), else None
        self._block: Optional[int] = getattr(self.model, "block_length",
                                             None)
        if self._block is not None:
            self._check_block_config(dcfg)
        spec = self.model.cache_spec()
        if dcfg.prefix_sharing and per_slot_names(spec):
            raise NotImplementedError(
                f"{type(self.model).__name__} keeps per-slot recurrent "
                f"state ({', '.join(per_slot_names(spec))}): a shared "
                "page holds a prefix's keys, but the state at a prefix's "
                "end is not kept anywhere to be shared (prefix_sharing "
                "needs state snapshots: ROADMAP, Queue 2)")
        #: the cache's window a slot, where it keeps one (a
        #: :class:`~apex_tpu.inference.kv_cache.Windowed` spec), and the
        #: positions an allocated page covers (a page of pooled columns
        #: covers ``page_size * stride``)
        self._windowed = windowed_entry(spec, cache)
        self._page_positions = page_positions(spec, cache)
        if dcfg.prefix_sharing and self._windowed is not None:
            raise NotImplementedError(
                f"{type(self.model).__name__} keeps the last "
                f"{self._windowed.window} positions in a window buffer a "
                "slot: a window buffer cannot be shared between "
                "sequences, only the closed windows' pooled pages could "
                "(prefix_sharing over those: ROADMAP, Queue 2)")
        limit = self.model.max_positions
        if limit is not None and dcfg.max_prompt_len > limit:
            raise ValueError(
                f"max_prompt_len ({dcfg.max_prompt_len}) exceeds the "
                f"learned position table ({limit})")
        # the model's tree for its compiled steps (matrices cast to
        # the compute dtype ONCE, here); the tree given is not kept, so
        # a caller that drops it frees what the cast replaced
        self.params = self.model.serving_params(params)
        self.config = config
        self.dcfg = dcfg
        self._time = time_fn
        # the carried cache state: the model's named pools, its
        # per-slot state (a row a decode slot; no pages) and, if it
        # keeps any, its device-side counters (read_counters)
        self.pools = alloc_named_pools(spec, cache, slots=dcfg.max_batch)
        if self.model.counter_names:
            self.pools[COUNTERS] = jnp.zeros(
                (len(self.model.counter_names),), jnp.int32)
        self.allocator = PageAllocator(cache.num_pages)
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(self.allocator, cache.page_size)
            if dcfg.prefix_sharing else None)
        self.queue: deque = deque()      # interactive lane
        self.be_queue: deque = deque()   # best-effort lane
        B, P = dcfg.max_batch, cache.pages_per_seq
        self._slots: List[Optional[_Slot]] = [None] * B
        self._page_tables = np.zeros((B, P), np.int32)
        self._positions = np.zeros((B,), np.int32)
        self._tokens = np.zeros((B,), np.int32)  # the verify step's
        self._active = np.zeros((B,), bool)
        #: the resident requests' trace ids, slot order: what the step
        #: spans carry as ``trace_ids``; None where the resident set
        #: changed since a traced step last asked
        self._trace_ids: Optional[Tuple[str, ...]] = None
        #: the plain decode loop's one step in flight, and ITS token
        #: vector, on the device: the next launch's ``tokens`` argument
        #: (the last launched step's output, with first tokens written in)
        self._inflight: Optional[_InFlight] = None
        self._dev_tokens = jnp.zeros((B,), jnp.int32)
        #: a block-generating model's per-slot block state, on the
        #: device (``inference.decode.init_block_state``), and what the
        #: host uploads with every launch: each slot's denoising steps
        #: and the position its sequence ends at, rounded up to a block
        self._blocks = None
        if self._block is not None:
            self._blocks = init_block_state(B, self._block,
                                            self.model.mask_id)
        self._block_steps = np.ones((B,), np.int32)
        self._block_ends = np.zeros((B,), np.int32)
        #: per-slot sampling draw counters — MONOTONIC for the life of
        #: the scheduler, across every generation a slot serves (the
        #: determinism contract: no (slot, draw) seed is ever replayed,
        #: even after drain-and-resubmit or preemption re-admission)
        self._draws = np.zeros((B,), np.int64)
        self._admit_counter = 0
        self.completed: List[Completion] = []
        self._carry: Dict[int, _Carry] = {}
        self.stats: Dict[str, float] = {
            "admitted": 0, "evicted": 0, "decode_steps": 0,
            "prefills": 0, "step_rebuilds": 0,
            "preemptions": 0, "chunk_steps": 0, "cow_copies": 0,
            "shared_full_pages": 0, "shared_tail_pages": 0,
            "spec_steps": 0, "spec_emitted": 0,
            # admission passes on which the head of a queue blocked,
            # by what it lacked
            "admit_blocked_slot": 0, "admit_blocked_pages": 0,
            # the one step in flight: launches that found the previous
            # step unread, reads a settling reader forced, and slot-steps
            # dropped because their sequence had ended on eos_id
            "decode_overlapped": 0, "decode_settles": 0,
            "wasted_slot_steps": 0,
            # a block-generating model's block-forwards read back (a
            # slot's step may hold two), and how many of them were commits
            "block_passes": 0, "block_commits": 0,
            # launches after which a slot's window buffer starts again
            # from empty (a windowed cache only)
            "window_rollovers": 0,
            # seconds (``time.perf_counter``) the loop was blocked in a
            # readback (a decode or verify step's tokens, a prefill's
            # first), and seconds inside ``step()`` less that wait:
            # wait / (wait + host) near 0 says the HOST sets the pace
            "device_wait_s": 0.0, "loop_host_s": 0.0,
        }
        #: since when ``step()`` has found nothing resident, queued or
        #: in flight (``self._time``), and its calls since: ONE
        #: ``serve.idle`` span a stretch, when work arrives
        self._idle_since: Optional[float] = None
        self._idle_polls = 0
        #: the registry the occupancy gauges' children were resolved
        #: in, and their handles (``_record_occupancy``)
        self._gauge_registry = None
        self._gauges: tuple = ()
        #: prefills run since the last decode/verify step ended: above
        #: zero, that step's token gap holds a prefill as well
        self._prefills_since_step = 0
        self._rebuilt_once = False
        self._draining = False
        # record-only uniformity seam: the serve config shapes every
        # compiled step (static batch/page shapes, lane layout) — in a
        # future multi-host serving topology a per-process difference
        # here is a divergent program, so record it where
        # check_uniform() can compare it across processes by name
        assert_uniform("serve.scheduler_config", {
            "decode": dataclasses.asdict(dcfg),
            "model": dataclasses.asdict(config),
        })
        #: submit wall-time per queued rid, until admission moves it
        #: into the slot
        self._submit_times: Dict[int, float] = {}
        #: per-lane SLO-burn detection (an
        #: :class:`~apex_tpu.observability.anomaly.AnomalyMonitor`):
        #: every TTFT / inter-token sample is also scored, so a lane
        #: regression raises ``apex_anomaly_ttft_total{lane=}`` and a
        #: structured alert without the driver polling percentiles
        self._anomaly = anomaly
        self._watchdog = watchdog
        self._beaten = False
        if watchdog is not None:
            # chain, don't clobber: the driver may have wired its own
            # pre-exit hook (the trainer's goodput finalize pattern)
            prev = watchdog.on_wedge

            def hook(info, _prev=prev):
                if _prev is not None:
                    _prev(info)
                self._on_wedge(info)

            watchdog.on_wedge = hook
        self._build_steps()

    def drain_manifest(self, settle: bool = True) -> List["ManifestEntry"]:
        """Snapshot of every unfinished request — queued (both lanes)
        then in-flight, each with the tokens already emitted across all
        its legs — structured for a frontend to resubmit elsewhere and
        SPLICE (emit only ``total[len(already_streamed):]``) rather
        than regenerate.  The step in flight is settled first, so
        ``emitted`` holds every launched token (a frontend that polls
        per step runs the decode loop in lockstep).  ``settle=False``
        is the watchdog thread's: the device is by definition hung, so
        it reads the host's state as it stands — non-destructive and
        lock-free, list() copies of the queues/slots make that
        racy-but-safe."""
        if settle:
            self._settle()
        out: List[ManifestEntry] = []
        for req in list(self.queue) + list(self.be_queue):
            c = self._carry.get(req.rid)
            out.append(ManifestEntry(
                rid=req.rid, lane=req.lane, phase="queued",
                prompt=list(c.prompt) if c is not None
                else list(req.prompt),
                emitted=list(c.tokens) if c is not None else [],
                remaining=req.max_new_tokens, eos_id=req.eos_id,
                trace_id=req.trace_id,
                denoising_steps=req.denoising_steps))
        for s in list(self._slots):
            if s is None:
                continue
            req = s.request
            c = self._carry.get(req.rid)
            gen = list(s.generated)
            out.append(ManifestEntry(
                rid=req.rid, lane=req.lane, phase="in_flight",
                prompt=list(c.prompt) if c is not None
                else list(req.prompt),
                emitted=(list(c.tokens) if c is not None else []) + gen,
                remaining=req.max_new_tokens - len(gen),
                eos_id=req.eos_id, trace_id=req.trace_id,
                denoising_steps=req.denoising_steps))
        return out

    def _on_wedge(self, info) -> None:
        """Watchdog pre-exit hook: one structured record carrying the
        full :meth:`drain_manifest` — rids, lanes, AND the tokens each
        in-flight request already emitted, so the frontend replaying it
        can resubmit the unfinished tail and splice the continuation
        instead of regenerating from scratch — plus the wedge counter.
        Runs on the watchdog thread; reads of the slot arrays are
        racy-but-safe (the decode thread is by definition wedged, in
        the readback of the step in flight: nothing here waits for
        it)."""
        manifest = self.drain_manifest(settle=False)
        queued = [m.rid for m in manifest if m.phase == "queued"]
        inflight = [m.rid for m in manifest if m.phase == "in_flight"]
        # EVERY entry, untruncated: this record IS the requeue manifest
        # — a frontend replaying it cannot recover ids (or emitted
        # tokens) a cap dropped.  One long line once per process death
        # is the cheap side of that trade (the wedge exits the process
        # right after this).
        log_structured(
            _logger, logging.ERROR, "serve.step_wedged",
            decode_step=self.stats["decode_steps"],
            queued_rids=queued, inflight_rids=inflight,
            queued=len(queued), inflight=len(inflight),
            manifest=[dataclasses.asdict(m) for m in manifest],
            elapsed_s=info.get("elapsed_s"))
        _metrics.inc("apex_serve_wedges_total",
                     help="decode steps the watchdog declared wedged")

    def _active_trace_ids(self) -> Tuple[str, ...]:
        """Trace ids of the resident requests, slot order — stamped on
        the batch-level decode/verify spans so a per-request exemplar's
        ``trace_id`` joins to the specific steps that served it, not
        just the whole-lifetime ``serve.request`` span.  A scan of the
        slots: ``_resident_trace_ids`` makes it once a change of the
        resident set, not once a step."""
        return tuple(
            self._slots[i].request.trace_id
            for i in np.flatnonzero(self._active).tolist()
            if self._slots[i] is not None
            and self._slots[i].request.trace_id is not None)

    def _resident_trace_ids(self) -> Tuple[str, ...]:
        """What a traced step span carries as ``trace_ids``: the tuple
        held since the last traced step, scanned anew only after the
        resident set changed (``_set_active``).  An untraced step never
        asks, so with tracing off nothing is built."""
        if self._trace_ids is None:
            self._trace_ids = self._active_trace_ids()
        return self._trace_ids

    def _set_active(self, slot: int, active: bool) -> None:
        """A slot joins or leaves the decode batch (admission's end,
        eviction, preemption)."""
        self._active[slot] = active
        self._trace_ids = None

    def _record_occupancy(self) -> None:
        """Serving gauges on the current registry (the scope seam:
        ``with MetricsScope(reg):`` around the serve loop routes them).
        Their children are resolved once a registry, not once a call."""
        reg = _metrics.get_metrics()
        if reg is not self._gauge_registry:
            self._resolve_gauges(reg)
        for gauge, value in zip(self._gauges, (
                len(self.queue) + len(self.be_queue), len(self.queue),
                len(self.be_queue), self.num_active,
                self.allocator.free_pages)):
            gauge.set(value)

    def _resolve_gauges(self, reg) -> None:
        self._gauge_registry = reg
        try:
            lanes = reg.gauge("apex_serve_lane_queue_depth",
                              "waiting requests, by lane", ("lane",))
            self._gauges = (
                reg.gauge("apex_serve_queue_depth",
                          "requests waiting for a slot+pages").labels(),
                lanes.labels(lane="interactive"),
                lanes.labels(lane="best_effort"),
                reg.gauge("apex_serve_active_slots",
                          "resident decoding sequences").labels(),
                reg.gauge("apex_serve_free_pages",
                          "allocatable KV pages").labels())
        except ValueError as e:
            # a caller-owned registry holds one of the names as another
            # kind: telemetry never changes the serve loop's control flow
            self._gauges = ()
            log_structured(_logger, logging.WARNING,
                           "metrics.record_failed",
                           metric="apex_serve_* occupancy gauges",
                           error=f"{type(e).__name__}: {e}")

    # ------------------------------------------------------------ build
    def _check_block_config(self, dcfg: DecodeConfig) -> None:
        """What a block-generating model cannot be served with yet,
        each with its reason."""
        name, W = type(self.model).__name__, self._block
        if dcfg.draft_len > 0:
            raise NotImplementedError(
                f"{name} generates by blocks of {W}: a step already "
                "yields up to a block a slot, and a drafted token would "
                "have to be verified against a block that is still being "
                "denoised (draft_len is not built for it)")
        if dcfg.prefill_chunk is not None:
            raise NotImplementedError(
                f"{name} generates by blocks of {W}: its block step "
                "forwards one block a slot under one shared length, not a "
                "chunk of consecutive positions under causal lengths "
                "(prefill_chunk is not built for it)")
        if dcfg.prefix_sharing:
            raise NotImplementedError(
                f"{name} generates by blocks of {W}: a shared tail page "
                "would be rewritten by every pass of the block that opens "
                "in it, and the trie indexes pages by tokens that a block "
                "in progress does not have yet (prefix_sharing is not "
                "built for it)")
        if dcfg.cache.page_size % W or any(
                b % W for b in dcfg.prefill_lengths):
            raise ValueError(
                f"page_size ({dcfg.cache.page_size}) and every prefill "
                f"length {dcfg.prefill_lengths} must be multiples of the "
                f"block length ({W}): a block never straddles a page")

    def _build_steps(self) -> None:
        d = self.dcfg
        if self._block is not None:
            self._decode = make_block_step(self.model, d)
            self._prefill = make_block_prefill(self.model, d)
            self._verify = self._chunk = self._sample_head = None
            return
        if d.draft_len > 0:
            self._verify = make_verify_step(self.model, d)
            self._decode = None
        else:
            self._decode = make_decode_step(self.model, d)
            self._verify = None
        if d.prefill_chunk is not None:
            self._chunk = make_prefill_chunk(self.model, d)
            self._sample_head = make_sample_head(self.model, d)
            self._prefill = None
        else:
            self._prefill = make_prefill(self.model, d)
            self._chunk = None
            self._sample_head = None

    def decode_cache_size(self) -> int:
        """Compiled-variant count of the decode-family step (the verify
        step when speculation is on) — the compile-once pin (1 after
        any number of steps at any occupancy/length/draft-hit mix)."""
        step = self._verify if self.dcfg.draft_len > 0 else self._decode
        return step._cache_size()

    def lower_decode_step(self):
        """The plain decode step lowered at exactly the shapes the loop
        runs it — for reading the compiled program (which kernels it
        holds), beside :meth:`decode_cache_size`."""
        if self._decode is None:
            raise ValueError("speculative serving runs the verify step; "
                             "there is no plain decode step to lower")
        B = self.dcfg.max_batch
        if self._block is not None:
            return self._decode.lower(
                self.params, self.pools, self._blocks,
                jnp.asarray(self._block_steps), jnp.asarray(self._block_ends),
                jnp.asarray(self._active), jnp.asarray(self._page_tables),
                jnp.zeros((B,), jnp.uint32))
        return self._decode.lower(
            self.params, self.pools, jnp.asarray(self._tokens),
            jnp.asarray(self._positions), jnp.asarray(self._active),
            jnp.asarray(self._page_tables), jnp.zeros((B,), jnp.uint32))

    def read_counters(self) -> Dict[str, int]:
        """The model's device-side counters (``counter_names``), summed
        over every decode step so far: ONE readback, for after a window
        — the steps themselves never read them."""
        self._settle()
        names = self.model.counter_names
        if not names:
            return {}
        values = np.asarray(self.pools[COUNTERS])
        return {n: int(v) for n, v in zip(names, values)}

    def slot_state(self, rid: int) -> Optional[Dict[str, jnp.ndarray]]:
        """The per-slot state of the RESIDENT request ``rid`` as it
        stands between two steps: for each of the cache spec's
        :class:`~apex_tpu.inference.kv_cache.PerSlot` entries, its
        slot's rows ``(layers,) + shape``, sliced out of the carried
        array (a copy; a few MB).  It has taken in the prompt and every
        emitted token but the last (``drain_manifest`` has them).  Of a
        windowed cache (:class:`~apex_tpu.inference.kv_cache.Windowed`)
        it is the request's pages of pooled columns, ``(layers, pages,
        heads, dim, page_size)`` a pool, page ``w`` window ``w``'s, a
        column a chunk that has closed, and under ``<name>.window`` its
        slot's window buffer, ``(layers, window_pages, heads, dim,
        page_size)``, column ``position mod window``.  None for a
        request that is queued, finished or unknown, and for a model
        that keeps neither."""
        spec = self.model.cache_spec()
        names = per_slot_names(spec)
        pooled = sorted(n for n in spec if n not in names) \
            if self._windowed is not None else []
        self._settle()
        for i, s in enumerate(self._slots):
            if s is not None and s.request.rid == rid and (names or pooled):
                out = {n: self.pools[n][:, i] for n in names}
                pages = jnp.asarray(s.pages, jnp.int32)
                out.update({n: self.pools[n][:, pages] for n in pooled})
                if pooled:
                    cache = self.dcfg.cache
                    wp = self._windowed.window // cache.page_size
                    own = cache.num_pages + i * wp + jnp.arange(wp)
                    out.update({f"{n}.window": self.pools[n][:, own]
                                for n in pooled})
                return out
        return None

    def _call(self, attr: str, *args):
        """Run a compiled step; on a deferred kernel-compile failure,
        attribute it to the registry, rebuild the steps ONCE (the new
        trace lowers the fallback impls), and retry."""
        try:
            return getattr(self, attr)(*args)
        except Exception as exc:  # noqa: BLE001 — attribution decides
            from apex_tpu.resilience.fallback import trip_from_exception

            tripped = trip_from_exception(exc)
            if not tripped or self._rebuilt_once:
                raise
            self._rebuilt_once = True
            self.stats["step_rebuilds"] += 1
            log_structured(
                _logger, logging.WARNING, "inference.step_rebuilt",
                tripped=tripped, error=f"{type(exc).__name__}: {exc}")
            self._build_steps()
            return getattr(self, attr)(*args)

    # ------------------------------------------------------------ seeds
    def _seed_at(self, slot: int, draw: int) -> int:
        return (self.dcfg.base_seed
                + slot * 0x9E3779B9 + draw * 0x85EBCA6B) & _MASK32

    def _seed(self, slot: int) -> int:
        d = int(self._draws[slot])
        self._draws[slot] += 1
        return self._seed_at(slot, d)

    # ---------------------------------------------------------- requests
    def submit(self, request: Request) -> None:
        """Queue a request (FIFO within its lane).  Requests that can
        NEVER fit the static shapes fail here, loudly, instead of
        wedging the queue head forever."""
        if self._draining:
            raise RuntimeError(
                "scheduler is draining (begin_drain) — submit to "
                "another replica")
        if request.lane not in LANES:
            raise ValueError(
                f"unknown lane {request.lane!r}; lanes are {LANES}")
        plen = len(request.prompt)
        if plen < 1:
            raise ValueError("empty prompt")
        if self.dcfg.prefill_chunk is None \
                and plen > self.dcfg.max_prompt_len:
            raise ValueError(
                f"prompt ({plen} tokens) exceeds max_prompt_len "
                f"({self.dcfg.max_prompt_len}) — set prefill_chunk to "
                f"admit long prompts as chunks")
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self._block is None and request.denoising_steps is not None:
            raise ValueError(
                f"denoising_steps ({request.denoising_steps}) is a "
                f"block-generating model's: {type(self.model).__name__} "
                "yields one token a step")
        if self._block is not None:
            steps = request.denoising_steps
            if steps is not None and not 1 <= steps <= self._block:
                raise ValueError(
                    f"denoising_steps ({steps}) must lie in [1, "
                    f"block_length = {self._block}]")
            if request.eos_id is not None:
                raise ValueError(
                    "a block-generating model emits exactly "
                    "max_new_tokens tokens: eos_id is not built for it")
        limit = self.model.max_positions
        if limit is not None and plen + request.max_new_tokens > limit:
            raise ValueError(
                f"prompt + max_new_tokens ({plen} + "
                f"{request.max_new_tokens}) exceeds the learned position "
                f"table ({limit})")
        need = self._total_pages(request)
        P = self.dcfg.cache.pages_per_seq
        if need > P:
            raise ValueError(
                f"request needs {need} pages; page tables hold {P} "
                f"(pages_per_seq) — raise pages_per_seq or shorten the "
                f"request")
        if need > self.allocator.num_pages - 1:
            raise ValueError(
                f"request needs {need} pages; the pool only has "
                f"{self.allocator.num_pages - 1} allocatable")
        if request.trace_id is None:
            request.trace_id = _tracing.new_trace_id()
        self._submit_times[request.rid] = self._time()
        (self.queue if request.lane == "interactive"
         else self.be_queue).append(request)
        self._record_occupancy()

    def cancel(self, rid: int) -> Optional[Request]:
        """Remove a still-QUEUED request (either lane) and return it;
        None when ``rid`` is resident or unknown — a decoding sequence
        is not cancellable mid-step, the caller suppresses its output
        instead (the frontend's hedge-loser path)."""
        self._settle()
        for q in (self.queue, self.be_queue):
            for req in q:
                if req.rid == rid:
                    q.remove(req)
                    self._submit_times.pop(rid, None)
                    self._carry.pop(rid, None)
                    _metrics.inc("apex_serve_cancelled_total",
                                 help="queued requests cancelled "
                                      "before admission")
                    self._record_occupancy()
                    return req
        return None

    @property
    def draining(self) -> bool:
        return self._draining

    def drained(self) -> bool:
        """True once a draining scheduler has no residents left — the
        planned-restart point where killing the replica drops nothing."""
        return self._draining and self._inflight is None \
            and all(s is None for s in self._slots)

    def begin_drain(self) -> List[ManifestEntry]:
        """Planned-restart entry: stop admitting (``submit`` raises,
        ``_admit`` is a no-op), hand back the queued requests as a
        manifest (they would otherwise wait forever), and let the
        residents finish through the ordinary step/evict path.  The
        caller re-routes the returned entries and polls :meth:`drained`
        before recycling the process."""
        self._draining = True
        manifest = [m for m in self.drain_manifest()   # settles
                    if m.phase == "queued"]
        for m in manifest:
            self._submit_times.pop(m.rid, None)
            self._carry.pop(m.rid, None)
        self.queue.clear()
        self.be_queue.clear()
        log_structured(
            _logger, logging.INFO, "serve.drain_begun",
            requeued=len(manifest), residents=self.num_active)
        self._record_occupancy()
        return manifest

    def _epoch(self, mono: float) -> float:
        """Epoch timestamp of the monotonic instant ``mono`` (the
        retro-emitted spans' clock: both endpoints are measured on
        ``self._time``, Chrome trace events want wall time)."""
        return time.time() - (self._time() - mono)

    def _total_pages(self, req: Request) -> int:
        """Worst-case page-table footprint: prompt + generation budget,
        plus the speculative write window (draft k/v land up to
        ``draft_len`` positions past the accepted stream and must never
        spill into an unreserved — garbage — table entry)."""
        total = len(req.prompt) + req.max_new_tokens + self.dcfg.draft_len
        if self._block is not None:     # whole blocks: the last one's
            total = -(-total // self._block) * self._block  # surplus too
        return pages_needed(total, self._page_positions)

    @property
    def num_active(self) -> int:
        return int(self._active.sum())

    def idle(self) -> bool:
        """Nothing queued, resident or in flight (a host read: a slot
        is released when its last token is emitted)."""
        return (not self.queue and not self.be_queue
                and self._inflight is None
                and all(s is None for s in self._slots))

    # ------------------------------------------------------------- admit
    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    def _plan(self, req: Request):
        """(total_pages, match, need_fresh) for admitting ``req`` NOW —
        recomputed on every attempt (the trie and pool move under us)."""
        total = self._total_pages(req)
        match = (self.prefix.match(req.prompt) if self.prefix is not None
                 else PrefixMatch((), None, 0))
        return total, match, total - match.num_full

    def _admit(self) -> int:
        queued = len(self.queue) + len(self.be_queue)
        if self._draining or not queued:
            # nothing to plan, and no span: an empty server calls
            # step() every millisecond and would flood the ring
            return 0
        self._admit_span = _tracing.span("serve.admit")
        with self._admit_span:
            admitted, blocked_on = self._admit_from(
                self.queue, can_preempt=True)
            if not self.queue:
                # best-effort fills leftover capacity only while no
                # interactive request waits (the lane priority
                # contract), so at most one head blocks a pass
                more, blocked_on = self._admit_from(
                    self.be_queue, can_preempt=False)
                admitted += more
            self._admit_span.set(queued=queued, admitted=admitted,
                                 blocked_on=blocked_on)
        return admitted

    def _admit_from(self, queue: deque, can_preempt: bool):
        """Admit from the head of ``queue`` while heads fit.  Returns
        ``(admitted, blocked_on)``: what the head that stopped the pass
        lacked (``"slot"`` or ``"pages"``), None when the queue
        emptied."""
        admitted = 0
        while queue:
            req = queue[0]
            slot = self._free_slot()
            total, match, need_fresh = self._plan(req)
            if slot is None or not self.allocator.can_allocate(need_fresh):
                if slot is not None and self.prefix is not None and \
                        self.prefix.release(
                            need_fresh - self.allocator.free_pages):
                    continue  # trie refs dropped — re-plan and retry
                if can_preempt and self._preempt_one():
                    continue  # a best-effort resident yielded — retry
                # FIFO: the head blocks, nothing overtakes it
                req.blocked_on = "slot" if slot is None else "pages"
                self.stats["admit_blocked_" + req.blocked_on] += 1
                return admitted, req.blocked_on
            queue.popleft()
            self._admit_into(slot, req, total, match, need_fresh)
            admitted += 1
        return admitted, None

    def _admit_into(self, slot: int, req: Request, total: int,
                    match: PrefixMatch, need_fresh: int) -> None:
        t0 = self._time()
        submitted = self._submit_times.pop(req.rid, t0)
        _metrics.observe("apex_serve_admission_wait_seconds",
                         t0 - submitted,
                         help="submit -> slot+pages reserved",
                         exemplar={"trace_id": req.trace_id,
                                   "rid": req.rid},
                         lane=req.lane)
        tracer = _tracing.get_tracer()
        if tracer is not None:
            # both endpoints are known only now — retro-emit the wait,
            # caused by the admission pass that ended it
            tracer.emit("serve.admission_wait", self._epoch(submitted),
                        t0 - submitted, parent=self._admit_span.id,
                        rid=req.rid, trace_id=req.trace_id,
                        lane=req.lane, blocked_on=req.blocked_on)
        fresh = self.allocator.allocate(need_fresh)
        assert fresh is not None  # _admit_from checked can_allocate
        if match.num_full:
            self.allocator.share(match.full_pages)
            self.stats["shared_full_pages"] += match.num_full
        table: List[int] = list(match.full_pages)
        it = iter(fresh)
        cow_reserve = None
        if match.tail_page is not None:
            self.allocator.share([match.tail_page])
            self.stats["shared_tail_pages"] += 1
            table.append(match.tail_page)
            cow_reserve = next(it)  # the tail's COW budget, held aside
        table.extend(it)
        P = self.dcfg.cache.pages_per_seq
        row = np.zeros((P,), np.int32)
        row[:len(table)] = table
        self._page_tables[slot] = row
        plen = len(req.prompt)
        self._admit_counter += 1
        s = _Slot(request=req, pages=table, generated=[],
                  token_times=[], submit_time=submitted, admit_time=t0,
                  admit_seq=self._admit_counter,
                  shared_len=match.shared_len, cow_reserve=cow_reserve)
        self._slots[slot] = s
        self.stats["admitted"] += 1
        if self.dcfg.prefill_chunk is not None:
            # chunked admission: compute starts past the shared prefix
            # (fully-cached prompt → one recompute pass over the last
            # position, no writes), one chunk per scheduler step
            s.chunk_next = (match.shared_len if match.shared_len < plen
                            else plen - 1)
            return
        if self._block is not None:
            self._admit_block(slot, row)
            return
        # padded to the shortest compiled length that holds the prompt
        padded = next(b for b in self.dcfg.prefill_lengths if b >= plen)
        prompt = np.zeros((1, padded), np.int32)
        prompt[0, :plen] = req.prompt
        # the span ends when the first token is ON THE HOST (the
        # readback waits for the device, and for what is left of a
        # decode step in flight: behind_step); dispatch_us is the
        # upload of the arguments and the enqueue
        behind = int(self._inflight is not None)
        with _tracing.span("serve.prefill", rid=req.rid,
                           trace_id=req.trace_id, lane=req.lane,
                           prompt_len=plen, tokens=plen,
                           padded_tokens=padded,
                           shared_len=match.shared_len) as sp:
            t_open = time.perf_counter()
            args = (jnp.asarray(prompt), jnp.int32(plen),
                    jnp.int32(match.shared_len), jnp.asarray(row),
                    jnp.uint32(self._seed(slot)), jnp.int32(slot))
            t_up = time.perf_counter()
            self.pools, first = self._call(
                "_prefill", self.params, self.pools, *args)
            t_enq = time.perf_counter()
            first = int(first)
            t_read = time.perf_counter()
            self.stats["device_wait_s"] += t_read - t_enq
            if _tracing.enabled():
                sp.set(**_launch_us(t_open, t_up, t_enq, t_read),
                       behind_step=behind)
        self._prefill_done()
        self._start_decoding(slot, first)

    def _admit_block(self, slot: int, row: np.ndarray) -> None:
        """A block-generating model's admission: the prompt's WHOLE
        blocks are prefilled (one launch, nothing sampled and nothing
        read back: the span times the enqueue), what is left of it
        opens the first block beside masks in the device's block state,
        and the slot is armed for block steps.  Its first tokens come
        with that block's commit.  Where the strategy fixes the passes,
        the steps to launch are the blocks' denoising passes and ONE
        more: every commit but the last rides the next block's first
        pass (``block_passes`` counts a commit a block)."""
        s = self._slots[slot]
        req, W = s.request, self._block
        plen = len(req.prompt)
        keep = plen // W * W
        steps = req.denoising_steps or self.model.denoising_steps
        end = -(-(plen + req.max_new_tokens) // W) * W
        blocks = (end - keep) // W
        fixed = self.model.remasking != "low_confidence_dynamic"
        s.block = _BlockPlan(
            start=keep, blocks=blocks,
            to_launch=(block_passes(W, steps, W - (plen - keep))
                       + (blocks - 1) * (block_passes(W, steps, W) - 1))
            if fixed else None,
            trace=[] if req.record_passes else None)
        if keep:
            padded = next(b for b in self.dcfg.prefill_lengths if b >= keep)
            prompt = np.zeros((1, padded), np.int32)
            prompt[0, :min(plen, padded)] = req.prompt[:padded]
            with _tracing.span("serve.prefill", rid=req.rid,
                               trace_id=req.trace_id, lane=req.lane,
                               prompt_len=plen, tokens=keep,
                               padded_tokens=padded, shared_len=0) as sp:
                t_open = time.perf_counter()
                args = (jnp.asarray(prompt), jnp.int32(keep),
                        jnp.asarray(row))
                t_up = time.perf_counter()
                self.pools = self._call("_prefill", self.params, self.pools,
                                        *args)
                t_enq = time.perf_counter()
                if _tracing.enabled():
                    sp.set(**_launch_us(t_open, t_up, t_enq, t_enq),
                           behind_step=int(self._inflight is not None))
            self._prefill_done()
        first = np.full((W,), self.model.mask_id, np.int32)
        first[:plen - keep] = req.prompt[keep:]
        self._blocks = _set_block(self._blocks, np.int32(slot),
                                  jnp.asarray(first), np.int32(keep))
        self._block_steps[slot] = steps
        self._block_ends[slot] = end
        self._positions[slot] = keep
        self._set_active(slot, True)

    def _prefill_done(self) -> None:
        self.stats["prefills"] += 1
        self._prefills_since_step += 1

    def _start_decoding(self, slot: int, first: int) -> None:
        """Common prefill epilogue (classic and chunked): record the
        first token, index the prompt's full pages into the prefix
        trie, arm the slot for decode, and evict degenerate (1-token /
        instant-eos) generations immediately."""
        s = self._slots[slot]
        req = s.request
        t_first = self._time()
        self._observe_first_token(s, t_first)
        s.generated.append(first)
        s.token_times.append(t_first)
        s.launched = 1
        s.chunk_next = None
        if self.prefix is not None:
            # full pages quiesce the moment the prompt is cached; the
            # (mutable) tail page waits for eviction
            self.prefix.register(req.prompt, [int(p) for p in s.pages])
        if self.dcfg.draft_len > 0:
            s.proposer = NGramProposer(self.dcfg.draft_len,
                                       self.dcfg.ngram_max,
                                       self.dcfg.ngram_min)
            s.proposer.extend(list(req.prompt) + [first])
        self._positions[slot] = len(req.prompt)  # where `first` caches
        self._set_active(slot, True)
        if (req.max_new_tokens == 1
                or (req.eos_id is not None and first == req.eos_id)):
            self._evict(slot)
        elif self._decode is None:
            self._tokens[slot] = first
        else:
            # the next launch reads its tokens off the device: one
            # small update a prefill, queued behind the step in flight
            # (in which this slot, free at its launch, rode masked)
            self._dev_tokens = _set_token(
                self._dev_tokens, np.int32(slot), np.int32(first))

    def _observe_first_token(self, s: _Slot, t_first: float) -> None:
        req = s.request
        _metrics.observe("apex_serve_ttft_seconds", t_first - s.submit_time,
                         help="submit -> first token (prefill incl. queue)",
                         exemplar={"trace_id": req.trace_id,
                                   "rid": req.rid},
                         lane=req.lane)
        if self._anomaly is not None:
            self._anomaly.observe("ttft", t_first - s.submit_time,
                                  lane=req.lane)

    # --------------------------------------------------------- preemption
    def _preempt_one(self) -> bool:
        """Evict the YOUNGEST best-effort resident (decoding or still
        chunk-prefilling) through the ordinary evict→recycle path and
        requeue its continuation at its lane's head.  Returns whether a
        victim yielded."""
        cands = [i for i, s in enumerate(self._slots)
                 if s is not None and s.request.lane == "best_effort"]
        if not cands:
            return False
        if self._inflight is not None:
            # a victim is owed its token of the step in flight, and its
            # continuation starts from caches that hold it: settle, and
            # let the pass plan again (the step may have freed a slot)
            self._settle()
            return True
        victim = max(cands, key=lambda i: self._slots[i].admit_seq)
        s = self._slots[victim]
        req = s.request
        c = self._carry.get(req.rid)
        if c is None:
            c = _Carry(prompt=list(req.prompt), tokens=[], times=[],
                       submit_time=s.submit_time,
                       admit_time=s.admit_time)
            self._carry[req.rid] = c
        c.preemptions += 1
        if s.block is not None and s.block.trace is not None:
            # the committed blocks' passes
            c.block_trace = (c.block_trace or []) + [
                t for t in s.block.trace
                if t[0] < len(req.prompt) + len(s.generated)]
        remaining = req.max_new_tokens - len(s.generated)
        cont_prompt = list(req.prompt) + list(s.generated)
        can_continue = (
            s.chunk_next is None and s.generated and remaining >= 1
            and (self.dcfg.prefill_chunk is not None
                 or len(cont_prompt) <= self.dcfg.max_prompt_len))
        if can_continue:
            c.tokens.extend(s.generated)
            c.times.extend(s.token_times)
            cont = Request(rid=req.rid, prompt=cont_prompt,
                           max_new_tokens=remaining, eos_id=req.eos_id,
                           lane=req.lane, trace_id=req.trace_id,
                           denoising_steps=req.denoising_steps,
                           record_passes=req.record_passes)
        else:  # restart this leg (its partial work is dropped)
            cont = Request(rid=req.rid, prompt=list(req.prompt),
                           max_new_tokens=req.max_new_tokens,
                           eos_id=req.eos_id, lane=req.lane,
                           trace_id=req.trace_id,
                           denoising_steps=req.denoising_steps,
                           record_passes=req.record_passes)
        self._release_slot(victim)
        self.stats["preemptions"] += 1
        _metrics.inc("apex_serve_preemptions_total",
                     help="best-effort residents evicted for the "
                          "interactive lane")
        log_structured(
            _logger, logging.INFO, "serve.preempted", rid=req.rid,
            generated=len(s.generated), requeued_prompt=len(cont.prompt))
        self._submit_times[req.rid] = self._time()
        self.be_queue.appendleft(cont)
        return True

    def _release_slot(self, slot: int) -> None:
        """Return a slot's pages (and unused COW reserve) to the
        allocator and clear its static-shape arrays.  A step in flight
        that still owes the slot a token ran past the sequence's
        ``eos_id``: the token is dropped and its draw handed back (it
        was never emitted, so the slot's next tenant draws what a
        lockstep loop would have given it)."""
        s = self._slots[slot]
        if self._inflight is not None and self._inflight.slots[slot]:
            self._inflight.slots[slot] = False
            self._draws[slot] -= 1
            self.stats["wasted_slot_steps"] += 1
            _metrics.inc("apex_serve_wasted_slot_steps_total",
                         help="slot-steps launched past a sequence's "
                              "eos_id and dropped")
        self.allocator.free(s.pages)
        if s.cow_reserve is not None:
            self.allocator.free([s.cow_reserve])
        self._slots[slot] = None
        self._set_active(slot, False)
        self._page_tables[slot] = 0
        self._positions[slot] = 0
        self._tokens[slot] = 0
        self._block_ends[slot] = 0

    # ------------------------------------------------------------- evict
    def _evict(self, slot: int) -> None:
        s = self._slots[slot]
        if self.prefix is not None and s.chunk_next is None:
            # the tail page is quiesced now — index it (full pages
            # re-index as a no-op walk, repairing released chains)
            self.prefix.register(
                s.request.prompt,
                [int(p) for p in self._page_tables[slot]], tail=True)
        c = self._carry.pop(s.request.rid, None)
        prompt = c.prompt if c is not None else list(s.request.prompt)
        tokens = (list(c.tokens) if c is not None else []) \
            + list(s.generated)
        times = (list(c.times) if c is not None else []) \
            + list(s.token_times)
        first_leg = c if c is not None else s
        submit, admit = first_leg.submit_time, first_leg.admit_time
        trace, block_attrs = None, {}
        if s.block is not None:
            if s.block.trace is not None:
                trace = ((c.block_trace or []) if c is not None else []) \
                    + s.block.trace
            # every leg's committed blocks: the last one's end, from the
            # ORIGINAL prompt's last whole block
            block_attrs = dict(
                denoising_steps=int(self._block_steps[slot]),
                blocks=(s.block.start + s.block.committed * self._block
                        - len(prompt) // self._block * self._block)
                // self._block)
        self._release_slot(slot)
        finish = self._time()
        self.completed.append(Completion(
            rid=s.request.rid, prompt=prompt, tokens=tokens,
            submit_time=submit, admit_time=admit, finish_time=finish,
            token_times=times, lane=s.request.lane,
            preemptions=c.preemptions if c is not None else 0,
            trace_id=s.request.trace_id, block_trace=trace))
        tracer = _tracing.get_tracer()
        if tracer is not None:
            # the whole-lifetime span (submit -> eviction), what the
            # TTFT-exemplar trace_id joins to, with the first token
            # taken apart: queue_s + prefill_s == ttft_s
            tracer.emit(
                "serve.request", self._epoch(submit), finish - submit,
                rid=s.request.rid, trace_id=s.request.trace_id,
                lane=s.request.lane, tokens=len(tokens),
                queue_s=round(admit - submit, 6),
                prefill_s=round(times[0] - admit, 6) if times else None,
                ttft_s=round(times[0] - submit, 6) if times else None,
                blocked_on=s.request.blocked_on,
                preemptions=c.preemptions if c is not None else 0,
                **block_attrs)
        self.stats["evicted"] += 1
        _metrics.inc("apex_serve_completions_total",
                     help="finished generations")
        _metrics.inc("apex_serve_generated_tokens_total", len(tokens),
                     help="tokens served")
        self._record_occupancy()

    # ----------------------------------------------------- chunked prefill
    def _advance_chunks(self) -> bool:
        """One prefill chunk per still-prefilling slot: the chunk's k/v
        scatter into the reserved pages through the multi-position
        decode forward (shared-prefix positions skip both compute and
        writes), and the final chunk's last hidden state feeds the
        sampling head for the first token."""
        progressed = False
        C = self.dcfg.prefill_chunk
        for i, s in enumerate(self._slots):
            if s is None or s.chunk_next is None:
                continue
            plen = len(s.request.prompt)
            start = s.chunk_next
            n_valid = min(C, plen - start)
            tok = np.zeros((C,), np.int32)
            tok[:n_valid] = s.request.prompt[start:start + n_valid]
            last = start + n_valid >= plen
            # a chunk that is not the last is left in flight (its span
            # times the enqueue); the last one's span ends when the
            # first token is on the host
            with _tracing.span("serve.prefill_chunk", rid=s.request.rid,
                               trace_id=s.request.trace_id,
                               lane=s.request.lane, chunk_start=start,
                               chunk_tokens=n_valid, last=last):
                self.pools, h_last = self._call(
                    "_chunk", self.params, self.pools, jnp.asarray(tok),
                    jnp.int32(start), jnp.int32(n_valid),
                    jnp.int32(s.shared_len),
                    jnp.asarray(self._page_tables[i]))
                if last:
                    first = self._call(
                        "_sample_head", self.params, h_last,
                        jnp.uint32(self._seed(i)))
                    t_enq = time.perf_counter()
                    first = int(first)
                    self.stats["device_wait_s"] += \
                        time.perf_counter() - t_enq
            self.stats["chunk_steps"] += 1
            s.chunk_next = start + n_valid
            progressed = True
            if last:
                self._prefill_done()
                self._start_decoding(i, first)
        return progressed

    # ------------------------------------------------------------- COW
    def _cow_for_writes(self, writers: np.ndarray, width: int) -> None:
        """Copy-on-write pass before a decode/verify step is launched:
        any page the write window (``positions .. positions + width -
        1``) of a slot in ``writers`` touches with refcount > 1 is
        copied into the slot's reserve and the table repointed — shared
        pages are never written through.  (The copy queues on the device
        behind a step in flight, ahead of the step it is for.)"""
        if self.prefix is None:
            return  # no sharing → no page can ever hold refcount > 1
        ps = self.dcfg.cache.page_size
        P = self.dcfg.cache.pages_per_seq
        for i in np.flatnonzero(writers):
            p0 = int(self._positions[i])
            first_ix = p0 // ps
            last_ix = min((p0 + width - 1) // ps, P - 1)
            for ix in range(first_ix, last_ix + 1):
                page = int(self._page_tables[i, ix])
                if page == GARBAGE_PAGE \
                        or self.allocator.refcount(page) <= 1:
                    continue
                s = self._slots[i]
                if s.cow_reserve is None:
                    raise RuntimeError(
                        f"slot {i}: divergent write into shared page "
                        f"{page} with no COW reserve — the admission "
                        f"plan must reserve one page per shared tail")
                new = s.cow_reserve
                s.cow_reserve = None
                self.pools = copy_page(self.pools, page, new)
                self.allocator.free([page])  # drop this slot's share
                self._page_tables[i, ix] = new
                s.pages[ix] = new
                self.stats["cow_copies"] += 1
                _metrics.inc("apex_serve_cow_copies_total",
                             help="shared pages copied before a "
                                  "divergent write")

    # -------------------------------------------------------------- step
    def step(self) -> bool:
        """One iteration of the serve loop.  Plain decode: launch step
        n+1, read step n back and emit it, then admit waiting requests
        (both lanes; their prefills queue behind n+1) and advance
        chunked prefills by one chunk each — it returns with a step in
        flight.  Speculative verify (``draft_len`` > 0; up to
        ``draft_len + 1`` tokens a slot): admit, advance chunks, then
        the synchronous verify step.  Returns True when any work
        happened."""
        t_in = time.perf_counter()
        waited = self.stats["device_wait_s"]
        if self._watchdog is not None:
            # the first interval covers the prefill/decode jit compiles
            # (the trainer loop's compile-grace pattern); steady state
            # uses the watchdog's own deadline
            self._watchdog.beat(
                self.stats["decode_steps"],
                deadline=(self._watchdog.first_deadline_sec
                          if not self._beaten else None))
            self._beaten = True
        monkey = active_monkey()
        if monkey is not None:
            # deterministic wedged-decode-step fault: the sleep holds
            # THIS step past the watchdog deadline, exactly how a hung
            # dispatch presents (plan key: decode steps taken so far)
            monkey.maybe_wedge_step(self.stats["decode_steps"])
        if self._idle_since is not None:
            # an empty server: only a submit ends the stretch
            if not self.queue and not self.be_queue:
                self._idle_polls += 1
                self.stats["loop_host_s"] += time.perf_counter() - t_in
                return False
            self._end_idle()
        # acceptance needs the tokens on the host, so a verify step
        # cannot be launched ahead of its predecessor's readback: it
        # runs whole, after admission
        speculative = self.dcfg.draft_len > 0
        stepped = False if speculative else self._step_decode()
        admitted = self._admit()
        progressed = False
        if self.dcfg.prefill_chunk is not None:
            progressed = self._advance_chunks()
        if speculative and self._active.any():
            self._step_verify()
            stepped = True
        worked = stepped or admitted > 0 or progressed
        if not worked and self.idle():
            self._idle_since, self._idle_polls = self._time(), 1
        self.stats["loop_host_s"] += (time.perf_counter() - t_in) - (
            self.stats["device_wait_s"] - waited)
        return worked

    def _end_idle(self) -> None:
        """Work has arrived on an empty server: ONE ``serve.idle`` span
        for the whole stretch, after the fact (a span a ``step()`` call
        would flood the ring: an empty server polls every
        millisecond)."""
        since, self._idle_since = self._idle_since, None
        tracer = _tracing.get_tracer()
        if tracer is not None:
            tracer.emit("serve.idle", self._epoch(since),
                        self._time() - since, polls=self._idle_polls)

    def _settle(self) -> None:
        """Read the step in flight back and emit it, launching nothing:
        what a reader of device state or of progress calls first."""
        if self._inflight is None:
            return
        self.stats["decode_settles"] += 1
        _metrics.inc("apex_serve_decode_settles_total",
                     help="steps in flight read back for a reader, "
                          "outside the loop's own order")
        self._step_decode(launch=False)

    def _next_writers(self) -> np.ndarray:
        """The slots the next plain step advances: the active ones
        whose budget the tokens emitted or in flight have not filled (a
        sequence's length is known before its last token is read)."""
        live = self._active.copy()
        for i in np.flatnonzero(live):
            s = self._slots[i]
            if s.block is None:
                live[i] = s.launched < s.request.max_new_tokens
            elif s.block.to_launch is not None:
                live[i] = s.launched < s.block.to_launch
            else:   # the readback says when the last block committed
                live[i] = s.block.committed < s.block.blocks
        return live

    def _step_decode(self, launch: bool = True) -> bool:
        """One iteration of the plain decode loop: launch the next
        step, THEN read the previous one back and emit it.  The span
        ``serve.decode_step`` runs from the launch to the PREVIOUS
        step's tokens on the host, and says where the host's time went:
        ``prep_us`` before it opened, then ``upload_us``,
        ``enqueue_us`` and ``wait_us``.  Returns whether it launched or
        read anything."""
        t_in = time.perf_counter()
        B = self.dcfg.max_batch
        prev, self._inflight = self._inflight, None
        live = self._next_writers() if launch else np.zeros((B,), bool)
        launching = bool(live.any())
        if prev is None and not launching:
            return False
        seeds = np.zeros((B,), np.uint32)
        W = self._block
        if launching:
            # positions, seeds and the COW pass advance at launch
            self._cow_for_writes(live, width=1)
            for i in np.flatnonzero(live):
                seeds[i] = self._seed(i)
                self._slots[i].launched += 1
        overlapped = int(launching and prev is not None)
        # attrs (slot scan, active count) are only worth computing when
        # a tracer is installed — this is the highest-frequency span in
        # the serving path and the off case must stay near-zero
        traced = _tracing.enabled()
        attrs = (dict(decode_step=self.stats["decode_steps"],
                      active=int((live if launching else prev.slots).sum()),
                      trace_ids=self._resident_trace_ids(),
                      prefills_before=self._prefills_since_step,
                      in_flight=overlapped)
                 if traced else {})
        if traced and W is not None:
            # rows the launched step carries: two blocks a live slot
            attrs["block_rows"] = 2 * W * int(live.sum()) if launching else 0
        next_tokens = None
        with _tracing.span("serve.decode_step", **attrs) as sp:
            t_open = t_up = t_enq = time.perf_counter()
            if launching and W is not None:
                # the block's ids, pass count and position are the
                # device's; the host says who is live, each slot's
                # denoising steps and where its sequence ends
                args = (jnp.asarray(self._block_steps.copy()),
                        jnp.asarray(self._block_ends.copy()),
                        jnp.asarray(live),
                        jnp.asarray(self._page_tables.copy()),
                        jnp.asarray(seeds))
                t_up = time.perf_counter()
                self.pools, self._blocks, out = self._call(
                    "_decode", self.params, self.pools, self._blocks, *args)
                out.copy_to_host_async()
                self._inflight = _InFlight(out, live.copy())
                t_enq = time.perf_counter()
            elif launching:
                # COPIES of the arrays the host goes on changing while
                # the step is in flight (an upload may alias or still be
                # reading its numpy buffer after the launch returns)
                args = (jnp.asarray(self._positions.copy()),
                        jnp.asarray(live),
                        jnp.asarray(self._page_tables.copy()),
                        jnp.asarray(seeds))
                t_up = time.perf_counter()
                self.pools, self._dev_tokens = self._call(
                    "_decode", self.params, self.pools, self._dev_tokens,
                    *args)
                self._dev_tokens.copy_to_host_async()
                self._inflight = _InFlight(self._dev_tokens, live.copy())
                self._positions[live] += 1
                if self._windowed is not None:
                    self.stats["window_rollovers"] += int(np.sum(
                        self._positions[live] % self._windowed.window == 0))
                t_enq = time.perf_counter()
            if prev is not None:
                next_tokens = np.asarray(prev.tokens)
            t_read = time.perf_counter()
            self.stats["device_wait_s"] += t_read - t_enq
            if traced:
                sp.set(prep_us=int((t_open - t_in) * 1e6),
                       **_launch_us(t_open, t_up, t_enq, t_read))
                if W is not None and prev is not None:
                    sp.set(**self._block_attrs(prev, next_tokens))
        self._prefills_since_step = 0
        if overlapped:
            self.stats["decode_overlapped"] += 1
            _metrics.inc("apex_serve_decode_overlapped_total",
                         help="decode steps launched before the "
                              "previous step's tokens were read back")
        if prev is not None and W is not None:
            self._emit_blocks(prev, next_tokens)
        elif prev is not None:
            self._emit(prev, next_tokens)
        return True

    def _block_tokens(self, s: _Slot, ids: np.ndarray) -> List[int]:
        """The tokens the commit of ``s``'s next block to commit emits,
        ``ids`` the block as committed: its positions from the prompt's
        end to the request's (the first block holds what was left of the
        prompt, the last one a surplus that is generated and dropped)."""
        start = s.block.start + s.block.committed * self._block
        plen = len(s.request.prompt)
        lo = max(plen - start, 0)
        hi = min(plen + s.request.max_new_tokens - start, self._block)
        return [int(t) for t in ids[lo:hi]]

    def _block_attrs(self, step: _InFlight, out: np.ndarray) -> Dict:
        """Of the block step read back, for its span: the commits it
        held, those of them that rode a denoising pass of their slot,
        the positions unmasked and the tokens it emits."""
        W = self._block
        rows = out[step.slots]
        commit = rows[:, W + 2] == 1
        return dict(
            commits=int(commit.sum()),
            fused_commits=int((commit & (rows[:, W] == BLOCK_DENOISE)).sum()),
            unmasked=int(rows[:, W + 1].sum()),
            emitted=sum(len(self._block_tokens(self._slots[i],
                                               out[i, W + 3:]))
                        for i in np.flatnonzero(step.slots)
                        if out[i, W + 2] == 1))

    def _emit_blocks(self, step: _InFlight, out: np.ndarray) -> None:
        """A block step's readback -> end of the iteration.  A slot's
        row holds up to two block-forwards, counted and kept (where the
        request keeps a trace) one by one: the COMMIT of the block it
        held, whose tokens are emitted under ONE stamp (now, the moment
        they are on the host) and whose request is evicted if the block
        was its last; then the open block's denoising pass, which emits
        nothing (the pass that leaves a block clean makes it the held
        one: its commit is in the next step's row)."""
        W = self._block
        with _tracing.span("serve.emit") as emit_span:
            now = self._time()
            self.stats["decode_steps"] += 1
            self._record_occupancy()
            tokens, evicted = 0, self.stats["evicted"]
            denoised = commits = 0
            gaps: Dict[str, list] = {}
            for i in np.flatnonzero(step.slots):
                s, row = self._slots[i], out[i]
                plan = s.block
                if row[W + 2] == 1:
                    held = row[W + 3:]
                    if plan.trace is not None:
                        plan.trace.append((
                            plan.start + plan.committed * W,
                            np.concatenate([held, [BLOCK_COMMIT, 0]])))
                    for tok in self._block_tokens(s, held):
                        if not s.token_times:
                            self._observe_first_token(s, now)
                            s.generated.append(tok)
                            s.token_times.append(now)
                        else:
                            self._emit_token(s, tok, now, gaps)
                        tokens += 1
                    commits += 1
                    plan.committed += 1
                    self._positions[i] = plan.start + plan.committed * W
                    if plan.committed >= plan.blocks:
                        self._evict(i)
                        continue
                if row[W] != BLOCK_DENOISE:     # no open block, or the
                    continue                    # device dropped the pass
                denoised += 1
                if plan.trace is not None:
                    plan.trace.append((plan.start + plan.opened * W,
                                       row[:W + 2]))
                if self.model.mask_id not in row[:W]:
                    plan.opened += 1
            self.stats["block_passes"] += denoised + commits
            self.stats["block_commits"] += commits
            self._observe_gaps(gaps)
            emit_span.set(tokens=tokens,
                          evicted=self.stats["evicted"] - evicted)

    def _emit(self, step: _InFlight, next_tokens: np.ndarray) -> None:
        """Readback -> end of the iteration: token bookkeeping,
        histogram observations, evictions.  A token's time is now, the
        moment it is on the host."""
        with _tracing.span("serve.emit") as emit_span:
            now = self._time()
            self.stats["decode_steps"] += 1
            self._record_occupancy()
            tokens, evicted = 0, self.stats["evicted"]
            gaps: Dict[str, list] = {}
            for i in np.flatnonzero(step.slots):
                s = self._slots[i]
                tok = int(next_tokens[i])
                self._emit_token(s, tok, now, gaps)
                tokens += 1
                if (len(s.generated) >= s.request.max_new_tokens
                        or (s.request.eos_id is not None
                            and tok == s.request.eos_id)):
                    self._evict(i)
            self._observe_gaps(gaps)
            emit_span.set(tokens=tokens,
                          evicted=self.stats["evicted"] - evicted)

    def _emit_token(self, s: _Slot, tok: int, now: float,
                    gaps: Dict[str, list]) -> None:
        """One emitted token of a decode or verify step: its gap into
        the step's ``gaps`` (a list a lane of ``(gap, request)``), then
        the slot's stream and times."""
        gap = now - s.token_times[-1]
        gaps.setdefault(s.request.lane, []).append((gap, s.request))
        if self._anomaly is not None:
            self._anomaly.observe("inter_token", gap, lane=s.request.lane)
        s.generated.append(tok)
        s.token_times.append(now)

    def _observe_gaps(self, gaps: Dict[str, list]) -> None:
        """A step's token gaps into the inter-token histogram: ONE call
        a lane (128 calls a step were half of the host's iteration at
        128 slots), the exemplar that of the step's largest gap."""
        for lane, pairs in gaps.items():
            _, worst = max(pairs, key=lambda p: p[0])
            _metrics.observe_many(
                "apex_serve_inter_token_seconds", [g for g, _ in pairs],
                help="previous token -> this token",
                exemplar={"trace_id": worst.trace_id, "rid": worst.rid},
                lane=lane)

    def _step_verify(self) -> None:
        """The speculative step: draft, verify all ``draft_len + 1``
        positions in ONE batched pass, accept the longest matching
        prefix per slot.  Emissions spend the same (slot, draw) seeds
        as the plain decode path — the token stream is bitwise the
        non-speculative stream, delivered faster."""
        B = self.dcfg.max_batch
        W = self.dcfg.draft_len + 1
        self._cow_for_writes(self._active, width=W)
        tokmat = np.zeros((B, W), np.int32)
        seeds = np.zeros((B, W), np.uint32)
        for i in range(B):
            if not self._active[i]:
                continue
            tokmat[i, 0] = self._tokens[i]
            drafts = self._slots[i].proposer.propose()
            if drafts:
                k = min(len(drafts), W - 1)
                tokmat[i, 1:1 + k] = drafts[:k]
            d0 = int(self._draws[i])
            for j in range(W):
                seeds[i, j] = self._seed_at(i, d0 + j)
        # the verify span is ended by hand so the spec ACCEPT counts —
        # known only after the host accepts per slot — ride its attrs
        verify_attrs = (dict(decode_step=self.stats["decode_steps"],
                             active=int(self._active.sum()),
                             draft_len=W - 1,
                             trace_ids=self._resident_trace_ids(),
                             prefills_before=self._prefills_since_step)
                        if _tracing.enabled() else {})
        verify_span = _tracing.span("serve.verify_step", **verify_attrs)
        emit_span = None
        emitted_before = self.stats["spec_emitted"]
        evicted_before = self.stats["evicted"]
        try:
            self.pools, sampled = self._call(
                "_verify", self.params, self.pools,
                jnp.asarray(tokmat), jnp.asarray(self._positions),
                jnp.asarray(self._active), jnp.asarray(self._page_tables),
                jnp.asarray(seeds))
            t_enq = time.perf_counter()
            sampled = np.asarray(sampled)
            self.stats["device_wait_s"] += time.perf_counter() - t_enq
            self._prefills_since_step = 0
            # the accept loop, under the verify span as its child
            emit_span = _tracing.span("serve.emit")
            now = self._time()
            self.stats["decode_steps"] += 1
            self.stats["spec_steps"] += 1
            self._record_occupancy()
            gaps: Dict[str, list] = {}
            for i in range(B):
                if not self._active[i]:
                    continue
                s = self._slots[i]
                emit = accepted_tokens(tokmat[i], sampled[i])
                out: List[int] = []
                for tok in emit:  # clamp to the generation budget / eos
                    out.append(tok)
                    if s.request.eos_id is not None \
                            and tok == s.request.eos_id:
                        break
                    if len(s.generated) + len(out) \
                            >= s.request.max_new_tokens:
                        break
                self._draws[i] += len(out)  # one draw per emission
                for tok in out:
                    self._emit_token(s, tok, now, gaps)
                s.proposer.extend(out)
                self.stats["spec_emitted"] += len(out)
                _metrics.inc("apex_serve_spec_emitted_total", len(out),
                             help="tokens emitted by verify steps")
                self._tokens[i] = out[-1]
                self._positions[i] += len(out)
                if (len(s.generated) >= s.request.max_new_tokens
                        or (s.request.eos_id is not None
                            and out[-1] == s.request.eos_id)):
                    self._evict(i)
            self._observe_gaps(gaps)
        except BaseException:
            verify_span.set(error=True)
            raise
        finally:
            # the accept loop can raise too — the spans must never leak
            # open (they would render as a phantom wedged verify step
            # in every later export and flight-recorder dump)
            emitted = self.stats["spec_emitted"] - emitted_before
            if emit_span is not None:
                emit_span.end(
                    tokens=emitted,
                    evicted=self.stats["evicted"] - evicted_before)
            verify_span.end(emitted=emitted)

    def run_until_drained(self, max_steps: int = 10_000) -> List[Completion]:
        """Drive ``step()`` until queues and slots are empty (the
        test/driver convenience loop)."""
        for _ in range(max_steps):
            if self.idle():
                return self.completed
            self.step()
        raise RuntimeError(
            f"serve loop not drained after {max_steps} steps "
            f"(queue={len(self.queue) + len(self.be_queue)}, "
            f"active={self.num_active})")
