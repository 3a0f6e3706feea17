"""Host-side distributed tracing: where did a step's wall time go?

The metrics layer (PR 10) answers *how much* — counters, histograms,
goodput fractions; this module answers *where*: a near-zero-overhead
span API over the host-side phases of a run (data wait, step dispatch,
telemetry harvest, checkpoint save/restore, serving admission → prefill
→ decode, supervisor attempt/backoff), correlated with logs and
metrics through the one ``(run_id, step)`` join key
(:mod:`~apex_tpu.observability.correlation`), and exported two ways:

- **JSONL** (:meth:`Tracer.export_jsonl`): one line per span — the
  ``log_structured`` greppability contract, same fields every other
  sidecar carries (``ts``/``rank``/``run_id``/``step``).
- **Chrome trace-event / Perfetto JSON**
  (:meth:`Tracer.export_chrome`): load the file straight into
  https://ui.perfetto.dev (or ``chrome://tracing``) — spans render per
  thread with their attributes as args.

Design constraints, each load-bearing:

- **Spans wrap DISPATCH, never run inside jit.**  A traced step is the
  SAME compiled program as an untraced one: tracing on/off is pinned
  to identical collective counts, zero extra host transfers, and
  bitwise-identical loss/params (tests/test_lowered_invariants.py::
  TestTracingTrainStep, tests/test_tracing.py).  Because dispatch is
  asynchronous, a dispatch span measures *host* time — queueing a
  step, not running it.  That is exactly what the span name says
  (``train.step.dispatch``); treating it as device step time is the
  lie analyzer rule APX112 exists to flag.  Real step wall time shows
  up as the steady-state dispatch cadence once the device queue
  throttles the host.
- **Near-zero overhead when off.**  :func:`span` with no tracer
  configured returns a no-op singleton — one module-global read, no
  allocation, no lock.
- **Bounded memory.**  The span buffer is a ring (``capacity`` spans,
  oldest dropped, drop count kept): tracing a week-long run costs the
  same memory as tracing a minute.
- **Thread-aware.**  Spans record their thread id and name — the
  watchdog, preemption, and async-checkpoint threads show up as their
  own Perfetto tracks.
- **Crash-forensics ready.**  OPEN spans (started, never finished —
  the wedged dispatch) are tracked and included in exports and in
  :mod:`~apex_tpu.observability.flightrec` dumps, flagged
  ``open=True`` with their elapsed time: the last open span of a
  wedged process names the step that wedged.

Span naming schema (see docs/observability.md for the full table):
``<subsystem>.<phase>`` — ``train.step.dispatch``, ``train.data_wait``,
``train.checkpoint_save``, ``serve.admit``, ``serve.prefill``,
``serve.decode_step``, ``serve.emit``, ``serve.request``,
``supervisor.attempt``.

A span around device work ends when its result is ON THE HOST, or is
named ``.dispatch`` (``serve.prefill`` and ``serve.decode_step`` end in
a readback; ``train.step.dispatch`` times the enqueue and says so).

Every span has an ``id`` (process-monotonic) and a ``parent``: the id
of the span that was open on the same thread when it started, or None.
A retro-emitted span (:meth:`Tracer.emit`) names its parent itself.

**Compilation is in the trace too.**  While a tracer is installed, every
trace, lowering and backend compile (or cache read) JAX makes becomes a
``compile.trace`` / ``compile.lower`` / ``compile.backend`` span with
JAX's own endpoints, a child of the span open on the compiling thread
(:class:`_CompileSpans`): the first ``serve.prefill`` of a bucket reads
as its compile children plus the rest, and a stall in service names its
cause.  With no tracer installed nothing is registered with
``jax.monitoring``.
"""

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from apex_tpu.observability.correlation import step_context

__all__ = [
    "TracedStep", "Tracer", "TracingScope", "configure", "disable",
    "enabled", "export_run", "get_tracer", "instant", "new_trace_id",
    "span",
]

SCHEMA = "apex_tpu_trace_v1"

_TRACER: Optional["Tracer"] = None

_TRACE_IDS = itertools.count()

_SPAN_IDS = itertools.count(1)


def new_trace_id() -> str:
    """A process-unique request/trace id (``<pid-hex>-<n-hex>``) —
    what the serving scheduler stamps on every request so a p99
    histogram outlier joins back to its spans.  Monotonic per process:
    two requests can never share one."""
    return f"{os.getpid():x}-{next(_TRACE_IDS):x}"


# --------------------------------------------------------------- span core
class _Span:
    """One in-flight span; records itself into the tracer on exit.
    Also usable as a context manager (the common spelling)."""

    __slots__ = ("_tracer", "name", "attrs", "ts", "_t0", "tid",
                 "thread", "_done", "id", "parent", "_stack")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        # correlation captured at START: the step the span belongs to
        # is the step the loop had set when the phase began
        self.attrs = {**step_context(), **attrs}
        self.ts = time.time()
        self._t0 = time.perf_counter()
        t = threading.current_thread()
        self.tid = t.ident or 0
        self.thread = t.name
        self._done = False
        self.id = next(_SPAN_IDS)
        # the span that caused this one: whatever this thread had open
        self._stack = tracer._open_stack()
        self.parent = self._stack[-1].id if self._stack else None
        self._stack.append(self)
        tracer._opened(self)

    def elapsed(self) -> float:
        return time.perf_counter() - self._t0

    def set(self, **attrs) -> "_Span":
        """Attach attributes mid-span (spec accept counts, result
        sizes)."""
        self.attrs.update(attrs)
        return self

    def end(self, **attrs) -> None:
        if self._done:
            return
        self._done = True
        # usually the top; a handle ended out of order (or from another
        # thread) still leaves the stack it was pushed on
        self._stack.remove(self)
        if attrs:
            self.attrs.update(attrs)
        self._tracer._finished(self, self.elapsed())

    # ------------------------------------------------- context manager
    def __enter__(self) -> "_Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        self.end()
        return False


class _NoopSpan:
    """The disabled-tracing singleton: every operation is a no-op."""

    __slots__ = ()
    id = None
    parent = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    def end(self, **attrs):
        pass

    def elapsed(self) -> float:
        return 0.0


_NOOP = _NoopSpan()


class Tracer:
    """Bounded in-memory span buffer + exporters.

    Thread-safe: spans may start/finish on any thread (the watchdog
    fires from its own).  ``capacity`` bounds FINISHED spans (ring —
    oldest dropped, counted in ``dropped``); open spans are tracked in
    a side table so a crash dump can name what never finished."""

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=self.capacity)
        self._open: Dict[int, _Span] = {}
        # per-thread stack of this tracer's open spans (``.stack``): its
        # top is the parent of whatever starts next on that thread
        self._local = threading.local()
        self._listeners: List[Callable[[dict], None]] = []
        self.started = 0
        self.finished = 0
        self.dropped = 0
        #: compile events whose span could not be recorded (an error in
        #: the listener, swallowed): a reader of the compile spans says so
        self.compile_errors = 0

    # ----------------------------------------------------------- record
    def span(self, name: str, **attrs) -> _Span:
        """Start a span; ``with tracer.span("x"):`` or keep the handle
        and call ``.end()``."""
        return _Span(self, name, attrs)

    def instant(self, name: str, **attrs) -> None:
        """A zero-duration marker event (Chrome ``i`` phase)."""
        t = threading.current_thread()
        stack = self._open_stack()
        self._record({
            "name": name, "ph": "i", "ts": time.time(), "dur_us": 0,
            "tid": t.ident or 0, "thread": t.name,
            "id": next(_SPAN_IDS),
            "parent": stack[-1].id if stack else None,
            "attrs": {**step_context(), **attrs},
        })

    def emit(self, name: str, start_ts: float, dur_s: float,
             parent: Optional[int] = None, **attrs) -> None:
        """Retro-record a COMPLETED span from its measured endpoints
        (the serving scheduler's admission wait: both timestamps are
        known only at admit time).  It started before whatever is open
        now, so its ``parent`` is what the caller says (the ``id`` of
        the span that ended it), not the top of the thread's stack."""
        t = threading.current_thread()
        self._record({
            "name": name, "ph": "X", "ts": float(start_ts),
            "dur_us": max(int(dur_s * 1e6), 0),
            "tid": t.ident or 0, "thread": t.name,
            "id": next(_SPAN_IDS), "parent": parent,
            "attrs": {**step_context(), **attrs},
        })

    def _compiled(self, name: str, start: float, end: float,
                  attrs: Dict[str, Any]) -> None:
        """One of JAX's compile events as a span (:class:`_CompileSpans`
        calls this on the compiling thread): a child of the innermost
        span open there that started before it."""
        parent = next((s.id for s in reversed(self._open_stack())
                       if s.ts <= start), None)
        self.emit(name, start, end - start, parent=parent, **attrs)

    def add_listener(self, fn: Callable[[dict], None]) -> None:
        """Call ``fn(span_dict)`` on every finished span — the flight
        recorder's feed.  Listener errors are swallowed (observers
        never participate)."""
        self._listeners.append(fn)

    # ------------------------------------------------------- internals
    def _open_stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _opened(self, s: _Span) -> None:
        with self._lock:
            self.started += 1
            self._open[id(s)] = s

    def _finished(self, s: _Span, dur_s: float) -> None:
        with self._lock:
            self._open.pop(id(s), None)
        self._record({
            "name": s.name, "ph": "X", "ts": s.ts,
            "dur_us": max(int(dur_s * 1e6), 0),
            "tid": s.tid, "thread": s.thread,
            "id": s.id, "parent": s.parent, "attrs": dict(s.attrs),
        })

    def _record(self, rec: dict) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(rec)
            self.finished += 1
            listeners = tuple(self._listeners)
        for fn in listeners:
            try:
                fn(rec)
            except Exception:  # noqa: BLE001 — observers never participate
                pass

    # ---------------------------------------------------------- export
    def spans(self) -> List[dict]:
        """Finished spans, oldest first (a snapshot copy)."""
        with self._lock:
            return [dict(s) for s in self._spans]

    def open_spans(self) -> List[dict]:
        """Started-but-unfinished spans with their elapsed time so far
        — the wedged dispatch shows up HERE, flagged ``open``."""
        with self._lock:
            live = list(self._open.values())
        return [{
            "name": s.name, "ph": "X", "ts": s.ts,
            "dur_us": max(int(s.elapsed() * 1e6), 0),
            "tid": s.tid, "thread": s.thread,
            "id": s.id, "parent": s.parent,
            "attrs": dict(s.attrs), "open": True,
        } for s in live]

    def export_jsonl(self, path) -> int:
        """One JSON line per span (finished then open), the sidecar
        contract fields (``ts``/``rank``; ``run_id``/``step`` ride the
        span attrs).  One open/flush/fsync for the whole file append.
        Returns lines written."""
        rank = _rank()
        lines = []
        for rec in self.spans() + self.open_spans():
            lines.append(json.dumps({
                "span": rec["name"], "ph": rec["ph"],
                "ts": round(rec["ts"], 6), "dur_us": rec["dur_us"],
                "tid": rec["tid"], "thread": rec["thread"],
                "id": rec["id"], "parent": rec["parent"],
                "rank": rank, "open": rec.get("open", False),
                **rec.get("attrs", {}),
            }, sort_keys=True, default=str))
        if lines:
            with open(path, "a") as f:
                f.write("\n".join(lines) + "\n")
                f.flush()
                os.fsync(f.fileno())
        return len(lines)

    def export_chrome(self, path) -> int:
        """Chrome trace-event JSON (Perfetto-loadable), written
        ATOMICALLY (tmp+fsync+rename — a wedge dump must never publish
        a torn trace).  Timestamps are epoch microseconds; each thread
        gets a ``thread_name`` metadata event so watchdog/checkpoint
        threads render as named tracks.  Returns the event count."""
        from apex_tpu.io.native import atomic_output

        pid = os.getpid()
        events = []
        threads = {}
        for rec in self.spans() + self.open_spans():
            threads.setdefault(rec["tid"], rec["thread"])
            args = dict(rec.get("attrs", {}), id=rec["id"],
                        parent=rec["parent"])
            if rec.get("open"):
                args["open"] = True
            events.append({
                "name": rec["name"], "ph": rec["ph"],
                "ts": int(rec["ts"] * 1e6), "dur": rec["dur_us"],
                "pid": pid, "tid": rec["tid"], "args": args,
            })
        for tid, tname in sorted(threads.items()):
            events.append({
                "name": "thread_name", "ph": "M", "pid": pid,
                "tid": tid, "args": {"name": tname},
            })
        doc = {"schema": SCHEMA, "displayTimeUnit": "ms",
               "traceEvents": events,
               "otherData": {"rank": _rank(), "dropped": self.dropped}}
        with atomic_output(path) as f:
            f.write(json.dumps(doc).encode())
        return len(events)


def _rank() -> int:
    # the ONE rank resolution (metrics JSONL and span exports join on
    # the rank field — they must never disagree)
    from apex_tpu.observability.metrics import _rank as metrics_rank

    return metrics_rank()


# ---------------------------------------------------------- compile spans
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


class _CompileSpans:
    """JAX's compile events, retro-recorded as spans of the installed
    tracer through :meth:`Tracer.emit`.

    JAX announces each trace, lowering and backend compile twice, on
    the thread that makes it and on ``time.time()``, the tracer's
    clock: a scalar at its start and a time span at its end, both with
    the program's ``fun_name``.  The end becomes a span:

    - ``compile.trace``: Python tracing of a jitted function to a jaxpr;
    - ``compile.lower``: jaxpr to StableHLO, Pallas kernels included;
    - ``compile.backend``: the backend compile, or the read of the
      persistent cache in its place.  ``cache`` says which, from the
      cache's own events on that thread since the last backend span:
      ``"hit"`` (read), ``"miss"`` (compiled and written) or ``"off"``
      (neither: no cache directory, or a compile JAX found too quick
      or too small to keep).

    ``parent`` is the innermost span open on the compiling thread that
    started before the event did (``serve.prefill``,
    ``train.step.dispatch``, ... or None), so a program is named by its
    caller's attributes (a prefill bucket by ``padded_tokens``), and
    after warm-up ANY compile child of a serving span is a program the
    warm-up did not build: the cause of that span's stall.

    **Whole programs only.**  JAX also announces the trace of every
    jitted ``jnp`` function called inside a program's trace or inside a
    lowering rule (thousands a warm-up).  Only an event that started
    with no other compile event open on its thread is recorded; what it
    contains is part of its time.  So the spans of one thread never
    overlap, and there are at most three a program.

    The listeners run only while something compiles; a steady step
    never reaches them."""

    def __init__(self):
        self._local = threading.local()     # .depth, .cache
        self._listening = False

    def listen(self, on: bool) -> None:
        """Register with ``jax.monitoring``, or take the listeners away
        again."""
        if on == self._listening:
            return
        from jax import monitoring

        if on:
            # a thread that was inside a compile when the last tracer
            # left saw a start and no end: its depth does not carry over
            self._local = threading.local()
            monitoring.register_scalar_listener(self._on_start)
            monitoring.register_event_time_span_listener(self._on_end)
            monitoring.register_event_listener(self._on_cache)
        else:
            monitoring.unregister_scalar_listener(self._on_start)
            monitoring.unregister_event_time_span_listener(self._on_end)
            monitoring.unregister_event_listener(self._on_cache)
        self._listening = on

    def _on_start(self, event: str, value, **kw) -> None:
        if event in _COMPILE_SPANS:
            self._local.depth = getattr(self._local, "depth", 0) + 1

    def _on_cache(self, event: str, **kw) -> None:
        verdict = _CACHE_EVENTS.get(event)
        if verdict is not None:
            self._local.cache = verdict

    def _on_end(self, event: str, start: float, end: float, **kw) -> None:
        name = _COMPILE_SPANS.get(event)
        if name is None:
            return
        local = self._local
        # listeners installed in the middle of a compile see an end
        # without its start
        depth = local.depth = max(getattr(local, "depth", 0) - 1, 0)
        attrs = {}
        if name == "compile.backend":   # the verdict is this compile's
            attrs["cache"] = getattr(local, "cache", None) or "off"
            local.cache = None
        tracer = _TRACER
        if depth or tracer is None:
            return
        attrs["fun_name"] = str(kw.get("fun_name"))
        try:
            tracer._compiled(name, start, end, attrs)
        except Exception:  # noqa: BLE001 — observers never participate
            tracer.compile_errors += 1


_COMPILES = _CompileSpans()


def _install(tracer: Optional[Tracer]) -> None:
    """The one place the process tracer changes: JAX's compile events
    are listened to exactly while there is one."""
    global _TRACER
    _TRACER = tracer
    _COMPILES.listen(tracer is not None)


# ------------------------------------------------------- global configure
def configure(capacity: int = 4096,
              tracer: Optional[Tracer] = None) -> Tracer:
    """Install (and return) the process tracer; until this is called
    every :func:`span`/:func:`instant` is a no-op."""
    _install(tracer if tracer is not None else Tracer(capacity=capacity))
    return _TRACER


def disable() -> None:
    _install(None)


def get_tracer() -> Optional[Tracer]:
    return _TRACER


def enabled() -> bool:
    return _TRACER is not None


class TracingScope:
    """``with TracingScope() as tracer:`` — scope a tracer for tests /
    embedded engines (restores the previous one on exit, exactly the
    :class:`~apex_tpu.observability.metrics.MetricsScope` pattern)."""

    def __init__(self, tracer: Optional[Tracer] = None,
                 capacity: int = 4096):
        self.tracer = tracer if tracer is not None \
            else Tracer(capacity=capacity)
        self._prev: Optional[Tracer] = None

    def __enter__(self) -> Tracer:
        self._prev = _TRACER
        _install(self.tracer)
        return self.tracer

    def __exit__(self, *exc):
        _install(self._prev)
        return False


def span(name: str, **attrs):
    """Module-level span against the current tracer — THE instrumented
    spelling (``with span("train.data_wait"): ...``).  One global read
    and a no-op singleton when tracing is off."""
    t = _TRACER
    return t.span(name, **attrs) if t is not None else _NOOP


def instant(name: str, **attrs) -> None:
    t = _TRACER
    if t is not None:
        t.instant(name, **attrs)


def export_run(dir_path, run_id, tracer: Optional["Tracer"] = None
               ) -> Optional[Dict[str, Any]]:
    """Export the current trace under ``dir_path`` with THE repo-wide
    artifact convention — ``trace_<run_id>_<pid>.json`` (Perfetto/
    Chrome) plus ``spans_<run_id>_<pid>.jsonl`` (sidecar contract) —
    the one spelling shared by the train/serve drivers and the wedge
    hook (the e2e forensics test and the docs table both glob these
    names).  Creates ``dir_path`` if missing; returns
    ``{"chrome", "jsonl", "events", "dropped"}``, or None when no
    tracer is installed."""
    t = tracer if tracer is not None else _TRACER
    if t is None:
        return None
    os.makedirs(str(dir_path), exist_ok=True)
    pid = os.getpid()
    chrome = os.path.join(str(dir_path), f"trace_{run_id}_{pid}.json")
    jsonl = os.path.join(str(dir_path), f"spans_{run_id}_{pid}.jsonl")
    n = t.export_chrome(chrome)
    t.export_jsonl(jsonl)
    return {"chrome": chrome, "jsonl": jsonl, "events": n,
            "dropped": t.dropped}


# ----------------------------------------------------- dispatch wrapping
class TracedStep:
    """Wrap a compiled step callable in a DISPATCH span.

    The wrapper lives entirely outside jit: ``lower``/``_cache_size``
    and every other attribute delegate to the wrapped callable, so the
    compiled program — collective counts, host transfers, donation —
    is byte-identical with tracing on or off (the lowered-tier pin),
    and loss/params stay bitwise (the parity pin).  The span measures
    HOST dispatch time (async dispatch returns before the device
    runs); in steady state the device queue throttles dispatch, so the
    span cadence tracks real step time — but a single span is not a
    step-time measurement (analyzer rule APX112's subject)."""

    def __init__(self, fn, name: str = "step.dispatch",
                 attrs: Optional[Dict[str, Any]] = None):
        self._fn = fn
        self._name = str(name)
        self._attrs = dict(attrs or {})

    def __call__(self, *args, **kw):
        # ONE call site, tracer or none: a Mosaic kernel's bytecode
        # holds the lines of the Python frames that traced it, this one
        # among them, and the persistent compile cache's key holds the
        # bytecode, so a second call site was a second executable
        # (PR 49: a traced run compiled the train step anew, 64 s)
        t = _TRACER
        with (t.span(self._name, dispatch=True, **self._attrs)
              if t is not None else _NOOP):
            return self._fn(*args, **kw)

    def __getattr__(self, name):
        return getattr(self._fn, name)
