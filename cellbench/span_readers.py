"""Arithmetic shared by the per-layer metrics that read the serving
scheduler's spans (``apex_tpu/inference/scheduler.py``): a first token
taken apart into queue and prefill, the share of decode steps that
followed a prefill, and the device's idle time split by whether the
host was inside a device call.

``ctx["spans"]`` holds the traced stretch only (4 s), and a
``serve.request`` span lives 8 to 27 s, so the readers of whole
requests take the program's own buffer (:func:`program_spans`).  They
keep the window's requests (``rid`` below the adapter's warm-up ids)
and leave out what the profiler disturbed, by the rule of
``WindowTrace.undisturbed`` read off the spans: a stretch of a second
or more without a ``serve.decode_step`` span is the profiler starting
or stopping, and the two seconds after it are its backlog.

Every reader returns None where the program records no such span or
attribute (a parent commit older than the spans).
"""

from typing import Dict, List, Optional, Sequence, Tuple

from cellbench import arith
from cellbench.adapters.serve import WARMUP_RID
from cellbench.profiling import WindowTrace
from cellbench.trace import reduce as tr

STEP = "serve.decode_step"
#: spans that are one device call, launch to readback: idle time under
#: them is the device waiting for the host to launch or to read
DEVICE_CALLS = (STEP, "serve.prefill", "serve.verify_step")
STALL_S = 1.0


def program_spans() -> List[dict]:
    """Every span the program's tracer still holds (the adapter
    configured it with room for the whole run)."""
    from apex_tpu.observability import tracing

    tracer = tracing.get_tracer()
    return tracer.spans() if tracer is not None else []


def _end(span: dict) -> float:
    return span["ts"] + span["dur_us"] / 1e6


def stalls(spans: Sequence[dict]) -> List[Tuple[float, float]]:
    """Stretches of ``STALL_S`` or more between one decode step and the
    next, as (from, to) in the spans' clock."""
    steps = sorted((s for s in spans if s["name"] == STEP),
                   key=lambda s: s["ts"])
    return [(_end(a), b["ts"]) for a, b in zip(steps, steps[1:])
            if b["ts"] - _end(a) >= STALL_S]


def disturbed(t0: float, t1: float, stalled) -> bool:
    """Whether [t0, t1] touches a stall or the settling after it (the
    step whose end opens a stall is not part of it)."""
    return any(t0 <= b + WindowTrace.SETTLE_S and t1 > a
               for a, b in stalled)


def window_requests(spans: Sequence[dict]) -> List[dict]:
    """``serve.request`` spans of the window's own requests that carry
    the decomposition of their first token."""
    return [s for s in spans if s["name"] == "serve.request"
            and s["attrs"].get("rid", WARMUP_RID) < WARMUP_RID
            and s["attrs"].get("queue_s") is not None
            and s["attrs"].get("prefill_s") is not None]


def first_token_part_p90_ms(spans: Sequence[dict], key: str
                            ) -> Optional[float]:
    """90th percentile of ``queue_s`` or ``prefill_s`` over the
    window's requests whose submit -> first token the profiler left
    alone."""
    stalled = stalls(spans)
    kept = [1e3 * r["attrs"][key] for r in window_requests(spans)
            if not disturbed(r["ts"], r["ts"] + r["attrs"]["ttft_s"],
                             stalled)]
    return arith.percentile(kept, 90) if kept else None


def steps_after_prefill_percent(spans: Sequence[dict]) -> Optional[float]:
    """Share of the decode steps, while the window's requests were
    arriving and outside the profiler's stalls, that had one prefill or
    more run since the step before: those steps' token gaps are a
    decode step PLUS a prefill."""
    requests = window_requests(spans)
    if not requests:
        return None
    lo = min(r["ts"] for r in requests)
    hi = max(r["ts"] for r in requests)
    stalled = stalls(spans)
    steps = [s["attrs"]["prefills_before"] for s in spans
             if s["name"] == STEP and lo <= s["ts"] <= hi
             and "prefills_before" in s["attrs"]
             and not disturbed(s["ts"], _end(s), stalled)]
    if not steps:
        return None
    return 100.0 * sum(1 for n in steps if n >= 1) / len(steps)


def _covered_ns(lo: int, hi: int, intervals) -> int:
    """ns of [lo, hi) that the union of ``intervals`` covers."""
    return tr.busy_ns([["", max(lo, a), min(hi, b) - max(lo, a)]
                       for a, b in intervals if a < hi and b > lo])


def idle_split_ms_per_step(ctx: Dict) -> Optional[Dict[str, float]]:
    """The traced stretch's device idle time per traced step, split by
    what the host was in at each idle moment: ``in_call`` inside a
    device call's span (launch and readback; ``serve.emit`` nested in a
    verify step is bookkeeping and does not count), ``between_calls``
    everywhere else (admission, bookkeeping, the caller's loop).  The
    two add up to the idle share times the stretch.

    The overlap is taken moment by moment, not gap by gap as
    ``reduce.idle_gaps`` does: the device idles once per call, for 3.5
    to 4.7 ms, from the end of step A on the device over A's readback,
    the bookkeeping and B's launch to B's start, and no one span covers
    half of such a gap in every run (chip runs of PR 24: all of the
    chat cell's gaps went to ``serve.decode_step``, two thirds of the
    over cell's to a whole-life span, for the same anatomy)."""
    red, n = ctx["reduced"], ctx["counters"].get("traced_steps")
    if red is None or not n:
        return None
    busy = tr.merged(tr.clip(red.first_device(), red.lo, red.hi))
    edges = [red.lo] + [t for pair in busy for t in pair] + [red.hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]

    def stretch_of(names):
        return [(s[1], s[1] + s[2]) for s in red.host_spans
                if s[0] in names and s[1] < red.hi and s[1] + s[2] > red.lo]

    calls, emits = stretch_of(DEVICE_CALLS), stretch_of(("serve.emit",))
    nested = [(max(a, c), min(b, d)) for a, b in calls for c, d in emits
              if max(a, c) < min(b, d)]
    in_call = sum(_covered_ns(a, b, calls) - _covered_ns(a, b, nested)
                  for a, b in gaps)
    total = sum(b - a for a, b in gaps)
    return {"in_call": in_call / 1e6 / n,
            "between_calls": (total - in_call) / 1e6 / n}
