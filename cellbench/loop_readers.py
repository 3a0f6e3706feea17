"""Arithmetic of the per-layer metrics that take the serve loop's own
iteration apart (``apex_tpu/inference/scheduler.py``): what the host
does in a decode period (``host_iter.*``), how much of a launch is
upload (``launch_upload.*``), what a synchronous prefill costs the
device (``prefill_stall.*``), the share of a prefill that is padding
(``prefill_padding.*``) and the prefills' share of the WHOLE window
(``prefill_share_window.serve``).

The span readers take the program's own buffer over the whole window
(``span_readers.program_spans``), keep what started while the window's
requests were arriving, and leave out what the profiler disturbed, by
``span_readers.stalls`` / ``disturbed``.  The two that read the device
trace work on the traced stretch.

Every reader returns None where the spans lack the attributes this
module reads (``wait_us``, ``upload_us``, ``behind_step``: a program
older than them), so such a program's runs print none of the nine.
"""

import bisect
import re
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from cellbench import decode_ops, span_readers
from cellbench.profiling import WindowTrace
from cellbench.span_readers import (
    STEP, _covered_ns, _end, disturbed, stalls,
)
from cellbench.trace import reduce as tr

PREFILL = "serve.prefill"
ADMIT = "serve.admit"


def _window(spans: Sequence[dict]) -> Optional[Tuple[float, float]]:
    """First to last submit of the window's own requests."""
    requests = span_readers.window_requests(spans)
    if not requests:
        return None
    return (min(r["ts"] for r in requests), max(r["ts"] for r in requests))


def _kept(spans: Sequence[dict], name: str, attr: str) -> List[dict]:
    """Spans called ``name`` that carry ``attr``, started inside the
    window and untouched by the profiler's stalls, by start."""
    win = _window(spans)
    if win is None:
        return []
    stalled = stalls(spans)
    return sorted((s for s in spans if s["name"] == name
                   and attr in s["attrs"] and win[0] <= s["ts"] <= win[1]
                   and not disturbed(s["ts"], _end(s), stalled)),
                  key=lambda s: s["ts"])


def host_iter_ms(spans: Sequence[dict], notes: Optional[list] = None
                 ) -> Optional[float]:
    """Mean, over pairs of CONSECUTIVE iterations that both launched
    onto a step in flight, of the period (start to start) less the time
    blocked on the device in it: the step's own ``wait_us`` and that of
    the prefills run between the two.  What is left is everything the
    host does in a period, the caller's loop included; an empty server
    is not in it (``in_flight`` is 0 after one)."""
    win = _window(spans)
    if win is None:
        return None
    stalled = stalls(spans)
    steps = sorted((s for s in spans if s["name"] == STEP),
                   key=lambda s: s["ts"])
    prefills = sorted((s["ts"], s["attrs"].get("wait_us", 0))
                      for s in spans if s["name"] == PREFILL)
    starts = [ts for ts, _ in prefills]
    periods, step_waits, prefill_waits = [], [], []
    for a, b in zip(steps, steps[1:]):
        if not (a["attrs"].get("in_flight") and b["attrs"].get("in_flight")
                and "wait_us" in a["attrs"] and win[0] <= a["ts"] <= win[1]
                and not disturbed(a["ts"], b["ts"], stalled)):
            continue
        between = prefills[bisect.bisect_left(starts, a["ts"]):
                           bisect.bisect_left(starts, b["ts"])]
        periods.append((b["ts"] - a["ts"]) * 1e3)
        step_waits.append(a["attrs"]["wait_us"] / 1e3)
        prefill_waits.append(sum(w for _, w in between) / 1e3)
    if not periods:
        return None
    period, wait, behind = (statistics.fmean(v) for v in (
        periods, step_waits, prefill_waits))
    if notes is not None:
        notes.append(
            f"host_iter: {len(periods)} periods, mean {period:.3f} ms = "
            f"host {period - wait - behind:.3f} + wait for the step "
            f"{wait:.3f} + wait for prefills {behind:.3f}")
    return period - wait - behind


def launch_upload_ms(spans: Sequence[dict], notes: Optional[list] = None
                     ) -> Optional[float]:
    """Mean ``upload_us`` of the iterations that launched onto a step
    in flight: the four host arrays made device arrays."""
    kept = [s["attrs"] for s in _kept(spans, STEP, "upload_us")
            if s["attrs"].get("in_flight")]
    if not kept:
        return None
    if notes is not None:
        notes.append(
            f"launch_upload: {len(kept)} launches, mean dispatch_us "
            f"{statistics.fmean(a['dispatch_us'] for a in kept) / 1e3:.3f} "
            f"ms, prep_us "
            f"{statistics.fmean(a['prep_us'] for a in kept) / 1e3:.3f} ms")
    return statistics.fmean(a["upload_us"] for a in kept) / 1e3


def prefill_padding_percent(spans: Sequence[dict]) -> Optional[float]:
    """Share of the window's prefilled positions that are padding."""
    kept = _kept(spans, PREFILL, "wait_us")
    padded = sum(s["attrs"]["padded_tokens"] for s in kept)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(s["attrs"]["tokens"] for s in kept) / padded)


def _on_trace_clock(red, spans: Sequence[dict]) -> List[Tuple[int, int, dict]]:
    """``(start_ns, end_ns, attrs)`` on the trace's clock for the
    ``serve.prefill`` spans of ``spans`` (the host's clock, with
    attributes).  ``Reduced.host_spans`` holds the same spans on the
    trace's clock without their attributes, and the harness keeps the
    offset to itself: a prefill's length in µs names it among them."""
    starts: Dict[int, List[int]] = {}
    for name, start, dur in red.host_spans:
        if name == PREFILL:
            starts.setdefault(dur, []).append(start)
    mine = [s for s in spans if s["name"] == PREFILL]
    offs = []
    for s in mine:
        found = starts.get(int(s["dur_us"] / 1e6 * 1e9), ())
        if len(found) == 1:
            offs.append(found[0] - s["ts"] * 1e9)
    if not offs:
        return []
    off = statistics.median(offs)
    return [(int(s["ts"] * 1e9 + off), int(_end(s) * 1e9 + off), s["attrs"])
            for s in mine]


def prefill_stall_ms(ctx: Dict) -> Optional[float]:
    """Device idle time of the traced stretch, moment by moment (as
    ``span_readers.idle_split_ms_per_step`` takes it), from the start
    of a ``serve.admit`` pass that ran a prefill to the end of the
    first ``serve.decode_step`` after it (the caller's loop and that
    step's preparation lie between the two spans, under neither), over
    the stretch's prefills: what one synchronous prefill's launch,
    readback and late relaunch cost the device."""
    red = ctx["reduced"]
    mine = [s["attrs"] for s in ctx["spans"] if s["name"] == PREFILL
            and "behind_step" in s["attrs"]]
    if red is None or not mine:
        return None

    def inside(name):
        return sorted((s[1], s[1] + s[2]) for s in red.host_spans
                      if s[0] == name and red.lo <= s[1] < red.hi)

    prefills, steps = inside(PREFILL), inside(STEP)
    if not prefills:
        return None
    stalled = []
    for a, b in inside(ADMIT):
        if any(a <= p < b for p, _ in prefills):
            after = next((e for s, e in steps if s >= b), b)
            stalled.append((a, after))
    busy = tr.merged(tr.clip(red.first_device(), red.lo, red.hi))
    edges = [red.lo] + [t for pair in busy for t in pair] + [red.hi]
    idle = sum(_covered_ns(a, b, stalled)
               for a, b in zip(edges[::2], edges[1::2]) if b > a)
    means = ", ".join(
        f"{k} {statistics.fmean(a[k] for a in mine) / 1e3:.3f}"
        for k in ("upload_us", "enqueue_us", "wait_us"))
    ctx["notes"].append(
        f"prefill_stall: {len(prefills)} prefills in the stretch; of the "
        f"{len(mine)} that lie whole in it, means in ms: {means}; "
        f"{sum(a['behind_step'] for a in mine)} behind a step in flight")
    return idle / 1e6 / len(prefills)


def prefill_share_window_percent(ctx: Dict) -> Optional[float]:
    """The prefills' device time over the WHOLE undisturbed window, in
    percent of its seconds: the traced stretch gives the median device
    time of a ``jit_prefill`` program a ``padded_tokens`` bucket (each
    program matched to the ``serve.prefill`` span it started under),
    the window's spans say how often each bucket ran."""
    red = ctx["reduced"]
    if red is None or not red.modules:
        return None
    rx = re.compile(decode_ops.PREFILL_PROGRAM)
    programs = sorted((e[1], e[2])
                      for e in next(iter(red.modules.values()))
                      if rx.search(e[0]))
    by_bucket: Dict[int, List[float]] = {}
    for a, b, attrs in _on_trace_clock(red, ctx["spans"]):
        ns = sum(d for s, d in programs if a <= s < b)
        if ns and "wait_us" in attrs:
            by_bucket.setdefault(attrs["padded_tokens"], []).append(ns / 1e9)
    spans = span_readers.program_spans()
    kept = _kept(spans, PREFILL, "wait_us")
    if not by_bucket or not kept:
        return None
    cost = {n: statistics.median(v) for n, v in by_bucket.items()}
    for n in sorted({s["attrs"]["padded_tokens"] for s in kept} - set(cost)):
        near = min(by_bucket, key=lambda m: abs(m - n))
        cost[n] = cost[near] * n / near
        ctx["notes"].append(
            f"prefill_share_window: the stretch ran no prefill of {n}; "
            f"it takes the one of {near} scaled by {n / near:.3f}")
    win = _window(spans)
    lost = tr.busy_ns([
        ["", int(max(a, win[0]) * 1e9),
         int((min(b + WindowTrace.SETTLE_S, win[1]) - max(a, win[0])) * 1e9)]
        for a, b in stalls(spans)
        if a < win[1] and b + WindowTrace.SETTLE_S > win[0]]) / 1e9
    seconds = (win[1] - win[0]) - lost
    if seconds <= 0:
        return None
    ctx["notes"].append(
        f"prefill_share_window: {len(kept)} prefills in {seconds:.2f} "
        f"undisturbed s; device s a bucket "
        f"{ {n: round(c, 4) for n, c in sorted(cost.items())} } from "
        f"{sum(len(v) for v in by_bucket.values())} traced")
    return 100.0 * sum(cost[s["attrs"]["padded_tokens"]]
                       for s in kept) / seconds
