"""Arithmetic of the per-layer metric that takes ``setup_s`` apart
(``trace_lower.setup``): the program's compile spans
(``apex_tpu/observability/tracing.py``: ``compile.trace``,
``compile.lower``, ``compile.backend``, each with JAX's own endpoints
and the ``fun_name`` of its program) read from the program's own buffer
(``span_readers.program_spans``), since set-up lies before the traced
stretch.

The number is the seconds of set-up spent tracing and lowering: what
every program pays at every start, whatever the compile cache holds,
and what moves with the code (programs warmed, layers unrolled, a
kernel's body).  What the cache does decide, and what is left of
``setup_s``, go to the notes.

"Before the window" has one meaning: ended before the first span of the
traced stretch (``ctx["spans"]``).  Returns None where the program
records no compile span (a parent commit older than them), where there
is no traced stretch to end set-up, or where its tracer lost spans.
"""

import re
from collections import Counter, defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from cellbench.span_readers import _end
from cellbench.trace import reduce as tr

TRACE, LOWER, BACKEND = "compile.trace", "compile.lower", "compile.backend"
COMPILE = (TRACE, LOWER, BACKEND)
_JIT = re.compile(r"^jit\((.*)\)$")


def union_s(spans: Sequence[dict]) -> float:
    """Seconds the spans' intervals cover, thread by thread: an inner
    program's trace lies inside its caller's and counts once."""
    by_thread = defaultdict(list)
    for s in spans:
        by_thread[s.get("tid")].append(["", s["ts"], s["dur_us"] / 1e6])
    return float(sum(b - a for events in by_thread.values()
                     for a, b in tr.merged(events)))


def program_of(span: dict) -> str:
    """``step`` of both ``step`` (a trace) and ``jit(step)`` (its
    lowering and compile)."""
    name = str(span["attrs"].get("fun_name"))
    found = _JIT.match(name)
    return found.group(1) if found else name


def _caller(span: dict, by_id: Dict[int, dict]) -> str:
    """What names the call that compiled: its span's ``padded_tokens``
    (a prefill bucket) or its span's name."""
    parent = by_id.get(span.get("parent"))
    if parent is None:
        return "no span"
    padded = parent["attrs"].get("padded_tokens")
    return parent["name"] if padded is None else f"{padded} tokens"


def top_programs(compiles: Sequence[dict], spans: Sequence[dict], n: int = 5
                 ) -> List[Tuple[str, str, Dict[str, float]]]:
    """The ``n`` (program, caller) pairs with the most compile seconds,
    each as seconds by kind of span."""
    by_id = {s["id"]: s for s in spans if "id" in s}
    secs = defaultdict(Counter)
    for s in compiles:
        secs[program_of(s), _caller(s, by_id)][s["name"]] += s["dur_us"] / 1e6
    ranked = sorted(secs.items(), key=lambda kv: -sum(kv[1].values()))
    return [(fun, caller, {k: parts[k] for k in COMPILE})
            for (fun, caller), parts in ranked[:n]]


def trace_lower_s(spans: Sequence[dict], ctx: Dict, dropped: int,
                  errors: int = 0) -> Optional[float]:
    """Seconds in ``compile.trace`` and ``compile.lower`` spans that
    ended before the traced stretch began (``ctx["spans"]``; a compile
    inside the window already fails the run), as the union of their
    intervals a thread.  ``dropped``: spans the tracer's ring lost;
    ``errors``: compile events its listener failed to record."""
    compiles = [s for s in spans if s["name"] in COMPILE]
    inside = ctx["spans"]
    if not compiles or not inside:
        return None
    lo = min(s["ts"] for s in inside)
    hi = max(_end(s) for s in inside)
    before = [s for s in compiles if _end(s) <= lo]
    in_window = sum(1 for s in compiles if _end(s) > lo and s["ts"] < hi)
    value = union_s([s for s in before if s["name"] in (TRACE, LOWER)])

    secs, programs = Counter(), Counter()
    for s in before:
        key = s["name"] if s["name"] != BACKEND \
            else s["attrs"].get("cache", "off")
        secs[key] += s["dur_us"] / 1e6
        programs[key] += 1
    backend = ", ".join(f"{cache} {secs[cache]:.2f} s ({programs[cache]} "
                        f"programs)" for cache in ("hit", "miss", "off"))
    setup_s = ctx["e2e"].get("setup_s")
    rest = ("not known" if setup_s is None else
            f"{setup_s - union_s(before):.2f} s of {setup_s:.2f}")
    notes = ctx["notes"]
    notes.append(
        f"set-up's compile spans: trace {secs[TRACE]:.2f} s + lower "
        f"{secs[LOWER]:.2f} s in {programs[LOWER]} programs; backend "
        f"{backend}; set-up less every compile span (weights, uploads, "
        f"first runs) {rest}; {in_window} compile spans inside the window "
        f"(0 expected); tracer dropped {dropped}, failed to record "
        f"{errors}")
    notes.append("set-up's compile seconds, the most: " + "; ".join(
        f"{fun} under {caller}: trace {p[TRACE]:.2f} + lower "
        f"{p[LOWER]:.2f} + backend {p[BACKEND]:.2f}"
        for fun, caller, p in top_programs(before, spans)))
    return None if dropped or errors else value
