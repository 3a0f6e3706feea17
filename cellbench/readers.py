"""The general readers of per-layer metrics.

A per-layer metric is ``cellbench/layer_metrics/<name>.json``: its
layer, unit, the end-to-end metric it should move, the cells that report
it, and a ``reader`` block naming one of the kinds below with its
parameters.  A metric that needs arithmetic of its own puts
``<name>.py`` beside the JSON with a ``read(ctx)`` function, which takes
the place of the kind.  A reader that finds nothing to read returns
None and the harness leaves the metric out of the line.

``ctx`` (a dict) is what a traced run hands every reader:

- ``reduced``: :class:`cellbench.trace.reduce.Reduced` of the traced
  window (None when no device trace could be taken);
- ``spans``: the program's host spans inside the traced window,
  ``{"name", "ts", "dur_us", "attrs"}`` (``observability/tracing``);
- ``counters``: numbers the adapter counted (steps and tokens in the
  traced window, occupancy, compiled-step memory, ...);
- ``e2e``: this run's end-to-end values;
- ``model``, ``args``, ``traffic``: the configuration and the mix;
- ``peaks``: this device's row of ``peaks.json``; ``chips``;
- ``counts(name)``: loads ``cellbench/counts/<name>.py``.
"""

import statistics
from typing import Callable, Dict, Optional


def _steps(ctx) -> Optional[float]:
    n = ctx["counters"].get("traced_steps")
    return n if n else None


def counter(ctx, key, scale=1.0):
    val = ctx["counters"].get(key)
    return None if val is None else val * scale


def e2e(ctx, key, scale=1.0):
    val = ctx["e2e"].get(key)
    return None if val is None else val * scale


def span_median_ms(ctx, span):
    durs = [s["dur_us"] / 1e3 for s in ctx["spans"] if s["name"] == span]
    return statistics.median(durs) if durs else None


def span_gap_mean_ms(ctx, after, before):
    """Mean host time from the end of a span named in ``after`` to the
    start of the next span named in ``before`` (a mean, not a median: it
    times the total the host adds between device calls)."""
    names = set(after) | set(before)
    seq = sorted((s for s in ctx["spans"] if s["name"] in names),
                 key=lambda s: s["ts"])
    gaps = []
    for a, b in zip(seq, seq[1:]):
        if a["name"] in after and b["name"] in before:
            gaps.append((b["ts"] - (a["ts"] + a["dur_us"] / 1e6)) * 1e3)
    return statistics.fmean(gaps) if gaps else None


def device_ms_per_step(ctx, pattern):
    red, n = ctx["reduced"], _steps(ctx)
    if red is None or n is None:
        return None
    secs = red.seconds(pattern)
    return secs * 1e3 / n if secs > 0 else None


def device_other_ms_per_step(ctx, exclude):
    """Device time of the ops matching none of ``exclude``."""
    red, n = ctx["reduced"], _steps(ctx)
    if red is None or n is None:
        return None
    rx = "|".join(f"(?:{p})" for p in exclude)
    secs = red.seconds("") - red.seconds(rx)
    return secs * 1e3 / n


def program_median_ms(ctx, pattern):
    """Median device time of the programs (jitted functions) whose name
    matches, first device."""
    import re

    red = ctx["reduced"]
    if red is None or not red.modules:
        return None
    rx = re.compile(pattern)
    durs = [e[2] / 1e6 for e in next(iter(red.modules.values()))
            if rx.search(e[0])]
    return statistics.median(durs) if durs else None


def device_idle_percent(ctx):
    red = ctx["reduced"]
    return None if red is None else 100.0 * red.idle_share


def roofline_percent(ctx, counts, kernels):
    """The least time the chip could take for the calls the trace
    holds, over the time they took.  ``counts`` names a module under
    ``cellbench/counts/`` whose ``per_call(ctx)`` gives, for each kernel
    pattern in ``kernels``, the operations and bytes one call needs."""
    red = ctx["reduced"]
    if red is None:
        return None
    work = ctx["counts"](counts).per_call(ctx)
    peaks = ctx["peaks"]
    least = took = 0.0
    bound_by = {}
    for pattern in kernels:
        calls, secs = red.count(pattern), red.seconds(pattern)
        if not calls or pattern not in work:
            continue
        w = work[pattern]
        t_ops = w["flops"] / peaks["bf16_flops_per_s"]
        t_bytes = w["bytes"] / peaks["hbm_bytes_per_s"]
        bound_by[pattern] = "compute" if t_ops >= t_bytes else "memory"
        least += calls * max(t_ops, t_bytes)
        took += secs
    if took <= 0:
        return None
    ctx["notes"].append(f"roofline {counts}: bound by {bound_by}")
    return 100.0 * least / took


KINDS: Dict[str, Callable] = {
    f.__name__: f for f in (
        counter, e2e, span_median_ms, span_gap_mean_ms, program_median_ms,
        device_ms_per_step,
        device_other_ms_per_step, device_idle_percent, roofline_percent)
}


def read(metric: Dict, ctx: Dict, custom=None):
    """Value of one per-layer metric in this run, or None."""
    if custom is not None:
        return custom.read(ctx)
    spec = dict(metric["reader"])
    kind = spec.pop("kind")
    if kind not in KINDS:
        raise ValueError(f"metric {metric['name']}: unknown reader kind "
                         f"{kind!r}")
    return KINDS[kind](ctx, **spec)
