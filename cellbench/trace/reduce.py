"""From a device trace and host spans to metrics.

Two stages, so the arithmetic can be checked on a small recorded trace
(``cellbench/trace/recorded_v5e.json``) without a chip:

``load_xplane`` reads the profiler's ``.xplane.pb`` with
``jax.profiler.ProfileData`` into plain lists: per device the operations
that ran on it, ``[label, start_ns, duration_ns]`` (the "XLA Ops" line;
the label is the HLO instruction's text, which begins with a Pallas
kernel's own name), the programs that ran (the "XLA Modules" line), and
the one host annotation that ties the trace's clock to ``time.time()``.

The op line nests: a ``while`` holds the ops of its body.  A busy
interval is a union, which nesting does not disturb; a time by name is
taken from each event's SELF time, its duration less its children's, so
a loop is not counted on top of its body.

Everything else works on those lists: the union of busy intervals, the
idle share, time by kernel-name pattern, the operations that took most
time, and the idle gaps attributed to the host span that covers them.
"""

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Sequence  # [label, start_ns, duration_ns(, self_ns)]
SYNC_NAME = "cellbench.sync"

#: the lines of a device plane: one event per executed HLO op, and one
#: per executed program
_OP_LINE, _MODULE_LINE = "XLA Ops", "XLA Modules"
LABEL_CHARS = 160


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path: str) -> Dict:
    """``{"devices": {name: [Event]}, "modules": {name: [Event]},
    "sync_ns": int | None}`` from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[List]] = {}
    modules: Dict[str, List[List]] = {}
    sync_ns = None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name not in (_OP_LINE, _MODULE_LINE):
                    continue
                events = sorted(
                    ([e.name[:LABEL_CHARS], int(e.start_ns),
                      int(e.duration_ns)] for e in line.events),
                    key=lambda ev: ev[1])
                (devices if line.name == _OP_LINE
                 else modules)[plane.name] = events
        elif plane.name.startswith("/host:") and sync_ns is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == SYNC_NAME:
                        sync_ns = int(e.start_ns)
                        break
                if sync_ns is not None:
                    break
    return {"devices": devices, "modules": modules, "sync_ns": sync_ns}


# ------------------------------------------------------------- arithmetic
def merged(events: Iterable[Event]) -> List[Tuple[int, int]]:
    """The union of the events' intervals as sorted, disjoint
    (start, end) pairs."""
    out: List[List[int]] = []
    for ev in sorted(events, key=lambda ev: ev[1]):
        start, end = ev[1], ev[1] + ev[2]
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def clip(events: Iterable[Event], lo: int, hi: int) -> List[List]:
    """Events cut to the window [lo, hi)."""
    out = []
    for ev in events:
        a, b = max(ev[1], lo), min(ev[1] + ev[2], hi)
        if b > a:
            out.append([ev[0], a, b - a])
    return out


def with_self_times(events: Iterable[Event]) -> List[List]:
    """[label, start, duration, self] for every event: its duration
    less the durations of the events that lie directly inside it."""
    out = [[e[0], e[1], e[2], e[2]]
           for e in sorted(events, key=lambda ev: (ev[1], -ev[2]))]
    stack: List[List] = []
    for ev in out:
        end = ev[1] + ev[2]
        while stack and not (stack[-1][1] <= ev[1]
                             and end <= stack[-1][1] + stack[-1][2]):
            stack.pop()
        if stack:
            stack[-1][3] -= ev[2]
        stack.append(ev)
    for ev in out:
        ev[3] = max(ev[3], 0)
    return out


def _self(ev: Event) -> int:
    return ev[3] if len(ev) > 3 else ev[2]


def busy_ns(events: Iterable[Event]) -> int:
    return sum(b - a for a, b in merged(events))


def time_by_pattern(events: Iterable[Event], pattern: str) -> int:
    """Summed self time (ns) of the events whose label matches."""
    rx = re.compile(pattern)
    return sum(_self(ev) for ev in events if rx.search(ev[0]))


def top_ops(events: Iterable[Event], n: int = 10) -> List[List]:
    """The ``n`` operations with the most summed self time, as
    [label, seconds]."""
    total: Dict[str, int] = {}
    for ev in events:
        total[ev[0]] = total.get(ev[0], 0) + _self(ev)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


def idle_gaps(events: Iterable[Event], lo: int, hi: int,
              host_spans: Sequence[Sequence], n: int = 10) -> List[List]:
    """Idle time of one device in [lo, hi), attributed to what the host
    was doing: each gap between busy intervals goes to the shortest host
    span (``[name, start_ns, duration_ns]`` on the trace's clock) that
    covers at least half of it (the innermost: a span for a request's
    whole life covers everything and says nothing), or to ``"(no host
    span)"``.  Returns the ``n`` largest totals as [name, seconds]."""
    busy = merged(clip(events, lo, hi))
    gaps, cursor = [], lo
    for a, b in busy:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if hi > cursor:
        gaps.append((cursor, hi))
    spans = sorted(([s[0], int(s[1]), int(s[1]) + int(s[2])]
                    for s in host_spans), key=lambda s: s[1])
    total: Dict[str, int] = {}
    for a, b in gaps:
        best, best_len = "(no host span)", None
        for name, s0, s1 in spans:
            if s0 >= b:
                break
            cover = min(b, s1) - max(a, s0)
            if 2 * cover >= b - a and (best_len is None
                                       or s1 - s0 < best_len):
                best, best_len = name, s1 - s0
        total[best] = total.get(best, 0) + (b - a)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


class Reduced:
    """One traced window: per-device op events cut to the window, the
    host spans moved onto the trace's clock, and the numbers every
    reader starts from."""

    def __init__(self, devices: Dict[str, List[Event]], lo: int, hi: int,
                 host_spans: Sequence[Sequence] = (),
                 modules: Optional[Dict[str, List[Event]]] = None):
        self.lo, self.hi = int(lo), int(hi)
        self.devices = {k: with_self_times(clip(v, self.lo, self.hi))
                        for k, v in sorted(devices.items())}
        self.modules = {k: [e for e in v if self.lo <= e[1] < self.hi]
                        for k, v in sorted((modules or {}).items())}
        self.host_spans = [list(s) for s in host_spans]
        self.window_s = (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(busy_ns(ev) for ev in self.devices.values()) \
            / len(self.devices) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds(self, pattern: str) -> float:
        """Time matching ``pattern``, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(time_by_pattern(ev, pattern)
                   for ev in self.devices.values()) \
            / len(self.devices) / 1e9

    def count(self, pattern: str) -> float:
        """Events matching ``pattern``, averaged over the devices."""
        rx = re.compile(pattern)
        if not self.devices:
            return 0.0
        return sum(1 for ev in self.devices.values() for e in ev
                   if rx.search(e[0])) / len(self.devices)

    def programs(self, pattern: str) -> float:
        """Programs whose name matches that started inside the window,
        averaged over the devices."""
        rx = re.compile(pattern)
        if not self.modules:
            return 0.0
        return sum(1 for ev in self.modules.values() for e in ev
                   if rx.search(e[0])) / len(self.modules)

    def first_device(self) -> List[Event]:
        return next(iter(self.devices.values()), [])

    def breakdown(self) -> Dict[str, List]:
        ev = self.first_device()
        return {"device_ops": top_ops(ev, 10),
                "idle_gaps": idle_gaps(ev, self.lo, self.hi,
                                       self.host_spans, 10)}


def reduce_trace(loaded: Dict, sync_host_s: Optional[float],
                 window_host_s: Tuple[float, float],
                 host_spans_s: Sequence[Sequence] = ()) -> Reduced:
    """Put the host's window and spans (``time.time()`` seconds) on the
    trace's clock through the sync annotation, and cut the trace to the
    window.  ``host_spans_s`` are [name, start_s, duration_s]."""
    if loaded["sync_ns"] is None or sync_host_s is None:
        raise ValueError("the trace holds no clock annotation")
    off = loaded["sync_ns"] - sync_host_s * 1e9   # trace_ns = host_ns + off
    to_ns = lambda t: int(t * 1e9 + off)
    spans = [[n, to_ns(s), int(d * 1e9)] for n, s, d in host_spans_s]
    return Reduced(loaded["devices"], to_ns(window_host_s[0]),
                   to_ns(window_host_s[1]), spans, loaded.get("modules"))
