"""The device trace of a few steady seconds inside the window."""

import shutil
import time
from pathlib import Path
from typing import Optional, Sequence

from cellbench.trace import reduce as tr


class WindowTrace:
    """Starts ``jax.profiler`` once the window is ``start_at`` seconds
    old and stops it ``length`` seconds later.  The adapter calls
    :meth:`tick` between units of work and brackets start and stop with
    whatever makes the traced stretch hold whole units.  Off (every
    method a no-op) unless ``enabled``.  Starting and stopping the
    profiler holds the calling thread for seconds (3.4 s and about 1 s
    on a v5e host); :attr:`stalls` records both stretches so that a
    reading of single requests can leave out those they disturbed."""

    SETTLE_S = 2.0      # after a stall, until its backlog is admitted

    def __init__(self, enabled: bool, out_dir: Path, start_at: float,
                 length: float):
        self.enabled = enabled
        self.dir = Path(out_dir)
        self.start_at, self.length = start_at, length
        self.t_start: Optional[float] = None    # time.time()
        self.t_stop: Optional[float] = None
        self.sync_host_s: Optional[float] = None
        self.stalls: list = []                  # (from, to), time.time()

    @property
    def running(self) -> bool:
        return self.t_start is not None and self.t_stop is None

    def should_start(self, age: float) -> bool:
        return self.enabled and self.t_start is None and age >= self.start_at

    def should_stop(self, age: float) -> bool:
        return self.running and age >= self.start_at + self.length

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        t_call = time.time()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.sync_host_s = time.time()
        with jax.profiler.TraceAnnotation(tr.SYNC_NAME):
            pass
        self.t_start = time.time()
        self.stalls.append((t_call, self.t_start))

    def undisturbed(self, t: float) -> bool:
        """False for a moment inside a stall or the settling after it."""
        return not any(a <= t <= b + self.SETTLE_S for a, b in self.stalls)

    def stop(self) -> None:
        import jax

        if not self.running:
            return
        self.t_stop = time.time()
        jax.profiler.stop_trace()
        self.stalls.append((self.t_stop, time.time()))

    def reduced(self, host_spans: Sequence[dict]) -> Optional[tr.Reduced]:
        """The traced stretch reduced, with the program's host spans
        (``observability/tracing`` records) on its clock; None when no
        device plane was recorded (a CPU run)."""
        if self.t_start is None or self.t_stop is None:
            return None
        loaded = tr.load_xplane(tr.find_xplane(str(self.dir)))
        shutil.rmtree(self.dir, ignore_errors=True)
        if not loaded["devices"]:
            return None
        spans = [[s["name"], s["ts"], s["dur_us"] / 1e6]
                 for s in host_spans]
        return tr.reduce_trace(loaded, self.sync_host_s,
                               (self.t_start, self.t_stop), spans)

    def spans_inside(self, host_spans: Sequence[dict]) -> list:
        if self.t_start is None or self.t_stop is None:
            return []
        return [s for s in host_spans
                if s["ts"] >= self.t_start
                and s["ts"] + s["dur_us"] / 1e6 <= self.t_stop]
