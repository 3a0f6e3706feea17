"""What the two adapters share around their windows."""

import time
from typing import Dict, List


class Phases:
    """Where set-up's seconds go: ``mark(name)`` after each phase, one
    line of all of them when the window opens."""

    def __init__(self, t_setup_start: float):
        self.last = t_setup_start
        self.parts: List[str] = []

    def mark(self, name: str) -> None:
        now = time.time()
        self.parts.append(f"{name} {now - self.last:.2f} s")
        self.last = now

    def line(self) -> str:
        return "set-up: " + ", ".join(self.parts)


def program_bytes(mem) -> int:
    """HBM a compiled program needs, from ``Compiled.memory_analysis()``:
    arguments + temporaries + outputs that alias no argument."""
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


class CompileWatch:
    """Seconds of every backend compile between :meth:`start` and
    :meth:`stop`: inside a window there should be none."""

    def __init__(self):
        self.durations: List[float] = []

    def _on_event(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.durations.append(duration)

    def start(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def stop(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)


def tripped_kernels() -> Dict[str, str]:
    """Kernels the fallback registry degraded to their reference."""
    from apex_tpu.resilience.fallback import get_registry

    return {k: v["error"] for k, v in get_registry().status().items()
            if v["tripped"]}


def judge(checks, faults: Dict[str, object], log) -> bool:
    """Print each number compared beside its limit, and each fault of
    the run that was found (a name with a non-empty value); True when
    every number is inside its limit and there is no fault."""
    ok = True
    for name, value, limit in checks:
        good = value <= limit
        ok = ok and good
        log(f"correct: {name} = {value:.6g} (limit {limit:g}) "
            f"{'ok' if good else 'FAILED'}")
    for name, bad in faults.items():
        if bad:
            ok = False
            log(f"correct: {name}: {bad} FAILED")
    return ok
