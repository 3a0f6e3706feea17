"""apex_tpu.inference.fleet — fault-tolerant multi-replica serving.

The frontend half of the serving resilience story: PR 11 gave replicas
a watchdog that emits the ``serve.step_wedged`` requeue manifest and a
supervisor that restarts them, and this package is the layer that
actually CONSUMES those signals, so a replica death is an absorbed
event instead of N dropped streams:

- :mod:`~apex_tpu.inference.fleet.replica` — replica lifecycle
  (starting → warm → serving → draining → dead) with heartbeats and
  per-replica state gauges; :class:`LocalReplica` is the in-process
  incarnation the tests drive.
- :mod:`~apex_tpu.inference.fleet.journal` — the request journal and
  the splice invariant that makes multi-leg streams gapless and
  duplicate-free (bitwise the unkilled stream under greedy decoding).
- :mod:`~apex_tpu.inference.fleet.router` — health-gated placement:
  prefix-affinity first, lane-aware least-loaded fallback, and the
  graceful-brownout ladder (shed best-effort, then typed
  :class:`Overloaded` rejections with retry-after).
- :mod:`~apex_tpu.inference.fleet.frontend` — the
  :class:`FleetFrontend` tying it together: replay-on-failure (wedge →
  manifest, kill → journal), one bounded hedged retry for interactive
  stragglers, drain-then-restart with zero drops, and the
  ``serve.fleet_config`` uniformity registration.

See docs/inference.md ("Serving fleet") for health-state semantics,
the replay contract, and the knob table; ``tests/test_fleet.py`` holds
the chaos matrix (kill-137 / wedge-75 / brownout / drain-restart).
"""

from apex_tpu.inference.fleet.frontend import FleetFrontend
from apex_tpu.inference.fleet.journal import (
    FleetCompletion, JournalEntry, RequestJournal,
)
from apex_tpu.inference.fleet.replica import (
    LocalReplica, REPLICA_STATES, ReplicaKilled, ReplicaWedged,
)
from apex_tpu.inference.fleet.router import Overloaded, Router, RouterConfig

__all__ = [
    "FleetCompletion", "FleetFrontend", "JournalEntry", "LocalReplica",
    "Overloaded", "REPLICA_STATES", "ReplicaKilled", "ReplicaWedged",
    "RequestJournal", "Router", "RouterConfig",
]
