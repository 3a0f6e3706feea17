"""Rank-aware logging.

Reference: ``apex/__init__.py:32-43`` (``RankInfoFormatter``) and
``apex/transformer/log_util.py``.  On TPU the "rank" is the JAX process
index, shown only once this process holds a backend: formatting a log
line must never open the device (see ``utils/platform.process_rank``).
"""

import json
import logging
import sys


def _rank_info() -> str:
    from apex_tpu.utils.platform import process_rank

    rank = process_rank()
    return "[p-/-]" if rank is None else f"[p{rank[0]}/{rank[1]}]"


class RankInfoFormatter(logging.Formatter):
    """Prepends JAX process/rank info to every record."""

    def format(self, record):
        record.rank_info = _rank_info()
        return super().format(record)


_FORMAT = "%(asctime)s %(rank_info)s %(name)s %(levelname)s: %(message)s"


def get_logger(name: str = "apex_tpu") -> logging.Logger:
    logger = logging.getLogger(name)
    if not logger.handlers:
        handler = logging.StreamHandler(sys.stderr)
        handler.setFormatter(RankInfoFormatter(_FORMAT))
        logger.addHandler(handler)
        logger.propagate = False
    return logger


def set_logging_level(level) -> None:
    """Reference: apex/transformer/log_util.py (set_logging_level)."""
    get_logger().setLevel(level)


def log_structured(logger: logging.Logger, level: int, event: str,
                   **fields) -> None:
    """One-line machine-parseable log record: ``EVENT {json fields}``.

    The resilience runtime (kernel fallback, step guard, preemption)
    reports through this so a wedged-run postmortem can grep one event
    name and get every occurrence with its context as JSON.  When the loop
    set a step-correlation context
    (:func:`apex_tpu.observability.set_step_context`), every record
    additionally carries ``(run_id, step)`` so it joins against metrics
    points and xprof ranges.  When a flight recorder is installed
    (:func:`apex_tpu.observability.flightrec.install`), every record is
    ALSO appended to its bounded event ring — the postmortem dump then
    holds the last N structured events without any per-call-site
    wiring."""
    try:
        from apex_tpu.observability.correlation import step_context

        fields = {**step_context(), **fields}
    except ImportError:  # pragma: no cover — torn installs only
        pass
    try:
        from apex_tpu.observability.flightrec import observe_event

        observe_event(event, fields)  # no-op without an installed recorder
    except ImportError:  # pragma: no cover — torn installs only
        pass
    try:
        payload = json.dumps(fields, sort_keys=True, default=str)
    except (TypeError, ValueError):
        payload = json.dumps({k: repr(v) for k, v in fields.items()},
                             sort_keys=True)
    logger.log(level, "%s %s", event, payload)
