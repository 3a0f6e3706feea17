"""Traffic from data.  A mix is a data file under ``cellbench/traffic/``
whose ``generator`` key names a module of ``cellbench/generators/`` and
whose distributions name modules of ``cellbench/dists/``; this module
finds both by name and holds what they share."""

import dataclasses
import importlib
from typing import Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class TimedRequest:
    rid: int
    due: float            # seconds after the window opens
    prompt: List[int]
    max_new_tokens: int


def _by_name(kind: str, name: str):
    try:
        return importlib.import_module(f"cellbench.{kind}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"cellbench.{kind}.{name}":
            raise
        raise ValueError(f"no cellbench/{kind}/{name}.py") from None


def generator(mix: Dict):
    """The module that the mix's ``generator`` key names."""
    return _by_name("generators", mix["generator"])


def quantile_set(spec: Dict, n: int) -> np.ndarray:
    """``n`` values at the quantiles (i + 0.5) / n of the distribution
    ``spec["dist"]``: a deterministic picture of it, not a sample."""
    u = (np.arange(n) + 0.5) / n
    return np.asarray(_by_name("dists", spec["dist"]).inverse_cdf(spec, u),
                      float)


def describe(requests: List[TimedRequest]) -> Dict[str, float]:
    """Counts printed with every run, so a reader sees the mix."""
    p = [len(r.prompt) for r in requests]
    o = [r.max_new_tokens for r in requests]
    return {"requests": len(requests), "prompt_tokens": int(sum(p)),
            "output_tokens": int(sum(o)),
            "prompt_median": float(np.median(p)), "prompt_max": max(p),
            "output_median": float(np.median(o)), "output_max": max(o),
            "last_due_s": requests[-1].due}
