"""``"generator": "open_loop"``: requests offered at a fixed rate,
whatever the server does.

Mix keys: ``arrivals.rate`` (requests a second), ``arrivals.gaps`` (the
distribution of the gap between arrivals, rescaled to the rate:
``exponential`` is a Poisson process), ``lengths.prompt`` and
``lengths.output`` (a distribution each, clipped to ``min``..``max``),
``in_flight_at_open``.

Every seed gets the SAME set of prompt lengths, output lengths and
gaps: the quantiles of the mix's distributions, so a window's work is
fixed by the mix and not by the luck of a draw.  The seed draws their
ORDER, each of the three shuffled on its own, and the token ids.  So
which prompt meets which answer, and what arrives in a clump with what,
is chance, as in a drawn sample: a seed can put its long answers
together and fill every slot, and the tails show it.
"""

from typing import Dict, List

import numpy as np

from cellbench.loadgen import TimedRequest, quantile_set


def _lengths(spec: Dict, n: int) -> np.ndarray:
    vals = quantile_set(spec, n)
    lo = spec.get("min", 1)
    hi = spec.get("max", max(int(vals.max()), lo))
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def _gaps(arrivals: Dict, n: int, seconds: float) -> np.ndarray:
    """``n`` gaps whose sum is ``seconds * n / (n + 0.5)``, so that the
    last request is due just inside the window."""
    gaps = quantile_set(arrivals["gaps"], n)
    return gaps * (seconds * n / (n + 0.5)) / gaps.sum()


def requests(mix: Dict, vocab: int, seed: int,
             seconds: float) -> List[TimedRequest]:
    """The requests of one window, in due order."""
    rate = mix["arrivals"]["rate"]
    if not rate or rate <= 0:
        raise ValueError("the mix's arrivals.rate is not set")
    n = max(int(round(rate * seconds)), 1)
    rng = np.random.RandomState(seed % (2 ** 32))
    prompts = rng.permutation(_lengths(mix["lengths"]["prompt"], n))
    outputs = rng.permutation(_lengths(mix["lengths"]["output"], n))
    due = np.cumsum(rng.permutation(_gaps(mix["arrivals"], n, seconds)))
    return [TimedRequest(
        rid=i, due=float(due[i]), max_new_tokens=int(outputs[i]),
        prompt=rng.randint(0, vocab, size=int(prompts[i])).tolist())
        for i in range(n)]


def in_flight_at_open(mix: Dict, vocab: int, seed: int) -> List[TimedRequest]:
    """The requests a server in steady state would hold when the window
    opens, so that a run does not spend its first seconds filling an
    empty server: ``mix["in_flight_at_open"]`` of them, each drawn from
    the mix's set of requests with a chance in proportion to its output
    length (a longer answer is longer in flight) and cut to what would
    be left of it at a moment drawn evenly over its life.  They are due
    at 0, served during set-up and the window, and not counted among the
    window's requests."""
    n = int(mix.get("in_flight_at_open", 0))
    if n <= 0:
        return []
    rng = np.random.RandomState((seed + 0x5EED) % (2 ** 32))
    pool = 200
    prompts = _lengths(mix["lengths"]["prompt"], pool)
    outputs = _lengths(mix["lengths"]["output"], pool)
    picks = rng.choice(pool, size=n, p=outputs / outputs.sum())
    out = []
    for i, j in enumerate(picks):
        left = max(int(np.ceil(rng.uniform() * outputs[j])), 1)
        plen = int(prompts[rng.randint(pool)])
        out.append(TimedRequest(
            rid=i, due=0.0, max_new_tokens=left,
            prompt=rng.randint(0, vocab, size=plen).tolist()))
    return out
