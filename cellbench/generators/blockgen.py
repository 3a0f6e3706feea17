"""``"generator": "blockgen"``: :mod:`cellbench.generators.open_loop`'s
open loop for a model that generates by diffusion over blocks.

The same keys and the same quantile sets (every seed gets the same
prompt lengths, answer lengths and gaps; the seed draws their order and
the ids), and besides:

- ``lengths.output.multiple_of`` (the block length): an answer's length
  is rounded UP to a multiple of it (a request's ``gen_length`` is
  fixed: there is no end-of-sequence stop);
- ``lengths.denoising_steps``: the values a request's
  ``denoising_steps`` takes,
  in equal shares (``[2, 4]``: half of the requests 2, the other half
  4), dealt by the seed (:func:`steps`); a
  :class:`~cellbench.loadgen.TimedRequest` has no field for it, so the
  adapter asks for it by ``rid``;
- ids are drawn below ``vocab - 1``: the slice's last row stands for the
  mask token and no prompt holds it;
- the requests in flight at the open are the SAME set of prompt lengths,
  remaining answers and steps for every seed (:data:`HELD_DRAW`; the
  seed draws their ids): with 64 slots that draw alone would move a
  window's tokens a second (``adapters/serve_evabyte.in_flight`` has
  the measurement).
"""

import dataclasses
from typing import Dict, List

import numpy as np

from cellbench.generators import open_loop
from cellbench.loadgen import TimedRequest

#: the draw that fixes the in-flight set's lengths for every seed
HELD_DRAW = 0


def _blocks(n: int, block: int) -> int:
    return -(-int(n) // block) * block


def _redrawn(rs: List[TimedRequest], mix: Dict, vocab: int, rng
             ) -> List[TimedRequest]:
    """``rs`` with answers of whole blocks and ids below the mask's."""
    W = int(mix["lengths"]["output"]["multiple_of"])
    return [dataclasses.replace(
        r, max_new_tokens=_blocks(r.max_new_tokens, W),
        prompt=rng.randint(0, vocab - 1, size=len(r.prompt)).tolist())
        for r in rs]


def requests(mix: Dict, vocab: int, seed: int,
             seconds: float) -> List[TimedRequest]:
    """The requests of one window, in due order."""
    rng = np.random.RandomState((seed + 0xB10C) % (2 ** 32))
    return _redrawn(open_loop.requests(mix, vocab, seed, seconds), mix,
                    vocab, rng)


def in_flight_at_open(mix: Dict, vocab: int, seed: int
                      ) -> List[TimedRequest]:
    """The requests a server in steady state would hold when the window
    opens: one set of lengths for every seed, ids from the seed."""
    rng = np.random.RandomState((seed + 0x5EED) % (2 ** 32))
    return _redrawn(open_loop.in_flight_at_open(mix, vocab, HELD_DRAW), mix,
                    vocab, rng)


def steps(mix: Dict, n: int, seed: int) -> List[int]:
    """``denoising_steps`` of ``n`` requests, by rid: the mix's values in
    equal shares, dealt by the seed."""
    values = [int(v) for v in mix["lengths"]["denoising_steps"]]
    dealt = np.resize(np.asarray(values), n)
    return np.random.RandomState((seed + 0x57E9) % (2 ** 32)) \
        .permutation(dealt).tolist()
