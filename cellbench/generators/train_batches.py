"""``"generator": "train_batches"``: a training job's token rows.

Mix keys: ``global_batch``; ``tokens`` (``uniform`` over the
vocabulary, the only source there is)."""

from typing import Dict, Iterator, List

import numpy as np


def batches(mix: Dict, vocab: int, seq: int, seed: int) -> Iterator[np.ndarray]:
    """An endless stream of (global_batch, seq + 1) int32 token rows,
    uniform over the vocabulary, every row different; column 0..seq-1
    are the inputs and 1..seq the targets."""
    if mix.get("tokens", "uniform") != "uniform":
        raise ValueError(f"unknown token source {mix['tokens']!r}")
    rng = np.random.RandomState(seed % (2 ** 32))
    B = int(mix["global_batch"])
    while True:
        yield rng.randint(0, vocab, size=(B, seq + 1)).astype(np.int32)


def first_batches(mix: Dict, vocab: int, seq: int, seed: int,
                  n: int) -> List[np.ndarray]:
    """The first ``n`` of :func:`batches` (what the reference follows)."""
    it = batches(mix, vocab, seq, seed)
    return [next(it) for _ in range(n)]
