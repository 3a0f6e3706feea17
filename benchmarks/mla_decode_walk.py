"""``apex_mla_decode_attention`` alone, at the two latent cells' shapes:
what a layer's call costs, and whether its tiles' copies or their
arithmetic bound it.

128 slots, a ``(576, 128)`` bf16 latent tile, 512 of its rows the
values; 64 heads over 16 page slots with contexts as ``longgen-1.25knee``
draws them (about 640 positions, up to 2,048), 32 heads over 48 with
``docgen-1.25knee``'s (about 1,550, up to 5,120).  The kernel runs over
the layers of a stacked pool inside one compiled loop, as the decode
step's layer scan runs it, and the host's clock divides by the calls.
One JSON line a (shape, variant):

- ``walk``: the kernel as the launcher plans it;
- ``grid``: the form the launcher keeps for a page under 128 lanes, at
  these shapes (all there was before PR 33): ``(128, page slots / 8)``
  grid steps of eight BlockSpec tiles;
- ``copies_only``: every copy, no tile arithmetic;
- ``arithmetic_only``: every tile's arithmetic on whatever the slot
  holds, no copy started or waited for;
- ``empty``: every length 0 — what 128 grid steps cost;
- ``full``: every table full — the longest walk.

``roofline_pct`` is the metric's own reckoning
(``cellbench/counts/mla_decode_attention.py``): a sequence's context
read once, 1,152 B a position, at the device's HBM rate
(``cellbench/peaks.json``; a device it does not list, as the CPU of a
rehearsal, gets none), over the call's time.

    python benchmarks/mla_decode_walk.py            # on the chip
    python benchmarks/mla_decode_walk.py --interpret --slots 4 --reps 1
"""

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import jax
import jax.numpy as jnp
import numpy as np

#: (heads, page slots a sequence, layers, prompt median, prompt range)
SHAPES = {
    "longgen": dict(heads=64, pages_per_seq=16, layers=6,
                    prompt=(256, 32, 1024)),
    "docgen": dict(heads=32, pages_per_seq=48, layers=3,
                   prompt=(1024, 128, 4096)),
}
DC, DL, PAGE = 576, 512, 128


def context_lengths(rng, slots, prompt):
    """A slot's context in the middle of the window: its prompt
    (lognormal, sigma 0.8, clipped) and a uniform share of its answer
    (lognormal median 512, sigma 0.5, 128-1,024)."""
    median, lo, hi = prompt
    p = np.clip(median * np.exp(0.8 * rng.randn(slots)), lo, hi)
    a = np.clip(512 * np.exp(0.5 * rng.randn(slots)), 128, 1024)
    return np.maximum((p + a * rng.rand(slots)).astype(np.int32), 1)


def build(shape, slots, seed, interpret):
    s = SHAPES[shape]
    rng = np.random.RandomState(seed)
    P, L = s["pages_per_seq"], s["layers"]
    lengths = context_lengths(rng, slots, s["prompt"])
    live = -(-lengths // PAGE)
    pages = 1 + int(live.sum()) if not interpret else 1 + slots * P
    # a sequence's pages lie scattered through the pool, as an
    # allocator that serves many sequences leaves them
    order = 1 + rng.permutation(pages - 1)
    pt = np.zeros((slots, P), np.int32)
    at = 0
    for b, n in enumerate(live):
        pt[b, :n] = order[at:at + n]
        at += n
    key = jax.random.PRNGKey(seed)
    pool = jax.random.normal(key, (L, pages, 1, DC, PAGE), jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 1),
                          (slots, s["heads"], DC), jnp.bfloat16)
    return q, pool, jnp.asarray(pt), lengths


def time_calls(q, pool, pt, lengths, reps, interpret):
    from apex_tpu.ops.mla_decode_pallas import mla_decode_pallas

    L = pool.shape[0]
    n = jnp.asarray(lengths, jnp.int32)

    @jax.jit
    def run(q, pool, pt, n):
        def body(i, out):
            del out
            return mla_decode_pallas(q, pool, pt, n, DL, 0.1447,
                                     interpret=interpret,
                                     layer=jax.lax.rem(i, L))
        return jax.lax.fori_loop(
            0, reps * L, body, jnp.zeros(q.shape[:2] + (DL,), q.dtype))

    run(q, pool, pt, n).block_until_ready()         # compile, warm up
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        run(q, pool, pt, n).block_until_ready()
        best = min(best, time.perf_counter() - t)
    return best / (reps * L)


class _NoCopy:
    def start(self):
        pass

    def wait(self):
        pass


def measure(shape, variant, slots, reps, interpret, walk_slots=None):
    from apex_tpu.ops import mla_decode_pallas as m

    q, pool, pt, lengths = build(shape, slots, 33, interpret)
    P = pt.shape[1]
    if variant == "empty":
        lengths = np.zeros_like(lengths)
    elif variant == "full":
        lengths = np.full_like(lengths, P * PAGE)
        pt = jnp.asarray(
            1 + np.arange(slots * P).reshape(slots, P) % (pool.shape[1] - 1),
            jnp.int32)
    keep = (m._tile_scores, m._tile_update, m._tile_copy, m.WALK_SLOTS,
            m._plan)
    try:
        if walk_slots:
            m.WALK_SLOTS = walk_slots
        if variant == "grid":
            tiles = m._tiles_per_step(P)
            m._plan = lambda rows, P, page: ((rows, P // tiles), tiles)
        if variant == "copies_only":
            m._tile_scores = lambda q, kv, *a: jnp.zeros(
                (q.shape[0], kv.shape[1]), jnp.float32)
            m._tile_update = lambda *a, **k: None
        if variant == "arithmetic_only":
            m._tile_copy = lambda *a: _NoCopy()
        seconds = time_calls(q, pool, pt, lengths, reps, interpret)
    finally:
        (m._tile_scores, m._tile_update, m._tile_copy, m.WALK_SLOTS,
         m._plan) = keep
    tiles = int((-(-lengths // PAGE)).sum())
    needed = float(lengths.sum()) * DC * 2
    dev = jax.devices()[0]
    peaks = json.loads((REPO / "cellbench" / "peaks.json").read_text())
    rate = peaks.get(dev.device_kind, {}).get("hbm_bytes_per_s")
    return {
        "shape": shape, "variant": variant,
        "walk_slots": walk_slots or m.WALK_SLOTS,
        "us_a_call": seconds * 1e6,
        "us_a_sequence": seconds * 1e6 / slots,
        "us_a_live_tile": seconds * 1e6 / tiles if tiles else None,
        "mean_context": float(lengths.mean()), "live_tiles": tiles,
        "roofline_pct": 100 * needed / rate / seconds if rate else None,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
    }


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", nargs="+", default=sorted(SHAPES),
                    choices=sorted(SHAPES))
    ap.add_argument("--variants", nargs="+",
                    default=["walk", "grid", "copies_only",
                             "arithmetic_only", "empty", "full"])
    ap.add_argument("--walk-slots", nargs="+", type=int, default=[0],
                    help="VMEM slots to try in the launcher's place "
                         "(0: the launcher's own)")
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--reps", type=int, default=40)
    ap.add_argument("--interpret", action="store_true",
                    help="the Pallas interpreter (a CPU rehearsal: the "
                         "times mean nothing)")
    return ap


def main():
    args = parse_args().parse_args()
    for shape in args.shapes:
        for walk_slots in args.walk_slots:
            for variant in args.variants:
                print(json.dumps(measure(
                    shape, variant, args.slots, args.reps, args.interpret,
                    walk_slots)), flush=True)


if __name__ == "__main__":
    main()
