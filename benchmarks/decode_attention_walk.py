"""``apex_decode_attention`` alone, at the shapes of the five cells that
call its walk: what a row and a page cost, and whether a page's copy or
its arithmetic bounds the walk.

Every row of a call has the same number of live pages (1, 2, 4, 8, 16),
its pages scattered through the pool as an allocator that serves many
sequences leaves them; the kernel runs ``--reps`` times inside one
compiled loop, each call's output the next call's query, and the host's
clock divides by the calls.  One JSON line a (shape, depth, variant):

- ``walk``: the kernel as the launcher builds it, with the ring
  ``depth`` slots deep (2: one page in flight beside the one attended,
  all there was before PR 50; 0: what ``_plan`` gives the shape, the
  line's ``planned_depth``).  The library takes no depth: the script
  plans in ``_plan``'s place;
- ``copies_only``: every copy, no page's arithmetic;
- ``arithmetic_only``: every page's arithmetic on whatever its slot
  holds, no copy started or waited for.

``us_a_row`` lists the time a row at each count of live pages;
``intercept_us`` and ``slope_us`` are the least-squares line through
them: a row's fixed cost (the grid step, the query's and the output's
blocks) and a page's.  ``copy_us_at_peak`` is a page's k and v blocks at
the device's HBM rate (``cellbench/peaks.json``; a device it does not
list, as the CPU of a rehearsal, gets none).

    python benchmarks/decode_attention_walk.py            # on the chip
    python benchmarks/decode_attention_walk.py --interpret --rows 3 \\
        --reps 1 --pages 1 2 --shapes agentgen blockgen
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

#: rows (slots), query heads (the block cell's folded: two blocks of 4
#: rows over 8 a key/value head), key/value heads, head dim, lengths a row
SHAPES = {
    "agentgen": dict(rows=256, heads=32, kv=8, D=64, halves=1),
    "h1chat": dict(rows=96, heads=20, kv=4, D=128, halves=1),
    "blockgen": dict(rows=64, heads=256, kv=4, D=128, halves=2),
    "gpt2chat": dict(rows=20, heads=20, kv=20, D=64, halves=1),
    "bytegen": dict(rows=20, heads=32, kv=32, D=128, halves=1),
}
PAGE = 128


def build(shape, rows, max_pages, seed):
    s = SHAPES[shape]
    rng = np.random.RandomState(seed)
    pages = 1 + rows * max_pages
    pt = 1 + rng.permutation(pages - 1).reshape(rows, max_pages)
    key = jax.random.PRNGKey(seed)
    pool = (1, pages, s["kv"], s["D"], PAGE)
    k = jax.random.normal(key, pool, jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 1), pool, jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 2),
                          (rows, s["heads"], s["D"]), jnp.bfloat16)
    return q, k, v, jnp.asarray(pt, jnp.int32)


def time_calls(run, args, calls):
    run(*args).block_until_ready()                  # compile, warm up
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        run(*args).block_until_ready()
        best = min(best, time.perf_counter() - t)
    return best / calls


class _NoCopy:
    def start(self):
        pass

    def wait(self):
        pass


def measure(shape, variant, depth, data, page_counts, reps, interpret):
    from apex_tpu.ops import decode_attention_pallas as m

    s = SHAPES[shape]
    q, k, v, pt = data
    rows = q.shape[0]
    plan = m._plan
    planned = plan(rows, s["kv"], s["heads"] // s["kv"], s["D"], pt.shape[1],
                   PAGE, k.dtype)
    keep = (m._plan, m._attend, m._page_copies)
    try:
        if depth:
            m._plan = lambda *a: plan(*a)[:2] + (depth,)
        if variant == "copies_only":
            m._attend = lambda *a, **kw: None
        if variant == "arithmetic_only":
            m._page_copies = lambda *a: [_NoCopy(), _NoCopy()]

        @jax.jit
        def run(q, k, v, pt, n):
            return jax.lax.fori_loop(
                0, reps, lambda _, q: m.paged_decode_attention_pallas(
                    q, k, v, pt, n, interpret=interpret, layer=0), q)

        us = []
        for pages in page_counts:
            n = np.full((rows,), pages * PAGE - 5, np.int32)
            if s["halves"] == 2:        # the held block and the open one
                n = np.stack([n - 4, n], axis=1)
            us.append(time_calls(run, (q, k, v, pt, jnp.asarray(n)), reps)
                      * 1e6 / rows)
    finally:
        m._plan, m._attend, m._page_copies = keep
    slope, intercept = (np.polyfit(page_counts, us, 1)
                        if len(page_counts) > 1 else (None, None))
    dev = jax.devices()[0]
    peaks = json.loads((REPO / "cellbench" / "peaks.json").read_text())
    rate = peaks.get(dev.device_kind, {}).get("hbm_bytes_per_s")
    page_bytes = 2 * s["kv"] * s["D"] * PAGE * k.dtype.itemsize
    return {
        "shape": shape, "variant": variant, "depth": depth or planned[2],
        "planned_depth": planned[2], "h_blk": planned[0],
        "grid": planned[1], "pages": list(page_counts), "us_a_row": us,
        "intercept_us": intercept, "slope_us": slope,
        "copy_us_at_peak": page_bytes / rate * 1e6 if rate else None,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
    }


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES),
                    choices=list(SHAPES))
    ap.add_argument("--depths", nargs="+", type=int, default=[2, 3, 4],
                    help="slots of the ring (0: what _plan gives)")
    ap.add_argument("--variants", nargs="+",
                    default=["walk", "copies_only", "arithmetic_only"])
    ap.add_argument("--pages", nargs="+", type=int,
                    default=[1, 2, 4, 8, 16], help="live pages a row")
    ap.add_argument("--rows", type=int, default=0,
                    help="rows of a call (0: the cell's slots)")
    ap.add_argument("--reps", type=int, default=100)
    ap.add_argument("--interpret", action="store_true",
                    help="the Pallas interpreter (a CPU rehearsal: the "
                         "times mean nothing)")
    return ap


def main():
    args = parse_args().parse_args()
    for shape in args.shapes:
        data = build(shape, args.rows or SHAPES[shape]["rows"],
                     max(args.pages), 50)
        for variant in args.variants:
            # without copies the ring's depth changes nothing
            depths = args.depths[-1:] if variant == "arithmetic_only" \
                else args.depths
            for depth in depths:
                print(json.dumps(measure(
                    shape, variant, depth, data, args.pages, args.reps,
                    args.interpret)), flush=True)


if __name__ == "__main__":
    main()
