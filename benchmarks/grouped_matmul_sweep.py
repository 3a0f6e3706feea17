"""The grouped matmuls of the expert layer alone, over row tile x
contraction tile x column tile: the trainable layer's chunk at the train
cell's shape, or (``--shape``, ``--draws serving``) a serving program's
call.

``transformer/expert_parallel._chunk_ffn`` runs three grouped matmuls
over a chunk's rows sorted by expert and its backward six more;
``trinity-mini.train-8k`` walks one chunk of 20,480 rows a layer with
about 16,190 of them live, 1,012 an expert over 16 experts of 2,048 x
1,024, fifteen products a layer a step.  Six (kernel, shape) cases are
distinct among them:

- ``gate_up``: ``gmm`` rows (M, 2048) x w (G, 2048, 1024), the gate and
  the up projection (seven of the fifteen contract over 2,048: these six
  and ``d_act``);
- ``down``: ``gmm`` act (M, 1024) x w (G, 1024, 2048);
- ``d_act``: ``gmm`` dy (M, 2048) x w_down (G, 1024, 2048) transposed,
  the activation's cotangent;
- ``d_rows``: ``gmm`` dgate (M, 1024) x w_gate (G, 2048, 1024)
  transposed, the rows' cotangent (through the gate and through the up);
- ``tgmm_gate_up``: ``tgmm`` rows^T x dgate into (G, 2048, 1024);
- ``tgmm_down``: ``tgmm`` act^T x dy into (G, 1024, 2048).

megablox's kernels (``jax.experimental.pallas.ops.tpu.megablox.gmm``)
are called as the layer calls them, bf16 in and out.  One JSON line a
candidate ``(tm, tk, tn)`` and a draw of group sizes (``even``: 1,012
+- 7 rows a group; ``skewed``: one group of 4,048 rows, one empty, the
rest even): the milliseconds a call (``--reps`` calls chained inside ONE
program through the group sizes and waited for once, the best of three:
a call timed alone reads the host's dispatch), the share of the time
the draw's live rows take at the MXU's published peak (0.345 ms at
16,190 rows), ``plan`` where the candidate is what
``expert_parallel.grouped_tiling`` gives the call and ``parent`` where
it is the one tiling every caller had until PR 46, or ``error``: what
the compiler refused (a block past the scoped VMEM limit).  A
``metadata`` line a row tile is the XLA ops before the kernel alone
(``make_group_metadata``), which every timing includes.

**A serving program's call** (``--shape ROWS GROUPS HIDDEN WIDTH
--draws serving --live-groups L --rows-a-group R``): the static buffer
of a decode step, a block step or a prefill bucket over the STACKED
layers' experts as groups, of which one layer's ``L`` get rows:
``round(L * R)`` rows drawn as a router draws them (multinomial over the
live groups, the other groups empty, edges wherever they fall, the
buffer's tail dead).  A ``gmm`` line then also says what the draw costs
at the candidate by megablox's own block indices (:func:`traffic`): the
``visits`` ((group, row tile) pairs: the grid's middle axis), how many
of them are a group's second or later (``straddling``), ``mb_owed``
(the live groups' matrices, the live row tiles in and out, each once)
and ``mb_moved`` (what the pipeline fetches, a block again whenever its
index moves).  The calls of the four MoE serving cells (PERF.md, PR 48):

    cell, program        --shape                 --live-groups --rows-a-group
    lfm2 decode step     1024  96 2048 1792      32            31.4
    lfm2 prefill 512     2048  96 2048 1792      32            64
    sdar block step      1024 768 2048  768      16            20
    sdar prefill 512     4096 768 2048  768      16            32
    kimi decode step     1024 384 2304 1024      32            3.8
    kimi prefill 2048   16384 384 2304 1024      32            64
    gigachat decode step 1024  80 7168 2048      16            4
    gigachat prefill 512 4096  80 7168 2048      16            16

    python benchmarks/grouped_matmul_sweep.py > chiprun_out/gmm_sweep.jsonl
    python benchmarks/grouped_matmul_sweep.py --compile-only   # no chip:
        # every candidate through the compile-only v5e client (libtpu
        # builds one from a topology name), "compiled": true or "error"
    python benchmarks/grouped_matmul_sweep.py --interpret   # CPU
        # rehearsal: tiny shapes through the Pallas interpreter, no
        # timing meant
"""

import argparse
import itertools
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox.gmm import (
    gmm, make_group_metadata, tgmm)

from apex_tpu.transformer import expert_parallel as ep

PEAK_TFLOPS = 197.0   # TPU v5e, bf16 (Google Cloud documentation)
PEAK_GBPS = 819.0     # its HBM (the same page)
BF16, I32 = jnp.bfloat16, jnp.int32
FILL = 0.791    # ``moe_buffer_fill.moe8k``: 1,012 rows an expert
#: product: (kernel as ``grouped_tiling`` names it, whether the
#: contraction is the layer's hidden width)
PRODUCTS = {
    "gate_up": ("gmm", True), "down": ("gmm", False),
    "d_act": ("gmm_t", True), "d_rows": ("gmm_t", False),
    "tgmm_gate_up": ("tgmm", True), "tgmm_down": ("tgmm", False),
}


def draw(name, rows, groups, seed, live_groups=None, rows_a_group=None):
    """Group sizes that fill ``FILL`` of ``rows``, the tail dead; or
    ("serving") ``live_groups * rows_a_group`` rows thrown at the live
    groups alone, one layer's run of them in the middle of the stack."""
    rng = np.random.default_rng(seed)
    if name == "serving":
        live = min(rows, round(live_groups * rows_a_group))
        sizes = np.zeros(groups, np.int64)
        first = groups // live_groups // 2 * live_groups
        sizes[first:first + live_groups] = rng.multinomial(
            live, np.full(live_groups, 1.0 / live_groups))
        return jnp.asarray(sizes, I32)
    near = lambda mean, n: mean + rng.integers(
        -(mean // 128), mean // 128 + 1, n)
    mean = int(rows * FILL) // groups
    sizes = near(mean, groups)
    if name == "skewed":
        sizes[0], sizes[1] = 0, mean * 4
        sizes[2:] = near((mean * groups - sizes[1]) // (groups - 2),
                         groups - 2)
    return jnp.asarray(sizes, I32)


def traffic(sizes, tiling, K, N, itemsize=2):
    """What one ``gmm`` over ``sizes`` costs at ``tiling`` by megablox's
    block indices, walked as its grid ``(column tiles, visits,
    contraction tiles)`` walks them: rows ``(row tile, k_i)``, weights
    ``(group, k_i, n_i)``, output ``(row tile, n_i)``; a block is
    fetched (the output: written back) once a run of equal indices."""
    tm, tk, tn = tiling
    sizes = np.asarray(sizes)
    ends = np.cumsum(sizes)
    visit = [(g, t) for g, (a, b) in enumerate(zip(ends - sizes, ends))
             if b > a for t in range(a // tm, -(-b // tm))]
    tiles_k, tiles_n = -(-K // tk), -(-N // tn)
    moved, last = dict.fromkeys(("lhs", "rhs", "out"), 0), {}
    for n_i in range(tiles_n):
        cols = min(tn, N - n_i * tn)        # a short last tile: what is there
        for g, t in visit:
            for k_i in range(tiles_k):
                deep = min(tk, K - k_i * tk)
                at = {"lhs": ((t, k_i), tm * deep),
                      "rhs": ((g, k_i, n_i), deep * cols),
                      "out": ((t, n_i), tm * cols)}
                for name, (index, elements) in at.items():
                    moved[name] += (index != last.get(name)) * elements
                last = {name: index for name, (index, _) in at.items()}
    live_groups = int(np.sum(sizes > 0))
    row_tiles = len({t for _, t in visit})
    mb = lambda elements: round(elements * itemsize / 1e6, 2)
    return {"visits": len(visit), "straddling": len(visit) - live_groups,
            "mb_owed": mb(live_groups * K * N + row_tiles * tm * (K + N)),
            "mb_moved": mb(sum(moved.values()))}


def widths(product, shape):
    """(contraction, columns) of a product at ``shape``."""
    _, _, H, F = shape
    return (H, F) if PRODUCTS[product][1] else (F, H)


def parent_tiling(tm, K, N):
    """The one tiling every caller had until PR 46, clipped to the
    widths (and, for the rehearsal's tiny shapes, to ``tm``)."""
    return (min(ep.GROUPED_TILING[0], tm), min(ep.GROUPED_TILING[1], K),
            min(ep.GROUPED_TILING[2], N))


def chained(product, tiling, shape, reps, interpret):
    """``reps`` calls of one product in one program; each call's group
    sizes wait for an element of the call before (and take nothing from
    it), so the calls run one after another and nothing is hoisted."""
    kind = PRODUCTS[product][0]
    M, G = shape[:2]
    K, N = widths(product, shape)

    def call(a, b, sizes):
        if kind == "tgmm":
            return tgmm(a.swapaxes(0, 1), b, sizes, BF16, tiling,
                        interpret=interpret)
        return gmm(a, b, sizes, BF16, tiling,
                   transpose_rhs=kind == "gmm_t", interpret=interpret)

    def many(a, b, sizes):
        def body(_, sizes):
            out = call(a, b, sizes)
            return sizes + jnp.isnan(out.ravel()[0]).astype(I32)
        return jax.lax.fori_loop(0, reps, body, sizes)

    b = (M, N) if kind == "tgmm" else (G, N, K) if kind == "gmm_t" \
        else (G, K, N)
    return many, [((M, K), BF16), (b, BF16), ((G,), I32)]


def metadata(tm, shape, reps):
    """The XLA ops before a kernel alone, chained the same way."""
    M, G = shape[:2]

    def many(sizes):
        def body(_, sizes):
            (offsets, ids, tiles), n = make_group_metadata(
                group_sizes=sizes, m=M, tm=tm, start_group=jnp.int32(0),
                num_nonzero_groups=G, visit_empty_groups=False)
            return sizes + (offsets[0] + ids[0] + tiles[0] + n < 0
                            ).astype(I32)
        return jax.lax.fori_loop(0, reps, body, sizes)

    return many


def candidates(product, shape, tms, parent_only):
    K, N = widths(product, shape)
    if parent_only:
        return [parent_tiling(tms[0], K, N)]
    tks = sorted({min(1024, K), K})
    # a half of the columns too where it tiles: two even column tiles
    tns = sorted({min(256, N), min(512, N), min(1024, N), N}
                 | ({N // 2} if N % 256 == 0 and N >= 1024 else set()))
    return list(itertools.product(tms, tks, tns))


def described_v5e():
    """A compile-only v5e device (no chip is opened)."""
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies

    return topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu").devices[0]


def timed(fn, args, reps):
    """Milliseconds a call: the best of three runs of the chained
    program, after one that compiles it."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best / reps * 1e3


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--products", nargs="+", default=list(PRODUCTS))
    ap.add_argument("--draws", nargs="+", default=["even", "skewed"],
                    choices=["even", "skewed", "serving"])
    ap.add_argument("--shape", nargs=4, type=int, default=[20480, 16, 2048,
                                                           1024],
                    metavar=("ROWS", "GROUPS", "HIDDEN", "WIDTH"),
                    help="rows of the buffer, stacked groups, the layer's "
                    "hidden width, an expert's width")
    ap.add_argument("--live-groups", type=int, help="serving: the groups "
                    "of one layer, which alone get rows")
    ap.add_argument("--rows-a-group", type=float, help="serving: the mean "
                    "rows of a live group")
    ap.add_argument("--tm", nargs="+", type=int, default=[128, 256, 512])
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--parent-only", action="store_true", help="only the "
                    "tiling every caller had until PR 46")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args(argv)
    if "serving" in args.draws and not (args.live_groups
                                        and args.rows_a_group):
        ap.error("--draws serving needs --live-groups and --rows-a-group")
    shape = (512, 4, 256, 128) if args.interpret else tuple(args.shape)
    tms = [8, 32] if args.interpret else args.tm
    reps = 1 if args.interpret or args.compile_only else args.reps
    M, G = shape[:2]
    device = described_v5e() if args.compile_only else jax.devices()[0]
    sharding = jax.sharding.SingleDeviceSharding(device)
    sizes = {name: draw(name, M, G, args.seed, args.live_groups,
                        args.rows_a_group) for name in args.draws}
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 2)
    if not args.compile_only:
        for tm in tms:
            line = {"product": "metadata", "tm": tm, "rows": M, "groups": G,
                    "device": device.device_kind}
            fn = jax.jit(metadata(tm, shape, reps))
            for name, s in sizes.items():
                line[f"ms_{name}"] = round(timed(fn, (s,), reps), 4)
            print(json.dumps(line), flush=True)
    for product in args.products:
        kind = PRODUCTS[product][0]
        K, N = widths(product, shape)
        for tiling in candidates(product, shape, tms, args.parent_only):
            many, avals = chained(product, tiling, shape, reps,
                                  args.interpret)
            line = {"product": product, "kernel": kind.split("_")[0],
                    "tiling": list(tiling), "rows": M, "groups": G,
                    "contraction": K, "columns": N,
                    "parent": tiling == parent_tiling(tms[0], K, N),
                    "plan": tiling == ep.grouped_tiling(kind, M, G, K, N,
                                                        BF16),
                    "device": device.device_kind}
            if kind != "tgmm":
                for name, s in sizes.items():
                    line.update({f"{key}_{name}": value for key, value in
                                 traffic(s, tiling, K, N).items()})
            try:
                if args.compile_only:
                    jax.jit(many).lower(*[
                        jax.ShapeDtypeStruct(s, d, sharding=sharding)
                        for s, d in avals]).compile()
                    line["compiled"] = True
                else:
                    fn = jax.jit(many)
                    a, b = (jax.random.normal(k, s, d)
                            for k, (s, d) in zip(keys, avals))
                    for name, s in sizes.items():
                        live = int(jnp.sum(s))
                        ms = timed(fn, (a, b, s), reps)
                        line[f"ms_{name}"] = round(ms, 4)
                        line[f"peak_share_{name}"] = round(
                            2.0 * live * K * N / (PEAK_TFLOPS * 1e9) / ms, 4)
                        if f"mb_owed_{name}" in line:
                            line[f"hbm_share_{name}"] = round(
                                line[f"mb_owed_{name}"] / PEAK_GBPS / ms, 4)
            except Exception as err:    # what the compiler refuses
                line["error"] = f"{type(err).__name__}: {err}"[-400:]
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
