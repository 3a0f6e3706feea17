"""Flash-attention grid-block × sub-tile × run-cap sweep, one kernel at
a time.

The three kernels (``apex_flash_fwd``, ``apex_flash_dq``,
``apex_flash_dkv``) walk a grid block in square sub-tiles and visit
only those the causal diagonal leaves alive
(``flash_attention_pallas.live_subtiles``).  What a grid block and a
sub-tile should measure is a property of the chip, so this sweep
measures it, on the real chip, at the shapes the benchmark's cells run:

- ``train``: S 1024, D 64, bf16, 128 heads: fwd, dq and dkv, causal
  (``gpt2-medium.train-b8``);
- ``eva``: Sq 2048, keys = a pooled buffer of 0 / 512 / 1,024 rows then
  the window, D 128, bf16, 32 heads: fwd with a key bias and a negative
  ``k_offset`` (``evabyte-6.5b.serve-bytedoc-over``'s prefill);
- ``mla``: S 512–4096, D 192, bf16, 32 heads: fwd, causal (the latent
  family's prefill);
- ``afmoe``: S 8192, D 128, bf16, GQA 32:4, one sequence: fwd, dq and
  dkv, once under a sliding window of 2,048 keys (the band) and once
  causal (the triangle): the two kinds of layer of
  ``trinity-mini.train-8k``.  Both read ONE row a phase (the table is
  keyed by shape, not by window), so read the two cases' lines
  together: a step runs three band calls to one full call;
- ``long`` (``--long``): S 4096 / 8192 at D 64 and their ring-attention
  chunk shapes (Sq/cp for cp ∈ {2, 4}), fwd and bwd.

Each kernel is timed ALONE (its ``pallas_call`` built with explicit
blocks, sub-tile and run cap, 50 calls chained inside one program: a
call takes half a millisecond and the host's dispatch as long, so calls
timed one by one read the host), and each line carries beside the
timing the static counter (sub-tiles visited, masked, skipped a head,
and the bodies the kernel's code holds) and what ONE call costs a
program that holds it before it runs: the seconds to trace and lower it
and the bytes of its serialized module (``jax.export``; every start pays
the first, compile cache or not).  The roofline
share counts the causal half of the square as the work, at the
published 197 TFLOP/s of a v5e.  The last line is the per-(shape,
phase) ``tuned_blocks_table`` that ``install_tuned_blocks.py`` ships
into the kernel source: rows ``[[S, D, dtype, phase], [bq, bk, sub]]``,
the ``"bwd"`` row the one that minimises dq + dkv together (both read
it).

    python benchmarks/flash_sweep.py [--quick] [--shapes train eva mla]
    python benchmarks/flash_sweep.py --table-from a.jsonl b.jsonl
        # no timing: the table of several kept outputs together
    python benchmarks/flash_sweep.py --quick --interpret   # CPU smoke:
        # tiny shapes through the Pallas interpreter, still emits a
        # valid tuned_blocks_table line (tests/test_flash_lowering.py)
"""

import argparse
import functools
import itertools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp

from apex_tpu.ops import flash_attention_pallas as fap
from apex_tpu.ops._pallas_tiling import RUN_COLUMNS

PEAK_TFLOPS = 197.0   # TPU v5e, bf16 (Google Cloud documentation)
#: matmul-halves of the S x S square a kernel computes (QK^T and PV;
#: + dP and dQ; + dV, dP and dK), as cellbench/counts/flash_attention.py
MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}
BF16 = jnp.bfloat16


def _case(name, phases, BH, Sq, D, pooled=0, bias=False, kv=None,
          window=None):
    """``kv``: key/value heads (grouped-query attention; default one a
    query head); ``window``: a sliding window of that many keys."""
    return dict(name=name, phases=phases, BH=BH, Sq=Sq, Sk=Sq + pooled,
                D=D, k_offset=-pooled, bias=bias, kv=kv or BH,
                window=window)


def cases(groups, quick):
    out = []
    if "train" in groups:
        out.append(_case("train", ("fwd", "dq", "dkv"), 128, 1024, 64))
    if "eva" in groups:
        for pooled in ((512,) if quick else (0, 512, 1024)):
            out.append(_case(f"eva+{pooled}", ("fwd",), 32, 2048, 128,
                             pooled=pooled, bias=pooled > 0))
    if "mla" in groups:
        for S in ((1024,) if quick else (512, 1024, 2048, 4096)):
            out.append(_case(f"mla{S}", ("fwd",), 32, S, 192))
    if "afmoe" in groups:
        for name, window in (("afmoe8k-band", 2048), ("afmoe8k-full", None)):
            out.append(_case(name, ("fwd", "dq", "dkv"), 32, 8192, 128,
                             kv=4, window=window))
    if "long" in groups:
        long = [(24, 4096, 64), (8, 8192, 64)]
        ring = [(BH * cp, S // cp, D) for BH, S, D in long for cp in (2, 4)]
        for BH, S, D in long + [s for s in ring if s not in long]:
            out.append(_case(f"long{S}", ("fwd", "dq", "dkv"), BH, S, D))
    return out


def useful_tflop(case, phase):
    """Causal work of one call: the live triangle (and the whole pooled
    buffer beside it) or, under a window, the band; 2·rows·cols·D a
    matmul."""
    S, W = case["Sq"], case["window"]
    if W is not None and W < S:
        live = W * (W + 1) / 2 + (S - W) * W
    else:
        live = S * (S + 1) / 2 + S * -case["k_offset"]
    return case["BH"] * MATMULS[phase] * 2 * live * case["D"] / 1e12


@functools.lru_cache(maxsize=1)
def inputs_of(name, BH, Sq, Sk, D, k_offset, biased, interpret, kv=None,
              window=None):
    """A case's operands, made once: q, k, v, the cotangent, the key
    bias (half of a pooled buffer hidden), and the forward's ``lse`` and
    ``delta`` the backward kernels read (through the dispatcher's own
    blocks: their values do not depend on the blocks timed)."""
    kq, kk, kv_key, kd = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (BH, Sq, D), BF16)
    k = jax.random.normal(kk, (kv or BH, Sk, D), BF16)
    v = jax.random.normal(kv_key, (kv or BH, Sk, D), BF16)
    do = jax.random.normal(kd, (BH, Sq, D), BF16)
    bias = None
    if biased:
        col = jnp.arange(Sk)
        hidden = (col >= -k_offset // 2) & (col < -k_offset)
        bias = jnp.where(hidden, fap.NEG_INF, 0.0).astype(
            jnp.float32)[None, None, :]
    out, lse = fap.flash_fwd_pallas(
        q, k, v, float(D) ** -0.5, True, 0, k_offset, kv_bias=bias,
        heads=BH, kv_heads=kv or BH, interpret=interpret, window=window)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    return q, k, v, do, lse, delta, bias


def build(case, phase, bq, bk, sub, run, interpret, iters):
    """``iters`` calls of the kernel in ONE program and its inputs,
    blocks, sub-tile and run cap (sub-tiles a run) explicit (no table,
    no clamp: what is asked is what is timed).  Each call's output
    feeds the next one's input, so the loop runs them one after another
    and the host's dispatch (most of a millisecond here) is paid once,
    not a call."""
    BH, Sq, Sk, D = case["BH"], case["Sq"], case["Sk"], case["D"]
    KV, window = case["kv"], case["window"]
    q, k, v, do, lse, delta, bias = inputs_of(
        case["name"], BH, Sq, Sk, D, case["k_offset"], case["bias"],
        interpret, KV, window)
    subs = (sub, sub, run) if sub else (bq, bk, 1)
    static = (Sq, Sk, D, BH, KV, float(D) ** -0.5, True, 0,
              case["k_offset"], bq, bk, subs, case["bias"], interpret)
    bias = () if bias is None else (bias,)
    if phase == "fwd":
        return chained(fap._fwd_call(BH, *static, "bfloat16", window), 0,
                       iters), (q, k, v) + bias
    if phase == "dq":
        call = fap._dq_pallas_call(BH, KV, *static, "bfloat16", window)
        return chained(call, 0, iters), (q, k, v, do, lse, delta) + bias
    # dkv holds its tiles keys by queries: statistics as rows, the key
    # bias as a column (flash_bwd_pallas reshapes them so)
    call = fap._dkv_pallas_call(BH, KV, *static, "bfloat16", "bfloat16",
                                window)
    return chained(call, 1, iters), (
        q, k, v, do, lse.reshape(BH, 1, Sq), delta.reshape(BH, 1, Sq)
    ) + tuple(b.reshape(-1, Sk, 1) for b in bias)


def chained(call, feed, iters):
    """``iters`` calls, the first output of each (out, dq, dk) put in
    the place of argument ``feed`` (q, q, k) of the next."""
    def many(*args):
        def body(_, x):
            out = call(*args[:feed], x, *args[feed + 1:])
            out = out[0] if isinstance(out, (tuple, list)) else out
            return out.astype(x.dtype)
        return jax.lax.fori_loop(0, iters, body, args[feed])
    return jax.jit(many)


def code_size(fn, args):
    """What one call of the kernel costs a program before it runs:
    seconds to trace and lower it, and the bytes of the serialized
    module (for the TPU, whatever the host: nothing is compiled)."""
    from jax import export

    t0 = time.perf_counter()
    exported = export.export(fn, platforms=["tpu"])(*args)
    seconds = time.perf_counter() - t0
    return round(seconds, 3), len(exported.mlir_module_serialized)


def time_kernel(fn, args, iters):
    """Seconds a call: the best of three runs of the chained program."""
    jax.block_until_ready(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def candidates(case, phase, blocks, subs, runs):
    """(bq, bk, sub, run, shipped) that divide the shape; a sub-tile
    divides both blocks (None: the block is one tile, what the kernels
    did before they walked sub-tiles); ``run``: the sub-tiles a run at
    most, for each cap of ``runs`` (columns) that gives another number
    of them; ``shipped``: it is the kernels' own cap for that sub-tile.
    The whole key length is always a candidate key block: one block
    along the keys is code with no state carried between grid steps."""
    Sq, Sk = case["Sq"], case["Sk"]
    # (8,192 keys of 128 in one block do not fit VMEM: not a candidate)
    key_blocks = sorted(set(blocks) | ({Sk} if Sk <= 4096 else set()))
    for bq, bk in itertools.product(blocks, key_blocks):
        if bq > Sq or Sq % bq or bk > Sk or Sk % bk:
            continue
        for sub in subs:
            if sub is None and bq * bk <= 1024 * 1024:
                yield bq, bk, None, 1, True
            elif sub and bq % sub == 0 and bk % sub == 0:
                walked = max(bq, bk) // sub
                for run in sorted({min(max(1, c // sub), walked)
                                   for c in runs}):
                    yield bq, bk, sub, run, run == min(fap.run_cap(sub),
                                                       walked)


#: the query block the dispatcher gives a call whose key length is not
#: its query length (the table's blocks are read where Sk == Sq only:
#: ``flash_attention_pallas._resolve_targets``), forward
DISPATCH_BQ = fap.DEFAULT_BLOCK["fwd"]


#: a sub-tile within this share of the fastest is as good: among those
#: the table takes the one whose kernels hold the least code
SLACK = 0.02


def tuned_table(records, slack=SLACK):
    """The ``tuned_blocks_table`` rows ``[[S, D, dtype, phase], [bq,
    bk, sub]]`` from a sweep's timing records (pure: the last line of a
    sweep, or ``--table-from`` kept outputs).

    Blocks come from the self-attention case of a shape (the table's
    blocks are read where Sk == Sq); the ``"bwd"`` row is the
    combination under which dq and dkv together take least, since both
    kernels read that one row.  The SUB-TILE of a row is read whatever
    the key length, so it is the one under which all of the shape's
    cases together take least: the self-attention case at its best
    blocks for that sub-tile, a case with more keys at the query block
    the dispatcher gives it (``DISPATCH_BQ``) and its best key block.
    A kernel's code is paid for once a compiled program before it runs
    (module docstring of the kernels), so among the sub-tiles within
    ``slack`` of the fastest the row takes the one whose kernels hold
    the fewest bodies."""
    cost = {}   # (S, D, phase) -> {sub: {case: {(bq, bk): ms}}}
    for r in records:
        # the run cap is the kernels' constant, not a column of the
        # table: only what the dispatcher would run is a candidate
        if "ms" not in r or not r.get("run_shipped", True):
            continue
        _, Sq, Sk, D = r["shape"]
        if Sk != Sq and r["bq"] != min(DISPATCH_BQ, Sq):
            continue
        by_case = cost.setdefault((Sq, D), {}).setdefault(
            r["sub"], {}).setdefault((r["case"], Sk == Sq), {})
        by_case.setdefault((r["bq"], r["bk"]), {})[r["phase"]] = (
            r["ms"], r.get("subtiles", {}).get("bodies", 0))
    rows = {}
    for (S, D), by_sub in cost.items():
        for phase, kernels in (("fwd", ("fwd",)), ("bwd", ("dq", "dkv"))):
            found = []   # (ms, bodies, row) a sub-tile
            for sub, by_case in by_sub.items():
                total, bodies, blocks = 0.0, 0, None
                for (_, own), by_blocks in by_case.items():
                    timed = {b: (sum(got[k][0] for k in kernels),
                                 sum(got[k][1] for k in kernels))
                             for b, got in by_blocks.items()
                             if all(k in got for k in kernels)}
                    if not timed:
                        continue
                    b = min(timed, key=timed.get)
                    total, bodies = total + timed[b][0], bodies + timed[b][1]
                    if own:
                        blocks = b
                if blocks:
                    found.append((total, bodies,
                                  blocks + ((sub,) if sub else ())))
            if found:
                fastest = min(f[0] for f in found)
                rows[(S, D, phase)] = min(
                    (f for f in found if f[0] <= (1 + slack) * fastest),
                    key=lambda f: (f[1], f[0]))[2]
    return [[[S, D, "bfloat16", phase], list(row)]
            for (S, D, phase), row in sorted(rows.items())]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer shapes/blocks")
    ap.add_argument("--shapes", nargs="+", default=["train", "eva", "mla"],
                    choices=["train", "eva", "mla", "afmoe", "long"])
    ap.add_argument("--long", action="store_true",
                    help="add the 4096/8192 shapes and their ring chunks")
    ap.add_argument("--blocks", nargs="+", type=int,
                    default=[512, 1024, 2048])
    ap.add_argument("--subs", nargs="+", type=int, default=[128, 256, 512],
                    help="sub-tile sides; 0 = the block as one tile")
    ap.add_argument("--runs", nargs="+", type=int,
                    default=[RUN_COLUMNS],
                    help="run caps in columns (a sub-tile's side: no runs); "
                         "only the kernels' own feeds the table")
    ap.add_argument("--iters", type=int, default=50,
                    help="calls chained in one program a timing")
    ap.add_argument("--interpret", action="store_true",
                    help="Pallas interpreter mode (CPU smoke test only — "
                         "timings are meaningless)")
    ap.add_argument("--table-from", nargs="+", metavar="JSONL",
                    help="time nothing: print the tuned_blocks_table of "
                         "these kept sweep outputs together")
    args = ap.parse_args()
    if args.table_from:
        records = []
        for path in args.table_from:
            with open(path) as f:
                records += [json.loads(line) for line in f
                            if line.startswith('{"case"')]
        print(json.dumps({"tuned_blocks_table": tuned_table(
            [r for r in records if "best" not in r])}))
        return

    groups = list(args.shapes) + (["long"] if args.long else [])
    subs = [s or None for s in args.subs]
    blocks = args.blocks
    todo = cases(groups, args.quick)
    if args.quick:
        blocks, subs = blocks[:2], subs[:2]
    if args.interpret:
        # interpret mode = CPU: real shapes through the interpreter take
        # minutes — the smoke contract is a tiny shape, both phases
        todo = [_case("tiny", ("fwd", "dq", "dkv"), 2, 256, 64)]
        blocks, subs, args.iters = [128, 256], [128, None], 1
        args.runs = [128, 256]
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "peak_tflops": PEAK_TFLOPS}),
          flush=True)

    records, best = [], {}
    for case in todo:
        for phase in case["phases"]:
            for bq, bk, sub, run, shipped in candidates(
                    case, phase, blocks, subs, args.runs):
                rec = {"case": case["name"], "phase": phase,
                       "shape": [case["BH"], case["Sq"], case["Sk"], case["D"]],
                       "bq": bq, "bk": bk, "sub": sub, "run": run,
                       "run_shipped": shipped}
                try:
                    fn, inputs = build(case, phase, bq, bk, sub, run,
                                       args.interpret, args.iters)
                    one, _ = build(case, phase, bq, bk, sub, run,
                                   args.interpret, 1)
                    lower_s, module_bytes = code_size(one, inputs)
                    sec = time_kernel(fn, inputs, args.iters)
                except Exception as e:  # noqa: BLE001 — a combo can exceed VMEM
                    print(json.dumps({**rec, "error": type(e).__name__}),
                          flush=True)
                    continue
                visited, masked, skipped, bodies = fap.live_subtiles(
                    phase, case["Sq"], case["Sk"], 0, case["k_offset"],
                    bq, bk, sub, run=run, window=case["window"])
                tflops = useful_tflop(case, phase) / sec
                rec.update(
                    ms=round(sec * 1e3, 4),
                    us_per_head=round(sec * 1e6 / case["BH"], 3),
                    tflops=round(tflops, 2),
                    pct_peak=round(100 * tflops / PEAK_TFLOPS, 1),
                    subtiles={"visited": visited, "masked": masked,
                              "skipped": skipped, "bodies": bodies},
                    lower_s=lower_s, module_bytes=module_bytes)
                print(json.dumps(rec), flush=True)
                records.append(rec)
                key = (case["name"], phase)
                if key not in best or rec["ms"] < best[key]["ms"]:
                    best[key] = rec
    for rec in best.values():
        print(json.dumps({**rec, "best": True}), flush=True)
    # table-ready rows in the list-of-pairs format set_tuned_blocks
    # accepts directly:
    #   set_tuned_blocks(json.loads(line)["tuned_blocks_table"])
    print(json.dumps({"tuned_blocks_table": tuned_table(records)}),
          flush=True)


if __name__ == "__main__":
    main()
