"""The combine of the no-drop expert layer's chunk alone: every way tried
of summing a token's rows out of the chunk, at the train cell's shape.

``transformer/expert_parallel._sum_own`` adds, for each of ``T`` tokens,
the rows it holds among the ``R`` rows of a chunk (sorted by expert) into
the float32 carry.  ``trinity-mini.train-8k`` calls it twelve times a
step at ``y`` (20480, 2048) bfloat16, ``T`` 16,384, ``top_k`` 8; until
PR 42 a call was ``top_k`` gathers of (T, H) float32 and their sum, 7.98
ms.  One JSON line a candidate and a routing (``eighth``: uniform over
128 experts, 16 held, so 1/8 of the assignments; ``all``: every
assignment held, the chunk full): the milliseconds a call (``--reps``
calls chained through the donated carry and waited for once) and the
largest difference from ``gathers_f32`` relative to the sum of a token's
absolute terms.

- ``gathers_f32``: what ran until PR 42 (the weighted float32 rows
  written out, a gather a slot, the sum of the eight);
- ``gathers_rows``: the plain form that stands (the rows gathered in
  their own dtype, the weight applied after the gather);
- ``held_first``: the same with each token's slots ordered held-first
  and only as many passes as the fullest token needs;
- ``segment_sum``: one XLA scatter-add of the chunk's weighted rows;
- ``sorted_kernel``: ``apex_moe_combine`` (the rows gathered once into
  token order, each token's run added in place); ``sorted_prepare`` is
  its XLA part alone (sort, staircase, gather), ``sorted_kernel_f32``
  the same kernel handed float32 rows;
- ``row_dma``: a kernel that copies one row a held assignment out of
  the float32 weighted rows (no sort, no gather) and sums a token
  block's ``top_k`` slabs.

    python benchmarks/moe_combine_sweep.py > chiprun_out/combine_sweep.jsonl
    python benchmarks/moe_combine_sweep.py --interpret   # CPU rehearsal:
        # tiny shapes through the Pallas interpreter, no timing meant
"""

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import moe_combine_pallas as combine
from apex_tpu.transformer import expert_parallel as ep

F32 = jnp.float32
E, HELD = 128, 16


def routing(name, T, K, R, key):
    """Chunk 0 of a draw, as ``_held_chunks`` hands it to the combine:
    ``(token, valid, slot)``."""
    expert = jnp.argsort(jax.random.uniform(key, (T, E)), axis=1)[:, :K]
    live = expert < HELD if name == "eighth" else jnp.ones((T, K), bool)
    A = T * K
    order = jnp.argsort(jnp.where(live, expert % HELD, HELD).reshape(A),
                        stable=True)
    slot = jnp.zeros((A,), jnp.int32).at[order].set(
        jnp.arange(A, dtype=jnp.int32)).reshape(T, K)
    return (order[:R] // K).astype(jnp.int32), \
        jnp.arange(R) < jnp.sum(live), slot


def gathers_f32(out, y, w, token, valid, slot):
    rows = y.astype(F32) * w[:, None]
    local = ep._rows_of(slot, 0, y.shape[0])
    total = 0.0
    for k in range(slot.shape[1]):
        total = total + jnp.take(rows, local[:, k], axis=0, mode="fill",
                                 fill_value=0)
    return out + total


def held_first(out, y, w, token, valid, slot):
    """Slots ordered held-first a token (a sort of ``top_k``), then a
    pass a rank while any token still holds one."""
    R = y.shape[0]
    local = jnp.sort(jnp.where(slot < jnp.sum(valid),
                               ep._rows_of(slot, 0, R), R), axis=1)
    passes = jnp.max(jnp.sum(local < R, axis=1))

    def one(k, out):
        rows = jax.lax.dynamic_index_in_dim(local, k, 1, keepdims=False)
        own = jnp.take(y, rows, axis=0, mode="fill", fill_value=0)
        return out + own.astype(F32) * jnp.take(
            w, rows, mode="fill", fill_value=0)[:, None]

    return jax.lax.fori_loop(0, passes, one, out)


def segment_sum(out, y, w, token, valid, slot):
    return out.at[jnp.where(valid, token, out.shape[0])].add(
        y.astype(F32) * w[:, None], mode="drop")


def sorted_prepare(out, y, w, token, valid, slot):
    T = out.shape[0]
    parts = combine._token_order(y, w, jnp.where(valid, token, T), T,
                                 combine.token_block(T))
    # something of every part into the carry, so none is dead code
    return out.at[0, 0].add(sum(jnp.sum(p[..., :1].astype(F32))
                                for p in parts))


# ------------------------------------------------------ form 2: row copies
ROW_BLOCK = 32


def _row_dma_kernel(local_ref, rows_ref, carry_ref, mask_ref, out_ref, slab,
                    sem, *, top_k, n_rows):
    b = pl.program_id(0)
    base = b * ROW_BLOCK * top_k

    def start(i, n):
        row = local_ref[base + i]

        @pl.when(row < n_rows)
        def _():
            pltpu.make_async_copy(
                rows_ref.at[pl.ds(row, 1)],
                slab.at[i % top_k, pl.ds(i // top_k, 1)], sem).start()

        return n + (row < n_rows).astype(jnp.int32)

    started = jax.lax.fori_loop(0, ROW_BLOCK * top_k, start, 0)

    def wait(i, _):
        pltpu.make_async_copy(rows_ref.at[pl.ds(0, 1)],
                              slab.at[0, pl.ds(0, 1)], sem).wait()
        return 0

    jax.lax.fori_loop(0, started, wait, 0)
    total = carry_ref[...]
    for k in range(top_k):
        total = total + jnp.where(mask_ref[:, k:k + 1] != 0, slab[k], 0.0)
    out_ref[...] = total


def row_dma(out, y, w, token, valid, slot, interpret=False):
    T, H = out.shape
    K, R = slot.shape[1], y.shape[0]
    rows = y.astype(F32) * w[:, None]
    local = jnp.where(slot < jnp.sum(valid), ep._rows_of(slot, 0, R), R)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(T // ROW_BLOCK,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec((ROW_BLOCK, H), lambda b, local: (b, 0)),
                  pl.BlockSpec((ROW_BLOCK, K), lambda b, local: (b, 0))],
        out_specs=pl.BlockSpec((ROW_BLOCK, H), lambda b, local: (b, 0)),
        scratch_shapes=[pltpu.VMEM((K, ROW_BLOCK, H), F32),
                        pltpu.SemaphoreType.DMA(())])
    return pl.pallas_call(
        functools.partial(_row_dma_kernel, top_k=K, n_rows=R),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out.shape, out.dtype),
        input_output_aliases={2: 0}, interpret=interpret,
        name="sweep_moe_row_dma",
    )(local.reshape(-1), rows, out, (local < R).astype(jnp.int32))


def candidates(interpret):
    impl = "interpret" if interpret else "pallas"
    own = lambda impl, cast=None: (
        lambda out, y, w, token, valid, slot: ep._sum_own(
            out, y if cast is None else y.astype(cast), w, token, valid,
            slot, 0, impl))
    return {
        "gathers_f32": gathers_f32,
        "gathers_rows": own("xla"),
        "held_first": held_first,
        "segment_sum": segment_sum,
        "sorted_prepare": sorted_prepare,
        "sorted_kernel": own(impl),
        "sorted_kernel_f32": own(impl, F32),
        "row_dma": functools.partial(row_dma, interpret=interpret),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--routings", nargs="+", default=["eighth", "all"])
    ap.add_argument("--only", nargs="+", help="candidates by name")
    ap.add_argument("--unweighted", action="store_true", help="the "
                    "backward's call: no weight (the kernels' one-part path)")
    ap.add_argument("--columns", type=int, help="columns of a piece a "
                    "product of apex_moe_combine takes at once, in place "
                    "of the kernel's own")
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args(argv)
    if args.columns:
        combine.COLUMNS = args.columns
    device = jax.devices()[0]
    T, H, K, R = (256, 128, 8, 384) if args.interpret \
        else (16384, 2048, 8, 20480)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    y = jax.random.normal(keys[0], (R, H), jnp.bfloat16)
    table = candidates(args.interpret)
    for name in args.routings:
        token, valid, slot = routing(name, T, K, R, keys[1])
        y_live = jnp.where(valid[:, None], y, 0)
        w = jnp.where(valid, jax.random.uniform(keys[2], (R,)), 0.0)
        if args.unweighted:
            w = jnp.where(valid, 1.0, 0.0)
        operands = (y_live, w, token, valid, slot)
        fresh = lambda: jax.random.normal(keys[3], (T, H), F32)
        want = jax.jit(gathers_f32)(fresh(), *operands)
        size = jax.jit(gathers_f32)(jnp.abs(fresh()), jnp.abs(y_live), w,
                                    token, valid, slot)
        for cand in args.only or table:
            line = {"candidate": cand, "routing": name,
                    "held_rows": int(jnp.sum(valid)), "rows": R,
                    "tokens": T, "hidden": H, "top_k": K,
                    "weighted": not args.unweighted,
                    "columns": combine.COLUMNS,
                    "device": device.device_kind}
            fn = table[cand]
            if args.unweighted and cand.startswith("sorted_kernel"):
                fn = lambda out, y, w, *rest, _f=fn: _f(out, y, None, *rest)
            try:
                f = jax.jit(fn, donate_argnums=0)
                got = f(fresh(), *operands)
                if cand != "sorted_prepare":
                    line["max_err"] = float(jnp.max(
                        jnp.abs(got - want) / size))
                out = jax.block_until_ready(f(got, *operands))
                t0 = time.perf_counter()
                for _ in range(args.reps):
                    out = f(out, *operands)
                jax.block_until_ready(out)
                line["ms"] = round(
                    (time.perf_counter() - t0) / args.reps * 1e3, 4)
            except Exception as err:    # what the compiler refuses
                line["error"] = f"{type(err).__name__}: {err}"[:400]
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
