"""The three fused-CE kernels alone, over row block x vocabulary block x
table dtype, at the training cells' shapes.

``ops/fused_ce_pallas.plan_blocks`` picks ``(bn, bv)`` from shapes and
dtypes under a VMEM price; what the price cannot say is which of two
neighbours that both fit is faster, so this measures them on the chip:

- ``gpt``: 8,192 rows of 1,024 against 50,304 (``gpt2-medium.train-b8``);
- ``trinity``: 16,384 rows of 2,048 against 25,024
  (``trinity-mini.train-8k``).

One JSON line a variant: the kernel, the blocks asked for (``null``:
the planner's own) and taken, the table's dtype and its bytes a call
(``plan_blocks``), the milliseconds a call (``--reps`` calls queued
back to back and waited for once: a call is 4-20 ms, the host's
dispatch a tenth of that) and the share of the MXU's published peak
(one logits product forward, two in each backward kernel).

    python benchmarks/fused_ce_sweep.py > chiprun_out/ce_sweep.jsonl
    python benchmarks/fused_ce_sweep.py --shapes gpt --kernels fwd \
        --budget-mib 20    # asked-for blocks the price would clamp
    python benchmarks/fused_ce_sweep.py --interpret   # CPU rehearsal:
        # tiny shapes through the Pallas interpreter, no timing meant
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp

from apex_tpu.ops import fused_ce_pallas as ce

PEAK_TFLOPS = 197.0   # TPU v5e, bf16 (Google Cloud documentation)
PRODUCTS = {"fwd": 1, "dx": 2, "dembed": 2}
BF16, F32 = jnp.bfloat16, jnp.float32
SHAPES = {"gpt": (8192, 1024, 50304), "trinity": (16384, 2048, 25024)}
#: (bn, bv, table dtype); None: the planner's.  The first line of a
#: kernel is the planner's choice, the last what ran before PR 41 (the
#: float32 master at 256 rows by 512).  An asked-for ``bv`` is halved
#: to the planner's VMEM price: ``--budget-mib`` lifts that
VARIANTS = {
    "fwd": [(None, None, BF16), (256, 512, BF16), (512, 512, BF16),
            (1024, 256, BF16), (1024, 512, BF16), (256, 1024, BF16),
            (512, 1024, BF16), (1024, 1024, BF16), (256, 2048, BF16),
            (512, 2048, BF16), (256, 512, F32)],
    "dx": [(None, None, BF16), (256, 512, BF16), (512, 256, BF16),
           (512, 512, BF16), (256, 1024, BF16), (512, 1024, BF16),
           (256, 512, F32)],
    "dembed": [(None, None, BF16), (256, 256, BF16), (256, 512, BF16),
               (512, 256, BF16), (512, 512, BF16), (256, 512, F32)],
}


def _call(kernel, bn, bv, interpret):
    if kernel == "fwd":
        return lambda x, e, t, lse, g: ce.fused_ce_fwd_pallas(
            x, e, t, block_n=bn, block_v=bv, interpret=interpret)
    which = ("dx", "dembed").index(kernel)
    return lambda x, e, t, lse, g: ce.fused_ce_bwd_pallas(
        x, e, t, lse, g, block_n=bn, block_v=bv, interpret=interpret)[which]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", default=sorted(SHAPES))
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--kernels", nargs="+", default=sorted(VARIANTS))
    ap.add_argument("--budget-mib", type=float, help="price the blocks "
                    "against another VMEM budget than the kernels' own: "
                    "lets a variant through that the planner would clamp")
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args(argv)
    if args.budget_mib:
        ce._VMEM_BUDGET = args.budget_mib * 2 ** 20
    device = jax.devices()[0]
    for name in args.shapes:
        N, H, V = (64, 128, 640) if args.interpret else SHAPES[name]
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        x = jax.random.normal(keys[0], (N, H), BF16)
        master = 0.02 * jax.random.normal(keys[1], (V, H), F32)
        tables = {F32: master, BF16: master.astype(BF16)}
        t = jax.random.randint(keys[2], (N,), 0, V)
        g = jnp.full((N,), 1.0 / N, F32)
        m, l, _ = jax.jit(_call("fwd", None, None, args.interpret))(
            x, tables[BF16], t, None, None)
        lse = m + jnp.log(l)
        for kernel in args.kernels:
            for bn, bv, dtype in VARIANTS[kernel]:
                if args.interpret and bn:
                    bn, bv = bn // 16, max(128, bv // 4)
                e = tables[dtype]
                plan = ce.plan_blocks(kernel, N, H, V, x.dtype, dtype, bn, bv)
                line = {"shape": name, "kernel": kernel, "asked": [bn, bv],
                        "bn": plan.bn, "bv": plan.bv, "grid": plan.grid,
                        "table": str(plan.table_dtype),
                        "table_bytes": plan.table_bytes,
                        "vmem_bytes": plan.vmem_bytes,
                        "device": device.device_kind}
                try:
                    f = jax.jit(_call(kernel, bn, bv, args.interpret))
                    jax.block_until_ready(f(x, e, t, lse, g))
                    t0 = time.perf_counter()
                    for _ in range(args.reps):
                        out = f(x, e, t, lse, g)
                    jax.block_until_ready(out)
                    ms = (time.perf_counter() - t0) / args.reps * 1e3
                    flops = PRODUCTS[kernel] * 2 * N * H * V
                    line.update(ms=round(ms, 4), mxu_share=round(
                        flops / (ms * 1e-3) / (PEAK_TFLOPS * 1e12), 4))
                except Exception as err:   # a block the compiler refuses
                    line["error"] = f"{type(err).__name__}: {err}"[:300]
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
