"""The knee of an LFM2-MoE serving configuration, once, on the chip, from
a FULL start: ``python -m cellbench.sweep_lfm2_moe [--config ...] [--mix
...] [--window 40] [--rates 30,8,8,9,9,10,10,12]``.

``cellbench/sweep_kda_mla_moe.py``'s ladder (its :func:`offer`: one
window of open-loop load a rate over ONE build, the server filled
before every window as the cell fills it, the mix's
``in_flight_at_open`` requests admitted and prefilled before the clock
starts) over the scheduler that ``adapters/serve_lfm2_moe.py`` builds.
The first rate should be far above what the server can take: its tokens
a second are the capacity.  The rungs are ABSOLUTE rates, a rate given
twice being two windows of it (each window has a seed of its own), up
to a rung that plainly fails (every slot busy, ``ttft_p50`` in
seconds): shares of one overloaded window cannot pass that reading
(PERF.md, PR 44).  The knee is the highest rate at which the requests
in the system do not grow and a slot is free most of the time; it is
read by hand and written into the mix.
Prints one JSON line a window and a last line ``sweep: [...]``.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="lfm2-8b-a1b-serve-pp2")
    p.add_argument("--mix", default="agentgen-1.25knee")
    p.add_argument("--window", type=float, default=40.0)
    p.add_argument("--rates", default="30,8,8,9,9,10,10,12")
    a = p.parse_args(argv)
    sys.path.insert(0, str(ROOT))

    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from apex_tpu.inference import Request
    from cellbench import loadgen
    from cellbench import weights_lfm2_moe as weights
    from cellbench.adapters import serve_lfm2_moe as adapter
    from cellbench.adapters.serve import WARMUP_RID
    from cellbench.sweep_kda_mla_moe import offer

    if jax.devices()[0].platform != "tpu":
        sys.exit("cellbench.sweep_lfm2_moe: needs a TPU")
    conf = json.loads((ROOT / "cellbench" / "configs"
                       / f"{a.config}.json").read_text())
    mix = json.loads((ROOT / "cellbench" / "traffic"
                      / f"{a.mix}.json").read_text())
    vocab = weights.sizes(conf)["V"]
    sched, dcfg = adapter.build(conf, weights.seed_key(0), 0)
    adapter.warm_up(sched, dcfg, vocab, 0)
    gen = loadgen.generator(mix)
    rows = []
    for i, rate in enumerate(float(x) for x in a.rates.split(",")):
        for r in gen.in_flight_at_open(mix, vocab, i):
            sched.submit(Request(
                rid=WARMUP_RID + 1000 * (i + 1) + r.rid, prompt=r.prompt,
                max_new_tokens=r.max_new_tokens))
        while sched.queue and sched.num_active < dcfg.max_batch:
            sched.step()
        rows.append(offer(sched, mix, vocab, rate, a.window, 1 + i,
                          dcfg.max_batch))
    print("sweep: " + json.dumps(rows), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
