"""The knee of a Falcon-H1 serving configuration, once, on the chip, from
a FULL start: ``python -m cellbench.sweep_falcon_h1 [--config ...] [--mix
...] [--window 40] [--rates 20,5,6,7,8]``.

``cellbench/sweep_kda_mla_moe.py``'s ladder (its :func:`offer`: one
window of open-loop load a rate over ONE build, the server filled
before every window as the cell fills it, the mix's
``in_flight_at_open`` requests admitted and prefilled before the clock
starts) over the scheduler that ``adapters/serve_falcon_h1.py`` builds.
The first rate should be far above what the server can take: its tokens
a second are the capacity the others are shares of.  The knee is the
highest rate at which the requests in the system do not grow and a slot
is free most of the time; it is read by hand and written into the mix.
Prints one JSON line a window and a last line ``sweep: [...]``.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="falcon-h1-34b-serve-pp9")
    p.add_argument("--mix", default="h1chat-1.25knee")
    p.add_argument("--window", type=float, default=40.0)
    p.add_argument("--rates", default="20,5,6,7,8")
    a = p.parse_args(argv)
    sys.path.insert(0, str(ROOT))

    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from apex_tpu.inference import Request
    from cellbench import loadgen
    from cellbench import weights_falcon_h1 as weights
    from cellbench.adapters import serve_falcon_h1 as adapter
    from cellbench.adapters.serve import WARMUP_RID
    from cellbench.sweep_kda_mla_moe import offer

    if jax.devices()[0].platform != "tpu":
        sys.exit("cellbench.sweep_falcon_h1: needs a TPU")
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
