"""The knee of a block-generating serving configuration, once, on the
chip, from a FULL start: ``python -m cellbench.sweep_sdar_moe [--config
...] [--mix ...] [--window 40] [--rates 30,8,10,12,14 | --rates 30
--shares 0.8,0.9,1.0]``.

``cellbench/sweep_kda_mla_moe.py``'s ladder (its :func:`offer`: one
window of open-loop load a rate over ONE build, the server filled
before every window as the cell fills it, the mix's
``in_flight_at_open`` requests admitted and prefilled before the clock
starts) over the scheduler that ``adapters/serve_sdar_moe.py`` builds,
every request with the ``denoising_steps`` the generator deals it.  The
first rate should be far above what the server can take: its tokens a
second are the capacity the others are shares of (``--shares``: the
rates after the first are these shares of the capacity the first window
read, in requests a second by the window's mean answer).  Shares of ONE
overloaded window cannot pass that reading, and a 40 s window is a
noisy one: bracket the knee with ``--rates`` alone, in absolute rates
up to a rung that plainly fails (every slot busy, ``ttft_p50`` in
seconds), a rate given twice being two windows of it (each window has
a seed of its own).  The knee is the highest rate at which the requests
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
    p.add_argument("--config", default="sdar-30b-a3b-serve-ep8")
    p.add_argument("--mix", default="blockgen-1.25knee")
    p.add_argument("--window", type=float, default=40.0)
    p.add_argument("--rates", default="30,8,10,12,14")
    p.add_argument("--shares", default="")
    a = p.parse_args(argv)
    sys.path.insert(0, str(ROOT))

    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from apex_tpu.inference import Request
    from cellbench import loadgen
    from cellbench import weights_sdar_moe as weights
    from cellbench.adapters import serve_sdar_moe as adapter
    from cellbench.adapters.serve import WARMUP_RID
    from cellbench.sweep_kda_mla_moe import offer

    if jax.devices()[0].platform != "tpu":
        sys.exit("cellbench.sweep_sdar_moe: needs a TPU")
    conf = json.loads((ROOT / "cellbench" / "configs"
                       / f"{a.config}.json").read_text())
    mix = json.loads((ROOT / "cellbench" / "traffic"
                      / f"{a.mix}.json").read_text())
    vocab = weights.sizes(conf)["V"]
    sched, dcfg = adapter.build(conf, weights.seed_key(0), 0)
    adapter.warm_up(sched, dcfg, vocab, 0)
    gen = loadgen.generator(mix)
    rows = []
    rates = [float(x) for x in a.rates.split(",")]
    shares = [float(x) for x in a.shares.split(",") if x]
    for i in range(len(rates) + len(shares)):
        if i < len(rates):
            rate = rates[i]
        else:   # a share of what the first, overloaded window completed
            first = rows[0]
            rate = round(shares[i - len(rates)] * first["tokens_per_s"]
                         / first["mean_answer_tokens"], 2)
        held = gen.in_flight_at_open(mix, vocab, i)
        for r, t in zip(held, gen.steps(mix, len(held), 0)):
            sched.submit(Request(
                rid=WARMUP_RID + 1000 * (i + 1) + r.rid, prompt=r.prompt,
                max_new_tokens=r.max_new_tokens, denoising_steps=t))
        while sched.queue and sched.num_active < dcfg.max_batch:
            sched.step()
        n = max(int(round(rate * a.window)), 1)
        dealt = adapter._Submitting(
            sched, dict(enumerate(gen.steps(mix, n, 1 + i))))
        row = offer(dealt, mix, vocab, rate, a.window, 1 + i,
                    dcfg.max_batch)
        offered = gen.requests(dict(mix, arrivals=dict(
            mix["arrivals"], rate=rate)), vocab, 1 + i, a.window)
        row["mean_answer_tokens"] = sum(
            r.max_new_tokens for r in offered) / len(offered)
        rows.append(row)
    print("sweep: " + json.dumps(rows), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
