"""The knee of a KDA/MLA serving configuration, once, on the chip, from
a FULL start: ``python -m cellbench.sweep_kda_mla_moe [--config ...]
[--mix ...] [--window 40] [--rates 12,3.5,4,4.5,5]``.

One window of open-loop load a rate (``serve.drive``; finished
requests, tokens a second, time to first token, how the requests in
the system grew: those of the window AND those in flight at its open)
over the scheduler that ``adapters/serve_kda_mla_moe.py`` builds, ONE
build for all rates.
Before every window the server is filled as the cell fills it (the
mix's ``in_flight_at_open`` requests, admitted and prefilled before the
clock starts): from an empty start the fill-up of 128 slots reads as
backlog and puts the knee too low (PERF.md, section 4).  The first rate
should be far above what the server can take: its tokens a second are
the capacity the others are shares of.  The knee is the highest rate at
which the queue stays mostly empty (``ttft_p50`` well under a second
and ``backlog_growth_per_window`` about zero); it is read by hand and
written into the mix.  Prints one JSON line a window and a last line
``sweep: [...]``.
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def offer(sched, mix, vocab, rate, window, seed, slots):
    """One window at ``rate`` on a server that is already full."""
    from cellbench import arith, loadgen
    from cellbench.adapters import serve

    mix = json.loads(json.dumps(mix))
    mix["arrivals"]["rate"] = rate
    requests = loadgen.generator(mix).requests(mix, vocab, seed, window)
    before = len(sched.completed)
    w = serve.drive(sched, requests, window, None, print)
    t0, t1 = w["t0"], w["t_close"]
    comps = sched.completed[before:]
    mine = {c.rid: c for c in comps if c.rid < serve.WARMUP_RID}
    held = [c for c in comps if c.rid >= serve.WARMUP_RID]
    tokens = sum(1 for c in comps for t in c.token_times if t0 <= t < t1)
    ttft = [1e3 * (c.token_times[0] - w["due_at"][r])
            for r, c in mine.items()]
    ts = np.linspace(0.0, t1 - t0, 81)
    due = np.array([w["due_at"][r] - t0 for r in mine])
    end = np.array([c.finish_time - t0 for c in mine.values()])
    left = np.array([c.finish_time - t0 for c in held])
    system = np.array([np.sum((due <= t) & (end > t)) + np.sum(left > t)
                       for t in ts], float)
    late = ts >= window / 3.0
    row = {"rate": rate, "window_s": window, "requests": len(requests),
           "finished": len(mine), "tokens_per_s": tokens / (t1 - t0),
           "ttft_p50_ms": arith.percentile(ttft, 50),
           "ttft_p90_ms": arith.percentile(ttft, 90),
           "in_system_at_third": float(system[27]),
           "in_system_at_close": float(system[-1]),
           "backlog_growth_per_window": float(
               np.polyfit(ts[late], system[late], 1)[0]) * window,
           "slots": slots, "occupancy_mean": float(np.mean(w["occupancy"])),
           "drain_s": w["t_drained"] - t1,
           "lateness_max_ms": 1e3 * max(w["lateness"], default=0.0)}
    print(json.dumps(row), flush=True)
    return row


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="kimi-linear-48b-a3b-serve-ep8")
    p.add_argument("--mix", default="docgen-1.25knee")
    p.add_argument("--window", type=float, default=40.0)
    p.add_argument("--rates", default="12,3.5,4,4.5,5,5.5")
    a = p.parse_args(argv)
    sys.path.insert(0, str(ROOT))

    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from apex_tpu.inference import Request
    from cellbench import loadgen
    from cellbench import weights_kda_mla_moe as weights
    from cellbench.adapters import serve_kda_mla_moe as adapter
    from cellbench.adapters.serve import WARMUP_RID

    if jax.devices()[0].platform != "tpu":
        sys.exit("cellbench.sweep_kda_mla_moe: needs a TPU")
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
