"""Find the serving configuration's page size and knee, once, on the
chip: ``python -m cellbench.sweep --config gpt2-large-serve --mix
chat-0.8knee [--pages 16,64,128] [--window 20]``.

1. Page sizes: for each, build the server, fill every slot, time 20
   decode steps.  The configuration takes the fastest.
2. Saturation: at the file's page size, offer far more than the server
   can take for one window and read the output tokens per second.
3. Ladder: from that, offer 0.6 .. 1.2 of the matching request rate for
   one window each and watch the requests in the system.  The highest
   rate at which the backlog does not grow is the knee; the mixes'
   ``rate`` fields are written by hand from it.

Prints one JSON line per measurement and a last line ``sweep: {...}``.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def _json(path):
    with open(path) as f:
        return json.load(f)


def time_pages(conf, key, pages, steps=20):
    from apex_tpu.inference import Request
    from cellbench import weights
    from cellbench.adapters import serve

    rows = []
    s = weights.sizes(conf)
    rng = np.random.RandomState(0)
    for page in pages:
        t_build = time.time()
        sched, dcfg = serve.build(conf, key, 0, page_size=page)
        for i in range(dcfg.max_batch):
            sched.submit(Request(
                rid=i, prompt=rng.randint(0, s["V"], size=500).tolist(),
                max_new_tokens=400))
        sched.step()                # admits and prefills every slot
        for _ in range(3):
            sched.step()
        assert sched.num_active == dcfg.max_batch
        t = time.monotonic()
        for _ in range(steps):
            sched.step()
        per_step = (time.monotonic() - t) / steps
        rows.append({"page_size": page, "decode_step_ms": 1e3 * per_step,
                     "active": sched.num_active, "context": 500,
                     "build_and_compile_s": time.time() - t_build})
        print(json.dumps(rows[-1]), flush=True)
        del sched
        gc.collect()
    return rows


def offer(sched, mix, vocab, rate, window, seed):
    from cellbench import arith, loadgen
    from cellbench.adapters import serve

    mix = json.loads(json.dumps(mix))
    mix["arrivals"]["rate"] = rate
    requests = loadgen.generator(mix).requests(mix, vocab, seed, window)
    before = len(sched.completed)
    w = serve.drive(sched, requests, window, None, print)
    done = {c.rid: c for c in sched.completed[before:]}
    t0, t1 = w["t0"], w["t_close"]
    tokens = sum(1 for c in done.values() for t in c.token_times
                 if t0 <= t < t1)
    ttft = [1e3 * (c.token_times[0] - w["due_at"][r])
            for r, c in done.items()]
    gaps = [1e3 * float(g) for c in done.values()
            for g in np.diff(c.token_times)]
    ts = np.array([a for a, _ in w["in_system"]])
    ns = np.array([b for _, b in w["in_system"]], float)
    late = ts >= window / 3.0
    slope = float(np.polyfit(ts[late], ns[late], 1)[0]) if late.sum() > 2 \
        else float("nan")
    row = {"rate": rate, "window_s": window, "requests": len(requests),
           "finished": len(done),
           "tokens_per_s": tokens / (t1 - t0),
           "ttft_p50_ms": arith.percentile(ttft, 50),
           "ttft_p90_ms": arith.percentile(ttft, 90),
           "gap_p50_ms": arith.percentile(gaps, 50),
           "gap_p95_ms": arith.percentile(gaps, 95),
           "in_system_mean_mid": float(ns[(ts >= window / 3) & (ts < 2 * window / 3)].mean()),
           "in_system_mean_end": float(ns[ts >= 2 * window / 3].mean()),
           "in_system_at_close": float(ns[-1]),
           "backlog_growth_per_window": slope * window,
           "occupancy_mean": float(np.mean(w["occupancy"])),
           "drain_s": w["t_drained"] - t1,
           "lateness_max_ms": 1e3 * max(w["lateness"], default=0.0)}
    print(json.dumps(row), flush=True)
    return row


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="gpt2-large-serve")
    p.add_argument("--mix", default="chat-0.8knee")
    p.add_argument("--pages", default="16,64,128")
    p.add_argument("--window", type=float, default=20.0)
    p.add_argument("--shares", default="0.6,0.7,0.8,0.9,1.0,1.1,1.2")
    p.add_argument("--base", type=float, default=None,
                   help="requests/s the shares are of; skips saturation")
    a = p.parse_args(argv)
    sys.path.insert(0, str(ROOT))

    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from cellbench import weights
    from cellbench.adapters import serve

    if jax.devices()[0].platform != "tpu":
        sys.exit("cellbench.sweep: needs a TPU")
    conf = _json(ROOT / "cellbench" / "configs" / f"{a.config}.json")
    mix = _json(ROOT / "cellbench" / "traffic" / f"{a.mix}.json")
    key = weights.seed_key(0)
    s = weights.sizes(conf)
    out = {"pages": [], "ladder": []}
    pages = [int(x) for x in a.pages.split(",") if x]
    if pages:
        out["pages"] = time_pages(conf, key, pages)

    sched, dcfg = serve.build(conf, key, 0)
    from cellbench import loadgen

    mean_out = float(np.mean([r.max_new_tokens for r in
                              loadgen.generator(mix).requests(
        dict(mix, arrivals=dict(mix["arrivals"], rate=10.0)),
        s["V"], 0, 100.0)]))
    if a.base is None:
        sat = offer(sched, mix, s["V"], 10.0, a.window, seed=1)
        out["saturation"] = sat
        base = sat["tokens_per_s"] / mean_out
    else:
        base = a.base
    print(json.dumps({"mean_output_tokens": mean_out,
                      "saturated_requests_per_s": base}), flush=True)
    for i, share in enumerate(float(x) for x in a.shares.split(",")):
        out["ladder"].append(dict(
            offer(sched, mix, s["V"], share * base, a.window, seed=2 + i),
            share_of_saturation=share))
    print("sweep: " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
