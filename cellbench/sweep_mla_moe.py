"""The knee of a latent-attention, sparse-expert serving configuration,
once, on the chip: ``python -m cellbench.sweep_mla_moe [--config ...]
[--mix ...] [--window 20] [--shares 0.8,0.9,1.0,1.1]``.

``cellbench/sweep.py``'s method and its ``offer`` (a saturation window,
then a ladder of windows at shares of the matching request rate; the
highest rate at which the backlog does not grow is the knee) over the
scheduler that ``adapters/serve_mla_moe.py`` builds.  The page size is
not swept: a latent tile is 576 x 128.  Prints one JSON line per
window and a last line ``sweep: {...}``.
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="gigachat3.1-702b-a36b-serve-ep16")
    p.add_argument("--mix", default="longgen-1.25knee")
    p.add_argument("--window", type=float, default=20.0)
    p.add_argument("--saturate", type=float, default=40.0,
                   help="requests/s offered in the saturation window")
    p.add_argument("--shares", default="0.8,0.9,1.0,1.1")
    p.add_argument("--base", type=float, default=None,
                   help="requests/s the shares are of; skips saturation")
    a = p.parse_args(argv)
    sys.path.insert(0, str(ROOT))

    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from cellbench import loadgen, sweep
    from cellbench import weights_mla_moe as weights
    from cellbench.adapters import serve_mla_moe as adapter

    if jax.devices()[0].platform != "tpu":
        sys.exit("cellbench.sweep_mla_moe: needs a TPU")
    conf = json.loads((ROOT / "cellbench" / "configs"
                       / f"{a.config}.json").read_text())
    mix = json.loads((ROOT / "cellbench" / "traffic"
                      / f"{a.mix}.json").read_text())
    vocab = weights.sizes(conf)["V"]
    sched, dcfg = adapter.build(conf, weights.seed_key(0), 0)
    adapter.warm_up(sched, dcfg, vocab, 0)
    mean_out = float(np.mean([
        r.max_new_tokens for r in loadgen.generator(mix).requests(
            dict(mix, arrivals=dict(mix["arrivals"], rate=10.0)),
            vocab, 0, 100.0)]))
    out = {"ladder": []}
    if a.base is None:
        out["saturation"] = sweep.offer(sched, mix, vocab, a.saturate,
                                        a.window, seed=1)
        base = out["saturation"]["tokens_per_s"] / mean_out
    else:
        base = a.base
    print(json.dumps({"mean_output_tokens": mean_out,
                      "saturated_requests_per_s": base}), flush=True)
    for i, share in enumerate(float(x) for x in a.shares.split(",")):
        out["ladder"].append(dict(
            sweep.offer(sched, mix, vocab, share * base, a.window,
                        seed=2 + i),
            share_of_saturation=share))
    print("sweep: " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
