"""Readings for the limits of ``correct``, on the chip, in one process:
``python -m cellbench.control --workload <name> --seeds 12
--control-seeds 3 --seconds <s>``.

For each seed the cell runs as the benchmark runs it (a short window at
the cell's own size and load) and prints every number compared.  For
each control seed the plain reference, computed at the nearest precision
below the one the configuration states, is put in the program's place.
The last line gives, for each number, the largest that the sound runs
read and the smallest that the control reads: a limit belongs between
them, with room on both sides (``PERF.md`` lists the readings).
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
#: the nearest precision below the one a configuration states
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn",
         "float16": "float8_e4m3fn"}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    p.add_argument("--seconds", type=float, default=5.0)
    a = p.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from cellbench.cells import Bench
    from cellbench.run import run_cell

    cell = Bench(ROOT).cell(a.workload)
    stated = cell["config_file"]["cellbench"]["args"]["compute_dtype"]
    sound, control = {}, {}

    def note(into, checks):
        for name, value, _ in checks:
            # one row a number: drop the bracketed detail, pool the steps
            key = name.split(" (")[0].split(", step")[0]
            into.setdefault(key, []).append(value)

    for i in range(a.seeds + a.control_seeds):
        seed = a.first_seed + 7919 * i
        lower = LOWER[stated] if i >= a.seeds else None
        out = run_cell(ROOT, a.workload, seed, a.seconds, False,
                       control=lower, return_checks=True)
        note(control if lower else sound, out["checks"])
        print(json.dumps({"seed": seed, "control": lower,
                          "correct": out["correct"],
                          "failed": out["failed"],
                          "checks": [[n, v, lim]
                                     for n, v, lim in out["checks"]]}),
              flush=True)
    summary = {k: {"sound_max": max(sound.get(k, [float("nan")])),
                   "sound_all": sound.get(k, []),
                   "control_min": min(control.get(k, [float("nan")])),
                   "control_all": control.get(k, [])}
               for k in sorted(set(sound) | set(control))}
    print("control: " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
