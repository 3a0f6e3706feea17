"""Every op of a cell's STEP by name: one traced run of a benchmark cell,
the trace kept, and the self time of every device op that lies inside
the cell's most frequent program (a serving cell's decode or block step,
a training cell's step), summed by op name, in ms a step.

A traced run's result line holds the ten largest ops of the whole trace
(``breakdown.device_ops``); a cost made of many small ones is not in it,
and a step's rise or fall "by op" is a difference of two such lists, one
a checkout.  ``--root`` is the checkout to run (the tree this file lies
in, or a parent commit unpacked beside it), so one chip call reads both:

    python benchmarks/cell_step_ops.py --workload \\
        sdar-30b-a3b.serve-blockgen-over --seed 2147500102 \\
        --out chiprun_out/ops_change.json
    python benchmarks/cell_step_ops.py --root .chip_scratch/parent ...

Prints the run's metrics on one JSON line, then the ops, largest first.
Refuses to run without a TPU, as the benchmark does.
"""

import argparse
import bisect
import glob
import json
import os
import re
import sys
import types
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def by_name(loaded: dict) -> dict:
    """Of a loaded trace (``cellbench.trace.reduce.load_xplane``): the
    most frequent program's whole executions, and the self time of the
    ops inside them by name (trailing ``.N`` cut), in ms an execution."""
    from cellbench.trace import reduce

    device = next(iter(loaded["devices"]))
    programs = {}
    for m in loaded["modules"][device]:
        programs.setdefault(m[0], []).append(m)
    name = max(programs, key=lambda n: len(programs[n]))
    steps = sorted(programs[name], key=lambda m: m[1])[1:-1]
    spans = [(m[1], m[1] + m[2]) for m in steps]
    starts = [a for a, _ in spans]
    total = {}
    for label, start, _, own in reduce.with_self_times(
            loaded["devices"][device]):
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < spans[i][1]:
            key = re.sub(r"[.\d]+$", "", label.lstrip("%"))
            total[key] = total.get(key, 0) + own
    n = max(len(steps), 1)
    return {"program": name, "steps": len(steps),
            "step_ms": sum(m[2] for m in steps) / n / 1e6,
            "ops_ms": sorted(((k, v / n / 1e6) for k, v in total.items()),
                             key=lambda kv: -kv[1]),
            "programs": {k: [len(v), sum(m[2] for m in v) / 1e6]
                         for k, v in programs.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--root", default=str(REPO),
                    help="the checkout whose program and benchmark run")
    ap.add_argument("--out", help="write the whole table here, as JSON")
    ap.add_argument("--top", type=int, default=40)
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    out = args.out and os.path.abspath(args.out)    # not under --root
    os.chdir(root)
    sys.path.insert(0, root)
    import cellbench.profiling as profiling

    # the harness deletes the trace once it has reduced it: keep it
    profiling.shutil = types.SimpleNamespace(rmtree=lambda *a, **k: None)
    from cellbench.run import run_cell
    from cellbench.trace import reduce

    result = run_cell(Path(root), args.workload, args.seed, args.seconds,
                      True)
    traces = sorted(glob.glob(os.path.join(
        root, ".cellbench_trace", "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime)
    table = by_name(reduce.load_xplane(traces[-1]))
    table.update(
        root=root, workload=args.workload, seed=args.seed,
        correct=result.get("correct"), failed=result.get("failed"),
        metrics={k: v["value"] if isinstance(v, dict) else v
                 for k, v in result.get("metrics", {}).items()})
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump(table, f, indent=1)
    print(json.dumps({k: v for k, v in table.items() if k != "ops_ms"}))
    for name, ms in table["ops_ms"][:args.top]:
        print(f"{ms:9.3f} ms  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
