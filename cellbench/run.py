"""One run of one cell: ``python -m cellbench --workload <name> --seed
<n> --seconds <s> --trace <0|1>``.

Refuses to start without a TPU (or with fewer chips than the cell asks
for), builds the cell through its adapter, warms up only that cell's
shapes, measures for ``--seconds``, checks the timed path's outputs
against the plain reference outside the window, and prints as its last
line one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, in a traced run, ``breakdown``.  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.
"""

import argparse
import importlib
import json
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, Optional

CHECKOUT = Path(__file__).resolve().parents[1]
# libtpu logs to the fixed /tmp/tpu_logs unless told otherwise; keep a
# run's files under the TMPDIR it was given
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))


def parse(argv=None):
    p = argparse.ArgumentParser(prog="python -m cellbench")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def log(msg: str) -> None:
    print(msg, flush=True)


def device_facts(chips: int, require_tpu: bool) -> Dict:
    """The device as JAX reports it; exits without a result where it is
    no TPU or holds fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    facts = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    if require_tpu and facts["platform"] != "tpu":
        sys.exit(f"cellbench: needs a TPU; JAX reports {facts} - "
                 f"refusing to run")
    if facts["count"] < chips:
        sys.exit(f"cellbench: the cell needs {chips} chips; JAX reports "
                 f"{facts}")
    return facts


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, control: Optional[str] = None,
             return_checks: bool = False) -> Dict:
    """Everything but the argument parsing and the printing of the
    result line.  ``require_tpu=False`` is for the tests, which drive a
    tiny cell on the CPU: such a run reports counts and
    ``device.platform: cpu``, and no device metric.

    ``setup_s`` runs from the moment the accelerator's runtime is up
    (``jax.devices()`` has returned) to the opening of the window: the
    program's imports, weights, compilation or cache reads, warm-up.
    What comes before is Python's and JAX's start and the TPU runtime's,
    11 to 18 s on one v5e by the machine's state and none of it the
    program's or the cell's; it is logged in every run and kept out of
    the metric (``PERF.md``, section 2)."""
    t_called = time.time()
    from cellbench import readers
    from cellbench.cells import Bench
    from cellbench.profiling import WindowTrace

    bench = Bench(root)
    cell = bench.cell(workload)

    import jax

    t_imported = time.time()
    device = device_facts(cell["chips"], require_tpu)
    t_setup_start = time.time()
    log(f"cellbench: before set-up, not in setup_s: importing jax "
        f"{t_imported - t_called:.2f} s, jax.devices() "
        f"{t_setup_start - t_imported:.2f} s")

    from apex_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program of a run goes to the cache, however quick to compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    on_chip = device["platform"] == "tpu"
    log(f"cellbench: {workload} seed {seed} seconds {seconds} trace "
        f"{int(trace)} device {json.dumps(device)} cache {cache_dir}")

    adapter_name = cell["config_file"]["cellbench"]["adapter"]
    try:        # cellbench/adapters/<name>.py, found by the config's name
        adapter = importlib.import_module(f"cellbench.adapters.{adapter_name}")
    except ModuleNotFoundError as e:
        if e.name != f"cellbench.adapters.{adapter_name}":
            raise
        raise SystemExit(f"cellbench: no cellbench/adapters/"
                         f"{adapter_name}.py") from None

    start_at = 0.3 * seconds
    window_trace = WindowTrace(
        trace, Path(root) / ".cellbench_trace", start_at,
        min(4.0, 0.3 * seconds))
    res = adapter.run({
        "cell": cell, "seed": seed, "seconds": seconds, "trace": trace,
        "log": log, "window_trace": window_trace, "control": control,
        "t_setup_start": t_setup_start,
    })

    e2e_values = dict(res["e2e"], setup_s=res["setup_s"])
    dev_out = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    out = {"correct": bool(res["correct"]), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": {}, "device": dev_out}
    if return_checks:       # cellbench.control reads the numbers compared
        out["checks"] = res["checks"]
    if not trace:
        for m in bench.end_to_end(workload):
            if m["name"] not in e2e_values:
                raise SystemExit(f"cellbench: the {adapter_name} adapter "
                                 f"reports no {m['name']}")
            # a time or a rate read on the CPU is not a device number
            if on_chip or m["name"] == "setup_s":
                out["metrics"][m["name"]] = {
                    "value": e2e_values[m["name"]], "unit": m["unit"]}
        return out

    reduced = window_trace.reduced(res["host_spans"]) if on_chip else None
    notes = []
    ctx = {
        "reduced": reduced,
        "spans": window_trace.spans_inside(res["host_spans"]),
        "counters": res["counters"], "e2e": e2e_values,
        "model": cell["config_file"],
        "args": cell["config_file"]["cellbench"]["args"],
        "traffic": cell["traffic_file"], "chips": cell["chips"],
        "peaks": bench.peaks(device["kind"]) if on_chip else None,
        "counts": bench.counts, "notes": notes,
    }
    for m in bench.per_layer(workload):
        if not on_chip and m["source"] != "program_counter":
            continue
        value = readers.read(m, ctx, bench.custom_reader(m["name"]))
        if value is not None:
            out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    for note in notes:
        log(f"cellbench: {note}")
    if reduced is not None:
        out["device"]["busy_s"] = reduced.busy_s
        out["device"]["window_s"] = reduced.window_s
        out["breakdown"] = reduced.breakdown()
    return out


def main(argv=None) -> int:
    args = parse(argv)
    if not (CHECKOUT / "apex_tpu").is_dir():
        sys.exit("cellbench: no apex_tpu/ beside cellbench/ - this is not "
                 "a checkout of the system under test")
    out = run_cell(CHECKOUT, args.workload, args.seed, args.seconds,
                   bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
