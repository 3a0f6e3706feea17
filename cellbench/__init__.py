"""cellbench: the benchmark of apex_tpu.

One command runs one cell (a model configuration under a traffic mix or
training job) once, in a new process, on the TPU it is started on::

    python -m cellbench --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one mix, one per-layer
metric or one kernel's operation count is a file of its own under this
directory, found by the name in ``BENCHMARK.json``; the harness never
names a cell.  See ``PERF.md`` for what is measured and why.
"""
