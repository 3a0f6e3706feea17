"""Adapters: how the benchmark drives a program's normal path.  A
configuration names its adapter (``cellbench.adapter`` in its file) and
``cellbench/run.py`` imports ``cellbench/adapters/<name>.py``; a model
that the two GPT-2 adapters here cannot build brings a file of its own.

An adapter is ``run(env) -> dict``.  ``env`` holds ``cell`` (the
workload's entry with ``config_file`` and ``traffic_file`` loaded),
``seed``, ``seconds``, ``trace``, ``log``, ``window_trace``
(:class:`cellbench.profiling.WindowTrace`), ``control`` and
``t_setup_start`` (when ``jax.devices()`` returned: ``setup_s`` is the
time from there to the opening of the window).  It returns ``correct``, ``attempted``, ``failed``,
``setup_s``, ``e2e`` (every end-to-end value the cell reports, by
name), ``memory_peak_bytes``, ``host_spans``, ``counters`` and
``checks`` (each number compared, with its limit).
"""
