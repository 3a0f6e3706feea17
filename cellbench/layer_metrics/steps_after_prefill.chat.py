"""On what share of decode steps a prefill ran first
(``prefills_before`` on ``serve.decode_step``), over the whole window."""

from cellbench import span_readers


def read(ctx):
    return span_readers.steps_after_prefill_percent(
        span_readers.program_spans())
