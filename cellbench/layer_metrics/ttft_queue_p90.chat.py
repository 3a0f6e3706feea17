"""The queue's part of the first token: p90 of ``queue_s`` on the
``serve.request`` spans of the whole window (the program's buffer, not
the traced stretch: a request outlives it)."""

from cellbench import span_readers


def read(ctx):
    return span_readers.first_token_part_p90_ms(
        span_readers.program_spans(), "queue_s")
