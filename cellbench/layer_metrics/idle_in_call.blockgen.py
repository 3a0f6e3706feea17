"""Device idle time per traced step inside a device call's span
(``span_readers.idle_split_ms_per_step``)."""

from cellbench import span_readers


def read(ctx):
    split = span_readers.idle_split_ms_per_step(ctx)
    return None if split is None else split["in_call"]
