"""The share of prefilled positions that are padding
(``loop_readers.prefill_padding_percent``), over the whole window."""

from cellbench import loop_readers, span_readers


def read(ctx):
    return loop_readers.prefill_padding_percent(span_readers.program_spans())
