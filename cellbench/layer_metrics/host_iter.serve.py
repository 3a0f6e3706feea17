"""What the host does in a decode period (``loop_readers.host_iter_ms``),
over the whole window."""

from cellbench import loop_readers, span_readers


def read(ctx):
    return loop_readers.host_iter_ms(span_readers.program_spans(),
                                     ctx["notes"])
