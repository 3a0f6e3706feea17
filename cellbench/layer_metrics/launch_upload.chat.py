"""The upload's part of a launch (``loop_readers.launch_upload_ms``), over
the whole window."""

from cellbench import loop_readers, span_readers


def read(ctx):
    return loop_readers.launch_upload_ms(span_readers.program_spans(),
                                         ctx["notes"])
