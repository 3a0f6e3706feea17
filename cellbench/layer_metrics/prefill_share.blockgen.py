"""Share of the device's busy time that lies inside prefill programs
(``cellbench/decode_ops.py``)."""

from cellbench import decode_ops


def read(ctx):
    share = decode_ops.program_share_of_busy(ctx["reduced"],
                                             decode_ops.PREFILL_PROGRAM)
    return None if share is None else 100.0 * share
