"""Device time of the held experts' grouped matmuls inside decode-step
programs, per traced decode step (the prefill runs them too, and is
left out: ``cellbench/decode_ops.py``)."""

from cellbench import decode_ops


def read(ctx):
    n = ctx["counters"].get("traced_steps")
    found = decode_ops.seconds_in_programs(
        ctx["reduced"], decode_ops.GROUPED_MATMUL,
        decode_ops.DECODE_PROGRAM)
    if not n or found is None or found[0] <= 0:
        return None
    return found[0] * 1e3 / n
