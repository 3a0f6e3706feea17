"""The held experts' grouped matmuls inside decode steps against their
roofline (``counts/moe_experts.py``: weights of the experts hit read
once, 6 operations a weight-column an assignment, from the program's
device-side counters)."""

from cellbench import decode_ops


def read(ctx):
    found = decode_ops.seconds_in_programs(
        ctx["reduced"], decode_ops.GROUPED_MATMUL,
        decode_ops.DECODE_PROGRAM)
    if found is None:
        return None
    return decode_ops.roofline_percent(
        ctx, "moe_experts", ctx["counts"]("moe_experts").total(ctx),
        found[0])
