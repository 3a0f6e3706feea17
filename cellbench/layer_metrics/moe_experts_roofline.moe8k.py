"""The held experts' grouped matmuls of a training step (forward,
recompute, and both backward products) against their roofline: the
least time for the calls the trace holds, each over the assignments
held in a layer (``counts/moe_experts_train.py``, from the program's
device-side counters), over their device time."""

from cellbench import decode_ops

GROUPED_MATMUL = r"^%\w*gmm"


def read(ctx):
    red = ctx["reduced"]
    if red is None or "layer_types" not in ctx["model"]:
        return None
    work = ctx["counts"]("moe_experts_train").per_call(ctx)
    calls = red.count(GROUPED_MATMUL)
    if work is None or not calls:
        return None
    return decode_ops.roofline_percent(
        ctx, "moe_experts_train",
        {k: v * calls for k, v in work.items()},
        red.seconds(GROUPED_MATMUL))
