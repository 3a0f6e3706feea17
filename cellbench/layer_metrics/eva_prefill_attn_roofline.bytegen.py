"""apex_flash_fwd against its roofline in the windowed prefill: the
products of the causal windows and the visible pooled pairs of the
traced prefills (``counts/eva_prefill_attention.py``)."""

from cellbench import decode_ops


def read(ctx):
    red = ctx["reduced"]
    counts = ctx["counts"]("eva_prefill_attention")
    work = counts.total(ctx)
    if red is None or work is None:
        return None
    return decode_ops.roofline_percent(ctx, "eva_prefill_attention", work,
                                       red.seconds(counts.KERNEL))
