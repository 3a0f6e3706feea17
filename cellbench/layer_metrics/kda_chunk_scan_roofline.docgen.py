"""apex_kda_chunk_scan against its roofline: a chunk's operands read
and its outputs written once, its four products, for the padded tokens
of the traced prefills (``counts/kda_prefill.py``)."""

from cellbench import decode_ops


def read(ctx):
    red = ctx["reduced"]
    counts = ctx["counts"]("kda_prefill")
    work = counts.total(ctx)
    if red is None or work is None:
        return None
    return decode_ops.roofline_percent(ctx, "kda_chunk_scan", work,
                                       red.seconds(counts.KERNEL))
