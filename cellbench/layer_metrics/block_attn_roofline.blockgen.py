"""apex_decode_attention against its roofline in a block step: a live
slot's columns are read once a step a layer for all of its block's rows
(``counts/block_decode_attention.py``, from the program's device-side
counter ``blk_kv_cols``)."""

from cellbench import decode_ops


def read(ctx):
    red = ctx["reduced"]
    work = ctx["counts"]("block_decode_attention").total(ctx)
    if red is None or work is None:
        return None
    return decode_ops.roofline_percent(
        ctx, "block_decode_attention", work,
        red.seconds("apex_decode_attention"))
