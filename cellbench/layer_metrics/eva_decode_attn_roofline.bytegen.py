"""apex_decode_attention against its roofline in a windowed cache: the
work is the columns the program counted on the device (the live columns
of the slots' window buffers and the pooled columns of their closed
windows, ``counts/eva_decode_attention.py``)."""

from cellbench import decode_ops


def read(ctx):
    red = ctx["reduced"]
    work = ctx["counts"]("eva_decode_attention").total(ctx)
    if red is None or work is None:
        return None
    return decode_ops.roofline_percent(
        ctx, "eva_decode_attention", work,
        red.seconds("apex_decode_attention"))
