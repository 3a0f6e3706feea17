"""apex_decode_attention against its roofline where only SOME layers
attend: each traced decode token reads its context's keys and values
once a key/value head in each ATTENTION layer
(``counts/hybrid_decode_attention.py``)."""

from cellbench import decode_ops


def read(ctx):
    red = ctx["reduced"]
    work = ctx["counts"]("hybrid_decode_attention").total(ctx)
    if red is None or work is None:
        return None
    return decode_ops.roofline_percent(
        ctx, "decode_attention", work, red.seconds("apex_decode_attention"))
