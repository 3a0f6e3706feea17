"""apex_mla_decode_attention against its roofline: the work is what the
decode tokens inside the traced stretch needed, each reading its own
context's latent columns once (``counts/mla_decode_attention.py``)."""

from cellbench import decode_ops


def read(ctx):
    red = ctx["reduced"]
    if red is None or not ctx["counters"].get("traced_kv_positions"):
        return None
    return decode_ops.roofline_percent(
        ctx, "mla_decode_attention",
        ctx["counts"]("mla_decode_attention").total(ctx),
        red.seconds("apex_mla_decode_attention"))
