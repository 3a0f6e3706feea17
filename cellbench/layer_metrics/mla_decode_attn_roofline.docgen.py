"""apex_mla_decode_attention against its roofline, as
``mla_decode_attn_roofline.longgen`` reads it, over the layers that
attend: ``counts/mla_decode_attention.py`` multiplies by the
configuration's ``num_hidden_layers``, and here only the MLA layers
(the adapter's ``mla_layers``) read a latent cache."""

from cellbench import decode_ops


def read(ctx):
    red, c = ctx["reduced"], ctx["counters"]
    if red is None or not c.get("traced_kv_positions") \
            or not c.get("mla_layers"):
        return None
    attending = dict(ctx, model=dict(ctx["model"],
                                     num_hidden_layers=c["mla_layers"]))
    return decode_ops.roofline_percent(
        ctx, "mla_decode_attention",
        ctx["counts"]("mla_decode_attention").total(attending),
        red.seconds("apex_mla_decode_attention"))
