"""apex_decode_attention against its roofline.  The work is what the
decode tokens inside the traced stretch needed, not what the kernel's
grid walks: each token reads the keys and values of its own context
once (``counts/decode_attention.py``)."""


def read(ctx):
    red = ctx["reduced"]
    if red is None or not ctx["counters"].get("traced_kv_positions"):
        return None
    took = red.seconds("apex_decode_attention")
    if took <= 0:
        return None
    work = ctx["counts"]("decode_attention").total(ctx)
    peaks = ctx["peaks"]
    t_ops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    ctx["notes"].append(
        "roofline decode_attention: bound by "
        + ("compute" if t_ops >= t_bytes else "memory"))
    return 100.0 * max(t_ops, t_bytes) / took
