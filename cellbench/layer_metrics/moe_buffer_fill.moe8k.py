"""How full the expert layer's static chunks are: live rows over the
rows of the chunks walked, whole window (device-side counters
``moe_assignments_held`` and ``moe_buffer_rows``)."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("moe_buffer_rows") or c.get("moe_assignments_held") is None:
        return None
    return 100.0 * c["moe_assignments_held"] / c["moe_buffer_rows"]
