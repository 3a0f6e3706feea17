"""Operations and bytes the decode attention of a windowed cache needs
for the decode steps of the traced stretch.  The program counts, on the
device and over the whole window, the columns a layer's attention had to
read for its active slots: the live columns of their window buffers
(``eva_window_cols``) and the pooled columns of their closed windows
(``eva_summary_cols``); every layer reads as many.  A column is one
position's, or one chunk's, keys and values of all heads in the cache's
dtype (32 heads of 128, k and v, bfloat16: 16,384 B) and costs 4
operations a value pair (q.k and p.v).  The traced stretch's share of
the window's columns is reckoned from the requests' lengths (the
adapter's ``traced_*_cols`` over its ``expected_*_cols``: the positions
decoded inside the stretch against those decoded inside the window),
not from its share of the steps: a stretch with a slot free, or with
shorter contexts than the window's average, reads fewer columns a step
(scaled by steps, a stretch that followed the profiler's stall read
121% of its roofline)."""


def total(ctx):
    model, args, c = ctx["model"], ctx["args"], ctx["counters"]
    window = (c.get("expected_window_cols") or 0) \
        + (c.get("expected_summary_cols") or 0)
    if not window or c.get("traced_window_cols") is None \
            or c.get("eva_window_cols") is None:
        return None
    share = (c["traced_window_cols"] + c["traced_summary_cols"]) / window
    cols = (c["eva_window_cols"] + c["eva_summary_cols"]) * c["layers"] \
        * share
    H = model["hidden_size"]
    item = 2 if args["kv_dtype"] == "bfloat16" else 4
    return {"flops": 4.0 * H * cols, "bytes": 2.0 * H * item * cols}
