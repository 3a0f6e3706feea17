"""Operations and bytes the paged attention of a BLOCK step needs for
the traced stretch.  A live slot's block of ``W`` rows sees ``position +
W`` columns, the same for every row, so a step has to read each of them
ONCE a slot a layer, whatever the kernel walks: ``2 *
num_key_value_heads * head_dim`` values a column in the cache's dtype
(2,048 B at 4 heads of 128 in bfloat16), and spends ``4 *
num_attention_heads * head_dim`` operations a column a ROW (q.k and p.v,
a query head).  The program counts the columns on the device over the
whole window (``blk_kv_cols``: summed over live slots, every layer reads
as many); the traced stretch's share is its share of the window's block
steps."""


def total(ctx):
    model, args, c = ctx["model"], ctx["args"], ctx["counters"]
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "num_hidden_layers")
    if not c.get("decode_steps") or not c.get("traced_steps") \
            or c.get("blk_kv_cols") is None \
            or any(not model.get(k) for k in keys) \
            or not args.get("block_length"):
        return None
    cols = c["blk_kv_cols"] * c["traced_steps"] / c["decode_steps"]
    d, L = model["head_dim"], model["num_hidden_layers"]
    item = 2 if args["kv_dtype"] == "bfloat16" else 4
    return {"flops": 4.0 * model["num_attention_heads"] * d * L
            * args["block_length"] * cols,
            "bytes": 2.0 * model["num_key_value_heads"] * d * item * L
            * cols}
