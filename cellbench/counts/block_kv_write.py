"""Bytes the K/V write of a BLOCK step needs for the traced stretch.  A
live slot's pass stores its block's ``W`` columns, keys and values, in
every layer: ``2 * num_key_value_heads * head_dim`` values a column in
the cache's dtype (2,048 B at 4 heads of 128 in bfloat16), written ONCE
a pass whatever tile the kernel reads and writes back for them.  No
operations.  The program counts the slot-passes on the device over the
whole window (``blk_denoise_passes + blk_commit_passes``); the traced
stretch's share is its share of the window's block steps."""


def total(ctx):
    model, args, c = ctx["model"], ctx["args"], ctx["counters"]
    keys = ("num_key_value_heads", "head_dim", "num_hidden_layers")
    if not c.get("decode_steps") or not c.get("traced_steps") \
            or c.get("blk_denoise_passes") is None \
            or c.get("blk_commit_passes") is None \
            or any(not model.get(k) for k in keys) \
            or not args.get("block_length"):
        return None
    passes = (c["blk_denoise_passes"] + c["blk_commit_passes"]) \
        * c["traced_steps"] / c["decode_steps"]
    item = 2 if args["kv_dtype"] == "bfloat16" else 4
    return {"flops": 0.0,
            "bytes": 2.0 * model["num_key_value_heads"] * model["head_dim"]
            * item * model["num_hidden_layers"] * args["block_length"]
            * passes}
