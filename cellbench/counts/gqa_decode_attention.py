"""Operations and bytes the paged decode attention needs for the decode
tokens of the traced stretch under GROUPED queries: each token, in each
layer, reads its context's keys and values once a KEY/VALUE head (``2 *
num_key_value_heads * head_dim`` values a position, in the cache's
dtype), whatever the number of query heads that share them, and spends
``4 * num_attention_heads * head_dim`` operations a position (q.k and
p.v, a query head)."""


def total(ctx):
    model, args = ctx["model"], ctx["args"]
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "num_hidden_layers")
    positions = ctx["counters"].get("traced_kv_positions")
    if not positions or any(not model.get(k) for k in keys):
        return None
    d, L = model["head_dim"], model["num_hidden_layers"]
    item = 2 if args["kv_dtype"] == "bfloat16" else 4
    return {"flops": 4.0 * model["num_attention_heads"] * d * L * positions,
            "bytes": 2.0 * model["num_key_value_heads"] * d * item * L
            * positions}
