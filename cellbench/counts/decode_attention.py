"""Operations and bytes the paged decode attention needs for the decode
tokens of the traced stretch: each token, in each layer, reads its
context's keys and values once (2 * H values a position, in the cache's
dtype) and spends 4 * H operations a position (q.k and p.v)."""


def total(ctx):
    model, args = ctx["model"], ctx["args"]
    H, L = model["n_embd"], model["n_layer"]
    item = 2 if args["kv_dtype"] == "bfloat16" else 4
    positions = ctx["counters"]["traced_kv_positions"]
    return {"flops": 4.0 * H * L * positions,
            "bytes": 2.0 * H * L * item * positions}
