"""Operations and bytes the paged decode attention needs for the decode
tokens of the traced stretch in a model where only SOME layers attend
(``layer_types``: the ``full_attention`` ones) under GROUPED queries:
each token, in each attention layer, reads its context's keys and
values once a KEY/VALUE head (``2 * num_key_value_heads * head size``
values a position, in the cache's dtype), whatever the number of query
heads that share them, and spends ``4 * num_attention_heads * head
size`` operations a position (q.k and p.v, a query head).  The head's
size is ``head_dim`` or, where the config has none, ``hidden_size /
num_attention_heads``."""


def attention_layers(model):
    """Layers that attend; None for a model without ``layer_types``."""
    kinds = model.get("layer_types")
    if not kinds:
        return None
    return sum(1 for k in kinds if k == "full_attention")


def total(ctx):
    model, args = ctx["model"], ctx["args"]
    keys = ("num_attention_heads", "num_key_value_heads", "hidden_size")
    positions = ctx["counters"].get("traced_kv_positions")
    L = attention_layers(model)
    if not positions or not L or any(not model.get(k) for k in keys):
        return None
    heads = model["num_attention_heads"]
    d = model.get("head_dim") or model["hidden_size"] // heads
    item = 2 if args["kv_dtype"] == "bfloat16" else 4
    return {"flops": 4.0 * heads * d * L * positions,
            "bytes": 2.0 * model["num_key_value_heads"] * d * item * L
            * positions}
