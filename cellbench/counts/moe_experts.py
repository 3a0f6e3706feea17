"""Operations and bytes the held experts' grouped matmuls need for the
decode steps of the traced stretch.  An expert that got at least one
token in a layer of a step has its three matrices (gate, up, down:
``hidden_size x moe_intermediate_size`` each) read once; an assignment
spends 2 operations a weight in each of the three.  The program counts
both on the device over the whole window (``moe_experts_hit``,
``moe_assignments_held``); the traced stretch's share is its share of
the window's decode steps."""


def total(ctx):
    model, args, c = ctx["model"], ctx["args"], ctx["counters"]
    if not c.get("decode_steps") or not c.get("traced_steps") \
            or c.get("moe_experts_hit") is None:
        return None
    share = c["traced_steps"] / c["decode_steps"]
    weights = 3.0 * model["hidden_size"] * model["moe_intermediate_size"]
    item = 2 if args["param_dtype"] == "bfloat16" else 4
    return {"flops": 2.0 * weights * c["moe_assignments_held"] * share,
            "bytes": weights * item * c["moe_experts_hit"] * share}
