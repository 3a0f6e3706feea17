"""Operations and bytes ONE grouped matmul of a training step's expert
layer needs, from the program's device-side counters.  A step runs
fifteen a layer (the forward's three projections, the same three again
in the layer's recompute and in the chunk walk's own backward, the
three products into the rows' cotangent and the three into the
weights'), every one over the assignments HELD in that layer: 2
operations a weight of one expert's ``hidden_size x
moe_intermediate_size`` matrix an assignment.  Bytes: the matrices of
the experts hit read (or, for a weight gradient, written) once, and an
assignment's row of ``hidden_size`` and of ``moe_intermediate_size``
read or written once, all in the compute dtype."""


def per_call(ctx):
    model, args, c = ctx["model"], ctx["args"], ctx["counters"]
    layer_steps = (c.get("steps") or 0) * (c.get("moe_layers") or 0)
    if not layer_steps or c.get("moe_assignments_held") is None:
        return None
    H, F = model["hidden_size"], model["moe_intermediate_size"]
    item = 2 if args["compute_dtype"] == "bfloat16" else 4
    rows = c["moe_assignments_held"] / layer_steps
    hit = c["moe_experts_hit"] / layer_steps
    return {"flops": 2.0 * H * F * rows,
            "bytes": item * (hit * H * F + rows * (H + F))}
