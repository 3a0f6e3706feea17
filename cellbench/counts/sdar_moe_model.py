"""Model operations of the block steps of an ``sdar_moe`` chip's share:
what ``mfu.blockgen`` divides by the window's seconds and the peak.

A forwarded row spends, in each layer, 2 operations a weight of the
attention matrices (q, k, v and o) and of the router; a HELD assignment
2 a weight of its expert's three matrices (the program counts the
assignments computed here, ``moe_assignments_held``, over all layers:
what the absent chips' experts would spend is not this chip's); a row
``4 * num_attention_heads * head_dim`` a column it attends over
(``blk_kv_cols`` counts a live slot's columns once a step, each of the
block's ``W`` rows sees them all); and a denoising pass's rows the head's
matrix over the slice held (a commit pass's rows need no logits).
Norms, rotations and the softmaxes are not counted, nor the prefills."""


def layer_row_weights(model) -> int:
    """Weights a forwarded row multiplies in a layer outside the
    experts: q, k, v, o and the router (its published width)."""
    H, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    return H * (heads + 2 * kv) * d + heads * d * H \
        + H * model["published"]["num_experts"]


def flops(model, c):
    """Operations of the block steps the counters ``c`` cover; None
    where the program kept no such counters (a family that does not
    generate by blocks)."""
    need = ("blk_rows_forwarded", "blk_denoise_passes", "blk_kv_cols",
            "moe_assignments_held")
    if any(c.get(k) is None for k in need) \
            or "moe_intermediate_size" not in model \
            or "num_experts" not in model.get("published", {}):
        return None
    L, H = model["num_hidden_layers"], model["hidden_size"]
    rows = c["blk_rows_forwarded"]
    W = model["cellbench"]["args"]["block_length"]
    return (rows * L * 2.0 * layer_row_weights(model)
            + c["moe_assignments_held"] * 2.0 * 3 * H
            * model["moe_intermediate_size"]
            + 4.0 * model["num_attention_heads"] * model["head_dim"] * L
            * W * c["blk_kv_cols"]
            + c["blk_denoise_passes"] * W * 2.0 * model["vocab_size"] * H)
