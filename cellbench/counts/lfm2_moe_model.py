"""Model operations of an ``lfm2_moe`` stage, a token: what
``mfu.agentgen`` multiplies the served tokens by.

A token through a layer spends 2 operations a weight of the layer's
matrices: a convolution mixer's ``in_proj`` and ``out_proj`` or an
attention mixer's q, k, v and o; a dense layer's three feed-forward
matrices, or an expert layer's router and the three matrices of each of
its ``num_experts_per_tok`` chosen experts (every expert is held here,
so every assignment is this chip's).  A convolution spends 2 a tap and
channel, an attention layer ``4 * num_attention_heads * head size`` a
position of the token's context.  The tied head's matrix is spent on a
SAMPLED token only: every decode token, one a prompt.  Norms, gates and
the rotation are not counted."""


def layer_kinds(model):
    """``(convolution layers, attention layers, dense layers, expert
    layers)`` of the file."""
    kinds = model["layer_types"]
    conv = sum(1 for k in kinds if k == "conv")
    dense = model["num_dense_layers"]
    return conv, len(kinds) - conv, dense, len(kinds) - dense


def token_matrix_weights(model) -> int:
    """Weights a token multiplies on its way through the stage, the head
    apart."""
    H = model["hidden_size"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    d = H // heads
    conv, attn, dense, moe = layer_kinds(model)
    return (conv * (3 * H * H + H * H)
            + attn * (H * (heads + 2 * kv) * d + heads * d * H)
            + dense * 3 * H * model["intermediate_size"]
            + moe * (H * model["num_experts"]
                     + model["num_experts_per_tok"] * 3 * H
                     * model["moe_intermediate_size"]))


def flops(model, tokens: float, sampled: float, kv_positions: float):
    """``tokens`` through the layers (prompt and decode alike),
    ``sampled`` of them through the head, attending over
    ``kv_positions`` cached positions in all (an attention layer)."""
    H = model["hidden_size"]
    conv, attn, _, _ = layer_kinds(model)
    per_token = 2.0 * token_matrix_weights(model) \
        + conv * 2.0 * model["conv_L_cache"] * H
    attention = 4.0 * H * attn      # heads x head size = hidden
    return (tokens * per_token + attention * kv_positions
            + sampled * 2.0 * model["vocab_size"] * H)
