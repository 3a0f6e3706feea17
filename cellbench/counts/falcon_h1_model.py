"""Model operations of a ``falcon_h1`` stage, a token: what ``mfu.h1chat``
multiplies the served tokens by.

A token through a layer spends 2 operations a weight of the layer's
matrices (q, k, v and o; ``in_proj`` and ``out_proj``; gate, up and
down), ``4 * num_attention_heads * head_dim`` a position of its context
in the attention, and about 6 an element of the recurrent state (the
decay, the rank-one update, the product with ``C``).  The head's matrix
is spent on a SAMPLED token only: every decode token, one a prompt.  The
convolution, the norms and the multipliers are not counted."""


def layer_matrix_weights(model) -> int:
    H, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    d_ssm = model["mamba_d_ssm"]
    gn = model["mamba_n_groups"] * model["mamba_d_state"]
    proj_in = 2 * d_ssm + 2 * gn + model["mamba_n_heads"]
    return (H * (heads + 2 * kv) * d + heads * d * H       # attention
            + H * proj_in + d_ssm * H                       # state space
            + 3 * H * model["intermediate_size"])           # gated MLP


def flops(model, tokens: float, sampled: float, kv_positions: float):
    """``tokens`` through the layers (prompt and decode alike),
    ``sampled`` of them through the head, attending over
    ``kv_positions`` cached positions in all (a layer)."""
    L = model["num_hidden_layers"]
    state = model["mamba_n_heads"] * model["mamba_d_head"] \
        * model["mamba_d_state"]
    per_token = L * (2.0 * layer_matrix_weights(model) + 6.0 * state)
    attention = 4.0 * model["num_attention_heads"] * model["head_dim"] * L
    return (tokens * per_token + attention * kv_positions
            + sampled * 2.0 * model["vocab_size"] * model["hidden_size"])
