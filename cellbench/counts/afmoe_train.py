"""Model operations a token of a training step needs on THIS chip, for
the ``afmoe`` cells' ``mfu``: the usual convention (6 a parameter of
every matrix a token passes through: forward 2, backward 4; recomputed
operations not counted), with what the convention's ``6N + 12LSH`` gets
wrong here taken apart:

- matrices held here and passed by every token: attention's five
  projections, the dense FFN, the router, the shared expert, the head
  over the held slice of the vocabulary (the embedding is a lookup);
- routed experts: a token passes through ``num_experts_per_tok``
  experts of which this chip holds its share, so only the HELD
  assignments count, from the program's device-side counter
  (``moe_assignments_held`` over the window's steps and tokens);
- attention: the scores and the weighted sum, 4 * head_dim operations
  a visible (query, key) pair a head forward, 8 backward: the causal
  triangle of a ``full_attention`` layer, the band of a
  ``sliding_attention`` layer (nothing masked or skipped counted).
"""


def flops_per_token(ctx):
    model, c = ctx["model"], ctx["counters"]
    steps, per_step = c.get("steps"), c.get("tokens_per_step")
    if not steps or not per_step or c.get("moe_assignments_held") is None:
        return None
    H, d = model["hidden_size"], model["head_dim"]
    n, kv = model["num_attention_heads"], model["num_key_value_heads"]
    seq = int(ctx["args"]["seq"])
    attn = H * d * (3 * n + 2 * kv)                 # q, gate, o; k, v
    dense = 3 * H * model["intermediate_size"]
    n_dense = model["num_dense_layers"]
    n_moe = model["num_hidden_layers"] - n_dense
    router = H * int(model.get("published", {}).get("num_experts",
                                                    model["num_experts"]))
    expert = 3 * H * model["moe_intermediate_size"]
    shared = expert * model["num_shared_experts"]
    matrices = (model["num_hidden_layers"] * attn + n_dense * dense
                + n_moe * (router + shared) + model["vocab_size"] * H)
    held_per_token = c["moe_assignments_held"] / (steps * per_step)
    visible_pairs = ctx["counts"]("flash_window_attention").visible_pairs
    pairs = sum(visible_pairs(seq, model["sliding_window"]
                              if kind == "sliding_attention" else None)
                for kind in model["layer_types"]) / seq
    return (6.0 * (matrices + held_per_token * expert)
            + 12.0 * d * n * pairs)

