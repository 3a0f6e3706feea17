"""Operations and bytes the KDA decode kernel needs for the decode
steps of the traced stretch.  One state update (one active slot in one
KDA layer) reads the slot's ``heads x d x d`` float32 state and writes
it back, and spends about 8 operations an element of it (the decay, the
``S^T k`` product, the rank-one update, the ``S^T q`` product).  The
program counts the updates on the device over the whole window
(``kda_state_updates``); the traced stretch's share is its share of the
window's decode steps."""


def total(ctx):
    lin, c = ctx["model"]["linear_attn_config"], ctx["counters"]
    if not c.get("decode_steps") or not c.get("traced_steps") \
            or c.get("kda_state_updates") is None:
        return None
    updates = c["kda_state_updates"] * c["traced_steps"] / c["decode_steps"]
    state = lin["num_heads"] * lin["head_dim"] * lin["head_dim"]
    return {"flops": 8.0 * state * updates,
            "bytes": 2.0 * 4 * state * updates}
