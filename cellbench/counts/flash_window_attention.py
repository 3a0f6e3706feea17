"""Operations and bytes one call of each flash-attention kernel needs in
a model whose layers are ``sliding_attention`` (a band of
``sliding_window`` keys) or ``full_attention`` (the causal triangle),
under grouped-query attention.  A call handles one layer's whole local
batch.  The BAND of a window call and the TRIANGLE of a full call are
the work: nothing masked or skipped is counted.

The two kinds of call run the same kernels under the same names, so the
trace cannot tell them apart; they are told apart by the LAYER PATTERN'S
RATIO: every step calls each kernel once a layer, so of the calls in a
traced stretch the share ``window layers / layers`` are window calls
(``per_call`` gives both kinds and the share).

forward: QK^T and PV, 4 * head_dim operations a visible pair a head;
dq: recompute S, dP = dO V^T, dQ = dS K: 6; dkv: recompute S, dV = P^T
dO, dP, dK = dS^T Q: 8.  Bytes: each tensor read or written once in the
compute dtype, queries' at ``num_attention_heads``, keys' and values'
at ``num_key_value_heads``; the per-row statistics are small beside
them.
"""


def visible_pairs(seq: int, window=None) -> float:
    """(query, key) pairs a causal call sees in one sequence: the
    triangle, or under a window the band."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq - window) * float(window)


def per_call(ctx):
    """``{"window_share": s, "window": {kernel: work}, "full": {kernel:
    work}}`` with ``work`` = ``{"flops", "bytes"}`` of ONE call."""
    model, args, mix = ctx["model"], ctx["args"], ctx["traffic"]
    n, kv, d = (model["num_attention_heads"], model["num_key_value_heads"],
                model["head_dim"])
    S = int(args["seq"])
    B = int(mix["global_batch"]) // ctx["chips"]
    item = 2 if args["compute_dtype"] == "bfloat16" else 4
    q_tensor = B * n * S * d * item
    kv_tensor = B * kv * S * d * item
    kinds = model["layer_types"]

    def work(window):
        pairs = B * n * d * visible_pairs(S, window)
        return {
            # q, o; k, v
            "apex_flash_fwd": {"flops": 4 * pairs,
                               "bytes": 2 * q_tensor + 2 * kv_tensor},
            # q, do, dq; k, v
            "apex_flash_dq": {"flops": 6 * pairs,
                              "bytes": 3 * q_tensor + 2 * kv_tensor},
            # q, do; k, v, dk, dv
            "apex_flash_dkv": {"flops": 8 * pairs,
                               "bytes": 2 * q_tensor + 4 * kv_tensor},
        }

    return {"window_share": sum(k == "sliding_attention" for k in kinds)
            / len(kinds),
            "window": work(model["sliding_window"]), "full": work(None)}
