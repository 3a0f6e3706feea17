"""Operations and bytes one call of each flash-attention kernel needs,
from the cell's shapes.  Causal: half of the S x S square.  A call
handles one layer's whole local batch.

forward: QK^T and PV, 2 matmuls of 2*S*S*hd -> 4*S^2*hd a head, halved.
dq: recompute S, dP = dO V^T, dQ = dS K -> 6*S^2*hd, halved.
dkv: recompute S, dV = P^T dO, dP, dK = dS^T Q -> 8*S^2*hd, halved.
Bytes: each (B, heads, S, hd) tensor read or written once in the
compute dtype; the per-row statistics are small beside them.
"""


def per_call(ctx):
    model, args, mix = ctx["model"], ctx["args"], ctx["traffic"]
    heads, hd = model["n_head"], model["n_embd"] // model["n_head"]
    S = int(args["seq"])
    B = int(mix["global_batch"]) // ctx["chips"]
    item = 2 if args["compute_dtype"] == "bfloat16" else 4
    tensor = B * heads * S * hd * item
    square = B * heads * S * S * hd / 2.0
    return {
        "apex_flash_fwd": {"flops": 4 * square, "bytes": 4 * tensor},
        "apex_flash_dq": {"flops": 6 * square, "bytes": 5 * tensor},
        "apex_flash_dkv": {"flops": 8 * square, "bytes": 6 * tensor},
    }
