"""Operations and bytes the absorbed latent decode attention needs for
the decode tokens of the traced stretch.  A token, in each layer, reads
its context's cached columns ONCE, not once a head: ``kv_lora_rank +
qk_rope_head_dim`` values a position in the cache's dtype; per head it
spends 2 operations a value on the scores (the whole column) and 2 a
value on the weighted sum (the latent part)."""


def total(ctx):
    model, args = ctx["model"], ctx["args"]
    latent, rope = model["kv_lora_rank"], model["qk_rope_head_dim"]
    heads, L = model["num_attention_heads"], model["num_hidden_layers"]
    item = 2 if args["kv_dtype"] == "bfloat16" else 4
    positions = ctx["counters"]["traced_kv_positions"]
    return {"flops": 2.0 * heads * ((latent + rope) + latent) * L
            * positions,
            "bytes": float(latent + rope) * item * L * positions}
