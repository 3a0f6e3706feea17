"""Model FLOP/s utilisation of the served window: the prompt tokens
prefilled and the tokens decoded inside it, times the operations a token
needs of this stage (``counts/lfm2_moe_model.py``), over the window's
seconds, the chips and the published bf16 peak.  The context a decode
token attends over is taken as the traced stretch's mean; a prompt
token's as half its prompt."""


def read(ctx):
    c, model, peaks = ctx["counters"], ctx["model"], ctx.get("peaks")
    need = ("window_s", "window_tokens", "window_prompt_tokens")
    if peaks is None or any(not c.get(k) for k in need) \
            or "conv_L_cache" not in model:
        return None
    decoded = c["window_tokens"]
    prompts = c["window_prompt_tokens"]
    context = (c["traced_kv_positions"] / c["traced_decode_tokens"]
               if c.get("traced_decode_tokens") else 0.0)
    positions = decoded * context + prompts * ctx["traffic"]["lengths"][
        "prompt"]["median"] / 2.0
    work = ctx["counts"]("lfm2_moe_model").flops(
        model, decoded + prompts, decoded, positions)
    return 100.0 * work / c["window_s"] \
        / (ctx["chips"] * peaks["bf16_flops_per_s"])
