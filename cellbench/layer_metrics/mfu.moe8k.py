"""Model FLOP/s utilisation of an ``afmoe`` training run: tokens per
second times THIS chip's model operations a token
(``counts/afmoe_train.py``: matrices held x 6, held assignments only,
the band's and the triangle's attention, recompute not counted) over
chips times the published bf16 peak."""


def read(ctx):
    rate = ctx["e2e"].get("train_tokens_per_s")
    if rate is None or ctx["peaks"] is None \
            or "layer_types" not in ctx["model"]:
        return None
    per_token = ctx["counts"]("afmoe_train").flops_per_token(ctx)
    if per_token is None:
        return None
    return 100.0 * rate * per_token / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
