"""Model FLOP/s utilisation of the run: tokens per second times the
operations a token needs (6N + 12*L*S*H, recompute not counted) over
chips times the published bf16 peak."""

from cellbench import arith


def read(ctx):
    rate = ctx["e2e"].get("train_tokens_per_s")
    if rate is None or ctx["peaks"] is None:
        return None
    return arith.mfu_percent(rate, ctx["counters"]["flops_per_token"],
                             ctx["chips"], ctx["peaks"]["bf16_flops_per_s"])
