"""Model FLOP/s utilisation of the window's block steps: the operations
the rows they forwarded need of this chip's share of the model
(``counts/sdar_moe_model.py``, from the program's device-side
counters), over the window's seconds, the chips and the published bf16
peak."""


def read(ctx):
    c, peaks = ctx["counters"], ctx.get("peaks")
    work = ctx["counts"]("sdar_moe_model").flops(ctx["model"], c)
    if peaks is None or work is None or not c.get("window_s"):
        return None
    return 100.0 * work / c["window_s"] \
        / (ctx["chips"] * peaks["bf16_flops_per_s"])
