"""The flash kernels of a model with window and full layers against
their roofline: the least time for the calls the trace holds (the band
of a window call, the triangle of a full call; the share of window
calls is the layer pattern's, ``counts/flash_window_attention.py``)
over their device time."""

KERNELS = ("apex_flash_fwd", "apex_flash_dq", "apex_flash_dkv")


def read(ctx):
    red = ctx["reduced"]
    if red is None or "layer_types" not in ctx["model"]:
        return None
    work = ctx["counts"]("flash_window_attention").per_call(ctx)
    peaks, share = ctx["peaks"], work["window_share"]
    least = took = 0.0
    for kernel in KERNELS:
        calls, secs = red.count(kernel), red.seconds(kernel)
        if not calls:
            continue
        for kind, part in (("window", share), ("full", 1.0 - share)):
            w = work[kind][kernel]
            least += calls * part * max(
                w["flops"] / peaks["bf16_flops_per_s"],
                w["bytes"] / peaks["hbm_bytes_per_s"])
        took += secs
    return 100.0 * least / took if took > 0 else None
