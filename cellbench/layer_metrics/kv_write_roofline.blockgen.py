"""apex_kv_write against its roofline in a block step: a live slot's
pass owes its block's columns once a layer
(``counts/block_kv_write.py``, from the program's device-side counters
``blk_denoise_passes`` and ``blk_commit_passes``)."""

from cellbench import decode_ops


def read(ctx):
    red = ctx["reduced"]
    work = ctx["counts"]("block_kv_write").total(ctx)
    if red is None or work is None:
        return None
    return decode_ops.roofline_percent(
        ctx, "block_kv_write", work, red.seconds("apex_kv_write"))
