"""apex_ssd_decode against its roofline: the work is the state updates
the program counted on the device (``ssm_state_updates``: active slots
x layers a step), each reading and writing one slot's state of one
layer whole (``counts/ssd_decode.py``)."""

from cellbench import decode_ops


def read(ctx):
    red = ctx["reduced"]
    work = ctx["counts"]("ssd_decode").total(ctx)
    if red is None or work is None:
        return None
    return decode_ops.roofline_percent(ctx, "ssd_decode", work,
                                       red.seconds("apex_ssd_decode"))
