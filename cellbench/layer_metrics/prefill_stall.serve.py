"""What a synchronous prefill costs the device
(``loop_readers.prefill_stall_ms``), in the traced stretch."""

from cellbench import loop_readers


def read(ctx):
    return loop_readers.prefill_stall_ms(ctx)
