"""The prefills' share of the whole window
(``loop_readers.prefill_share_window_percent``)."""

from cellbench import loop_readers


def read(ctx):
    return loop_readers.prefill_share_window_percent(ctx)
