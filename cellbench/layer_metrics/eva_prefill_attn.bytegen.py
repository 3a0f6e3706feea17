"""Device time of the windowed flash forward per 1,000 padded prompt
bytes: ``apex_flash_fwd`` over the ``padded_tokens`` of the
``serve.prefill`` spans inside the traced stretch
(``counts/eva_prefill_attention.py``)."""


def read(ctx):
    counts = ctx["counts"]("eva_prefill_attention")
    red, padded = ctx["reduced"], counts.padded_tokens(ctx)
    if red is None or not padded:
        return None
    secs = red.seconds(counts.KERNEL)
    return secs * 1e3 / (padded / 1e3) if secs > 0 else None
