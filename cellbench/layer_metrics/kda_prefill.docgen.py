"""Device time of the chunked delta rule, the solve inside the chunks
and the carry between them, per 1,000 padded prompt tokens: the loops
around ``apex_kda_chunk_scan`` (``counts/kda_prefill.py``) over the
``padded_tokens`` of the ``serve.prefill`` spans inside the traced
stretch."""


def read(ctx):
    counts = ctx["counts"]("kda_prefill")
    padded = counts.padded_tokens(ctx)
    secs = counts.delta_rule_seconds(ctx["reduced"], ctx["notes"])
    if secs is None or not padded:
        return None
    return secs[0] * 1e3 / (padded / 1e3)
