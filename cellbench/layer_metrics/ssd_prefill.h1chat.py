"""Device time of the chunked Mamba-2 scan per 1,000 padded prompt
tokens: the innermost loops of the prefill programs
(``counts/ssd_prefill.py``) over the ``padded_tokens`` of the
``serve.prefill`` spans inside the traced stretch
(``counts/kda_prefill.padded_tokens``)."""


def read(ctx):
    padded = ctx["counts"]("kda_prefill").padded_tokens(ctx)
    secs = ctx["counts"]("ssd_prefill").scan_seconds(ctx["reduced"],
                                                     ctx["notes"])
    if secs is None or not padded:
        return None
    return secs * 1e3 / (padded / 1e3)
