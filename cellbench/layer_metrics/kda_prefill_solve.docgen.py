"""The chunked delta rule's XLA half per 1,000 padded prompt tokens:
the loops around ``apex_kda_chunk_scan`` less the kernel's own time
(``counts/kda_prefill.py``): the triangular solve inside the chunks
and the decays it is built from."""


def read(ctx):
    counts = ctx["counts"]("kda_prefill")
    padded = counts.padded_tokens(ctx)
    secs = counts.delta_rule_seconds(ctx["reduced"], ctx["notes"])
    if secs is None or not padded:
        return None
    return (secs[0] - secs[1]) * 1e3 / (padded / 1e3)
