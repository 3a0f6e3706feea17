"""Output tokens a slot-pass: the tokens the window emitted over the
passes its block steps made (the program's device-side counters
``blk_denoise_passes`` and ``blk_commit_passes``).  1 is a token a slot
a step, what a plain decode step yields."""


def read(ctx):
    c = ctx["counters"]
    passes = (c.get("blk_denoise_passes") or 0) \
        + (c.get("blk_commit_passes") or 0)
    if not passes or c.get("window_tokens") is None:
        return None
    return c["window_tokens"] / passes
