"""Commit passes over all slot-passes of the window's block steps (the
program's device-side counters)."""


def read(ctx):
    c = ctx["counters"]
    passes = (c.get("blk_denoise_passes") or 0) \
        + (c.get("blk_commit_passes") or 0)
    if not passes:
        return None
    return 100.0 * c["blk_commit_passes"] / passes
