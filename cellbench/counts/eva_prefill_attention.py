"""Operations and bytes of the windowed flash forward over the prefills
of a traced stretch.  A prompt padded to ``P`` bytes is ``n = P /
window`` windows a layer.  A window's queries see their own window
causally (half of ``window x window`` score and value products: ``2
window^2 H`` operations, ``H`` the hidden size) and the ``window / chunk``
pooled pairs of each of the ``w`` windows before it (``4 window (window
/ chunk) w H``); what the kernel computes and masks (the upper half of
the diagonal blocks, the pooled buffer's hidden rows) is no work.  It
reads a window's queries, keys and values and writes its output once
(``4 window H`` values) and reads the visible pooled pairs (``2 (window
/ chunk) w H``).  The padded bytes are the ``serve.prefill`` spans'
``padded_tokens``; the kernel is ``apex_flash_fwd`` (in a serving run
only the prefill holds it)."""

KERNEL = r"^%apex_flash_fwd"


def _prefills(ctx):
    return [s["attrs"].get("padded_tokens", 0) for s in ctx["spans"]
            if s["name"] == "serve.prefill"]


def padded_tokens(ctx) -> int:
    return sum(_prefills(ctx))


def total(ctx):
    model, args, c = ctx["model"], ctx["args"], ctx["counters"]
    W, chunk = model["window_size"], model["chunk_size"]
    H, layers = model["hidden_size"], c.get("layers")
    per = W // chunk
    flops = values = 0.0
    for padded in _prefills(ctx):
        n = padded // W
        before = n * (n - 1) / 2        # sum of w over the windows
        flops += 2.0 * W * W * H * n + 4.0 * W * per * H * before
        values += 4.0 * W * H * n + 2.0 * per * H * before
    if not flops or not layers:
        return None
    item = 2 if args["compute_dtype"] == "bfloat16" else 4
    return {"flops": flops * layers, "bytes": values * item * layers}
