"""The chunked delta rule of the prefills of a traced stretch: its
padded tokens, its device time, and the operations and bytes its
kernel (``apex_kda_chunk_scan``) needs.

*Device time.*  ``apex_tpu.ops.kda.kda_chunked`` runs both halves of
the chunked delta rule, the solve inside the chunks (XLA fusions, which
a trace names ``%fusion.N``) and the carry between them (the kernel),
inside ONE loop over blocks of heads.  The trace's op line nests, so
that loop is an event (``%while.N``) that holds the kernel's events:
the innermost ``%while`` around an ``apex_kda_chunk_scan`` is one
layer's chunked delta rule, whole.  Where that loop holds any other
named kernel it is not that loop (the compiler took it apart and the
layer loop was found instead), and nothing is reported.

*Work of the kernel.*  For a padded prompt token, in each KDA layer and
head (``d`` wide, in chunks of 64 rows): it reads a row of ``W``,
``U0``, ``Qg`` and ``Kend`` (``d`` float32 each) and of the chunk's 64 x
64 matrix, writes a row of the output, and spends ``2 d`` operations a
row in each of three ``d``-wide products and ``2 * 64`` in the fourth.
The padded tokens are the ``serve.prefill`` spans' ``padded_tokens``."""

import re

CHUNK = 64
KERNEL = r"^%apex_kda_chunk_scan"
_LOOP = re.compile(r"^%while")
_NAMED = re.compile(r"^%(apex_|gmm|ragged-dot)")


def padded_tokens(ctx) -> int:
    return sum(s["attrs"].get("padded_tokens", 0) for s in ctx["spans"]
               if s["name"] == "serve.prefill")


def delta_rule_seconds(red, notes=None):
    """``(whole, kernel)`` device seconds of the chunked delta rule in
    the traced stretch: the loops around ``apex_kda_chunk_scan`` and
    the kernel's own time inside them; None where the trace has no such
    loop (``notes`` is told why, if the kernel ran at all)."""
    if red is None:
        return None
    events = red.first_device()
    kernel = re.compile(KERNEL)
    inside = lambda e, w: w[1] <= e[1] and e[1] + e[2] <= w[1] + w[2]
    loops = [e for e in events if _LOOP.search(e[0])]
    found = {}
    for k in (e for e in events if kernel.search(e[0])):
        around = [w for w in loops if inside(k, w)]
        if around:      # a kernel cut off by the stretch's edge has none
            w = min(around, key=lambda w: w[2])
            found[(w[1], w[2])] = w
    whole, own = 0, 0
    for w in found.values():
        held = [e for e in events if inside(e, w) and _NAMED.search(e[0])]
        other = [e[0][:40] for e in held if not kernel.search(e[0])]
        if other:
            if notes is not None:
                notes.append(f"kda_prefill: the innermost loop around "
                             f"apex_kda_chunk_scan also holds {other[:3]}")
            return None
        whole += w[2]
        own += sum(e[3] if len(e) > 3 else e[2] for e in held)
    if not found:
        n = sum(1 for e in events if kernel.search(e[0]))
        if n and notes is not None:
            notes.append(f"kda_prefill: {n} apex_kda_chunk_scan events, "
                         f"{len(loops)} loops, none around a kernel")
        return None
    return whole / 1e9, own / 1e9


def total(ctx):
    lin, c = ctx["model"]["linear_attn_config"], ctx["counters"]
    padded = padded_tokens(ctx)
    if not padded or not c.get("kda_layers"):
        return None
    d = lin["head_dim"]
    rows = padded * c["kda_layers"] * lin["num_heads"]
    return {"flops": 2.0 * d * (3 * d + CHUNK) * rows,
            "bytes": 4.0 * (5 * d + CHUNK) * rows}
