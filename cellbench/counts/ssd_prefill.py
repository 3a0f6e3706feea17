"""The chunked Mamba-2 scan of the prefills of a traced stretch: its
device time.

``apex_tpu.ops.ssd.ssd_chunked`` is plain XLA, and ONE ``lax.scan`` over
the chunks holds all of it: the decay-masked products inside a chunk and
the carried state between chunks.  The trace's op line nests, so that
loop is an event (``%while.N``) INSIDE the layer loop's (the other
``%while`` of a prefill program) that holds no further loop and no named
kernel: the innermost ``%while`` events of the prefill programs, one a
layer a prefill.  Where a prefill program holds no nested loop (a
program without this scan) nothing is reported.  The padded tokens are
``counts/kda_prefill.padded_tokens``: the ``serve.prefill`` spans'."""

import re

PREFILL_PROGRAM = re.compile(r"^jit_prefill")
_LOOP = re.compile(r"^%while")
_NAMED = re.compile(r"^%(apex_|gmm|ragged-dot)")


def scan_seconds(red, notes=None):
    """Device seconds of the chunked scans in the traced stretch, or
    None where the trace shows none (``notes`` is told why, where the
    trace has prefill programs at all)."""
    if red is None or not red.modules:
        return None
    programs = [(e[1], e[1] + e[2])
                for e in next(iter(red.modules.values()))
                if PREFILL_PROGRAM.search(e[0])]
    if not programs:
        return None
    events = red.first_device()
    inside = lambda e, w: w[1] <= e[1] and e[1] + e[2] <= w[1] + w[2]
    loops = [e for e in events if _LOOP.search(e[0])
             and any(a <= e[1] and e[1] + e[2] <= b for a, b in programs)]
    # a scan is a loop inside another (the layers') that holds none
    scans = [w for w in loops
             if any(o is not w and inside(w, o) for o in loops)
             and not any(o is not w and inside(o, w) for o in loops)]
    named = [e for e in events if _NAMED.search(e[0])]
    held = [e[0][:40] for w in scans for e in named if inside(e, w)]
    if held or not scans:
        if notes is not None:
            notes.append(
                f"ssd_prefill: {len(loops)} loops in {len(programs)} "
                f"prefill programs, {len(scans)} nested; they hold "
                f"{held[:3]}")
        return None
    return sum(w[2] for w in scans) / 1e9
