"""Device time of an operation inside the programs of one kind.

A traced stretch of a serving run holds two kinds of program, the
decode step and the prefill, and some operations run in both (the
grouped matmuls of an expert layer).  A per-step metric of the decode
step has to leave the prefill's share out: an operation belongs to the
program (an event of the trace's "XLA Modules" line) inside whose
interval it starts."""

import bisect
import re
from typing import Optional, Tuple

DECODE_PROGRAM = r"^jit_step"
PREFILL_PROGRAM = r"^jit_prefill"


def seconds_in_programs(red, op_pattern: str, program_pattern: str
                        ) -> Optional[Tuple[float, int]]:
    """(summed self seconds, calls) of the first device's operations
    matching ``op_pattern`` that start inside a program matching
    ``program_pattern``; None where the trace names no program."""
    if red is None or not red.modules:
        return None
    prx, orx = re.compile(program_pattern), re.compile(op_pattern)
    programs = sorted((e[1], e[1] + e[2])
                      for e in next(iter(red.modules.values()))
                      if prx.search(e[0]))
    if not programs:
        return None
    starts = [a for a, _ in programs]
    ns, calls = 0, 0
    for ev in red.first_device():
        if not orx.search(ev[0]):
            continue
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < programs[i][1]:
            ns += ev[3] if len(ev) > 3 else ev[2]
            calls += 1
    return ns / 1e9, calls


def program_share_of_busy(red, program_pattern: str) -> Optional[float]:
    """Share (0..1) of the device's busy time that lies inside programs
    matching ``program_pattern``."""
    if red is None or not red.modules or red.busy_s <= 0:
        return None
    rx = re.compile(program_pattern)
    inside = sum(min(e[1] + e[2], red.hi) - max(e[1], red.lo)
                 for e in next(iter(red.modules.values()))
                 if rx.search(e[0]) and e[1] < red.hi
                 and e[1] + e[2] > red.lo)
    return inside / 1e9 / red.busy_s


#: the held experts' grouped matmuls as the trace names them: the Pallas
#: grouped GEMM that ships with JAX (``%gmm.N``), or XLA:TPU's own
#: kernel for ``jax.lax.ragged_dot`` (``%ragged-dot-...``)
GROUPED_MATMUL = r"^%(gmm|ragged-dot)"


def roofline_percent(ctx, label: str, work, seconds: float
                     ) -> Optional[float]:
    """The least time the chip could take for ``work`` (``{"flops",
    "bytes"}``) over the ``seconds`` it took, in percent; notes which of
    the two bounds it."""
    if not work or seconds <= 0:
        return None
    peaks = ctx["peaks"]
    t_ops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    ctx["notes"].append(f"roofline {label}: bound by "
                        + ("compute" if t_ops >= t_bytes else "memory"))
    return 100.0 * max(t_ops, t_bytes) / seconds
