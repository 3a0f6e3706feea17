"""Pallas TPU flash attention (fwd + bwd kernels).

Reference: ``apex/contrib/fmha`` (CUDA flash-style fused MHA, seqlen
≤512) and ``apex/contrib/multihead_attn`` fused attention.  TPU
redesign: one VMEM-resident online-softmax kernel — a score tile never
touches HBM, running max/sum live in f32 across the key walk.

Three kernels, the standard flash decomposition:

- forward: grid ``(batch·heads, q_blocks, k_blocks)``, out block revisited
  across the k dimension, accumulator/max/sum in f32 scratch, writes
  ``out`` and the per-row logsumexp.
- dq backward: same grid; recomputes the score tile from (q, k, lse),
  accumulates ``dq`` in scratch.
- dk/dv backward: grid ``(batch·heads, k_blocks, q_blocks)`` (k outer),
  accumulates ``dk``/``dv`` in scratch.

**The causal walk is inside the kernels.**  Grid blocks stay large (a
grid step costs ~0.35 µs, as much as the work a small block would
skip), and a block is walked in square sub-tiles of ``sub`` rows and
columns (:data:`~apex_tpu.ops._pallas_tiling.SUBTILE`, or the tuned
row's): a strip of query rows over its key sub-tiles in ascending
order (forward, dq), a strip of keys over its query sub-tiles (dkv,
which holds its tiles transposed, keys by queries, so that neither
product into dk or dv transposes a tile).  Where the diagonal crosses a
block is a function of one integer, the block's first query row less
its first key column, and the values it takes over a call's grid are
known when the kernel is traced (``q_offset``, ``k_offset`` and the
sizes are static).  So the walk is straight-line code: one static
variant a distinct position of the diagonal, chosen on the device by
``pl.when`` on the grid indices (a grid of one block has one variant
and no test), and in it three classes of sub-tile
(:func:`live_subtiles` counts them from the same plans the code is
built from):

1. wholly above the diagonal: not visited;
2. wholly under it: computed with no ``iota`` and no ``where``;
3. crossed by it: one ``where`` against a constant index difference.

**The code the walk emits is budgeted.**  Straight-line code grows with
what it visits, and a kernel's code is paid for once a compiled PROGRAM
before anything runs: traced and lowered at every start (no compile
cache saves that), compiled, serialized, loaded.  So a strip's class-2
sub-tiles go in *runs* (:func:`_pieces`): one product over the run's
columns and ONE softmax update, at most ``RUN_COLUMNS`` wide, and a
strip is a body or two and not a body a sub-tile.  :func:`live_subtiles`
reports the *bodies* beside the sub-tiles; the tests hold them, and the
serialized module, under ceilings at the cells' shapes.

A non-causal call is the same code with every sub-tile in class 2, a
ring chunk wholly under the diagonal likewise, one wholly above it a
variant with no code.  A key bias hides columns by DATA and never
removes a sub-tile.  Rows that see no key at all (ring warm-up chunks,
a batch row whose keys are all padding) keep ``lse = NEG_INF`` and a
zero output: the forward zeroes them where it finalises, the backward
kernels turn their ``lse`` to ``+|NEG_INF|`` so every weight is 0.
Sums run in the order of the pieces, keys (dkv: queries) ascending; a
run is one term of that sum.

``delta = rowsum(dout · out)`` is precomputed by XLA (it fuses into the
preceding op).  ``q_offset``/``k_offset`` place the local blocks in the
global sequence so ring attention's cross-device causal masks work.

The ``lax.scan`` composite in :mod:`apex_tpu.ops.attention` remains the
numerics specification and the universal fallback (CPU, odd shapes).
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._pallas_tiling import LANES as _LANES
from apex_tpu.ops._pallas_tiling import SUBTILE as _SUBTILE
from apex_tpu.ops._pallas_tiling import VMEM_BUDGET as _VMEM_BUDGET
from apex_tpu.ops._pallas_tiling import flash_run_cap as run_cap
from apex_tpu.ops._pallas_tiling import flash_subtile as _flash_subtile
from apex_tpu.ops._pallas_tiling import flash_vmem_bytes as _flash_vmem_bytes
from apex_tpu.ops._pallas_tiling import sublane as _sublane

NEG_INF = -1e30

# Shared by all three kernels: batch·head and q-block (resp. k-block)
# grid revisits are order-free; only the innermost accumulation dim —
# where the scratch carry, its init, and its finalize live — is
# sequential.  Declaring this lets Mosaic software-pipeline the block
# DMAs across grid steps instead of serializing on the conservative
# default (numerics are identical either way — the arbitrary dim still
# runs in order).
_DIM_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# ------------------------------------------------------------ block tuning
# Measured per-shape block targets, keyed (seq_q, head_dim, dtype name,
# phase) -> (block_q, block_k[, sub]), phase ∈ {"fwd", "bwd"}; ``sub``
# is the side of the in-kernel sub-tile (absent: _SUBTILE).  The phases
# have different VMEM envelopes and different work a sub-tile, so one
# row cannot serve both.  Populated from benchmarks/flash_sweep.py runs
# on real hardware (benchmarks/install_tuned_blocks.py records the
# provenance in a comment at the table's head); consulted by the
# fwd/bwd entry points when the caller passes no explicit blocks,
# before the _pick_block static heuristic.
# Legacy 3-tuple (seq_q, head_dim, dtype) keys are read as fwd-only.
_TUNED_BLOCKS: dict = {
    # measured: TPU v5 lite (v5e), one chip, kernels timed alone, chained calls a program. Rows of D 64 and 192 and of (2048, 128): 2026-09-30, PR 36's sweep read again with runs in PR 37's; (2048, 128): sub-tile 512 (0.299 ms, 8 bodies) over 256 (0.284 ms, 18 bodies): the EVA window's programs pay for code eight times (PERF.md, PR 37). Rows of (8192, 128): 2026-09-30, PR 38's sweep (--shapes afmoe: GQA 32:4, a band of 2,048 keys and the causal triangle, three band calls weighed to one full call, ms a sequence; bodies summed over the four kernels): fwd (1024, 2048, 512) 15.79 ms, 28 bodies, where no row (1024, 1024, 256) read 17.46 and the fastest (2048, 2048, 512) 15.47 (its full call is refused for VMEM by the compile-only v5e client); bwd (1024, 1024, 256) 29.80 ms, 58 bodies, where no row (512, 512, 256) read 36.08 and the fastest (2048, 2048, 256) 27.77 with 140 bodies (PERF.md, PR 38) (benchmarks/flash_sweep.py)
    (512, 192, 'bfloat16', 'fwd'): (512, 512, 256),
    (1024, 64, 'bfloat16', 'bwd'): (1024, 1024, 256),
    (1024, 64, 'bfloat16', 'fwd'): (1024, 1024, 256),
    (1024, 192, 'bfloat16', 'fwd'): (1024, 1024, 256),
    (2048, 128, 'bfloat16', 'fwd'): (2048, 2048, 512),
    (2048, 192, 'bfloat16', 'fwd'): (1024, 2048, 256),
    (4096, 192, 'bfloat16', 'fwd'): (2048, 512, 512),
    (8192, 128, 'bfloat16', 'bwd'): (1024, 1024, 256),
    (8192, 128, 'bfloat16', 'fwd'): (1024, 2048, 512),
}

_PHASES = ("fwd", "bwd")

#: block targets of a shape with no measured row (the backward holds
#: more blocks a grid step; unmeasured shapes keep the grid they had)
DEFAULT_BLOCK = {"fwd": 1024, "bwd": 512}


def _tuned_row(seq_q, head_dim, dtype, phase):
    if phase not in _PHASES:
        raise ValueError(f"phase must be one of {_PHASES}, got {phase!r}")
    key = (int(seq_q), int(head_dim), jnp.dtype(dtype).name)
    hit = _TUNED_BLOCKS.get(key + (phase,))
    if hit is None and phase == "fwd":
        hit = _TUNED_BLOCKS.get(key)
    return hit


def tuned_blocks(seq_q, head_dim, dtype, phase="fwd"):
    """(block_q, block_k) measured best for this shape and phase, or
    None.  ``phase="fwd"`` also reads legacy 3-tuple entries (tables
    installed before the per-phase split are forward measurements)."""
    hit = _tuned_row(seq_q, head_dim, dtype, phase)
    return None if hit is None else tuple(hit[:2])


def tuned_subtile(seq_q, head_dim, dtype, phase="fwd"):
    """The sub-tile side the shape's tuned row names, or None (no row,
    or a row of blocks alone: the kernels' ``SUBTILE`` then)."""
    hit = _tuned_row(seq_q, head_dim, dtype, phase)
    return hit[2] if hit is not None and len(hit) > 2 else None


def set_tuned_blocks(table) -> None:
    """Install sweep-measured block targets: ``{(S, D, dtype[, phase]):
    (bq, bk[, sub])}`` or an iterable of ``[[S, D, dtype[, phase]],
    [bq, bk[, sub]]]`` pairs (the exact JSON flash_sweep.py prints as
    ``tuned_blocks_table``).  Three-element keys — the pre-per-phase
    format — install as ``"fwd"`` entries: old sweeps measured the
    forward dispatcher's path.  The dtype key is normalized through
    ``jnp.dtype`` so ``jnp.bfloat16``, ``'bfloat16'``, and ``np.dtype``
    all land on the same entry."""
    items = table.items() if hasattr(table, "items") else table
    for key, val in items:
        if len(key) == 3:
            (s, d, name), phase = key, "fwd"
        else:
            s, d, name, phase = key
        if phase not in _PHASES:
            raise ValueError(
                f"tuned-block phase must be one of {_PHASES}, got {phase!r}")
        _TUNED_BLOCKS[(int(s), int(d), jnp.dtype(name).name, str(phase))] = (
            tuple(int(x) for x in val))


def _pick_block(seq, target, align=_LANES, fits=None):
    """Largest divisor of ``seq`` ≤ target, preferring ``align``-aligned
    divisors (128 for the lane dim, the dtype sublane tile — 8 fp32 /
    16 bf16, via ``_sublane`` — for sublanes) — but only when the
    aligned candidate is at least half the largest divisor: a misaligned
    tile wastes ≤ (align−1) padded lanes, while a much smaller tile
    multiplies grid steps and k/v refetches (e.g. seq=640, target=512:
    320 misaligned beats 128 aligned).

    ``fits``: optional predicate over a candidate block — candidates it
    rejects are dropped BEFORE the size preference runs.  The callers
    pass the APX304 VMEM footprint formula
    (:func:`apex_tpu.ops._pallas_tiling.flash_vmem_bytes` ≤ budget) so
    an over-large target clamps to the biggest block that provably fits
    instead of overflowing when Mosaic first compiles at long seq.
    When NO candidate fits the smallest divisor (1) is returned — the
    least-over-budget choice; Mosaic gets the final word either way."""
    divisors = [b for b in range(1, min(target, seq) + 1) if seq % b == 0]
    if fits is not None:
        divisors = [b for b in divisors if fits(b)] or [1]
    best = divisors[-1]
    aligned = [b for b in divisors if b % align == 0]
    if aligned and 2 * aligned[-1] >= best:
        return aligned[-1]
    return best


# ------------------------------------------------------- the causal walk
# Where the diagonal crosses a block is a function of ONE integer, the
# block's first query row less its first key column, and every value
# it takes over a call's grid is known when the kernel is traced.  So
# nothing about the walk is decided on the device but which of a few
# static variants of the block's code runs (`_block_variants`): the
# sub-tiles a strip visits, and which of them pay a mask, are Python
# integers, and the code inside a block is straight-line.

def _clip(x, lo, hi):
    return min(max(x, lo), hi)


def _key_bounds(causal, gap, sub_q, sub_k, n, window=None):
    """For a strip of ``sub_q`` query rows over the ``n`` key sub-tiles
    of ``sub_k`` columns of a block, ``gap`` = the strip's first global
    row less the block's first global column: ``(dead, low, full,
    seen)``.  Sub-tiles ``[dead, seen)`` are visited: ``[full, seen)``
    are crossed by the diagonal, ``[seen, n)`` lie wholly above it;
    under a ``window`` (key ``j`` visible to query ``i`` iff ``0 <= i -
    j < window``) ``[0, dead)`` lie wholly under the band and ``[dead,
    low)`` are crossed by its lower edge (no window: both 0)."""
    if not causal:
        return 0, 0, n, n
    full = _clip((gap + 1) // sub_k, 0, n)
    seen = _clip((gap + sub_q - 1) // sub_k + 1, 0, n)
    if window is None:
        return 0, 0, full, seen
    dead = _clip((gap - window + 1) // sub_k, 0, n)
    if dead >= seen:        # nothing to visit: above the diagonal, or under the band
        return (0, 0, 0, 0) if seen == 0 else (n, n, n, n)
    low = _clip(-((window - gap - sub_q) // sub_k), dead, seen)
    return dead, low, _clip(full, dead, seen), seen


def _query_bounds(causal, lead, sub_k, sub_q, n, window=None):
    """For a strip of ``sub_k`` keys over the ``n`` query sub-tiles of
    ``sub_q`` rows of a block, ``lead`` = the strip's first global
    column less the block's first global row: ``(first, full, high,
    end)``.  Sub-tiles ``[first, end)`` are visited: ``[0, first)`` lie
    wholly above the diagonal, ``[first, full)`` are crossed by it;
    under a ``window`` ``[high, end)`` are crossed by the band's lower
    edge and ``[end, n)`` lie wholly under it (no window: both ``n``)."""
    if not causal:
        return 0, 0, n, n
    first = _clip(lead // sub_q, 0, n)
    full = _clip(-((-lead - sub_k + 1) // sub_q), 0, n)
    if window is None:
        return first, full, n, n
    end = _clip(-((-(window + lead + sub_k - 1)) // sub_q), 0, n)
    if first >= end:
        return (n, n, n, n) if first == n else (0, 0, 0, 0)
    high = _clip((window + lead) // sub_q, first, end)
    return first, _clip(full, first, end), high, end


def _strip_plan(phase, causal, d, bq, bk, sub_q, sub_k, window=None):
    """The bounds of every strip of a block whose first query row lies
    ``d`` past its first key column: by query strip ``(dead, low, full,
    seen)`` (forward, dq), by key strip ``(first, full, high, end)``
    (``"dkv"``).  Either way a strip visits sub-tiles ``[a, d)`` of its
    four numbers ``(a, b, c, d)``, masks those under ``b`` and those
    from ``c`` on, and computes ``[b, c)`` plain."""
    if phase == "dkv":
        return tuple(_query_bounds(causal, c * sub_k - d, sub_k, sub_q,
                                   bq // sub_q, window)
                     for c in range(bk // sub_k))
    return tuple(_key_bounds(causal, d + r * sub_q, sub_q, sub_k,
                             bk // sub_k, window) for r in range(bq // sub_q))


class _Band(NamedTuple):
    """A sliding window's static geometry over one kernel's grid: a
    block of the outer axis (query blocks in the forward and dq, key
    blocks in dkv) walks ``n_live`` blocks of the other axis, from
    :meth:`first` on.  The walked blocks all exist; those of them that
    the band does not reach are dead by their strips' plans."""

    window: int
    n_live: int
    base: int
    step: int
    div: int
    last: int

    def first(self, x):
        """First walked block of outer block ``x`` (an integer, or a
        traced grid index)."""
        f = (self.base + x * self.step) // self.div
        if isinstance(x, int):
            return _clip(f, 0, self.last)
        return jnp.clip(f, 0, self.last)


def _band(phase, window, q_offset, k_offset, bq, bk, nq, nk):
    """The :class:`_Band` of a call, or None without a window."""
    if window is None:
        return None
    off = q_offset - k_offset
    if phase == "dkv":
        # key block j: queries from its first key's row to its last
        # key's row + window - 1
        lo = [_clip((j * bk - off) // bq, 0, nq - 1) for j in range(nk)]
        hi = [_clip(((j + 1) * bk + window - 2 - off) // bq, 0, nq - 1)
              for j in range(nk)]
        base, step, div, n = -off, bk, bq, nq
    else:
        # query block i: keys from its first row - (window - 1) to its
        # last row
        lo = [_clip((off + i * bq - window + 1) // bk, 0, nk - 1)
              for i in range(nq)]
        hi = [_clip((off + (i + 1) * bq - 1) // bk, 0, nk - 1)
              for i in range(nq)]
        base, step, div, n = off - window + 1, bq, bk, nk
    n_live = max(1, max(h - l + 1 for l, h in zip(lo, hi)))
    return _Band(window, n_live, base, step, div, n - n_live)


def _block_distances(q_offset, k_offset, bq, bk, nq, nk, phase="fwd",
                     band=None):
    """First query row less first key column, of every grid block."""
    off = q_offset - k_offset
    if band is None:
        return [off + i * bq - j * bk for i in range(nq) for j in range(nk)]
    if phase == "dkv":
        return [off + (band.first(j) + ii) * bq - j * bk
                for j in range(nk) for ii in range(band.n_live)]
    return [off + i * bq - (band.first(i) + jj) * bk
            for i in range(nq) for jj in range(band.n_live)]


def _block_variants(phase, causal, q_offset, k_offset, bq, bk, nq, nk,
                    sub_q, sub_k, band=None):
    """``[(plan, d_min, d_max)]``: the distinct strip plans among the
    grid's blocks.  A plan's numbers only grow with ``d``, so the blocks
    that share one are an interval of ``d``; a plan with sub-tiles an
    edge crosses belongs to ONE ``d`` (the mask needs the exact
    distance), ``d_min == d_max``."""
    window = band.window if band else None
    spans = {}
    for d in _block_distances(q_offset, k_offset, bq, bk, nq, nk, phase,
                              band):
        plan = _strip_plan(phase, causal, d, bq, bk, sub_q, sub_k, window)
        crossed = _visited(plan)[1] > 0
        key = (plan, d if crossed else None)
        lo, hi = spans.get(key, (d, d))
        spans[key] = (min(lo, d), max(hi, d))
    return sorted(((plan, lo, hi) for (plan, _), (lo, hi) in spans.items()),
                  key=lambda v: v[1])


def _visited(plan):
    """(visited, masked) sub-tiles of a block with this plan."""
    return (sum(d - a for a, _, _, d in plan),
            sum((d - a) - max(0, c - b) for a, b, c, d in plan))


def _pieces(phase, bounds, run):
    """What a strip with these bounds computes, in the order it sums
    (ascending): ``[(t0, t1, masked)]``, each piece ONE copy of the
    tile arithmetic (a *body*) over sub-tiles ``[t0, t1)``.  The
    sub-tiles no edge touches go in runs of at most ``run``, one product
    and one update a run whatever its length (``masked`` False); a
    sub-tile an edge crosses is a piece of its own, ``masked`` the pair
    ``(lower, upper)`` of the edges that cross it: the band's lower
    edge, the diagonal."""
    a, b, c, d = bounds
    head_is = (False, True) if phase == "dkv" else (True, False)
    tail_is = head_is[::-1]
    head = [(t, t + 1, (True, True) if t >= c else head_is)
            for t in range(a, b)]
    runs = [(t, min(t + run, c), False) for t in range(b, c, run)]
    tail = [(t, t + 1, tail_is) for t in range(max(b, c), d)]
    return head + runs + tail


def live_subtiles(phase, Sq, Sk, q_offset, k_offset, bq, bk, sub,
                  causal=True, run=None, window=None):
    """``(visited, masked, skipped, bodies)``: the sub-tiles one head's
    call computes, those of them an edge crosses (the diagonal, or a
    ``window``'s lower edge: the only ones that pay a mask), and those
    never visited, summed over the grid blocks; and the *bodies*, the
    copies of the tile arithmetic the kernel's CODE holds (a run of
    unmasked sub-tiles is one, a crossed sub-tile one; summed over the
    static variants, not the blocks: it is what the kernel costs to
    lower, compile and load, once a compiled program).  All from the
    strip plans the kernels' code is built from (``"fwd"`` and the dq
    kernel walk keys by query strip, ``"dkv"`` queries by key strip;
    ``"bwd"`` counts as dq).  ``sub=None``: a block is one tile;
    ``run``: sub-tiles a run at most (default: :func:`run_cap` of the
    sub-tile).  A key bias hides columns by data and changes none of
    the four.  Under a ``window`` the grid blocks the band does not
    reach are not in the grid at all (:class:`_Band`): their sub-tiles
    count as skipped."""
    sub_q, sub_k = (sub, sub) if sub else (bq, bk)
    run = run or run_cap(sub)
    nq, nk = Sq // bq, Sk // bk
    band = _band(phase, window, q_offset, k_offset, bq, bk, nq, nk)
    total = (Sq // sub_q) * (Sk // sub_k)
    visited = masked = 0
    for d in _block_distances(q_offset, k_offset, bq, bk, nq, nk, phase,
                              band):
        v, m = _visited(_strip_plan(phase, causal, d, bq, bk, sub_q, sub_k,
                                    window))
        visited, masked = visited + v, masked + m
    bodies = sum(len(_pieces(phase, bounds, run))
                 for plan, _, _ in _block_variants(
                     phase, causal, q_offset, k_offset, bq, bk, nq, nk,
                     sub_q, sub_k, band)
                 for bounds in plan)
    return visited, masked, total - visited, bodies


def _for_this_block(phase, causal, q_offset, k_offset, bq, bk, nq, nk,
                    sub_q, sub_k, i, j, always, body, band=None):
    """``body(plan, d)`` for the block at grid position ``(i, j)``:
    traced indices choose among the static variants by ``pl.when`` on
    the one integer that tells them apart (``d`` is exact where the
    plan has masked sub-tiles); a variant with nothing to visit is no
    code at all, unless ``always`` (a block that has to write its
    outputs whatever it sees)."""
    d = q_offset - k_offset + i * bq - j * bk
    variants = _block_variants(phase, causal, q_offset, k_offset, bq, bk,
                               nq, nk, sub_q, sub_k, band)
    for plan, lo, hi in variants:
        if not (always or _visited(plan)[0]):
            continue
        if len(variants) == 1:
            body(plan, lo)
        else:
            conds = ([d >= lo] if lo > variants[0][1] else []) + (
                [d <= hi] if hi < variants[-1][2] else [])
            pl.when(functools.reduce(jnp.logical_and, conds))(
                functools.partial(body, plan, lo))


def _tile(t, size, until=None):
    """Rows (or columns) of sub-tile ``t``, or of sub-tiles ``[t,
    until)``."""
    return slice(t * size, (t + 1 if until is None else until) * size)


def _grid_index(axis, n):
    """The grid index, or the integer 0 where the axis has one block."""
    return 0 if n == 1 else pl.program_id(axis)


def _visible(rows, cols, first_key_less_first_query, transposed=False,
             edges=(False, True), window=None, block=None):
    """The mask of a piece an edge crosses: query index ≥ key index
    (the diagonal, ``edges[1]``) and query index − key index < ``window``
    (the band's lower edge, ``edges[0]``), each ONE compare of the index
    difference inside the piece against a constant.  ``transposed``:
    keys (sublanes) by queries.  ``block`` (the forward's; a power of
    two that every piece starts on a multiple of): a query sees to the
    END of its block of ``block`` positions, so its index counts as its
    block's last."""
    along = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    down = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    if block:
        down = down | (block - 1)
    diff = along - down if transposed else down - along
    lower, upper = edges
    if not lower:
        return diff >= first_key_less_first_query
    inside = diff < first_key_less_first_query + window
    return inside & (diff >= first_key_less_first_query) if upper else inside


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))   # a · bᵀ
_NN = ((1,), (0,))   # a · b


def _dead_rows_off(lse):
    """Rows no key reaches have ``lse = NEG_INF``: ``exp(s − lse)``
    would read 1 where ``s`` is a hidden key's ``NEG_INF`` too.  Turned
    to ``+|NEG_INF|`` every weight of such a row is exactly 0."""
    return jnp.where(lse > NEG_INF / 2, lse, -lse)


# ------------------------------------------------------------------ forward
def _fwd_kernel(*refs, scale, causal, has_bias, q_offset, k_offset,
                block_q, block_k, sub_q, sub_k, run, nq, nk, band=None,
                causal_block=None):
    """``nk``: key blocks a query block walks (under a window, the
    band's ``n_live``: grid index ``j`` is then the walk's, and the key
    block is ``band.first(i) + j``).  ``causal_block``: causal by block
    (:func:`flash_fwd_pallas`); only the sub-tiles the diagonal crosses
    differ, in their mask."""
    if has_bias:
        q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
        b_ref = None
    i, j = _grid_index(1, nq), _grid_index(2, nk)
    window = band.window if band else None

    def finalize(rows, m, l, acc):
        l = jnp.maximum(l, 1e-30)  # rows that saw no key (ring blocks)
        out = acc / l
        if causal or has_bias:
            # a row whose keys were all hidden so far weighs them 1
            # each (exp(NEG_INF - NEG_INF)); the first visible key wipes
            # that (corr = 0), and a row that never sees one is zero
            out = jnp.where(m > NEG_INF / 2, out, 0.0)
        o_ref[0, rows, :] = out.astype(o_ref.dtype)
        lse_ref[0, rows, :] = m + jnp.log(l)

    if nk > 1:
        @pl.when(j == 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

    def strip(r, gap, bounds):
        """Query strip ``r``, its first row ``gap`` past the block's
        first key, over the key sub-tiles its ``bounds`` name
        (:func:`_key_bounds`): plain ones in runs, crossed ones
        masked."""
        rows = _tile(r, sub_q)
        q = q_ref[0, rows, :]

        def score(c, until, masked):
            """The scores of key sub-tiles ``[c, until)``: biased, and
            masked where the diagonal crosses them."""
            cols = _tile(c, sub_k, until)
            s = _dot(q, k_ref[0, cols, :], _NT) * scale
            if b_ref is not None:
                s = s + b_ref[0, :, cols]  # (1, columns) key bias over rows
            if masked:
                s = jnp.where(_visible(sub_q, sub_k, c * sub_k - gap,
                                       edges=masked, window=window,
                                       block=causal_block),
                              s, NEG_INF)
            return s

        def update(c, until, s, carry):
            """One online-softmax update over key sub-tiles ``[c,
            until)``; a ``carry`` of None is a strip that has seen
            nothing yet."""
            m_new = jnp.max(s, axis=-1, keepdims=True)
            if carry is not None:
                m_prev, l_prev, acc = carry
                m_new = jnp.maximum(m_prev, m_new)
                corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = jnp.sum(p, axis=-1, keepdims=True)
            pv = _dot(p.astype(v_ref.dtype),
                      v_ref[0, _tile(c, sub_k, until), :], _NN)
            if carry is None:
                return m_new, l_new, pv
            return m_new, l_prev * corr + l_new, acc * corr + pv

        # one key block: the first tile starts the statistics
        carry = (None if nk == 1 else
                 (m_ref[rows, 0:1], l_ref[rows, 0:1], acc_ref[rows, :]))
        # keys ascending: the plain runs, then the diagonal's sub-tiles.
        # A piece's scores are written one piece AHEAD of its update, so
        # that its product has no softmax to wait for (the schedule of a
        # head at the train cell's shape: 3,791 bundles -> 3,441)
        pieces = _pieces("fwd", bounds, run)
        ahead = score(*pieces[0]) if pieces else None
        for n, (c, until, _) in enumerate(pieces):
            s, ahead = ahead, (score(*pieces[n + 1])
                               if n + 1 < len(pieces) else None)
            carry = update(c, until, s, carry)
        if carry is None:  # a strip no key of the call reaches
            carry = (jnp.full((sub_q, 1), NEG_INF, jnp.float32),
                     jnp.zeros((sub_q, 1), jnp.float32),
                     jnp.zeros((sub_q, acc_ref.shape[1]), jnp.float32))
        if nk == 1:
            finalize(rows, *carry)
        else:
            m, l, acc = carry
            m_ref[rows, :] = jnp.broadcast_to(m, (sub_q, m_ref.shape[1]))
            l_ref[rows, :] = jnp.broadcast_to(l, (sub_q, l_ref.shape[1]))
            acc_ref[rows, :] = acc

    def block(plan, d):
        for r, bounds in enumerate(plan):
            # one key block: finalised here, seen or not
            if bounds[3] > bounds[0] or nk == 1:
                strip(r, d + r * sub_q, bounds)

    _for_this_block("fwd", causal, q_offset, k_offset, block_q, block_k,
                    nq, nk, sub_q, sub_k, i,
                    j if band is None else band.first(i) + j,
                    nk == 1, block, band)

    if nk > 1:
        @pl.when(j == nk - 1)
        def _finalize():
            finalize(slice(None), m_ref[:, 0:1], l_ref[:, 0:1], acc_ref[:])


def _check_window(window, causal):
    if window is not None and (not causal or int(window) < 1):
        raise ValueError(f"a window ({window}) needs causal=True and at "
                         f"least one key")


def _walked(band, n):
    """``(block, extent)`` of a grid's walked axis: ``block(x, y)`` is
    the block that step ``y`` of outer block ``x``'s walk names.  No
    window: ``y`` itself over all ``n`` blocks (the index maps, and the
    code they lower to, are then what they were before windows)."""
    if band is None:
        return (lambda x, y: y), n
    return (lambda x, y: band.first(x) + y), band.n_live


def _band_kw(band):
    """The kernels' ``band`` argument, absent without a window."""
    return {} if band is None else {"band": band}


def _kv_row(b, heads, kv_heads):
    """Flattened k/v batch·head row for flattened q row ``b``: grouped-
    query attention maps each q head to its group's shared kv head
    (identity when kv_heads == heads)."""
    if kv_heads == heads:
        return b
    group = heads // kv_heads
    return (b // heads) * kv_heads + (b % heads) // group


def _resolve_targets(sq, sk, d, dtype, block_q, block_k, phase):
    """Per-phase block TARGETS: explicit args win, then the phase's
    tuned entry (self-attention shapes only — a block_k tuned for
    Sk == Sq must not leak onto cross-attention key lengths), then
    ``DEFAULT_BLOCK`` (fwd 1024 / bwd 512)."""
    default = DEFAULT_BLOCK[phase]
    if (block_q is None or block_k is None) and sk == sq:
        tuned = tuned_blocks(sq, d, dtype, phase=phase)
        if tuned is not None:
            block_q = block_q if block_q is not None else tuned[0]
            block_k = block_k if block_k is not None else tuned[1]
    return block_q or default, block_k or default


def _subtile_target(sq, d, dtype, phase):
    """The sub-tile side asked for at this shape: its tuned row's, or
    ``SUBTILE`` (the VMEM clamp and the kernels read the same one)."""
    return tuned_subtile(sq, d, dtype, phase=phase) or _SUBTILE


def _clamped_blocks(sq, sk, d, dtype, block_q, block_k, phase):
    """(bq, bk) divisor blocks for the targets, jointly clamped so the
    APX304-priced footprint of the resulting pallas_call stays inside
    the VMEM budget: pick bq by preference alone, clamp bk against it,
    then re-clamp bq against the chosen bk (a no-op unless the pair
    was over budget)."""
    target = _subtile_target(sq, d, dtype, phase)

    def fits(b_q, b_k):
        return _flash_vmem_bytes(
            b_q, b_k, d, phase,
            sub=_flash_subtile(b_q, b_k, target)) <= _VMEM_BUDGET

    bq = _pick_block(sq, block_q, align=_sublane(dtype))
    bk = _pick_block(sk, block_k, fits=lambda b: fits(bq, b))
    bq = _pick_block(sq, block_q, align=_sublane(dtype),
                     fits=lambda b: fits(b, bk))
    return bq, bk


def _subtiles(sq, d, dtype, phase, bq, bk):
    """(sub_q, sub_k, run) the kernels walk a ``(bq, bk)`` block in:
    squares of the shape's tuned sub-tile (whatever the key length: it
    only has to divide the blocks) or of ``SUBTILE``, brought down to a
    lane-tile multiple that divides both, and the sub-tiles a run of
    them at most (:func:`run_cap`); a block with none is one tile."""
    sub = _flash_subtile(bq, bk, _subtile_target(sq, d, dtype, phase))
    return (sub, sub, run_cap(sub)) if sub else (bq, bk, 1)


def dispatched(sq, sk, d, dtype, phase, block_q=None, block_k=None):
    """``(bq, bk, (sub_q, sub_k, run))``: the grid blocks, the
    sub-tiles and the run cap a call of this shape and phase is built
    with, given the caller's block targets (both launchers' own
    resolution; the static tests read the cells' shapes through it)."""
    bq, bk = _clamped_blocks(
        sq, sk, d, dtype,
        *_resolve_targets(sq, sk, d, dtype, block_q, block_k, phase), phase)
    return bq, bk, _subtiles(sq, d, dtype, phase, bq, bk)


def flash_fwd_pallas(q, k, v, scale, causal, q_offset, k_offset,
                     block_q=None, block_k=None, interpret=False,
                     out_dtype=None, kv_bias=None, heads=1, kv_heads=None,
                     window=None, block=None):
    """q: (BH, Sq, D); k/v: (B·kv_heads, Sk, D).  Returns
    (out, lse (BH, Sq, 1)).

    ``block``: a static block length ``W`` (a power of two) over a
    causal call without a window: visibility is causal by BLOCK, key
    ``j`` visible to query ``i`` iff ``j // W <= i // W`` (bidirectional
    inside a block; what a block-generating model's prefill attends by).
    Offsets and sub-tiles are multiples of ``W``, so no sub-tile above
    the diagonal becomes live: the walk is the causal one and only the
    crossed sub-tiles' mask changes (``col <= row | (W - 1)``).  The
    forward alone takes it; there is no backward.

    ``window``: a static sliding window over a causal call: key ``j`` is
    visible to query ``i`` iff ``0 <= i - j < window`` (global
    positions).  Key blocks the band does not reach are not in the grid
    (the index maps name live blocks only), sub-tiles under it are not
    visited, and its lower edge is one ``where`` as the diagonal is.

    ``kv_bias``: optional (B, 1, Sk) f32 additive key bias (0 valid /
    NEG_INF padded; the middle singleton keeps the block sublane-legal);
    ``heads`` maps the flattened batch·head grid index back to the batch
    row (b // heads).  ``kv_heads`` < heads = grouped-query attention:
    the kernel reads each q head's group-shared k/v block directly (no
    materialized head repeat in HBM).

    ``block_q``/``block_k`` default to the shape's tuned ``"fwd"`` entry
    (self-attention shapes) else 1024; either way the candidates are
    clamped against the shared VMEM footprint formula.
    ``out_dtype`` defaults to q.dtype; ring attention requests f32 so
    cross-chunk accumulation never rounds through bf16."""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    kv_heads = kv_heads or heads
    out_dtype = out_dtype or q.dtype
    bq, bk, sub = dispatched(Sq, Sk, D, q.dtype, "fwd", block_q, block_k)
    has_bias = kv_bias is not None
    _check_window(window, causal)
    if block is not None:
        block = int(block)
        if not causal or window is not None or block < 1 \
                or block & (block - 1) or q_offset % block \
                or k_offset % block or sub[0] % block or sub[1] % block:
            raise ValueError(
                f"block ({block}) needs causal=True, no window, a power of "
                f"two, and offsets ({q_offset}, {k_offset}) and sub-tiles "
                f"{sub[:2]} that are multiples of it")

    inputs = (q, k, v) if not has_bias else (q, k, v, kv_bias)
    call = _fwd_call(BH, Sq, Sk, D, heads, kv_heads, float(scale), causal,
                     q_offset, k_offset, bq, bk, sub, has_bias, interpret,
                     jnp.dtype(out_dtype).name, window, block)
    # jax.disable_jit(False): pallas_call cannot bind eagerly (its bind
    # params carry a dict), so the kernel stays one jitted op even when a
    # caller runs the surrounding program op-by-op under disable_jit().
    with jax.disable_jit(False):
        out, lse = call(*inputs)
    return out, lse


@functools.lru_cache(maxsize=512)
def _fwd_call(BH, Sq, Sk, D, heads, kv_heads, scale, causal,
              q_offset, k_offset, bq, bk, sub, has_bias, interpret,
              out_dtype_name, window=None, block=None):
    """The fwd ``pallas_call``, memoized on its static configuration —
    every argument is static by construction (they bake into the kernel
    closure), so eager callers (a ring chunk per hop, interpret-mode
    tests) reuse one traced kernel instead of rebuilding fresh index-map
    closures — and with them the whole compile — per invocation."""
    nq, nk = Sq // bq, Sk // bk
    band = _band("fwd", window, q_offset, k_offset, bq, bk, nq, nk)
    key_block, nk = _walked(band, nk)

    kv_spec = pl.BlockSpec(
        (1, bk, D),
        lambda b, i, j: (_kv_row(b, heads, kv_heads), key_block(i, j), 0),
        memory_space=pltpu.VMEM,
    )
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM),
        kv_spec,
        kv_spec,
    ]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, bk),
                         lambda b, i, j: (b // heads, 0, key_block(i, j)),
                         memory_space=pltpu.VMEM)
        )

    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, has_bias=has_bias,
            q_offset=q_offset, k_offset=k_offset, block_q=bq, block_k=bk,
            sub_q=sub[0], sub_k=sub[1], run=sub[2], nq=nq, nk=nk,
            **_band_kw(band), **({"causal_block": block} if block else {}),
        ),
        grid=(BH, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), jnp.dtype(out_dtype_name)),
            jax.ShapeDtypeStruct((BH, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=_DIM_SEMANTICS,
        interpret=interpret,
        name="apex_flash_fwd",
    )


# ----------------------------------------------------------------- backward
def _dq_kernel(*refs, scale, causal, has_bias, q_offset, k_offset,
               block_q, block_k, sub_q, sub_k, run, nq, nk, band=None):
    if has_bias:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, b_ref, dq_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref = refs
        b_ref = None
    i, j = _grid_index(1, nq), _grid_index(2, nk)
    window = band.window if band else None

    if nk > 1:
        @pl.when(j == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

    def strip(r, gap, bounds):
        rows = _tile(r, sub_q)
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        lse, delta = lse_ref[0, rows, :], delta_ref[0, rows, :]
        if has_bias:
            lse = _dead_rows_off(lse)

        def tile(c, until, masked, acc):
            cols = _tile(c, sub_k, until)
            k, v = k_ref[0, cols, :], v_ref[0, cols, :]
            s = _dot(q, k, _NT) * scale
            if b_ref is not None:
                s = s + b_ref[0, :, cols]
            p = jnp.exp(s - lse)
            if masked:
                p = jnp.where(_visible(sub_q, sub_k, c * sub_k - gap,
                                       edges=masked, window=window), p, 0.0)
            # ring passes an f32 cotangent with bf16 k/v: widen the
            # narrower operand instead of rounding do through bf16
            if v.dtype != do.dtype:
                v = v.astype(do.dtype)
            ds = p * (_dot(do, v, _NT) - delta)
            dq = _dot(ds.astype(k.dtype), k, _NN)
            return dq if acc is None else acc + dq

        acc = None if nk == 1 else acc_ref[rows, :]
        # keys ascending: the plain runs, then the diagonal's sub-tiles
        for piece in _pieces("dq", bounds, run):
            acc = tile(*piece, acc)
        if acc is None:  # a strip no key of the call reaches
            acc = jnp.zeros((sub_q, acc_ref.shape[1]), jnp.float32)
        if nk == 1:
            dq_ref[0, rows, :] = (scale * acc).astype(dq_ref.dtype)
        else:
            acc_ref[rows, :] = acc

    def block(plan, d):
        for r, bounds in enumerate(plan):
            if bounds[3] > bounds[0] or nk == 1:
                strip(r, d + r * sub_q, bounds)

    _for_this_block("dq", causal, q_offset, k_offset, block_q, block_k,
                    nq, nk, sub_q, sub_k, i,
                    j if band is None else band.first(i) + j,
                    nk == 1, block, band)

    if nk > 1:
        @pl.when(j == nk - 1)
        def _finalize():
            dq_ref[0] = (scale * acc_ref[:]).astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, causal, has_bias, q_offset, k_offset,
                block_q, block_k, sub_q, sub_k, run, nq, nk, nt, band=None):
    """k-block outer; the inner dimension ``t`` walks ALL nt = g·nq
    q-blocks that attend to this kv head — for grouped-query attention
    the g q-heads of the group accumulate into the same dk/dv block
    (i = t % nq is the q-block index within the current q head; ``nq``
    is the q-blocks a key block walks: under a window the band's
    ``n_live``, from ``band.first(j)`` on).  Inside
    a block a strip of keys walks its query sub-tiles, rows ascending:
    those the diagonal crosses, then those wholly under it, in runs.

    Every tile is held TRANSPOSED, keys down the sublanes and queries
    along the lanes: both products into dk and dv then contract over a
    tile's lanes (no transpose of p or ds), and lse and delta come as
    rows ``(BH, 1, Sq)``, the key bias as a column ``(B, Sk, 1)``."""
    if has_bias:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, b_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        b_ref = None
    j, t = _grid_index(1, nk), _grid_index(2, nt)
    i = 0 if nq == 1 else t % nq
    if band is not None:
        i = band.first(j) + i
    window = band.window if band else None

    if nt > 1:
        @pl.when(t == 0)
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

    def strip(c, lead, bounds):
        """Key strip ``c``, its first key ``lead`` past the block's
        first query row, over the query sub-tiles its ``bounds`` name
        (:func:`_query_bounds`): crossed ones masked, plain ones in
        runs."""
        cols = _tile(c, sub_k)
        k, v = k_ref[0, cols, :], v_ref[0, cols, :]
        bias = None if b_ref is None else b_ref[0, cols, :]  # (sub_k, 1)

        def tile(r, until, masked, carry):
            rows = _tile(r, sub_q, until)
            q, do = q_ref[0, rows, :], do_ref[0, rows, :]
            lse = lse_ref[0, :, rows]  # (1, sub_q)
            if has_bias:
                lse = _dead_rows_off(lse)
            s = _dot(k, q, _NT) * scale
            if bias is not None:
                s = s + bias
            p = jnp.exp(s - lse)
            if masked:
                p = jnp.where(_visible(sub_k, sub_q, lead - r * sub_q,
                                       transposed=True, edges=masked,
                                       window=window), p, 0.0)
            dv = _dot(p.astype(do.dtype), do, _NN)
            # widen v rather than rounding an f32 cotangent down (ring path)
            vw = v if v.dtype == do.dtype else v.astype(do.dtype)
            ds = p * (_dot(vw, do, _NT) - delta_ref[0, :, rows])
            dk = _dot(ds.astype(q.dtype), q, _NN)
            if carry is None:
                return dk, dv
            return carry[0] + dk, carry[1] + dv

        carry = None if nt == 1 else (dk_acc[cols, :], dv_acc[cols, :])
        # rows ascending: the diagonal's sub-tiles, then the plain runs
        for piece in _pieces("dkv", bounds, run):
            carry = tile(*piece, carry)
        zero = jnp.zeros((sub_k, dk_acc.shape[1]), jnp.float32)
        dk, dv = carry or (zero, zero)  # a strip no query of the call reaches
        if nt == 1:
            dk_ref[0, cols, :] = (scale * dk).astype(dk_ref.dtype)
            dv_ref[0, cols, :] = dv.astype(dv_ref.dtype)
        else:
            dk_acc[cols, :], dv_acc[cols, :] = dk, dv

    def block(plan, d):
        for c, bounds in enumerate(plan):
            if bounds[3] > bounds[0] or nt == 1:
                strip(c, c * sub_k - d, bounds)

    _for_this_block("dkv", causal, q_offset, k_offset, block_q, block_k,
                    nq, nk, sub_q, sub_k, i, j, nt == 1, block, band)

    if nt > 1:
        @pl.when(t == nt - 1)
        def _finalize():
            dk_ref[0] = (scale * dk_acc[:]).astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def flash_bwd_pallas(q, k, v, out, lse, do, scale, causal, q_offset, k_offset,
                     block_q=None, block_k=None, interpret=False, delta=None,
                     out_dtype=None, kv_bias=None, heads=1, kv_heads=None,
                     window=None):
    """q/out/do (BH, Sq, D); k/v (B·kv_heads, Sk, D); lse (BH, Sq, 1).
    Returns (dq, dk, dv) with dk/dv shaped like k/v.

    ``block_q``/``block_k`` default to the shape's tuned ``"bwd"`` entry
    (self-attention shapes) else 512 (unmeasured shapes keep the grid
    they had) — the backward consults its OWN per-phase table, never a
    forward measurement — and candidates are clamped against the bwd
    VMEM footprint formula.
    ``delta`` (rowsum of do·out over the FULL row) may be passed in when
    ``out`` covers more keys than this call sees — ring attention's
    backward, where each chunk-pair call sees only the local k/v chunk.
    ``out_dtype`` defaults to the input dtypes; ring passes f32.
    ``kv_bias``/``heads``/``kv_heads`` as in :func:`flash_fwd_pallas`;
    with grouped-query attention the dk/dv grid walks every q head of
    the group before finalizing, so the group sum happens in VMEM.
    ``window`` as in :func:`flash_fwd_pallas`: dq walks a query block's
    live key blocks, dkv a key block's live query blocks.
    """
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    kv_heads = kv_heads or heads
    BKV = k.shape[0]
    dq_dtype = out_dtype or q.dtype
    dk_dtype = out_dtype or k.dtype
    dv_dtype = out_dtype or v.dtype
    bq, bk, sub = dispatched(Sq, Sk, D, q.dtype, "bwd", block_q, block_k)
    has_bias = kv_bias is not None

    if delta is None:
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1, keepdims=True)

    inputs = (q, k, v, do, lse, delta)
    if has_bias:
        inputs = inputs + (kv_bias,)
    _check_window(window, causal)
    static = (BH, BKV, Sq, Sk, D, heads, kv_heads, float(scale), causal,
              q_offset, k_offset, bq, bk, sub, has_bias, interpret)
    dq_call = _dq_pallas_call(*static, jnp.dtype(dq_dtype).name, window)
    dkv_call = _dkv_pallas_call(*static, jnp.dtype(dk_dtype).name,
                                jnp.dtype(dv_dtype).name, window)
    # the dkv kernel reads the per-row statistics as rows and the key
    # bias as a column (its tiles are keys by queries): the same values
    rows = (lse.reshape(BH, 1, Sq), delta.reshape(BH, 1, Sq))
    bias_t = (kv_bias.reshape(-1, Sk, 1),) if has_bias else ()
    # jax.disable_jit(False): see flash_fwd_pallas — pallas_call cannot
    # bind eagerly, so both backward kernels stay jitted ops.
    with jax.disable_jit(False):
        dq = dq_call(*inputs)
        dk, dv = dkv_call(q, k, v, do, *rows, *bias_t)
    return dq, dk, dv


@functools.lru_cache(maxsize=512)
def _dq_pallas_call(BH, BKV, Sq, Sk, D, heads, kv_heads, scale, causal,
                    q_offset, k_offset, bq, bk, sub, has_bias, interpret,
                    dq_dtype_name, window=None):
    """The dq ``pallas_call``, memoized like :func:`_fwd_call`."""
    nq, nk = Sq // bq, Sk // bk
    band = _band("dq", window, q_offset, k_offset, bq, bk, nq, nk)
    key_block, nk = _walked(band, nk)
    q_spec = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec(
        (1, bk, D),
        lambda b, i, j: (_kv_row(b, heads, kv_heads), key_block(i, j), 0),
        memory_space=pltpu.VMEM,
    )
    r_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM)

    in_specs = [q_spec, k_spec, k_spec, q_spec, r_spec, r_spec]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, bk),
                         lambda b, i, j: (b // heads, 0, key_block(i, j)),
                         memory_space=pltpu.VMEM)
        )

    return pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, has_bias=has_bias,
            q_offset=q_offset, k_offset=k_offset, block_q=bq, block_k=bk,
            sub_q=sub[0], sub_k=sub[1], run=sub[2], nq=nq, nk=nk,
            **_band_kw(band),
        ),
        grid=(BH, nq, nk),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), jnp.dtype(dq_dtype_name)),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_DIM_SEMANTICS,
        interpret=interpret,
        name="apex_flash_dq",
    )


@functools.lru_cache(maxsize=512)
def _dkv_pallas_call(BH, BKV, Sq, Sk, D, heads, kv_heads, scale, causal,
                     q_offset, k_offset, bq, bk, sub, has_bias, interpret,
                     dk_dtype_name, dv_dtype_name, window=None):
    """The dk/dv ``pallas_call``, memoized like :func:`_fwd_call`."""
    nq, nk = Sq // bq, Sk // bk
    band = _band("dkv", window, q_offset, k_offset, bq, bk, nq, nk)
    query_block, nq = _walked(band, nq)
    group = heads // kv_heads

    # k-outer grid over the KV rows: index maps see (b, j, t) with
    # t ∈ [0, group·nq) walking q-blocks of every q head in the group
    # (qh = t // nq, qi = t % nq); the q row is the group member's.
    def _q_row(b, t):
        if group == 1:
            return b
        return (b // kv_heads) * heads + (b % kv_heads) * group + t // nq

    qT_spec = pl.BlockSpec(
        (1, bq, D), lambda b, j, t: (_q_row(b, t), query_block(j, t % nq), 0),
        memory_space=pltpu.VMEM,
    )
    kT_spec = pl.BlockSpec((1, bk, D), lambda b, j, t: (b, j, 0), memory_space=pltpu.VMEM)
    # lse and delta as ROWS (BH, 1, Sq): the kernel holds its tiles
    # keys by queries
    rT_spec = pl.BlockSpec(
        (1, 1, bq), lambda b, j, t: (_q_row(b, t), 0, query_block(j, t % nq)),
        memory_space=pltpu.VMEM,
    )

    in_specsT = [qT_spec, kT_spec, kT_spec, qT_spec, rT_spec, rT_spec]
    if has_bias:
        in_specsT.append(
            # the key bias as a COLUMN (B, Sk, 1)
            pl.BlockSpec((1, bk, 1), lambda b, j, t: (b // kv_heads, j, 0),
                         memory_space=pltpu.VMEM)
        )

    return pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, has_bias=has_bias,
            q_offset=q_offset, k_offset=k_offset, block_q=bq, block_k=bk,
            sub_q=sub[0], sub_k=sub[1], run=sub[2], nq=nq, nk=nk,
            nt=group * nq, **_band_kw(band),
        ),
        grid=(BKV, nk, group * nq),
        in_specs=in_specsT,
        out_specs=[kT_spec, kT_spec],
        out_shape=[
            jax.ShapeDtypeStruct((BKV, Sk, D), jnp.dtype(dk_dtype_name)),
            jax.ShapeDtypeStruct((BKV, Sk, D), jnp.dtype(dv_dtype_name)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=_DIM_SEMANTICS,
        interpret=interpret,
        name="apex_flash_dkv",
    )


# ---------------------------------------------------------------- dispatch
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13))
def _flash_pallas(q, k, v, kv_bias, scale, causal, q_offset, k_offset,
                  block_q, block_k, interpret, heads, kv_heads, window):
    out, _ = flash_fwd_pallas(q, k, v, scale, causal, q_offset, k_offset,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret, kv_bias=kv_bias, heads=heads,
                              kv_heads=kv_heads, window=window)
    return out


def _flash_pallas_fwd(q, k, v, kv_bias, scale, causal, q_offset, k_offset,
                      block_q, block_k, interpret, heads, kv_heads, window):
    out, lse = flash_fwd_pallas(q, k, v, scale, causal, q_offset, k_offset,
                                block_q=block_q, block_k=block_k,
                                interpret=interpret, kv_bias=kv_bias, heads=heads,
                                kv_heads=kv_heads, window=window)
    return out, (q, k, v, kv_bias, out, lse)


def _flash_pallas_bwd(scale, causal, q_offset, k_offset, block_q, block_k,
                      interpret, heads, kv_heads, window, res, g):
    q, k, v, kv_bias, out, lse = res
    # the nondiff blocks are the CALLER's (None = untuned): an explicit
    # block keeps the documented 512 cap (the backward holds more blocks
    # a grid step than the forward it was chosen for); None defers to
    # flash_bwd_pallas's own per-phase tuned entry — a forward
    # measurement never leaks onto the backward's different envelope
    dq, dk, dv = flash_bwd_pallas(q, k, v, out, lse, g, scale, causal,
                                  q_offset, k_offset,
                                  block_q=None if block_q is None else min(block_q, 512),
                                  block_k=None if block_k is None else min(block_k, 512),
                                  interpret=interpret, kv_bias=kv_bias,
                                  heads=heads, kv_heads=kv_heads,
                                  window=window)
    # the mask bias is data, not a trainable input: zero cotangent
    return (dq, dk, dv, None if kv_bias is None else jnp.zeros_like(kv_bias))


_flash_pallas.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


def flash_attention_pallas(q, k, v, causal=True, softmax_scale=None,
                           q_offset=0, k_offset=0, block_q=None, block_k=None,
                           interpret=False, kv_mask=None, window=None):
    """(B, H, S, D) flash attention via the Pallas kernels.

    ``window``: a static sliding window (needs ``causal``): key ``j`` is
    visible to query ``i`` iff ``0 <= i - j < window``; forward, dq and
    dkv all walk the band's blocks and sub-tiles only.

    ``kv_mask``: optional (B, Sk) bool key-validity mask (True = valid) —
    the fmha varlen/padding semantics (``apex/contrib/fmha/fmha.py:33-60``)
    expressed as a dense mask folded into the kernel.

    Grouped-query attention: k/v may carry fewer heads than q
    ((B, H_kv, Sk, D) with H % H_kv == 0) — the kernels index each q
    head's group-shared k/v block directly, so GQA costs no HBM head
    repeat and dk/dv group sums happen in VMEM scratch."""
    B, H, Sq, D = q.shape
    Hkv = k.shape[1]
    if H % Hkv != 0:
        raise ValueError(f"q heads ({H}) not divisible by kv heads ({Hkv})")
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(D)
    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * Hkv, k.shape[2], D)
    vf = v.reshape(B * Hkv, v.shape[2], D)
    if kv_mask is None:
        bias = None
    else:
        from apex_tpu.ops.attention import padding_bias

        bias = padding_bias(kv_mask)[:, None, :]
    # the RAW (possibly-None) blocks thread through the custom_vjp's
    # nondiff args: each phase resolves its own tuned entry at its own
    # entry point, so a forward-tuned (bq, bk) never leaks onto the
    # backward kernels' different VMEM envelope
    out = _flash_pallas(qf, kf, vf, bias, scale, causal, q_offset, k_offset,
                        block_q, block_k, interpret, H, Hkv,
                        None if window is None else int(window))
    return out.reshape(B, H, Sq, D)


def pallas_flash_available(q, k) -> bool:
    """Kernel path: real TPU, lane-aligned sequence blocks, ≥8 head dim."""
    from apex_tpu.utils.platform import on_tpu

    return (
        on_tpu()
        and q.shape[2] % 128 == 0
        and k.shape[2] % 128 == 0
        and q.shape[3] % 8 == 0
        and q.dtype in (jnp.float32, jnp.bfloat16)
    )
