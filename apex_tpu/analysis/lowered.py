"""Lowered-artifact invariant checkers — the analyzer's SECOND tier.

The AST tier (the rest of this package) proves properties of source
code; this module proves properties of what the compiler was actually
ASKED to do, by pattern-matching ``jax.jit(...).lower(...)`` artifacts.
The two tiers are complementary: no AST rule can see that a bucketed
optimizer's grad sync lowered to one reduce-scatter per bucket, and no
HLO grep survives a refactor that renames the function it was pinned
to — these checkers live in tests, next to the step builders they pin.

**This module imports jax** and is deliberately NOT imported by
``apex_tpu.analysis.__init__`` or the CLI: the no-jax contract of the
AST tier (runs in broken containers, over trees that do not import)
stays intact.  Import it explicitly — ``from apex_tpu.analysis import
lowered`` — from test code.

Checkers accept a ``jax.stages.Lowered``, anything with ``as_text()``,
or a plain StableHLO/MHLO text dump.  They assert on the LOWERING, not
the compiled module, wherever possible: the CPU backend's compile
rewrites TPU-irrelevant details (e.g. upcasting bf16 collectives), so
the lowering is what faithfully records the program's intent.  The one
exception is :func:`assert_donation_covers` with ``compiled=True``,
which reads the compiled module's ``input_output_alias`` header — the
aliasing table only materializes at compile time.

Born from PR 4's inline string-grep asserts in
``tests/test_distributed_optimizers.py`` (per-bucket reduce-scatters,
no whole-tree concat, donation aliasing), refactored here so
``tests/test_lowered_invariants.py`` can pin the same invariants on
the real GPT train steps.
"""

from __future__ import annotations

import math
import re
from typing import Dict, List, Optional, Sequence

import jax

__all__ = [
    "hlo_text", "count_collectives", "operand_dtypes",
    "collective_sites", "collective_schedule", "mesh_axis_groups",
    "assert_collective_axes", "assert_collective_dtype",
    "assert_no_host_transfer", "assert_no_recompile",
    "assert_no_whole_tree_concat", "assert_same_collective_schedule",
    "assert_interleaved", "interleave_gaps",
    "assert_donation_covers", "donated_buffer_count",
    "host_transfer_sites",
    "arg_shardings", "sharding_of", "assert_sharding",
    "spmd_collective_sites", "assert_spmd_collectives",
    "pallas_kernels", "large_result_instructions", "entry_instructions",
    "entry_users",
]

#: collective ops that carry a reduction REGION in StableHLO — their
#: type signature follows the closing ``})`` of the region, so the
#: dtype regex must skip it (re.S); region-less ops type right after
#: their attribute dict on the same line.
_REGION_OPS = {"reduce_scatter", "all_reduce", "reduce"}


def hlo_text(artifact) -> str:
    """The StableHLO/MHLO text of a lowering artifact: a str passes
    through, anything with ``as_text()`` (``Lowered``, ``Compiled``)
    is rendered."""
    if isinstance(artifact, str):
        return artifact
    if hasattr(artifact, "as_text"):
        return artifact.as_text()
    raise TypeError(
        f"expected StableHLO text or an object with as_text() "
        f"(jax.stages.Lowered / Compiled), got {type(artifact).__name__}")


def _op_occurrences(txt: str, kind: str) -> List[str]:
    # MLIR prints ops in generic quoted form ("stablehlo.reduce_scatter")
    # inside shard_map bodies and pretty unquoted form (stablehlo.
    # concatenate) elsewhere — match the dotted name either way
    return re.findall(
        r'(?:stablehlo|mhlo)\.' + re.escape(kind) + r'\b', txt)


#: how far past an op's name to look for its attribute dict — the
#: replica_groups attr precedes the (possibly multi-line) reduction
#: region, so a bounded window is enough and never bleeds into the
#: NEXT collective's attrs (ops are > 40 chars of SSA plumbing apart).
_ATTR_WINDOW = 4000


def _parse_replica_groups(window: str) -> Optional[List[List[int]]]:
    m = re.search(r'replica_groups\s*=\s*dense<([^>]*)>', window)
    if m is None:
        return None
    body = m.group(1).strip()
    try:
        if body.startswith("["):
            import ast

            val = ast.literal_eval(body)
            if isinstance(val, list) and val and not isinstance(val[0], list):
                val = [val]
            return [[int(x) for x in grp] for grp in val]
        # splat form dense<0> : tensor<1x1xi64> — a single singleton
        return [[int(body)]]
    except (ValueError, SyntaxError):
        return None


def collective_sites(artifact, kind: str) -> List[dict]:
    """Every ``kind`` collective in program order, as
    ``{"dtype": str|None, "replica_groups": [[int, ...], ...]|None}``
    — the per-site view :func:`count_collectives`'s ``axes=`` filter
    and :func:`assert_collective_axes` are built on.  ``dtype`` is the
    first operand's element type (as in :func:`operand_dtypes`);
    ``replica_groups`` indexes the mesh's logical device order (what
    shard_map lowers), None when the op carries no parseable groups."""
    txt = hlo_text(artifact)
    if kind in _REGION_OPS:
        dt_pat = re.compile(r'\}\)\s*:\s*\(tensor<[0-9x]*x?(\w+)>', re.S)
    else:
        dt_pat = re.compile(r':\s*\(tensor<[0-9x]*x?(\w+)>')
    sites = []
    for m in re.finditer(
            r'"?(?:stablehlo|mhlo)\.' + re.escape(kind) + r'\b', txt):
        window = txt[m.start():m.start() + _ATTR_WINDOW]
        dt = dt_pat.search(window)
        sites.append({
            "dtype": dt.group(1) if dt else None,
            "replica_groups": _parse_replica_groups(window),
        })
    return sites


def mesh_axis_groups(mesh, axes) -> List[List[int]]:
    """The ``replica_groups`` a collective over ``axes`` of ``mesh``
    lowers with: the partition of the mesh's logical device indices
    (row-major over ``mesh.axis_names``) that varies exactly the named
    axes and holds every other axis fixed — e.g. on
    ``Mesh((2, 2), ("dp_out", "dp_in"))``, ``("dp_in",)`` gives
    ``[[0, 1], [2, 3]]`` and ``("dp_out",)`` gives ``[[0, 2], [1, 3]]``."""
    import numpy as np

    names = list(mesh.axis_names)
    axes = [axes] if isinstance(axes, str) else list(axes)
    unknown = [a for a in axes if a not in names]
    if unknown:
        raise ValueError(f"axes {unknown} not on mesh {tuple(names)}")
    shape = [mesh.shape[n] for n in names]
    ids = np.arange(int(np.prod(shape))).reshape(shape)
    other = [i for i, n in enumerate(names) if n not in axes]
    coll = [names.index(a) for a in axes]
    group_size = int(np.prod([shape[i] for i in coll])) if coll else 1
    return ids.transpose(other + coll).reshape(-1, group_size).tolist()


def _groups_key(groups) -> Optional[frozenset]:
    """Order-insensitive identity of a replica-group partition (the
    lowering may emit groups, and ids within groups, in any order)."""
    if groups is None:
        return None
    return frozenset(frozenset(g) for g in groups)


#: the cross-device ops a schedule tracks, in one program-order scan.
#: ``reduce`` (local) is deliberately absent; ``collective_permute``
#: and ``collective_broadcast`` carry no replica_groups — their groups
#: entry is None and the kind/dtype/shape still pin the sequence.
_SCHEDULE_KINDS = (
    "all_gather", "all_reduce", "all_to_all", "collective_broadcast",
    "collective_permute", "reduce_scatter",
)


def collective_schedule(artifact, mesh=None) -> List[dict]:
    """The ordered cross-device communication sequence of a lowering:
    one entry per collective op in program order, each
    ``{"kind", "dtype", "shape", "groups"}`` — ``shape`` is the first
    operand's dims tuple (None when unparseable), ``groups`` the
    order-insensitive :func:`_groups_key` of its replica groups.

    Two processes that lower DIFFERENT schedules for the same step
    deadlock the pod: each rank blocks in its own next collective,
    device-side, with no error.  This is the thing
    ``assert_same_collective_schedule`` pins and the APX209/210/211
    divergence rules prove statically.

    With ``mesh=`` given, each entry also carries ``"axes"`` — the
    mesh-axis subset whose :func:`mesh_axis_groups` partition equals
    the op's groups (None when no subset matches, e.g. GSPMD-chosen
    groupings that cross axis boundaries)."""
    txt = hlo_text(artifact)
    axis_of = None
    if mesh is not None:
        import itertools

        names = list(mesh.axis_names)
        axis_of = {}
        for r in range(1, len(names) + 1):
            for combo in itertools.combinations(names, r):
                key = _groups_key(mesh_axis_groups(mesh, combo))
                axis_of.setdefault(key, combo)
    occurrences = []
    for kind in _SCHEDULE_KINDS:
        # StableHLO/MHLO dotted spelling (jit/shard_map lowerings)
        for m in re.finditer(
                r'"?(?:stablehlo|mhlo)\.' + re.escape(kind) + r'\b', txt):
            occurrences.append((m.start(), kind, "mlir", m))
        # compiled-HLO dashed spelling (post-SPMD-partitioning modules;
        # only the plain/-start op, never the async -done — same rule
        # as spmd_collective_sites)
        dashed = kind.replace("_", "-")
        for m in re.finditer(
                r'=\s*\(?([a-zA-Z0-9]+)\[([0-9,]*)\][^=\n]*?\s'
                + re.escape(dashed) + r'(?:-start)?\(', txt):
            occurrences.append((m.start(), kind, "hlo", m))
    occurrences.sort(key=lambda o: o[0])
    schedule = []
    for pos, kind, form, m in occurrences:
        dtype = shape = None
        if form == "mlir":
            window = txt[pos:pos + _ATTR_WINDOW]
            if kind in _REGION_OPS:
                tm = re.search(r'\}\)\s*:\s*\(tensor<([0-9a-zA-Z_x]*)>',
                               window, re.S)
            else:
                tm = re.search(r':\s*\(tensor<([0-9a-zA-Z_x]*)>', window)
            if tm is not None:
                parts = tm.group(1).split("x")
                dtype = parts[-1] or None
                try:
                    shape = tuple(int(d) for d in parts[:-1])
                except ValueError:
                    shape = None
            groups = _parse_replica_groups(window)
        else:
            dtype = m.group(1)
            try:
                shape = tuple(int(d) for d in m.group(2).split(",")
                              if d.strip())
            except ValueError:
                shape = None
            line_end = txt.find("\n", m.end())
            window = txt[m.end():
                         line_end if line_end != -1 else len(txt)]
            gm = re.search(
                r'replica_groups=(\{\{[^}]*(?:\},\{[^}]*)*\}\}|'
                r'\[[^\]]+\]<=\[[^\]]+\](?:T\([\d,]+\))?)', window)
            groups = _parse_hlo_groups(gm.group(1)) if gm else None
        entry = {
            "kind": kind,
            "dtype": dtype,
            "shape": shape,
            "groups": _groups_key(groups),
        }
        if axis_of is not None:
            entry["axes"] = axis_of.get(entry["groups"])
        schedule.append(entry)
    return schedule


def _schedule_entry_str(entry: dict) -> str:
    groups = entry["groups"]
    g = "-" if groups is None else \
        "|".join(",".join(str(i) for i in sorted(grp))
                 for grp in sorted(groups, key=min))
    axes = entry.get("axes")
    over = f" over {axes}" if axes else ""
    return (f"{entry['kind']}<{'x'.join(map(str, entry['shape'] or ()))}"
            f"x{entry['dtype']}> groups=[{g}]{over}")


def assert_same_collective_schedule(*artifacts, labels=None,
                                    mesh=None) -> List[List[dict]]:
    """Assert every lowering emits the IDENTICAL ordered collective
    sequence (kind, dtype, shape, replica groups, position by
    position).  This is the single-process proof of multi-process
    safety: rank-specialized variants of one step that lower different
    schedules WILL wedge a real pod, and this assertion names the
    first diverging op instead.  Returns the schedules (first is the
    reference)."""
    if len(artifacts) < 2:
        raise ValueError("need at least two lowerings to compare")
    if labels is None:
        labels = [f"variant[{i}]" for i in range(len(artifacts))]
    labels = list(labels)
    if len(labels) != len(artifacts):
        raise ValueError(f"{len(labels)} labels for "
                         f"{len(artifacts)} lowerings")
    schedules = [collective_schedule(a, mesh=mesh) for a in artifacts]
    ref, ref_label = schedules[0], labels[0]
    for label, sched in zip(labels[1:], schedules[1:]):
        for i, (a, b) in enumerate(zip(ref, sched)):
            assert a == b, (
                f"collective schedules diverge at op {i}: "
                f"{ref_label} lowers {_schedule_entry_str(a)}, "
                f"{label} lowers {_schedule_entry_str(b)} — on a pod "
                f"these ranks block in different collectives and the "
                f"step wedges device-side with no error")
        assert len(ref) == len(sched), (
            f"collective schedules diverge in length: {ref_label} "
            f"lowers {len(ref)} collective(s), {label} lowers "
            f"{len(sched)} — the longer program blocks in a "
            f"collective its peers never enter")
    return schedules


def count_collectives(artifact, kind: str, *,
                      minimum: Optional[int] = None,
                      maximum: Optional[int] = None,
                      axes=None, mesh=None) -> int:
    """Occurrences of one collective (``reduce_scatter``,
    ``all_gather``, ``all_reduce``, ``all_to_all``,
    ``collective_permute``, ...) in the lowering.  With ``minimum``/
    ``maximum`` given, asserts the count is inside the bounds — the
    per-bucket contract reads ``count_collectives(txt,
    "reduce_scatter", minimum=n_buckets, maximum=n_buckets)``.

    ``axes=`` (with ``mesh=``) counts only the occurrences whose
    ``replica_groups`` equal the partition a collective over exactly
    those mesh axes lowers with — the per-hop contract of the
    hierarchical sync plan reads ``count_collectives(txt,
    "reduce_scatter", axes=("dp_in",), mesh=mesh, minimum=n,
    maximum=n)``."""
    if axes is not None:
        if mesh is None:
            raise ValueError("axes= filtering needs mesh= (the groups "
                             "are computed from the mesh layout)")
        want = _groups_key(mesh_axis_groups(mesh, axes))
        sites = collective_sites(artifact, kind)
        n = sum(1 for s in sites
                if _groups_key(s["replica_groups"]) == want)
        label = f"{kind} over axes {tuple(axes) if not isinstance(axes, str) else (axes,)}"
    else:
        txt = hlo_text(artifact)
        n = len(_op_occurrences(txt, kind))
        label = kind
    if minimum is not None:
        assert n >= minimum, (
            f"expected >= {minimum} {label} collective(s) in the "
            f"lowering, found {n} — the per-bucket plan did not lower "
            f"to per-bucket collectives")
    if maximum is not None:
        assert n <= maximum, (
            f"expected <= {maximum} {label} collective(s) in the "
            f"lowering, found {n} — something introduced extra "
            f"collectives (a whole-tree sync path?)")
    return n


def assert_collective_axes(artifact, kind: str, axes, mesh, *,
                           minimum: Optional[int] = None,
                           maximum: Optional[int] = None,
                           dtype: Optional[str] = None) -> int:
    """The per-hop pin: count ``kind`` collectives running over exactly
    ``axes`` of ``mesh`` (bounds as in :func:`count_collectives`), and
    — with ``dtype`` — assert EVERY one of those carries that operand
    element type (the hop's wire dtype).  Returns the matched count."""
    n = count_collectives(artifact, kind, axes=axes, mesh=mesh,
                          minimum=minimum, maximum=maximum)
    if dtype is not None:
        want = _groups_key(mesh_axis_groups(mesh, axes))
        bad = [s["dtype"] for s in collective_sites(artifact, kind)
               if _groups_key(s["replica_groups"]) == want
               and s["dtype"] != dtype]
        assert not bad, (
            f"{kind} over axes {axes} must run in {dtype}, found "
            f"{bad} — a hop is not on its wire dtype")
    return n


#: the matmul spellings between which interleaving is measured: the
#: StableHLO/MHLO dotted op and the compiled-HLO ``dot(`` instruction.
_DOT_PATTERNS = (
    r'"?(?:stablehlo|mhlo)\.dot_general\b',
    r'=\s*\(?[a-zA-Z0-9]+\[[0-9,]*\][^=\n]*?\sdot\(',
)


def _dot_events(txt: str) -> List[tuple]:
    """``(position, weight)`` events for every matmul REACHABLE at a
    program point, in text order.  Inline ``dot_general`` ops weigh 1
    at their own position; a ``call @fn`` site weighs the TRANSITIVE
    dot count of its callee at the call's position — jax outlines
    ``lax.scan`` bodies (and remat blocks) into private functions, so
    the backward scan's matmuls are textually out-of-line and only
    reachable through the ``stablehlo.while`` region's call sites."""
    raw = sorted(p for pat in _DOT_PATTERNS
                 for p in (m.start() for m in re.finditer(pat, txt)))
    starts = [(m.start(), m.group(1)) for m in re.finditer(
        r'func\.func[^\n]*?@([\w.$-]+)\(', txt)]
    spans = {}
    for i, (pos, name) in enumerate(starts):
        end = starts[i + 1][0] if i + 1 < len(starts) else len(txt)
        spans[name] = (pos, end)
    calls = [(m.start(), m.group(1)) for m in re.finditer(
        r'\bcall\s+@([\w.$-]+)\(', txt)]
    memo = {}

    def total(fn, trail):
        if fn in memo:
            return memo[fn]
        if fn not in spans or fn in trail:
            return 0
        lo, hi = spans[fn]
        n = sum(1 for p in raw if lo <= p < hi)
        n += sum(total(callee, trail | {fn}) for cp, callee in calls
                 if lo <= cp < hi)
        memo[fn] = n
        return n

    events = [(p, 1) for p in raw]
    events += [(cp, total(callee, frozenset()))
               for cp, callee in calls if total(callee, frozenset())]
    events.sort()
    return events


def interleave_gaps(artifact, kind: str = "reduce_scatter", *,
                    axes=None, mesh=None,
                    dtype: Optional[str] = None) -> List[int]:
    """How many ``dot_general`` ops sit STRICTLY BETWEEN each pair of
    consecutive ``kind`` collectives, in program order: a list of
    ``n_sites - 1`` counts.  ``axes=`` (with ``mesh=``) and ``dtype=``
    narrow the collectives to one hop / one wire dtype, exactly as in
    :func:`count_collectives` / :func:`assert_collective_axes` — the
    dots counted between them are ALL dots, unfiltered, because any
    matmul between two syncs is compute the scheduler can overlap.

    This is the lowering-level evidence for backward-overlapped grad
    sync: an unoverlapped step traces every bucket's collective after
    the whole backward (all gaps 0), an overlapped one issues bucket
    k's sync before bucket k+1's backward dots (some gap > 0)."""
    txt = hlo_text(artifact)
    want = None
    if axes is not None:
        if mesh is None:
            raise ValueError("axes= filtering needs mesh= (the groups "
                             "are computed from the mesh layout)")
        want = _groups_key(mesh_axis_groups(mesh, axes))
    sites = []
    # StableHLO/MHLO dotted spelling (jit/shard_map lowerings)
    for m in re.finditer(
            r'"?(?:stablehlo|mhlo)\.' + re.escape(kind) + r'\b', txt):
        window = txt[m.start():m.start() + _ATTR_WINDOW]
        if want is not None and \
                _groups_key(_parse_replica_groups(window)) != want:
            continue
        if dtype is not None:
            if kind in _REGION_OPS:
                tm = re.search(r'\}\)\s*:\s*\(tensor<([0-9a-zA-Z_x]*)>',
                               window, re.S)
            else:
                tm = re.search(r':\s*\(tensor<([0-9a-zA-Z_x]*)>', window)
            if tm is None or tm.group(1).split("x")[-1] != dtype:
                continue
        sites.append(m.start())
    # compiled-HLO dashed spelling (post-SPMD-partitioning modules)
    dashed = kind.replace("_", "-")
    for m in re.finditer(
            r'=\s*\(?([a-zA-Z0-9]+)\[[0-9,]*\][^=\n]*?\s'
            + re.escape(dashed) + r'(?:-start)?\(', txt):
        if dtype is not None and m.group(1) != dtype:
            continue
        if want is not None:
            line_end = txt.find("\n", m.end())
            window = txt[m.end():
                         line_end if line_end != -1 else len(txt)]
            gm = re.search(
                r'replica_groups=(\{\{[^}]*(?:\},\{[^}]*)*\}\}|'
                r'\[[^\]]+\]<=\[[^\]]+\](?:T\([\d,]+\))?)', window)
            groups = _parse_hlo_groups(gm.group(1)) if gm else None
            if _groups_key(groups) != want:
                continue
        sites.append(m.start())
    sites.sort()
    if len(sites) < 2:
        raise ValueError(
            f"interleaving needs at least two {kind} collectives in "
            f"the lowering to have a between, found {len(sites)} "
            f"(after axes/dtype filtering)")
    events = _dot_events(txt)
    gaps = []
    for lo, hi in zip(sites, sites[1:]):
        gaps.append(sum(w for p, w in events if lo < p < hi))
    return gaps


def assert_interleaved(artifact, kind: str = "reduce_scatter", *,
                       axes=None, mesh=None, dtype: Optional[str] = None,
                       min_between: int = 1,
                       gaps: str = "any") -> List[int]:
    """Pin the compute/communication interleaving shape of a lowering.

    ``gaps="any"`` (the overlapped shape): assert at least one pair of
    consecutive ``kind`` collectives has >= ``min_between``
    ``dot_general`` ops between them — backward matmuls run between
    bucket syncs, so the latency-hiding scheduler CAN overlap them.
    ``gaps="none"`` (the unoverlapped shape): assert every consecutive
    pair has ZERO dots between — all collectives trace after the whole
    backward.  ``gaps="all"`` is deliberately absent: buckets that
    become ready at the same backward stage legitimately sync
    back-to-back.  Returns the gap list from :func:`interleave_gaps`."""
    counts = interleave_gaps(artifact, kind, axes=axes, mesh=mesh,
                             dtype=dtype)
    if gaps == "any":
        assert any(c >= min_between for c in counts), (
            f"no pair of consecutive {kind} collectives has >= "
            f"{min_between} dot_general between them (gaps={counts}) — "
            f"every sync traces after the whole backward, so the "
            f"scheduler has no compute to hide the collectives behind")
    elif gaps == "none":
        assert all(c == 0 for c in counts), (
            f"found dot_general ops between consecutive {kind} "
            f"collectives (gaps={counts}) — the unoverlapped step "
            f"should trace every bucket sync after the whole backward")
    else:
        raise ValueError(f'gaps must be "any" or "none", got {gaps!r}')
    return counts


def operand_dtypes(artifact, kind: str) -> List[str]:
    """Element dtype of each ``kind`` collective's first operand, in
    program order (``["bf16", "f32"]`` for a two-dtype bucket plan).
    Ops with reduction regions type after the region's ``})``;
    region-less ops type directly."""
    txt = hlo_text(artifact)
    if kind in _REGION_OPS:
        pat = (r'"?(?:stablehlo|mhlo)\.' + re.escape(kind)
               + r'\b.*?\}\)\s*:\s*\(tensor<[0-9x]*x?(\w+)>')
        return re.findall(pat, txt, re.S)
    # the literal "( " before tensor<> is load-bearing: it anchors the
    # match to the op's TYPE SIGNATURE, skipping `dense<...> :
    # tensor<NxMxi64>` replica_groups attributes inside the attr dict
    pat = (r'"?(?:stablehlo|mhlo)\.' + re.escape(kind)
           + r'\b.*?:\s*\(tensor<[0-9x]*x?(\w+)>')
    return re.findall(pat, txt)


def assert_collective_dtype(artifact, kind: str, dtype: str,
                            mode: str = "any") -> None:
    """Assert the wire dtype of ``kind`` collectives: ``mode="any"`` —
    at least one runs in ``dtype`` (the bf16 bucket syncs in bf16);
    ``mode="all"`` — every one does (grad_sync_dtype=fp32 forces the
    whole plan up); ``mode="none"`` — none does."""
    dts = operand_dtypes(artifact, kind)
    if mode == "any":
        assert dtype in dts, (
            f"no {kind} with {dtype} operands in the lowering "
            f"(found {dts or 'none'}) — the {dtype} bucket is not "
            f"syncing on its own wire type")
    elif mode == "all":
        assert dts and all(d == dtype for d in dts), (
            f"expected every {kind} in {dtype}, found {dts or 'none'}")
    elif mode == "none":
        assert dtype not in dts, (
            f"found a {kind} with {dtype} operands ({dts}) — "
            f"expected none")
    else:
        raise ValueError(f"mode must be any/all/none, got {mode!r}")


def assert_no_whole_tree_concat(artifact, total_elements: int,
                                dtype: str = "f32") -> None:
    """No concatenate producing the FULL flat tree (``total_elements``
    x ``dtype``) anywhere in the lowering — the signature of the
    pre-bucket ``_flatten`` stub (one whole-model HBM round trip per
    step) that the bucket plan exists to avoid."""
    txt = hlo_text(artifact)
    m = re.search(
        r'"?(?:stablehlo|mhlo)\.concatenate"?.*->\s*tensor<'
        + str(int(total_elements)) + r'x' + re.escape(dtype) + r'>', txt)
    assert m is None, (
        f"the lowering concatenates the whole tree to one "
        f"tensor<{total_elements}x{dtype}> — a full-model flatten is "
        f"back in the step (the pre-bucket _flatten shape)")


#: StableHLO ops that move data across the device/host boundary
_HOST_TRANSFER_OPS = ("infeed", "outfeed", "send", "recv")

#: custom_call targets that round-trip through the host: Python
#: callbacks (io_callback / pure_callback / debug.print lower to
#: these) and explicit host-memory placement
_HOST_CALL_MARKERS = ("callback", "host")


def host_transfer_sites(artifact) -> List[str]:
    """Every host-transfer site in the lowering, as matched snippets:
    infeed/outfeed/send/recv ops plus ``custom_call`` targets naming a
    Python callback or host placement.  Empty list = the program runs
    entirely on device."""
    txt = hlo_text(artifact)
    sites = []
    for op in _HOST_TRANSFER_OPS:
        sites.extend(_op_occurrences(txt, op))
    # custom_call targets appear as `@target(` in pretty form and as
    # call_target_name = "target" in generic form
    targets = re.findall(
        r'custom_call\s*@([\w.\-]+)\(', txt)
    targets += re.findall(r'call_target_name\s*=\s*"([^"]+)"', txt)
    for t in targets:
        low = t.lower()
        if any(m in low for m in _HOST_CALL_MARKERS):
            sites.append(f"custom_call @{t}")
    return sites


def assert_no_host_transfer(artifact) -> None:
    """The lowering must contain ZERO host transfers — no infeed/
    outfeed/send/recv, no Python-callback or host-placement custom
    calls.  The decode-step contract (ROADMAP: "decode step pinned to
    zero host transfers"): one stray ``debug.print``, ``io_callback``,
    or host-pinned buffer inserts a device->host sync into a loop that
    runs tens of times per generated token."""
    sites = host_transfer_sites(artifact)
    assert not sites, (
        f"the lowering contains {len(sites)} host-transfer site(s): "
        f"{sites[:5]} — a compiled hot-loop step must run entirely on "
        f"device (drop the callback/debug print, or move the host work "
        f"between steps)")


def assert_no_recompile(fn, calls: Sequence = (), *,
                        label: Optional[str] = None) -> list:
    """The compile-once pin, generalized: drive a JITTED callable
    through a call matrix and assert its executable cache never grows
    past ONE entry.

    ``fn`` is anything carrying jax's ``_cache_size()`` (a
    ``jax.jit`` result); ``calls`` is an iterable of argument tuples —
    each is invoked in order, and the cache size is checked after
    EVERY call, so the failure message names the exact call whose
    occupancy/length/draft-hit/chunk-phase mix leaked into a traced
    shape.  With ``calls=()`` only the final state is asserted (the
    post-hoc spelling: run your scenario first, then pin).  Returns
    the per-call results.

    Born as the decode step's trace-count pin
    (tests/test_inference.py); every compile-once contract — decode,
    speculative verify, chunked prefill — now pins through this one
    helper.
    """
    size = getattr(fn, "_cache_size", None)
    if size is None or not callable(size):
        raise TypeError(
            f"assert_no_recompile needs a jitted callable exposing "
            f"_cache_size(); got {type(fn).__name__} — wrap the "
            f"function in jax.jit (or pass the scheduler's step "
            f"attribute, not its bound method)")
    name = label or getattr(fn, "__name__", repr(fn))
    results = []
    for i, args in enumerate(calls):
        results.append(fn(*args))
        n = size()
        assert n <= 1, (
            f"{name}: call {i} of the matrix grew the jit cache to {n} "
            f"compiled variants — an occupancy/length/draft/chunk "
            f"value leaked into a traced shape (argument shapes/dtypes "
            f"must be identical across the matrix)")
    n = size()
    assert n == 1, (
        f"{name}: expected exactly one compiled variant after the call "
        f"matrix, found {n} — "
        + ("the function was never called" if n == 0 else
           "shape-polymorphic retraces happened before this check"))
    return results


# ---------------------------------------------------------- GSPMD tier
# Checkers for the jit+NamedSharding step path (``make_train_step(
# spmd="auto")``): the LOWERING carries the program's sharding INTENT
# as ``mhlo.sharding`` attributes on the entry arguments, and the
# COMPILED module carries the collectives XLA's SPMD partitioner
# actually placed (the lowering of a GSPMD program has none — they
# only exist after partitioning).

def arg_shardings(artifact) -> List[dict]:
    """Per flattened entry argument of the lowering's ``@main``, in
    order: ``{"type": "8x16xf32", "sharding": str|None}`` — the MLIR
    tensor type and the ``mhlo.sharding`` HloSharding string (None for
    an unannotated argument)."""
    txt = hlo_text(artifact)
    m = re.search(r'func\.func\s+(?:public\s+)?@main\((.*?)\)\s*->', txt,
                  re.S)
    if m is None:
        raise ValueError("no @main function signature in the lowering "
                         "text — not a jax lowering artifact?")
    out = []
    # one %argN per entry: `%arg0: tensor<8x16xf32> {attrs...}`
    for am in re.finditer(
            r'%arg\d+:\s*tensor<([^>]*)>\s*(\{.*?\})?(?:,|$)',
            m.group(1), re.S):
        attrs = am.group(2) or ""
        sm = re.search(r'mhlo\.sharding\s*=\s*"([^"]*)"', attrs)
        out.append({"type": am.group(1),
                    "sharding": sm.group(1) if sm else None})
    return out


def _flat_arg_index(lowered, argpath) -> int:
    """Flattened entry-argument index of ``argpath``: an int passes
    through; a sequence of pytree keys (leading element = positional
    argnum) resolves through the lowering's ``in_tree`` — e.g.
    ``(0, "layers", "wq")`` for ``params["layers"]["wq"]`` of
    ``step.lower(params, ...)``.  The path must land on ONE leaf."""
    if isinstance(argpath, int):
        return argpath
    import jax.tree_util as jtu

    tree = lowered.in_tree
    args, _kwargs = jtu.tree_unflatten(tree, list(range(tree.num_leaves)))
    node = args
    for key in argpath:
        node = node[key]
    leaves = jtu.tree_leaves(node)
    if len(leaves) != 1:
        raise ValueError(
            f"argpath {argpath!r} names a subtree of {len(leaves)} "
            f"leaves — point it at one array (add the remaining keys)")
    return leaves[0]


def _aligned_sites(lowered) -> List[dict]:
    """:func:`arg_shardings` with the leaf alignment VERIFIED: the
    tensor-entry count must equal the lowering's pytree leaf count, or
    the flat-index mapping would silently read a neighboring
    argument's sharding (non-tensor entries — ``!stablehlo.token``
    from ordered effects — are skipped by the parser, which keeps
    alignment on current jax; this check makes any future drift loud
    instead of wrong)."""
    sites = arg_shardings(lowered)
    tree = getattr(lowered, "in_tree", None)
    if tree is not None and len(sites) != tree.num_leaves:
        raise ValueError(
            f"lowering has {len(sites)} tensor entry argument(s) but "
            f"the call's pytree has {tree.num_leaves} leaves — the "
            f"@main signature carries arguments this parser cannot "
            f"align (a token/effect arg lowered as a tensor?); the "
            f"argpath -> argument mapping would be unreliable")
    return sites


def sharding_of(lowered, argpath) -> Optional[str]:
    """The ``mhlo.sharding`` string the lowering records for one entry
    argument (see :func:`_flat_arg_index` for ``argpath``), or None
    when the argument carries no annotation."""
    sites = _aligned_sites(lowered)
    i = _flat_arg_index(lowered, argpath)
    if not 0 <= i < len(sites):
        raise IndexError(f"flat arg index {i} out of range "
                         f"({len(sites)} entry arguments)")
    return sites[i]["sharding"]


#: MLIR element type -> jnp dtype name, for re-lowering an argument's
#: aval when computing the EXPECTED sharding attribute
_MLIR_DTYPES = {
    "f64": "float64", "f32": "float32", "f16": "float16",
    "bf16": "bfloat16", "i64": "int64", "i32": "int32", "i16": "int16",
    "i8": "int8", "ui8": "uint8", "ui32": "uint32", "i1": "bool",
    "f8E4M3FN": "float8_e4m3fn", "f8E5M2": "float8_e5m2",
}


def _aval_of_type(mlir_type: str):
    """shape/dtype ShapeDtypeStruct of one ``8x16xf32`` MLIR tensor
    type."""
    parts = mlir_type.split("x")
    dims, dt = parts[:-1], parts[-1]
    if dt not in _MLIR_DTYPES:
        raise ValueError(f"unrecognized MLIR element type {dt!r} in "
                         f"tensor<{mlir_type}> — extend _MLIR_DTYPES")
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(tuple(int(d) for d in dims),
                                getattr(jnp, _MLIR_DTYPES[dt]))


def assert_sharding(lowered, argpath, mesh, spec) -> None:
    """The annotation pin: the lowering's ``mhlo.sharding`` for
    ``argpath`` must equal what ``NamedSharding(mesh, spec)`` lowers to
    on that argument's shape — computed by lowering a one-argument
    identity with that in_sharding and reading ITS attribute, so the
    expectation self-calibrates to the running jax's HloSharding
    spelling instead of hard-coding it."""
    from jax.sharding import NamedSharding

    sites = _aligned_sites(lowered)
    i = _flat_arg_index(lowered, argpath)
    got = sites[i]["sharding"]
    s = NamedSharding(mesh, spec)
    aval = _aval_of_type(sites[i]["type"])
    ref = jax.jit(lambda x: x, in_shardings=s).lower(
        jax.ShapeDtypeStruct(aval.shape, aval.dtype, sharding=s))
    want = arg_shardings(ref)[0]["sharding"]
    assert got == want, (
        f"entry arg {argpath!r} (flat #{i}, tensor<{sites[i]['type']}>) "
        f"lowered with sharding {got!r} but NamedSharding(mesh, "
        f"{spec}) lowers to {want!r} — the step's annotation drifted "
        f"from the intended layout")


def _compiled_text(artifact) -> str:
    """The post-SPMD-partitioning HLO text: a str passes through, a
    ``Lowered`` is compiled (collectives only exist after
    partitioning), anything else with ``as_text()`` is rendered."""
    if isinstance(artifact, str):
        return artifact
    if hasattr(artifact, "compile"):
        return artifact.compile().as_text()
    return hlo_text(artifact)


def _parse_hlo_groups(attr: str) -> Optional[List[List[int]]]:
    """Compiled-HLO ``replica_groups`` in either spelling: the literal
    ``{{0,1},{2,3}}`` or the iota ``[4,2]<=[8]`` /
    ``[2,4]<=[4,2]T(1,0)`` form."""
    attr = attr.strip()
    if attr.startswith("{"):
        groups = re.findall(r'\{([\d,\s]*)\}', attr)
        try:
            return [[int(x) for x in g.split(",") if x.strip()]
                    for g in groups]
        except ValueError:
            return None
    m = re.match(r'\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?', attr)
    if m is None:
        return None
    import numpy as np

    n_groups, group_size = int(m.group(1)), int(m.group(2))
    dims = [int(d) for d in m.group(3).split(",")]
    ids = np.arange(int(np.prod(dims))).reshape(dims)
    if m.group(4):
        ids = ids.transpose([int(p) for p in m.group(4).split(",")])
    return ids.reshape(n_groups, group_size).tolist()


def spmd_collective_sites(artifact, kind: str) -> List[dict]:
    """Every ``kind`` collective the SPMD partitioner placed in the
    compiled module, in program order, as ``{"dtype": str|None,
    "replica_groups": [[int, ...], ...]|None}``.  ``kind`` uses the
    underscore spelling (``all_reduce``); compiled HLO prints dashes
    and may split async pairs — only the ``-start``/plain op counts,
    never the ``-done``."""
    # a combined (tuple-shaped) collective of more than five operands
    # prints ``/*index=5*/`` markers inside its result type; their "="
    # would end the type match below, hiding exactly the big fused
    # grad all-reduce
    txt = re.sub(r'/\*index=\d+\*/', '', _compiled_text(artifact))
    dashed = kind.replace("_", "-")
    sites = []
    for m in re.finditer(
            r'=\s*\(?([a-zA-Z0-9]+)\[[^\]]*\][^=\n]*?\s'
            + re.escape(dashed) + r'(?:-start)?\(', txt):
        line_end = txt.find("\n", m.end())
        window = txt[m.end(): line_end if line_end != -1 else len(txt)]
        gm = re.search(r'replica_groups=(\{\{[^}]*(?:\},\{[^}]*)*\}\}|'
                       r'\[[^\]]+\]<=\[[^\]]+\](?:T\([\d,]+\))?)', window)
        sites.append({
            "dtype": m.group(1),
            "replica_groups": _parse_hlo_groups(gm.group(1)) if gm else None,
        })
    return sites


def assert_spmd_collectives(artifact, kind: str, axes=None, mesh=None, *,
                            minimum: Optional[int] = None,
                            maximum: Optional[int] = None,
                            dtype: Optional[str] = None) -> int:
    """The GSPMD program's collective-structure pin: count the ``kind``
    collectives XLA's partitioner placed (in the COMPILED module — a
    jit+NamedSharding lowering contains none), optionally filtered to
    the ones whose ``replica_groups`` equal a collective over exactly
    ``axes`` of ``mesh`` (the per-axis filtering of
    :func:`assert_collective_axes`, on the compiled-HLO spellings),
    with ``minimum``/``maximum`` bounds and an optional all-sites
    ``dtype`` pin.  Returns the matched count."""
    sites = spmd_collective_sites(artifact, kind)
    label = kind
    if axes is not None:
        if mesh is None:
            raise ValueError("axes= filtering needs mesh= (the groups "
                             "are computed from the mesh layout)")
        want = _groups_key(mesh_axis_groups(mesh, axes))
        sites = [s for s in sites
                 if _groups_key(s["replica_groups"]) == want]
        label = (f"{kind} over axes "
                 f"{tuple(axes) if not isinstance(axes, str) else (axes,)}")
    n = len(sites)
    if minimum is not None:
        assert n >= minimum, (
            f"expected >= {minimum} partitioner-placed {label} "
            f"collective(s) in the compiled module, found {n} — the "
            f"sharding annotations no longer induce the sync they "
            f"were written for (silent replication?)")
    if maximum is not None:
        assert n <= maximum, (
            f"expected <= {maximum} partitioner-placed {label} "
            f"collective(s) in the compiled module, found {n} — the "
            f"annotations induce extra data movement (a resharding "
            f"crept into the step)")
    if dtype is not None:
        bad = [s["dtype"] for s in sites if s["dtype"] != dtype]
        assert not bad, (
            f"every matched {label} must run in {dtype}, found {bad}")
    return n


# the kernel name sits last in the op-name stack, bare inside scans and
# remat ("…/apex_ln_fwd/pallas_call") or wrapped by the transform that
# produced the op ("jit(f)/transpose(jvp(apex_ln_bwd))/pallas_call")
_PALLAS_CALL = re.compile(
    r'custom_call_target="tpu_custom_call".*?'
    r'op_name="[^"]*?([A-Za-z_][\w.\-]*)\)*/pallas_call"')


def pallas_kernels(artifact) -> List[str]:
    """Names of the Pallas (Mosaic) kernels a COMPILED TPU module holds:
    one entry per ``tpu_custom_call`` instruction, in program order.

    The name is the kernel's ``pallas_call(name=...)``, which rides the
    instruction's op-name stack (``.../<name>/pallas_call``).  This is
    the proof that a kernel made it into the executable — the fallback
    registry's ``kernel_calls`` are counted at trace time, before a
    deferred lowering failure can happen."""
    return _PALLAS_CALL.findall(_compiled_text(artifact))


_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_HLO_ARRAY = re.compile(r"\b[a-z]+[0-9]*\[([0-9,]*)\]")


def _split_result_type(rest: str):
    """``rest`` is an instruction after ``name =``: its result type (an
    array, or a tuple with nested parentheses and layouts) and the
    opcode that follows it."""
    depth = 0
    for i, c in enumerate(rest):
        if c in "({":
            depth += 1
        elif c in ")}":
            depth -= 1
        elif c == " " and depth == 0:
            return rest[:i], rest[i + 1:].split("(", 1)[0].strip()
    return rest, ""


def large_result_instructions(artifact, min_elements: int,
                              containing: Sequence[int] = ()) -> List[dict]:
    """Instructions of a COMPILED module with a large array result.

    Every instruction with an array of at least ``min_elements``
    elements in its result — ``{"name", "opcode", "elements", "line"}``
    each, in program order, fusion bodies included.  ``containing`` keeps only arrays with these dimensions
    side by side somewhere in their shape (a pool's ``(num_pages,
    kv_heads)``: stacked weights are as large and are not the pool).

    The question it answers: what in this program produces (or passes
    on) a value as large as X?  For a serving step with X one layer of
    the KV pool, the sound answer is "its parameters, the tuples,
    ``get-tuple-element``s and ``while`` that carry them, and custom
    calls that alias an operand" — a ``copy``, ``fusion``,
    ``scatter`` or ``dynamic-update-slice`` there is XLA re-laying out,
    slicing or rebuilding the pool (PERF.md, PR 25: 54% of a decode
    step).  Which opcodes are sound is the caller's to say; an aliased
    custom call shows as ``custom-call`` with
    ``output_to_operand_aliasing`` in its line."""
    out = []
    want = tuple(int(d) for d in containing)
    for line in _compiled_text(artifact).splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        rtype, opcode = _split_result_type(m.group(2))
        sizes = []
        for dims in _HLO_ARRAY.findall(rtype):
            dims = tuple(int(d) for d in dims.split(",") if d)
            if any(dims[i:i + len(want)] == want
                   for i in range(len(dims) - len(want) + 1)):
                sizes.append(math.prod(dims))
        if sizes and max(sizes) >= min_elements:
            out.append({"name": m.group(1), "opcode": opcode,
                        "elements": max(sizes), "line": line.strip()})
    return out


_HLO_OPERAND = re.compile(r"%([\w.\-]+)")


def entry_instructions(artifact) -> Dict[str, dict]:
    """The ENTRY computation of a COMPILED module, by instruction name:
    ``{"type", "opcode", "operands", "line"}`` each, in program order.
    ``operands`` are the names inside the opcode's own parentheses;
    ``type`` is the result type as printed (array or tuple)."""
    txt = _compiled_text(artifact)
    out = {}
    for line in txt[txt.index("ENTRY "):].splitlines()[1:]:
        if line.startswith("}"):
            break
        m = _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        rtype, opcode = _split_result_type(m.group(2))
        args = m.group(2)[len(rtype) + 1 + len(opcode):]
        depth, end = 0, len(args)
        for i, c in enumerate(args):
            depth += c == "("
            depth -= c == ")"
            if depth == 0:
                end = i
                break
        out[m.group(1)] = {"type": rtype, "opcode": opcode, "line": line,
                           "operands": _HLO_OPERAND.findall(args[:end])}
    return out


def entry_users(instructions: Dict[str, dict], name: str) -> List[str]:
    """Names of the ENTRY instructions (:func:`entry_instructions`) that
    take ``name`` as an operand: how many fusions READ a value.  A
    value that a step should read once and finds two users for is a
    second pass over it through memory."""
    return [n for n, i in instructions.items() if name in i["operands"]]


def donated_buffer_count(artifact) -> int:
    """Buffers the LOWERING declares donatable: ``jax.buffer_donor``
    (shard_map inputs) plus ``tf.aliasing_output`` (plain-jit donated
    args pre-aliased to outputs)."""
    txt = hlo_text(artifact)
    return txt.count("jax.buffer_donor") + txt.count("tf.aliasing_output")


def _expected_leaves(donated_trees: Sequence, extra: int) -> int:
    return extra + sum(
        len(jax.tree_util.tree_leaves(t)) for t in donated_trees)


def assert_donation_covers(lowered, *donated_trees, extra: int = 0,
                           compiled: bool = True) -> None:
    """Every leaf of ``donated_trees`` (plus ``extra`` buffers) must be
    donated through the step: the lowering declares at least that many
    donatable buffers, and — with ``compiled=True`` — the compiled
    module's ``input_output_alias`` table actually aliases them to
    outputs.  Donation that LOWERS but does not ALIAS is the silent
    failure mode (XLA drops donations it cannot use, keeping the ~3x
    param-bytes peak the donation was written to avoid), so prefer the
    compiled check whenever the test budget allows; ``compiled=False``
    skips the XLA compile and pins only the declaration."""
    n = _expected_leaves(donated_trees, extra)
    assert n > 0, "no donated leaves to check — pass the donated trees"
    declared = donated_buffer_count(lowered)
    assert declared >= n, (
        f"{declared} buffer(s) declared donatable in the lowering but "
        f"the donated trees hold {n} leaves — donate_argnums is not "
        f"covering the state (dropped arg? tuple index drift?)")
    if not compiled:
        return
    hdr = lowered.compile().as_text().splitlines()[0]
    assert "input_output_alias=" in hdr, (
        f"compiled module has no input_output_alias table at all — "
        f"every donation was dropped: {hdr}")
    aliased = hdr.count("may-alias") + hdr.count("must-alias")
    assert aliased >= n, (
        f"only {aliased} aliased buffer(s) in input_output_alias for "
        f"{n} donated leaves — XLA dropped donations (dtype/layout "
        f"mismatch between the donated input and every output?)")
