"""Serving-path mutation hygiene rules.

- APX110: an in-place scatter (``.at[...].set/.add/...``) into a
  kv/pool-named buffer whose page index is not provably routed through
  the allocator/clamp seam — the COW-bypass hazard class — or a
  ``dynamic_update_slice`` on such a buffer, which has no seam at all.

The paged-KV pool has exactly one safe mutation discipline
(``inference/kv_cache.py``): every destination page index is either
(a) a device value routed through ``jnp.clip`` into the pool and/or a
``jnp.where`` that re-routes masked rows to the reserved garbage page
(the APX107 read-side contract, applied to writes), or (b) a HOST int
handed out by :class:`~apex_tpu.inference.kv_cache.PageAllocator` —
recognizable by the ``int(...)`` normalization at the seam
(``copy_page``).  A scatter that bypasses both is the class of bug
prefix sharing makes catastrophic: with refcounted pages, writing
through an unrouted index does not just corrupt ONE sequence's cache —
it mutates a page other sequences (and the prefix trie) still read,
silently changing *their* logits.  Copy-on-write only protects writes
that go through the scheduler's COW pass; a raw ``pool.at[idx].set``
is invisible to it.

There is a second reason to keep raw XLA writes off the pool, found on
the chip (PERF.md, PR 25): inside a jitted step an XLA scatter or
``dynamic_update_slice`` on the pool makes layout assignment copy the
WHOLE pool into the layout that write prefers, and back — more than
half of a decode step at GPT-2 large.  The sanctioned writers
(``write_decode_kv`` / ``write_prompt_kv``) therefore write in place
through one aliased Pallas kernel (``ops/kv_write_pallas.py``,
``apex_kv_write``) wherever a TPU runs them; their plain-XLA twin on
the same indices is the CPU and degrade path.
"""

from __future__ import annotations

import ast
from typing import Iterator, Set

from apex_tpu.analysis.core import (
    Finding, ModuleContext, Rule, last_name,
)

#: identifier substrings that mark a KV page-pool buffer (the decode
#: path's shared mutable state) — the APX110 scope guard
_POOL_NAMES = ("pool", "kv_cache", "kvcache")

#: calls whose results count as "routed through the seam": device-side
#: clamp/re-route (clip/where — the garbage-page discipline) and the
#: host-int normalization the allocator seam applies (int)
_SEAM_CALLS = ("clip", "where", "int")

#: kv_cache's own routing helpers — they return (dest, slot/live)
#: already clamped and garbage-routed, for the sanctioned writers
#: (``write_decode_kv`` / ``write_prompt_kv``, in place through
#: ``kv_write_pallas`` or their plain-XLA twin)
_SEAM_HELPERS = ("_row_targets", "_tile_targets")

#: XLA's slice-write family: no index discipline of its own, and on a
#: pool inside a step it makes XLA re-lay out the whole pool
_SLICE_WRITES = frozenset(
    {"dynamic_update_slice", "dynamic_update_slice_in_dim",
     "dynamic_update_index_in_dim"})

#: ``.at[...]`` verbs that WRITE (jnp's functional scatter family) —
#: ``.get`` is a read and stays out of reach
_MUTATION_VERBS = frozenset(
    {"set", "add", "subtract", "multiply", "divide", "power", "min",
     "max", "apply"})


def _mentions_pool(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        name = None
        if isinstance(sub, ast.Name):
            name = sub.id
        elif isinstance(sub, ast.Attribute):
            name = sub.attr
        if name is not None \
                and any(m in name.lower() for m in _POOL_NAMES):
            return True
    return False


def _contains_seam_call(node: ast.AST, routed: Set[str]) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) \
                and last_name(sub.func) in _SEAM_CALLS + _SEAM_HELPERS:
            return True
        if isinstance(sub, ast.Name) and sub.id in routed:
            return True
    return False


def _routed_names(fn: ast.AST) -> Set[str]:
    """Names assigned (directly, or through arithmetic on an already-
    routed name) from a clip/where/int call anywhere in the function —
    the write-side twin of ``rules_precision._clipped_names``."""
    routed: Set[str] = set()
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Assign)
                    and len(node.targets) == 1):
                continue
            tgt = node.targets[0]
            pairs = []
            if isinstance(tgt, ast.Name):
                pairs = [(tgt, node.value)]
            elif isinstance(tgt, ast.Tuple) \
                    and isinstance(node.value, ast.Tuple) \
                    and len(tgt.elts) == len(node.value.elts):
                # src, dst = int(src), int(dst) — element-wise
                pairs = list(zip(tgt.elts, node.value.elts))
            elif isinstance(tgt, ast.Tuple) \
                    and isinstance(node.value, ast.Call) \
                    and last_name(node.value.func) in _SEAM_HELPERS:
                # dest, slot = _row_targets(...) — every result routed
                pairs = [(t, node.value) for t in tgt.elts]
            for t, v in pairs:
                if isinstance(t, ast.Name) and t.id not in routed \
                        and _contains_seam_call(v, routed):
                    routed.add(t.id)
                    changed = True
    return routed


class KvPoolScatterBypassesSeam(Rule):
    """APX110: ``pool.at[idx].set(...)`` where ``idx`` is neither
    clamped/garbage-routed device data nor an allocator-normalized
    host int; or any ``dynamic_update_slice`` on a pool."""

    rule_id = "APX110"
    severity = "error"
    fix_hint = (
        "route the page index through the seam: clamp device indices "
        "into the pool and re-route masked rows to the garbage page "
        "(dest = jnp.where(mask, jnp.clip(rows, 0, num_pages - 1), "
        "GARBAGE_PAGE)), or normalize allocator-issued host ids with "
        "int(...) — or better, write through the kv_cache seam "
        "helpers (write_decode_kv / write_prompt_kv / copy_page), "
        "which the scheduler's copy-on-write pass knows about and "
        "which, inside a step, write in place through the aliased "
        "Pallas kernel (ops.kv_write_pallas, `apex_kv_write`) so that "
        "XLA never re-lays out the pool")

    _RELAYOUT = (
        "; and inside a jitted step a raw XLA write of the pool makes "
        "layout assignment copy the WHOLE pool to the layout the write "
        "prefers and back (PERF.md, PR 25: more than half of a decode "
        "step)")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) \
                    and last_name(node.func) in _SLICE_WRITES \
                    and node.args and _mentions_pool(node.args[0]):
                yield self.finding(
                    ctx, node,
                    f"kv/pool buffer written through "
                    f"`{last_name(node.func)}`: the write bypasses the "
                    f"allocator/clamp seam, so the scheduler's "
                    f"copy-on-write pass cannot see it" + self._RELAYOUT)
                continue
            if not isinstance(node, ast.Subscript):
                continue
            at = node.value
            if not (isinstance(at, ast.Attribute) and at.attr == "at"):
                continue
            if not _mentions_pool(at.value):
                continue
            # the mutation verb lives on the call ENCLOSING the
            # subscript: pool.at[idx].set(x) — bare pool.at[idx] and
            # .at[idx].get(...) (a read) mutate nothing
            attr = ctx.parent(node)
            if not (isinstance(attr, ast.Attribute)
                    and attr.attr in _MUTATION_VERBS
                    and isinstance(ctx.parent(attr), ast.Call)):
                continue
            fn = ctx.enclosing_function(node)
            routed = _routed_names(fn) if fn is not None else set()
            if _contains_seam_call(node.slice, routed):
                continue
            if self._index_is_static(node.slice):
                continue
            yield self.finding(
                ctx, node,
                f"kv/pool buffer scattered through `.at[...].{attr.attr}` "
                f"with a page index not routed through the "
                f"allocator/clamp seam: with refcounted prefix-shared "
                f"pages this write can mutate a page OTHER sequences "
                f"(and the prefix trie) still read — invisible to the "
                f"scheduler's copy-on-write pass, corrupting their "
                f"logits silently" + self._RELAYOUT)

    @staticmethod
    def _index_is_static(slice_node: ast.AST) -> bool:
        """Literal-only indices (constants, slices of constants) carry
        no corruptible page indirection."""
        for sub in ast.walk(slice_node):
            if isinstance(sub, ast.Name):
                return False
            if isinstance(sub, ast.Call):
                return False
        return True
