"""Donation/aliasing rules (the ``donate_argnums`` class).

``donate_argnums`` tells XLA it may reuse an input buffer's memory for
the outputs.  That is the difference between fitting and halving the
batch at 345M scale — and it is also
the one jit option whose misuse is INVISIBLE everywhere but the chip:
on CPU donation is a no-op, so a test that reads a donated buffer after
the step passes locally and reads garbage (or crashes with "array has
been deleted") on TPU.

- APX103: a Python name passed at a donated position is *read again*
  after the call without first being rebound — either from the call's
  own results (the safe ``params, state = step(params, state)`` idiom)
  or by a later assignment.  The usual shapes: logging a param norm
  from the pre-step tree, or rebinding the step's result to a NEW name
  while the stale donated name stays live.

Only statically certain cases are flagged: literal ``donate_argnums``
(a tuple/int of constants), plain-name arguments, no ``*args``
splatting at the call site.  Values threaded through variables are
trusted, same contract as the tiling and collective rules.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set

from apex_tpu.analysis.core import (
    Finding, ModuleContext, Rule, last_name,
)

__all__ = ["DonatedBufferReuse"]


def _literal_argnums(node: ast.AST) -> Optional[Set[int]]:
    """The donated positions if the donate_argnums value is a literal
    int or tuple/list of ints; None when it is computed (trusted)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: Set[int] = set()
        for elt in node.elts:
            if not (isinstance(elt, ast.Constant)
                    and isinstance(elt.value, int)):
                return None
            out.add(elt.value)
        return out
    return None


def _donate_kw(call: ast.Call) -> Optional[Set[int]]:
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            return _literal_argnums(kw.value)
    return None


def _is_jit(node: ast.AST) -> bool:
    return last_name(node) == "jit"


def _scoped_names(root: ast.AST) -> Iterator[ast.Name]:
    """Name nodes in ``root``'s OWN scope: descent stops at nested
    function/class/lambda definitions.  A same-named parameter or local
    in a nested scope is a different variable, not the donated buffer
    (flagging it was a reproduced false positive), and a true closure
    read's execution time is not statically certain — both sides of the
    only-statically-certain contract say stop at the scope boundary.
    ``root`` itself may be a def (the enclosing function): only nested
    scopes are skipped."""
    stack: List[ast.AST] = [root]
    while stack:
        node = stack.pop()
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda, ast.ClassDef)):
                continue
            if isinstance(child, ast.Name):
                yield child
            stack.append(child)


def _scope_bound_names(scope: ast.AST) -> Set[str]:
    """Names this scope binds: its parameters, Store/Del targets in its
    own body (nested scopes excluded), and the names of defs/classes
    declared directly in it."""
    names: Set[str] = set()
    if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
        a = scope.args
        for p in (*a.posonlyargs, *a.args, *a.kwonlyargs,
                  *([a.vararg] if a.vararg else []),
                  *([a.kwarg] if a.kwarg else [])):
            names.add(p.arg)
    stack: List[ast.AST] = [scope]
    while stack:
        node = stack.pop()
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                names.add(child.name)  # the def binds its name HERE,
                continue               # its body is another scope
            if isinstance(child, ast.Lambda):
                continue
            if isinstance(child, ast.Name) and isinstance(
                    child.ctx, (ast.Store, ast.Del)):
                names.add(child.id)
            stack.append(child)
    return names


def _target_names(stmt: ast.AST) -> Set[str]:
    """Names a statement (re)binds, for the safe-rebind check."""
    names: Set[str] = set()
    targets: List[ast.AST] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign, ast.For)):
        targets = [stmt.target]
    for t in targets:
        for sub in ast.walk(t):
            if isinstance(sub, ast.Name):
                names.add(sub.id)
    return names


class DonatedBufferReuse(Rule):
    """APX103: donated argument read after the donating call."""

    rule_id = "APX103"
    severity = "error"
    fix_hint = ("rebind the donated name from the call's own results "
                "(`params, state, ... = step(params, state, ...)`) or "
                "move the read before the call — after donation XLA may "
                "have reused the buffer for the outputs, so the old name "
                "is garbage on TPU even though CPU tests pass")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        donated = self._donated_bindings(ctx)
        if not donated:
            return
        bound_cache: Dict[int, Set[str]] = {}
        for call in ast.walk(ctx.tree):
            if not isinstance(call, ast.Call):
                continue
            name = last_name(call.func)
            nums = self._resolve(ctx, call, name, donated, bound_cache)
            if nums is None:
                continue
            if any(isinstance(a, ast.Starred) for a in call.args):
                continue  # positions unknowable under *args splat
            yield from self._check_call(ctx, call, nums)

    # ------------------------------------------------------- bindings
    def _donated_bindings(
            self, ctx: ModuleContext) -> Dict[int, Dict[str, Set[int]]]:
        """Names bound to a jit with literal donate_argnums, keyed by
        the id() of the scope (function node or module) that binds them:
        via ``step = jax.jit(f, donate_argnums=...)`` assignment or a
        ``@partial(jax.jit, donate_argnums=...)`` / ``@jax.jit(...)``
        decorator on a def.  Scope-keyed so an unrelated same-named
        function in another scope is never flagged (a reproduced false
        positive of the module-wide name match)."""
        out: Dict[int, Dict[str, Set[int]]] = {}

        def record(binding_node, name, nums):
            scope = ctx.enclosing_function(binding_node) or ctx.tree
            out.setdefault(id(scope), {})[name] = nums

        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Call) \
                    and _is_jit(node.value.func):
                nums = _donate_kw(node.value)
                if nums:
                    record(node, node.targets[0].id, nums)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if not isinstance(dec, ast.Call):
                        continue
                    # @jax.jit(donate_argnums=...) or
                    # @partial(jax.jit, donate_argnums=...)
                    if _is_jit(dec.func) or (
                            last_name(dec.func) == "partial" and dec.args
                            and _is_jit(dec.args[0])):
                        nums = _donate_kw(dec)
                        if nums:
                            record(node, node.name, nums)
        return out

    def _resolve(self, ctx: ModuleContext, call: ast.Call, name: str,
                 donated: Dict[int, Dict[str, Set[int]]],
                 bound_cache: Dict[int, Set[str]]) -> Optional[Set[int]]:
        """Python name resolution over the call's scope chain, innermost
        first: the nearest scope that binds ``name`` decides — donated
        positions if its binding is the donating one, None if ``name``
        is shadowed there by a parameter/local/def of the same name."""
        scope: Optional[ast.AST] = ctx.enclosing_function(call)
        while True:
            node = scope if scope is not None else ctx.tree
            nums = donated.get(id(node), {}).get(name)
            if nums is not None:
                return nums
            key = id(node)
            if key not in bound_cache:
                bound_cache[key] = _scope_bound_names(node)
            if name in bound_cache[key]:
                return None  # shadowed: a different, non-donating binding
            if scope is None:
                return None
            scope = ctx.enclosing_function(scope)

    # ------------------------------------------------------- call sites
    def _check_call(self, ctx: ModuleContext, call: ast.Call,
                    positions: Set[int]) -> Iterator[Finding]:
        stmt = self._enclosing_stmt(ctx, call)
        if stmt is None:
            return
        if isinstance(stmt, (ast.Return, ast.Raise)):
            # the donating call's value leaves the function immediately:
            # no later line of this scope can run after it in the same
            # invocation, so a read in a sibling branch (the early-return
            # shape) is provably NOT a read of the donated buffer
            return
        scope = ctx.enclosing_function(call)
        body_root = scope if scope is not None else ctx.tree
        rebound_here = _target_names(stmt)
        stmt_end = getattr(stmt, "end_lineno", stmt.lineno)
        for pos in sorted(positions):
            if pos >= len(call.args):
                continue
            arg = call.args[pos]
            if not isinstance(arg, ast.Name):
                continue  # only plain names are tracked
            if arg.id in rebound_here:
                continue  # the safe rebinding idiom
            # a read on the other arm of the same If can never execute
            # after the call in one invocation (and no enclosing loop
            # carries the donated buffer across): skip those, flag the
            # first reachable read
            bad = next(
                (b for b in self._reads_before_rebind(
                    body_root, arg.id, stmt_end)
                 if not self._exclusive_branches(ctx, call, b, body_root)),
                None)
            if bad is not None:
                yield self.finding(
                    ctx, bad,
                    f"`{arg.id}` is donated (donate_argnums position "
                    f"{pos}) at line {call.lineno} and read again here "
                    f"without being rebound — XLA may have reused its "
                    f"buffer for the outputs; works on CPU (donation is "
                    f"a no-op there), garbage or a deleted-array error "
                    f"on TPU")
                continue
            # loop-carried: a read textually BEFORE the call inside the
            # same loop runs AFTER it on the next iteration
            carried = self._loop_carried_read(ctx, call, arg.id, body_root)
            if carried is not None:
                yield self.finding(
                    ctx, carried,
                    f"`{arg.id}` is donated (donate_argnums position "
                    f"{pos}) at line {call.lineno} inside this loop and "
                    f"never rebound: on the NEXT iteration this read "
                    f"sees the donated buffer — works on CPU (donation "
                    f"is a no-op there), garbage or a deleted-array "
                    f"error on TPU")

    def _loop_carried_read(self, ctx: ModuleContext, call: ast.Call,
                           name: str, body_root: ast.AST
                           ) -> Optional[ast.AST]:
        """First Load of ``name`` in the call's nearest enclosing loop
        that executes after the donation via the NEXT iteration — i.e.
        any read in the loop body outside the donating call expression,
        with the name never stored in the loop (a store anywhere makes
        the next iteration's value uncertain: stay silent)."""
        loop: Optional[ast.AST] = None
        cur = ctx.parent(call)
        while cur is not None and cur is not body_root:
            if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)):
                loop = cur
                break
            cur = ctx.parent(cur)
        if loop is None:
            return None
        own = set(map(id, ast.walk(call)))
        first: Optional[ast.AST] = None
        for sub in _scoped_names(loop):
            if sub.id != name:
                continue
            if isinstance(sub.ctx, ast.Store):
                return None
            if isinstance(sub.ctx, ast.Load) and id(sub) not in own \
                    and (first is None or sub.lineno < first.lineno):
                first = sub
        return first

    def _exclusive_branches(self, ctx: ModuleContext, call: ast.AST,
                            read: ast.AST, body_root: ast.AST) -> bool:
        """True when ``call`` and ``read`` sit on different arms of the
        same ``if`` and no loop up to ``body_root`` can re-execute it —
        then the read provably never follows the donating call in one
        invocation (the assign-in-branch sibling of the early-return
        shape).  Inside a loop the flag stands: iteration 1 may donate
        and iteration 2 read the stale name."""
        anc_call = self._ancestors(ctx, call)
        anc_read = self._ancestors(ctx, read)
        for node in anc_call:
            if not isinstance(node, ast.If) or node not in anc_read:
                continue
            in_body_call = self._descends(node.body, anc_call)
            in_body_read = self._descends(node.body, anc_read)
            if in_body_call == in_body_read:
                continue  # same arm (or both under elif chains): not
                # exclusive at THIS If, but a deeper shared If may be
            cur = ctx.parent(node)
            while cur is not None and cur is not body_root:
                if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)):
                    return False  # loop may carry the buffer across arms
                cur = ctx.parent(cur)
            return True
        return False

    @staticmethod
    def _ancestors(ctx: ModuleContext, node: ast.AST) -> List[ast.AST]:
        out: List[ast.AST] = []
        cur: Optional[ast.AST] = node
        while cur is not None:
            out.append(cur)
            cur = ctx.parent(cur)
        return out

    @staticmethod
    def _descends(arm: List[ast.stmt], ancestors: List[ast.AST]) -> bool:
        """Does the ancestor chain pass through one of this If arm's
        statements?"""
        chain = set(map(id, ancestors))
        return any(id(s) in chain for s in arm)

    def _enclosing_stmt(self, ctx: ModuleContext,
                        node: ast.AST) -> Optional[ast.stmt]:
        cur: Optional[ast.AST] = node
        while cur is not None:
            parent = ctx.parent(cur)
            if isinstance(cur, ast.stmt) and isinstance(
                    parent, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Module, ast.For, ast.While, ast.If,
                             ast.With, ast.Try)):
                return cur
            cur = parent
        return None

    def _reads_before_rebind(self, body_root: ast.AST, name: str,
                             after_line: int) -> List[ast.AST]:
        """Loads of ``name`` after ``after_line`` that no Store precedes
        (straight-line approximation), in line order."""
        loads: List[ast.AST] = []
        first_store_line: Optional[int] = None
        for sub in _scoped_names(body_root):
            if sub.id != name or sub.lineno <= after_line:
                continue
            if isinstance(sub.ctx, ast.Store):
                if first_store_line is None or sub.lineno < first_store_line:
                    first_store_line = sub.lineno
            elif isinstance(sub.ctx, ast.Load):
                loads.append(sub)
        return sorted(
            (l for l in loads if first_store_line is None
             or l.lineno < first_store_line),
            key=lambda l: l.lineno)
