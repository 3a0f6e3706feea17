"""Trace-time host-state rules.

JAX traces a Python function ONCE per (shape, dtype, static-arg)
signature; everything the Python body reads from the host — env vars,
clocks, RNGs, mutated globals — is frozen into the jaxpr at that
moment.  The two failure shapes:

- APX101: a traced function *reads* host state.  The first trace wins
  forever; flipping the env var later does nothing (or worse, does
  something only for shapes not yet traced — a silent A/B corruption).
- APX102: code *mutates* ``os.environ`` mid-process to steer behavior.
  Even outside a traced function this desyncs with every jit cache
  entry built before the flip; the fix is threading an explicit
  argument (see ``GPTConfig.fused_ce_impl``).
"""

from __future__ import annotations

import ast
from typing import Iterator, Optional

from apex_tpu.analysis.core import (
    Finding, ModuleContext, Rule, dotted_name, last_name,
)

# host-state call patterns: dotted suffix -> what it captures
_HAZARD_CALLS = {
    "os.getenv": "environment variable",
    "os.environ.get": "environment variable",
    "time.time": "wall clock",
    "time.monotonic": "wall clock",
    "time.perf_counter": "wall clock",
    "time.time_ns": "wall clock",
    "datetime.now": "wall clock",
    "datetime.utcnow": "wall clock",
}

_RANDOM_MODULES = ("random.", "np.random.", "numpy.random.")
_ENV_MUTATORS = {"pop", "update", "setdefault", "clear"}


def _dotted(node: ast.AST) -> str:
    return dotted_name(node) or ""


def _is_os_environ(node: ast.AST) -> bool:
    return _dotted(node) in ("os.environ", "environ")


class TraceTimeHostStateRead(Rule):
    """APX101: host state read inside a trace-time function."""

    rule_id = "APX101"
    severity = "error"
    fix_hint = ("hoist the read out of the traced function and thread the "
                "value in as an argument (or a config field); for "
                "randomness use jax.random with an explicit key")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            hazard = self._hazard(node)
            if hazard is None:
                continue
            reason = ctx.traced_reason(node)
            if reason is None:
                continue
            yield self.finding(
                ctx, node,
                f"{hazard} read at trace time inside "
                f"`{ctx.enclosing_qualname(node)}` ({reason}); the value "
                f"is frozen into the first trace and silently stale for "
                f"every later call")

    def _hazard(self, node: ast.AST) -> Optional[str]:
        # os.environ["X"] / os.environ used as a value
        if isinstance(node, ast.Subscript) and _is_os_environ(node.value) \
                and isinstance(node.ctx, ast.Load):
            return "os.environ"
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            for suffix, what in _HAZARD_CALLS.items():
                if d == suffix or d.endswith("." + suffix):
                    return f"{what} ({d})"
            # bare-import spellings: `from os import environ, getenv`
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "get" \
                    and _is_os_environ(node.func.value):
                return f"environment variable ({d})"
            if d == "getenv":
                return "environment variable (getenv)"
            if any(d.startswith(m) for m in _RANDOM_MODULES):
                return f"host RNG ({d})"
        return None


class ProcessGlobalEnvMutation(Rule):
    """APX102: os.environ mutated inside a function body.

    Module-level assignments (startup config before any tracing) are
    deliberately exempt — the hazard is mutation *mid-process*, after
    jit caches already captured the old value.
    """

    rule_id = "APX102"
    severity = "error"
    fix_hint = ("thread the override as an explicit function/config "
                "argument (e.g. GPTConfig.fused_ce_impl) instead of "
                "flipping process-global state already-traced functions "
                "captured")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            desc = self._mutation(node)
            if desc is None:
                continue
            if ctx.enclosing_function(node) is None:
                continue  # module-level startup config is fine
            yield self.finding(
                ctx, node,
                f"{desc} inside `{ctx.enclosing_qualname(node)}`: "
                f"functions traced before this line keep the OLD value "
                f"(trace-time capture), so the flip silently applies to "
                f"some call paths and not others")

    def _mutation(self, node: ast.AST) -> Optional[str]:
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                if isinstance(t, ast.Subscript) and _is_os_environ(t.value):
                    return "os.environ[...] assignment"
        if isinstance(node, ast.Delete):
            for t in node.targets:
                if isinstance(t, ast.Subscript) and _is_os_environ(t.value):
                    return "del os.environ[...]"
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in _ENV_MUTATORS \
                    and _is_os_environ(f.value):
                return f"os.environ.{f.attr}(...)"
            d = _dotted(node.func)
            if d.endswith("os.putenv") or d == "putenv" \
                    or d.endswith("os.unsetenv") or d == "unsetenv":
                return f"{d}(...)"
        return None
