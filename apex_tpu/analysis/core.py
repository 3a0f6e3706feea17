"""Rule engine for the apex_tpu static analyzer.

Pure-stdlib ``ast`` analysis — importing this package must never import
jax (the analyzer has to run in a crippled CI container, in a pre-commit
hook, and against a tree that does not even import).  Each rule is a
class with an ``id``, ``severity``, and ``fix_hint`` that visits one
:class:`ModuleContext` and yields :class:`Finding`s; the contexts carry
the per-module facts every rule family needs — above all the
*traced-function index*, the set of functions whose bodies execute at
JAX trace time (jitted, ``custom_vjp``'d, passed to ``pl.pallas_call``
or a ``lax`` control-flow combinator, or reachable from one of those
through the module-local call graph).

Why trace-reachability is the load-bearing fact: Apex's CUDA extensions
fail at build time, but this rebuild's failure modes are deferred —
host state read during tracing is frozen into the jaxpr and silently
stale forever after.  The index turns "is this ``os.environ.get`` a
bug?" into a static question.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, Iterable, Iterator, List, Optional, Set, Tuple

SEVERITIES = ("error", "warning", "info")

# Entry points whose function-valued arguments are traced.  Last dotted
# component only: ``jax.jit``, ``jit``, and ``api.jit`` all match — a
# linter that misses ``from jax import jit`` is worse than one that
# over-asks, and the baseline absorbs deliberate cases.
TRACE_ENTRYPOINTS: Set[str] = {
    "jit", "pallas_call", "custom_vjp", "custom_jvp", "defvjp", "defjvp",
    "checkpoint", "remat", "grad", "value_and_grad", "vmap", "pmap",
    "shard_map", "xmap", "scan", "while_loop", "fori_loop", "cond",
    "switch", "named_call", "eval_shape",
}

# Decorators that make the decorated function traced.
TRACE_DECORATORS: Set[str] = {
    "jit", "custom_vjp", "custom_jvp", "checkpoint", "remat", "vmap",
    "pmap", "shard_map",
}

# Default collective-axis registry, used only when no parallel_state.py
# is found among the scanned roots (its ``*_AXIS`` constants are the
# source of truth; see discover_axis_registry).
DEFAULT_AXES: Tuple[str, ...] = ("dp", "pp", "cp", "tp", "dcn")


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    severity: str
    path: str
    line: int
    col: int
    symbol: str          # enclosing function qualname, or "<module>"
    message: str
    fix_hint: str

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"{self.severity}: {self.message}\n    fix: {self.fix_hint}")

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


class Rule:
    """One checkable invariant.  Subclasses set the class attributes and
    implement :meth:`check`."""

    rule_id: str = "APX000"
    severity: str = "error"
    fix_hint: str = ""

    def check(self, ctx: "ModuleContext") -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, ctx: "ModuleContext", node: ast.AST,
                message: str, fix_hint: Optional[str] = None) -> Finding:
        return Finding(
            rule=self.rule_id, severity=self.severity, path=ctx.path,
            line=getattr(node, "lineno", 0), col=getattr(node, "col_offset", 0),
            symbol=ctx.enclosing_qualname(node),
            message=message, fix_hint=fix_hint or self.fix_hint)


def dotted_name(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def last_name(node: ast.AST) -> Optional[str]:
    """Final dotted component of a Name/Attribute chain."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _is_partial(call: ast.Call) -> bool:
    return last_name(call.func) == "partial"


@dataclasses.dataclass
class FunctionInfo:
    node: ast.AST                 # FunctionDef | AsyncFunctionDef | Lambda
    qualname: str
    params: Set[str]


class ModuleContext:
    """Everything the rules need to know about one parsed module."""

    def __init__(self, path: str, source: str, tree: ast.Module,
                 axis_registry: Set[str], module_name: str = "",
                 is_package: bool = False):
        self.path = path
        self.source = source
        self.tree = tree
        self.axis_registry = axis_registry
        #: dotted module name (``apex_tpu.ops.fused_ce``) — what the
        #: cross-module linker resolves imports against; empty for
        #: single-file analysis (no linking possible)
        self.module_name = module_name
        #: True for a package ``__init__.py``: its level-1 relative
        #: imports resolve against the package ITSELF, not its parent
        self.is_package = is_package
        self._parents: Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                self._parents[child] = parent
        self.functions: Dict[str, FunctionInfo] = {}
        self._collect_functions()
        self._collect_imports()
        # qualname -> human-readable reason the function is traced
        self.traced: Dict[str, str] = {}
        # Lambda node -> reason (lambdas have no qualname; tracked by
        # identity so `jax.jit(lambda x: ...)` bodies are still scanned)
        self.traced_lambdas: Dict[ast.Lambda, str] = {}
        self._build_traced_index()

    # -------------------------------------------------------- structure
    def parent(self, node: ast.AST) -> Optional[ast.AST]:
        return self._parents.get(node)

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        cur = self._parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return cur
            cur = self._parents.get(cur)
        return None

    def enclosing_qualname(self, node: ast.AST) -> str:
        fn = node if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef)) else \
            self.enclosing_function(node)
        while fn is not None:
            for info in self.functions.values():
                if info.node is fn:
                    return info.qualname
            fn = self.enclosing_function(fn)
        return "<module>"

    def _collect_functions(self) -> None:
        def visit(node: ast.AST, prefix: str) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    qn = f"{prefix}{child.name}"
                    params = {a.arg for a in (
                        child.args.posonlyargs + child.args.args +
                        child.args.kwonlyargs)}
                    self.functions[qn] = FunctionInfo(child, qn, params)
                    visit(child, qn + ".")
                elif isinstance(child, ast.ClassDef):
                    visit(child, f"{prefix}{child.name}.")
                else:
                    visit(child, prefix)

        visit(self.tree, "")

    def _collect_imports(self) -> None:
        """Local-name → module bindings, for the cross-module linker.
        Function-local imports count too (the fused_ce shape: ``from
        ...fused_ce_pallas import fused_ce_fwd_pallas`` inside the
        traced closure)."""
        self.import_aliases: Dict[str, str] = {}      # alias -> module
        self.from_imports: Dict[str, Tuple[str, str]] = {}  # name -> (mod, attr)
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.asname:
                        self.import_aliases[a.asname] = a.name
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    parts = self.module_name.split(".") if self.module_name \
                        else []
                    # level=1 in pkg/mod.py → pkg; in pkg/__init__.py →
                    # pkg itself (python relative-import semantics)
                    keep = len(parts) - node.level + (1 if self.is_package
                                                      else 0)
                    base = ".".join(parts[: max(0, keep)])
                    mod = f"{base}.{node.module}" if node.module and base \
                        else (node.module or base)
                else:
                    mod = node.module or ""
                for a in node.names:
                    self.from_imports[a.asname or a.name] = (mod, a.name)

    def cross_module_calls(self):
        """``(module, func_name, reason)`` for every call inside traced
        code that resolves through this module's imports instead of the
        module-local call graph — the seeds the cross-module linker
        plants into OTHER modules' traced indexes."""
        out: List[Tuple[str, str, str]] = []
        src = self.module_name or self.path

        def scan(body_node, scope, reason):
            for sub in ast.walk(body_node):
                if not isinstance(sub, ast.Call):
                    continue
                d = dotted_name(sub.func)
                if d is None:
                    continue
                parts = d.split(".")
                if len(parts) == 1:
                    if self.resolve_function(parts[0], scope) is not None:
                        continue  # module-local binding shadows the import
                    tgt = self.from_imports.get(parts[0])
                    if tgt is not None:
                        out.append((*self._from_target(tgt), reason))
                    continue
                head, attr = parts[:-1], parts[-1]
                if head[0] in self.import_aliases:
                    mod = ".".join([self.import_aliases[head[0]]] + head[1:])
                elif head[0] in self.from_imports:
                    m0, a0 = self.from_imports[head[0]]
                    mod = ".".join([f"{m0}.{a0}" if m0 else a0] + head[1:])
                else:
                    # plain `import a.b.c` binds `a`; the dotted call
                    # carries the full module path already
                    mod = ".".join(head)
                out.append((mod, attr, reason))

        for qn in list(self.traced):
            info = self.functions.get(qn)
            if info is not None:
                scan(info.node, qn,
                     f"called (cross-module) from traced {src}:{qn}")
        for lam in list(self.traced_lambdas):
            scope = self.enclosing_qualname(lam)
            scan(lam, "" if scope == "<module>" else scope,
                 f"called (cross-module) from a traced lambda in {src}")
        return out

    @staticmethod
    def _from_target(tgt: Tuple[str, str]) -> Tuple[str, str]:
        mod, attr = tgt
        return (mod, attr) if mod else (attr, "")

    def mark_external(self, qualname: str, reason: str) -> bool:
        """Seed a function as traced from ANOTHER module's call graph
        and re-run local propagation; True if anything new was marked."""
        if qualname not in self.functions or qualname in self.traced:
            return False
        self.traced[qualname] = reason
        self._propagate()
        return True

    def resolve_function(self, name: str,
                         from_qualname: str = "") -> Optional[str]:
        """Bare name -> qualname: innermost lexical match first."""
        scope = from_qualname
        while True:
            candidate = f"{scope}.{name}" if scope else name
            if candidate in self.functions:
                return candidate
            if "." not in scope:
                break
            scope = scope.rsplit(".", 1)[0]
        return name if name in self.functions else None

    # ---------------------------------------------------- traced index
    def _function_args_of_call(self, call: ast.Call) -> Iterator[ast.AST]:
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            yield arg

    def _mark(self, qualname: Optional[str], reason: str) -> None:
        if qualname is not None and qualname not in self.traced:
            self.traced[qualname] = reason

    def _mark_value(self, value: ast.AST, reason: str, scope: str,
                    aliases: Dict[str, str]) -> None:
        """Mark the function a call argument refers to: a bare Name, a
        ``partial(f, ...)`` wrapper, or a name previously aliased to
        either (``kernel = functools.partial(_fwd_kernel, ...)``)."""
        if isinstance(value, ast.Lambda):
            self.traced_lambdas.setdefault(value, reason)
        elif isinstance(value, ast.Name):
            target = aliases.get(value.id, value.id)
            self._mark(self.resolve_function(target, scope), reason)
        elif isinstance(value, ast.Call) and _is_partial(value) and value.args:
            inner = value.args[0]
            if isinstance(inner, ast.Name):
                target = aliases.get(inner.id, inner.id)
                self._mark(self.resolve_function(target, scope), reason)
        elif isinstance(value, ast.Attribute):
            name = last_name(value)
            if name:
                self._mark(self.resolve_function(name, scope), reason)

    def _build_traced_index(self) -> None:
        # 1. decorator seeds
        for qn, info in self.functions.items():
            node = info.node
            for dec in getattr(node, "decorator_list", []):
                target = dec.func if isinstance(dec, ast.Call) else dec
                name = last_name(target)
                if name in TRACE_DECORATORS:
                    self._mark(qn, f"decorated @{name}")
                elif name == "partial" and isinstance(dec, ast.Call) and dec.args:
                    inner = last_name(dec.args[0])
                    if inner in TRACE_DECORATORS:
                        self._mark(qn, f"decorated @partial({inner}, ...)")

        # 2. alias map (name -> function name via `x = f` / `x = partial(f,..)`)
        aliases: Dict[str, str] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                tgt = node.targets[0].id
                if isinstance(node.value, ast.Name):
                    aliases[tgt] = node.value.id
                elif isinstance(node.value, ast.Call) \
                        and _is_partial(node.value) and node.value.args \
                        and isinstance(node.value.args[0], ast.Name):
                    aliases[tgt] = node.value.args[0].id

        # 3. call-site seeds: f passed to jit/pallas_call/scan/defvjp/...
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            entry = last_name(node.func)
            if entry not in TRACE_ENTRYPOINTS:
                continue
            scope = self.enclosing_qualname(node)
            scope = "" if scope == "<module>" else scope
            for arg in self._function_args_of_call(node):
                self._mark_value(arg, f"passed to {entry}", scope, aliases)

        # 4. fixpoint propagation: lexical nesting + module-local calls
        self._propagate()

    def _propagate(self) -> None:
        """The traced-index fixpoint (lexical nesting + module-local
        calls) — separated from seeding so the cross-module linker can
        re-run it after planting external seeds."""
        changed = True
        while changed:
            changed = False
            for lam, reason in list(self.traced_lambdas.items()):
                scope = self.enclosing_qualname(lam)
                scope = "" if scope == "<module>" else scope
                for sub in ast.walk(lam.body):
                    if isinstance(sub, ast.Call):
                        callee = last_name(sub.func)
                        resolved = callee and self.resolve_function(
                            callee, scope)
                        if resolved and resolved not in self.traced:
                            self.traced[resolved] = \
                                f"called from traced lambda ({reason})"
                            changed = True
            for qn in list(self.traced):
                reason = self.traced[qn]
                info = self.functions.get(qn)
                if info is None:
                    continue
                # nested defs run under the same trace
                for other_qn in self.functions:
                    if other_qn.startswith(qn + ".") \
                            and other_qn not in self.traced:
                        self.traced[other_qn] = f"nested in traced {qn}"
                        changed = True
                # module-local callees are traced too
                for sub in ast.walk(info.node):
                    if isinstance(sub, ast.Call):
                        callee = last_name(sub.func)
                        if callee is None:
                            continue
                        resolved = self.resolve_function(callee, qn)
                        if resolved is not None \
                                and resolved not in self.traced:
                            self.traced[resolved] = \
                                f"called from traced {qn} ({reason})"
                            changed = True

    def traced_reason(self, node: ast.AST) -> Optional[str]:
        """Why the function (or lambda) enclosing ``node`` executes at
        trace time, or None if it does not (as far as this module
        shows).  Walks the whole lexical chain so code nested anywhere
        under a traced def/lambda is covered."""
        fn = self.enclosing_function(node)
        while fn is not None:
            if isinstance(fn, ast.Lambda):
                if fn in self.traced_lambdas:
                    return self.traced_lambdas[fn]
            else:
                qn = self.enclosing_qualname(fn)
                if qn in self.traced:
                    return self.traced[qn]
            fn = self.enclosing_function(fn)
        return None

    def mentions(self, *needles: str) -> bool:
        return any(n in self.source for n in needles)


# ------------------------------------------------------------------ engine
def discover_axis_registry(paths: Iterable[str]) -> Set[str]:
    """Mesh axis names from ``*_AXIS = "..."`` constants in any
    ``parallel_state.py`` under the scanned roots — the same constants
    ``initialize_model_parallel`` builds the Mesh from, so the linter
    and the runtime cannot drift.  Falls back to the well-known set."""
    axes: Set[str] = set()
    for ps in _find_files(paths, basename="parallel_state.py"):
        try:
            tree = ast.parse(open(ps, encoding="utf-8").read())
        except (OSError, SyntaxError):
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt = node.targets[0]
                if isinstance(tgt, ast.Name) and tgt.id.endswith("_AXIS") \
                        and isinstance(node.value, ast.Constant) \
                        and isinstance(node.value.value, str):
                    axes.add(node.value.value)
    return axes or set(DEFAULT_AXES)


def _find_files(paths: Iterable[str], basename: Optional[str] = None,
                suffix: str = ".py") -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(suffix) and (basename is None
                                       or os.path.basename(p) == basename):
                out.append(p)
        elif os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d not in ("__pycache__", ".git"))
                for f in sorted(files):
                    if f.endswith(suffix) and (basename is None
                                               or f == basename):
                        out.append(os.path.join(root, f))
    return out


def _load_module(path: str, display: str, axis_registry: Set[str],
                 module_name: str = "", is_package: bool = False):
    """Parse one file into a :class:`ModuleContext`, or the APX000
    :class:`Finding` describing why it could not be parsed — the ONE
    read/parse/error shape both entry points share."""
    try:
        source = open(path, encoding="utf-8").read()
    except OSError as e:
        return Finding("APX000", "error", display, 0, 0,
                       "<module>", f"unreadable: {e}", "fix file access")
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return Finding("APX000", "error", display,
                       e.lineno or 0, e.offset or 0, "<module>",
                       f"syntax error: {e.msg}", "fix the syntax error")
    return ModuleContext(display, source, tree, axis_registry,
                         module_name=module_name, is_package=is_package)


def analyze_file(path: str, rules: Iterable[Rule], axis_registry: Set[str],
                 display_path: Optional[str] = None) -> List[Finding]:
    loaded = _load_module(path, display_path or path, axis_registry)
    if isinstance(loaded, Finding):
        return [loaded]
    findings: List[Finding] = []
    for rule in rules:
        findings.extend(rule.check(loaded))
    return findings


def _module_name_for(file: str, root: str) -> str:
    """Dotted module name of ``file`` as imported from ``root``'s
    parent: a package root (dir with ``__init__.py``) contributes its
    own name (``apex_tpu/ops/x.py`` scanned via root ``apex_tpu`` →
    ``apex_tpu.ops.x``); a bare dir's files are top-level modules; a
    file root is its own module (``chip_smoke.py`` → ``chip_smoke``)."""
    if os.path.isfile(root):
        rel = os.path.basename(file)
    else:
        rel = os.path.relpath(file, root)
        if os.path.isfile(os.path.join(root, "__init__.py")):
            rel = os.path.join(
                os.path.basename(os.path.abspath(root.rstrip(os.sep))), rel)
    mod = rel[:-3] if rel.endswith(".py") else rel
    mod = mod.replace(os.sep, ".").replace("/", ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod


def _link_cross_module(ctxs: Dict[str, Optional["ModuleContext"]]) -> None:
    """Global traced-reachability fixpoint: a function called from a
    traced function in ANOTHER module is traced too (the per-module
    index misses exactly this — e.g. ``fused_ce_pallas.
    _default_dot_dtype``'s env read reached from ``fused_ce._fwd``).
    ``None`` entries mark ambiguous module names (two scanned files
    claimed the same dotted name) — never linked through, so a seed
    cannot land in the wrong file.  Each module's call list is
    recomputed only when its traced set grew (``cross_module_calls``
    walks every traced body — a per-round full rescan would be
    O(rounds × corpus))."""
    live = [c for c in ctxs.values() if c is not None]
    memo: Dict[int, Tuple[int, list]] = {}
    changed = True
    while changed:
        changed = False
        for ctx in live:
            n = len(ctx.traced) + len(ctx.traced_lambdas)
            if memo.get(id(ctx), (-1,))[0] != n:
                memo[id(ctx)] = (n, ctx.cross_module_calls())
            for mod, attr, reason in memo[id(ctx)][1]:
                target = ctxs.get(mod)
                if target is None or target is ctx:
                    continue
                if target.mark_external(attr, reason):
                    changed = True


def _load_module_task(args):
    """Process-pool worker: parse one file AND build its traced index
    and axis-scope index (the per-file fixpoints are the expensive
    half of a scan) so ``--jobs N`` parallelizes real work, not just
    ``ast.parse``.  Top-level so it pickles under the spawn start
    method."""
    path, display, registry, module_name, is_package = args
    loaded = _load_module(path, display, set(registry),
                          module_name=module_name, is_package=is_package)
    if not isinstance(loaded, Finding):
        from apex_tpu.analysis import dataflow

        dataflow.scope_index(loaded)
        dataflow.taint_index(loaded)
        dataflow.thread_index(loaded)
    return loaded


def _load_all(tasks, jobs: int):
    """The per-file parse/index pass, serial or process-parallel.  The
    parallel path degrades to serial on ANY pool failure (a module
    whose AST defeats pickling, a sandbox without multiprocessing) —
    ``--jobs`` may never change results, only wall time."""
    if jobs <= 1 or len(tasks) <= 1:
        return [_load_module_task(t) for t in tasks]
    try:
        from concurrent.futures import ProcessPoolExecutor

        with ProcessPoolExecutor(max_workers=jobs) as pool:
            return list(pool.map(_load_module_task, tasks))
    except Exception:
        return [_load_module_task(t) for t in tasks]


def analyze_paths(paths: Iterable[str], rules: Iterable[Rule],
                  axis_registry: Optional[Set[str]] = None,
                  rel_to: Optional[str] = None, jobs: int = 1,
                  timings: Optional[Dict[str, float]] = None
                  ) -> List[Finding]:
    """Run every rule over every ``*.py`` under ``paths``; findings are
    sorted by (path, line, rule) for stable output and baselines.

    Unlike :func:`analyze_file`, this multi-file entry point links the
    per-module traced indexes across modules first (import-resolved
    call-graph reachability), so trace-time hazards in helpers reached
    only from another module's jitted code are still flagged.

    ``jobs``: parallelize the per-file parse + index build across N
    worker processes (the module linking and rule checks stay
    single-pass in this process — they need the full module set).
    ``timings``: pass a dict to collect per-rule wall seconds (the
    CLI's ``--timing``); keys are rule ids plus ``"<load>"`` and
    ``"<link>"`` for the two shared phases."""
    import time as _time

    paths = list(paths)
    registry = axis_registry if axis_registry is not None \
        else discover_axis_registry(paths)
    rules = list(rules)
    findings: List[Finding] = []
    ctxs: Dict[str, Optional[ModuleContext]] = {}
    ordered: List[ModuleContext] = []
    tasks = []
    for root in paths:
        for f in _find_files([root]):
            display = os.path.relpath(f, rel_to) if rel_to else f
            tasks.append((f, display, tuple(sorted(registry)),
                          _module_name_for(f, root),
                          os.path.basename(f) == "__init__.py"))
    t0 = _time.monotonic()
    for loaded in _load_all(tasks, jobs):
        if isinstance(loaded, Finding):
            findings.append(loaded)
            continue
        if loaded.module_name in ctxs:
            # two scanned files claim one dotted name (e.g. utils.py
            # under two bare roots): linking through the name would
            # plant seeds in whichever file happened to win — mark
            # ambiguous and never link through it
            ctxs[loaded.module_name] = None
        else:
            ctxs[loaded.module_name] = loaded
        ordered.append(loaded)
    if timings is not None:
        timings["<load>"] = _time.monotonic() - t0
    t0 = _time.monotonic()
    _link_cross_module(ctxs)
    # the axis-scope dataflow runs its own cross-module fixpoint so the
    # collective rules see shard_map wrappers that live in other files
    # (imported here, not at module top: dataflow imports core)
    from apex_tpu.analysis import dataflow
    dataflow.link_axis_scopes(ctxs)
    # ... and the host-divergence taint lattice runs ITS cross-module
    # fixpoint (imported taint-returning helpers, taint cycles)
    dataflow.link_taint(ctxs)
    # ... and the thread-reachability index links thread targets and
    # on_*-callback seams handed across module boundaries
    dataflow.link_threads(ctxs)
    if timings is not None:
        timings["<link>"] = _time.monotonic() - t0
    for rule in rules:
        t0 = _time.monotonic()
        for ctx in ordered:
            findings.extend(rule.check(ctx))
        if timings is not None:
            timings[rule.rule_id] = timings.get(rule.rule_id, 0.0) \
                + _time.monotonic() - t0
    findings.sort(key=lambda x: (x.path, x.line, x.rule))
    return findings
