"""AST linter for the repro tree (DESIGN.md §9).

The codebase's hot-path invariants — no host syncs inside jitted code, no
tracer-dependent Python branching, one Pallas dispatch policy — are enforced
dynamically by the test suite but are trivially easy to reintroduce in a
cold corner no test exercises. This module enforces them *statically*:

1. **Module index** (:class:`ModuleInfo`): every file under ``src/repro``
   is parsed once; function defs (including nested, by dotted qualname),
   import aliases and ``from``-imports are indexed so calls like
   ``MD.decode_step_slots`` resolve across modules.
2. **Jit reachability** (:class:`Analyzer`): roots are the functions that
   become jit/scan/cond/vmap/pallas bodies — passed by name, returned by a
   maker whose result is jitted (``jax.jit(make_slot_admit(cfg))`` marks
   every function nested in ``make_slot_admit``), decorated with ``jax.jit``
   / ``functools.partial(jax.jit, ...)`` / ``pallas_dispatch``, or called
   from a jitted lambda. The call graph is walked transitively; rules that
   only make sense inside traced code (host casts, numpy-on-traced,
   tracer branching) fire only in reachable functions.
3. **Taint** (in ``rules.py``): inside a reachable function, names assigned
   from ``jnp.``/``jax.``/``lax.`` calls (and subscripts/arithmetic over
   them) are treated as traced values. Parameters are deliberately NOT
   assumed traced — makers close over static Python config everywhere in
   this tree, and assuming params traced would drown the signal in false
   positives. The fixture tests in ``tests/test_analysis.py`` pin what each
   rule can and cannot see.

Suppressions: ``# lint: ignore[RA###] <reason>`` on the offending line
drops the finding but records it (``LintReport.suppressed``); the CLI
prints the count so blanket-suppressed trees stay visible in review.
"""
from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, Iterable, List, Optional, Set, Tuple

__all__ = [
    "Finding", "LintReport", "ModuleInfo", "Analyzer", "run_lint",
    "repo_src_root",
]

# dotted call targets whose function-valued arguments become traced bodies
JIT_WRAPPERS = {
    "jax.jit", "jax.pjit", "jax.vmap", "jax.pmap", "jax.grad",
    "jax.value_and_grad", "jax.eval_shape", "jax.checkpoint", "jax.remat",
    "jax.lax.scan", "jax.lax.cond", "jax.lax.while_loop",
    "jax.lax.fori_loop", "jax.lax.switch", "jax.lax.map",
    "jax.experimental.pallas.pallas_call",
    "jax.shard_map",
}
# method names that jit their function argument regardless of receiver:
# TraceGuard.wrap_jit(name, fn, ...) is the engine's registration point for
# every hot entry, so a body handed to it is a traced body even when the
# receiver (`self._guard`) can't be resolved statically
JIT_WRAPPER_METHODS = {"wrap_jit"}
# decorators that mark a def as a traced body outright
JIT_DECORATORS = {"jax.jit", "pallas_dispatch"}


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    col: int
    msg: str
    reason: str = ""          # suppression reason (suppressed findings only)

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.msg}"


@dataclasses.dataclass
class LintReport:
    findings: List[Finding]
    suppressed: List[Finding]

    @property
    def ok(self) -> bool:
        return not self.findings


def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.lax.scan' for an Attribute/Name chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class ModuleInfo:
    """One parsed module: function index, import maps, suppressions."""

    def __init__(self, name: str, path: str, source: str):
        self.name = name
        self.path = path
        self.source = source
        self.tree = ast.parse(source, filename=path)
        # qualname ('outer.inner' for nested defs) -> FunctionDef node
        self.funcs: Dict[str, ast.AST] = {}
        self.import_alias: Dict[str, str] = {}      # 'MD' -> 'repro.models.model'
        self.from_funcs: Dict[str, Tuple[str, str]] = {}  # 'init' -> (mod, name)
        self.suppressions: Dict[int, Tuple[Set[str], str]] = {}
        self._index()
        self._scan_suppressions()

    # ------------------------------------------------------------- indexing
    def _index(self) -> None:
        def visit(node: ast.AST, prefix: str) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    q = f"{prefix}{child.name}" if prefix else child.name
                    self.funcs[q] = child
                    visit(child, q + ".")
                elif isinstance(child, ast.ClassDef):
                    visit(child, (prefix + child.name + "."))
                else:
                    visit(child, prefix)

        visit(self.tree, "")
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.import_alias[a.asname or a.name.split(".")[0]] = (
                        a.name)
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    local = a.asname or a.name
                    full = f"{node.module}.{a.name}"
                    # `from repro.models import model as MD` -> module alias;
                    # `from x import f` -> either a function or a module;
                    # record both views, resolution tries funcs first.
                    self.import_alias[local] = full
                    self.from_funcs[local] = (node.module, a.name)

    def _scan_suppressions(self) -> None:
        import re
        pat = re.compile(r"#\s*lint:\s*ignore\[([A-Z0-9,\s]+)\]\s*(.*)")
        for i, line in enumerate(self.source.splitlines(), start=1):
            m = pat.search(line)
            if m:
                codes = {c.strip() for c in m.group(1).split(",") if c.strip()}
                self.suppressions[i] = (codes, m.group(2).strip())

    # ----------------------------------------------------------- resolution
    def expand(self, dotted: str) -> str:
        """Map a dotted call through this module's import aliases:
        'lax.scan' -> 'jax.lax.scan', 'MD.forward' ->
        'repro.models.model.forward'."""
        root, _, rest = dotted.partition(".")
        full = self.import_alias.get(root)
        if full is None:
            return dotted
        return f"{full}.{rest}" if rest else full


def repo_src_root() -> str:
    """Directory holding the ``repro`` package (…/src). ``repro`` is a
    namespace package (no __init__.py), so resolve via ``__path__``."""
    import repro
    return os.path.dirname(os.path.abspath(list(repro.__path__)[0]))


def load_modules(root: Optional[str] = None) -> Dict[str, ModuleInfo]:
    """Parse every repro module under ``root`` (default: the installed
    src tree) into :class:`ModuleInfo` keyed by module name."""
    root = root or repo_src_root()
    mods: Dict[str, ModuleInfo] = {}
    for dirpath, dirnames, filenames in os.walk(os.path.join(root, "repro")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root)
            name = rel[:-3].replace(os.sep, ".")
            if name.endswith(".__init__"):
                name = name[: -len(".__init__")]
            with open(path, "r", encoding="utf-8") as f:
                src = f.read()
            mods[name] = ModuleInfo(name, path, src)
    return mods


class Analyzer:
    """Cross-module jit-reachability over the parsed tree."""

    def __init__(self, modules: Dict[str, ModuleInfo]):
        self.modules = modules
        # (module_name, qualname) pairs
        self.roots: Set[Tuple[str, str]] = set()
        # static_argnames recorded for directly-jitted defs (rule RA006)
        self.jit_statics: Dict[Tuple[str, str], Set[str]] = {}
        self._edges: Dict[Tuple[str, str], Set[Tuple[str, str]]] = {}
        self._collect_roots()
        self._collect_edges()
        self.reachable = self._walk()

    # -------------------------------------------------------------- helpers
    def _resolve(self, mod: ModuleInfo, scope: str,
                 node: ast.AST) -> Optional[Tuple[str, str]]:
        """Resolve a Name/Attribute callee to (module, qualname)."""
        if isinstance(node, ast.Name):
            # innermost enclosing scope outward
            parts = scope.split(".") if scope else []
            for i in range(len(parts), -1, -1):
                q = ".".join(parts[:i] + [node.id])
                if q in mod.funcs:
                    return (mod.name, q)
            if node.id in mod.from_funcs:
                m, f = mod.from_funcs[node.id]
                target = self.modules.get(m)
                if target and f in target.funcs:
                    return (m, f)
                # `from x import y` where y is a module
                if f"{m}.{f}" in self.modules:
                    return None
            return None
        if isinstance(node, ast.Attribute):
            dotted = _dotted(node)
            if dotted is None:
                return None
            full = mod.expand(dotted)
            m, _, f = full.rpartition(".")
            target = self.modules.get(m)
            if target and f in target.funcs:
                return (m, f)
        return None

    @staticmethod
    def _unwrap_partial(node: ast.AST) -> ast.AST:
        """functools.partial(f, ...) -> f (one level)."""
        if (isinstance(node, ast.Call)
                and _dotted(node.func) in ("functools.partial", "partial")
                and node.args):
            return node.args[0]
        return node

    def _local_assigns(self, fn: ast.AST) -> Dict[str, List[ast.AST]]:
        # EVERY assignment to the name, not just the last: the engine picks
        # its jit bodies by branch (`fn = mesh_maker(...)` in one arm,
        # `fn = maker(...)` in the other) and both arms are traced bodies
        out: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                out.setdefault(node.targets[0].id, []).append(node.value)
        return out

    def _mark_body(self, mod: ModuleInfo, scope: str, arg: ast.AST,
                   assigns: Dict[str, ast.AST], depth: int = 0) -> None:
        """Mark the function(s) an argument expression denotes as roots."""
        if depth > 4:
            return
        arg = self._unwrap_partial(arg)
        if isinstance(arg, ast.IfExp):
            # `maker_a(...) if cond else maker_b(...)`: either arm may be
            # the jitted body depending on runtime config — mark both
            self._mark_body(mod, scope, arg.body, assigns, depth + 1)
            self._mark_body(mod, scope, arg.orelse, assigns, depth + 1)
            return
        if isinstance(arg, ast.Lambda):
            # a jitted lambda's callees are the traced bodies
            for sub in ast.walk(arg.body):
                if isinstance(sub, ast.Call):
                    t = self._resolve(mod, scope, sub.func)
                    if t:
                        self.roots.add(t)
            return
        if isinstance(arg, ast.Call):
            # jit(make_x(cfg)): every def nested in the maker is a body
            maker = self._resolve(mod, scope, arg.func)
            if maker:
                mmod, mq = maker
                for q in self.modules[mmod].funcs:
                    if q.startswith(mq + "."):
                        self.roots.add((mmod, q))
                # the maker itself runs on host but may return a plain
                # module function; treat it as reachable-for-rules too
                self.roots.add(maker)
            return
        target = self._resolve(mod, scope, arg)
        if target:
            self.roots.add(target)
            return
        if isinstance(arg, ast.Name) and arg.id in assigns:
            for value in assigns[arg.id]:
                self._mark_body(mod, scope, value, assigns, depth + 1)

    # ---------------------------------------------------------------- roots
    def _collect_roots(self) -> None:
        for mod in self.modules.values():
            # decorator-marked bodies
            for q, fn in mod.funcs.items():
                for dec in getattr(fn, "decorator_list", []):
                    d = self._unwrap_partial(dec)
                    dotted = _dotted(d if not isinstance(d, ast.Call)
                                     else d.func)
                    name = mod.expand(dotted) if dotted else None
                    base = dotted.rsplit(".", 1)[-1] if dotted else None
                    if name in JIT_DECORATORS or base in JIT_DECORATORS:
                        self.roots.add((mod.name, q))
                        self.jit_statics[(mod.name, q)] = (
                            self._static_names(dec))
            # call-site bodies, scoped so local assigns resolve
            for scope, fn in list(mod.funcs.items()) + [("", mod.tree)]:
                assigns = self._local_assigns(fn)
                for node in self._own_nodes(fn):
                    if not isinstance(node, ast.Call):
                        continue
                    dotted = _dotted(node.func)
                    if dotted is None:
                        continue
                    full = mod.expand(dotted)
                    short = dotted.rsplit(".", 1)[-1]
                    if full in JIT_WRAPPERS or short in JIT_WRAPPER_METHODS \
                            or (short == "pallas_call" and "pallas" in full):
                        for arg in list(node.args) + [
                                kw.value for kw in node.keywords]:
                            self._mark_body(mod, scope, arg, assigns)
                        # record static_argnames for directly-jitted defs
                        if full in ("jax.jit", "jax.pjit") and node.args:
                            t = self._resolve(mod, scope,
                                              self._unwrap_partial(
                                                  node.args[0]))
                            if t:
                                self.jit_statics.setdefault(
                                    t, set()).update(
                                        self._static_names(node))

    @staticmethod
    def _static_names(node: ast.AST) -> Set[str]:
        """static_argnames entries of a jit call/partial-decorator node."""
        out: Set[str] = set()
        if not isinstance(node, ast.Call):
            return out
        for kw in node.keywords:
            if kw.arg == "static_argnames":
                for sub in ast.walk(kw.value):
                    if isinstance(sub, ast.Constant) and isinstance(
                            sub.value, str):
                        out.add(sub.value)
        return out

    # ---------------------------------------------------------------- edges
    def _own_nodes(self, fn: ast.AST) -> Iterable[ast.AST]:
        """All nodes of ``fn`` excluding nested function bodies (those have
        their own entries)."""
        stack = (list(ast.iter_child_nodes(fn)) if isinstance(
            fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Module))
            else [fn])
        while stack:
            node = stack.pop()
            yield node
            for child in ast.iter_child_nodes(node):
                if isinstance(child,
                              (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                stack.append(child)

    def _collect_edges(self) -> None:
        for mod in self.modules.values():
            for q, fn in mod.funcs.items():
                edges: Set[Tuple[str, str]] = set()
                for node in self._own_nodes(fn):
                    if isinstance(node, ast.Call):
                        t = self._resolve(mod, q, node.func)
                        if t:
                            edges.add(t)
                    elif isinstance(node, (ast.Name, ast.Attribute)):
                        # passing a function by reference (partial args,
                        # tree.map callables) keeps it reachable
                        t = self._resolve(mod, q, node)
                        if t:
                            edges.add(t)
                self._edges[(mod.name, q)] = edges

    def _walk(self) -> Set[Tuple[str, str]]:
        seen: Set[Tuple[str, str]] = set()
        frontier = list(self.roots)
        while frontier:
            cur = frontier.pop()
            if cur in seen or cur[1] not in self.modules.get(
                    cur[0], ModuleInfo("", "<none>", "")).funcs:
                if cur in seen:
                    continue
            seen.add(cur)
            for nxt in self._edges.get(cur, ()):
                if nxt not in seen:
                    frontier.append(nxt)
        return seen


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_lint(root: Optional[str] = None,
             rules: Optional[Iterable[str]] = None) -> LintReport:
    """Lint the repro tree. ``root``: directory containing the ``repro``
    package (defaults to the installed one). ``rules``: optional rule-id
    allowlist."""
    from repro.analysis import rules as R
    modules = load_modules(root)
    analyzer = Analyzer(modules)
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    active = list(R.RULES)
    if rules is not None:
        wanted = set(rules)
        active = [r for r in active if r.rule_id in wanted]
    for mod in modules.values():
        for rule in active:
            for f in rule.check(mod, analyzer):
                sup = mod.suppressions.get(f.line)
                if sup and f.rule in sup[0]:
                    suppressed.append(dataclasses.replace(
                        f, reason=sup[1] or "(no reason given)"))
                else:
                    findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    suppressed.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return LintReport(findings, suppressed)
