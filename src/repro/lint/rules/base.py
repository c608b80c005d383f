"""Shared infrastructure for repro.lint rules: findings, the rule base
class, and the per-module AST index (imports, scopes, jit reachability).

Every rule is a class with a unique ``code`` (R1..R5), registered in
``repro.lint.rules`` exactly like a ``FedMethod`` in ``core.methods``.
A rule implements either or both hooks:

  check_module(mod)   called once per parsed source file (AST rules)
  check_project(ctx)  called once per lint run (whole-repo rules, e.g.
                      R5's live-registry dead-mask evaluation)

The jit-reachability index is module-local on purpose: a function is
"jit-reachable" when it is (a) passed to ``jax.jit`` / ``jax.vmap`` /
``jax.pmap`` / ``jax.shard_map`` / ``jax.lax.scan`` (possibly through
``functools.partial`` or ``obs.annotate(...)(...)``),
(b) decorated with a jit wrapper, or (c) referenced by name from the
body of another jit-reachable function in the same module.  Cross-module
tracing (``model.forward`` called from a jitted round body) is out of
scope — the callee module's own ``lax.scan`` entry points cover the hot
paths there.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class Finding:
    """One analyzer hit.  ``path`` is repo-relative (posix separators);
    ``line``/``col`` are 1-based/0-based as in CPython's ast."""
    rule: str
    path: str
    line: int
    col: int
    message: str
    # the stripped source line the finding sits on — baseline entries
    # match on (rule, path, line_text) so they survive line-number drift
    line_text: str = ""

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @property
    def sig(self) -> tuple:
        return (self.rule, self.path, self.line_text)

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


class Rule:
    """Base class for lint rules (see module docstring for the hooks)."""
    code: str = "R0"
    name: str = ""
    description: str = ""

    def check_module(self, mod: "ModuleInfo") -> list[Finding]:
        return []

    def check_project(self, ctx: "ProjectContext") -> list[Finding]:
        return []


@dataclasses.dataclass
class ProjectContext:
    """Whole-run context handed to ``Rule.check_project``."""
    root: str                      # repo root (directory of pyproject.toml)
    modules: list                  # every parsed ModuleInfo in the run

    def module(self, rel_suffix: str) -> Optional["ModuleInfo"]:
        """Find a parsed module by repo-relative path suffix."""
        for m in self.modules:
            if m.rel.endswith(rel_suffix):
                return m
        return None


# ---------------------------------------------------------------------------
# AST helpers
# ---------------------------------------------------------------------------

def dotted_name(node) -> str:
    """``jax.lax.scan`` for an Attribute chain, ``jit`` for a Name,
    '' for anything dynamic."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def last_seg(node) -> str:
    d = dotted_name(node)
    return d.rsplit(".", 1)[-1] if d else ""


def func_operand(node) -> Optional[ast.Name]:
    """Unwrap an expression to the function-valued Name it forwards:
    ``f`` / ``partial(f, ...)`` / ``jax.jit(f)`` / ``annotate(..)(jit(f))``."""
    if isinstance(node, ast.Name):
        return node
    if isinstance(node, ast.Call) and node.args:
        nm = last_seg(node.func)
        if nm in ("partial", "jit", "vmap", "pmap", "checkpoint", "remat"):
            return func_operand(node.args[0])
        if isinstance(node.func, ast.Call):        # annotate(...)(inner)
            return func_operand(node.args[0])
    return None


_JIT_WRAPPERS = ("jit", "vmap", "pmap", "shard_map")
FunctionNode = (ast.FunctionDef, ast.AsyncFunctionDef)


def jit_entry_operands(call: ast.Call) -> list:
    """Expressions passed as traced bodies to this call, if it is a jit
    wrapper / scan; [] otherwise."""
    nm = last_seg(call.func)
    dotted = dotted_name(call.func)
    if nm in _JIT_WRAPPERS and call.args:
        return [call.args[0]]
    if nm == "scan" and call.args and ("lax" in dotted or dotted == "scan"):
        return [call.args[0]]
    return []


def is_jit_decorator(dec) -> bool:
    if isinstance(dec, (ast.Name, ast.Attribute)):
        return last_seg(dec) in ("jit", "vmap", "pmap")
    if isinstance(dec, ast.Call):
        nm = last_seg(dec.func)
        if nm in ("jit", "vmap", "pmap"):
            return True
        if nm == "partial" and dec.args:
            return last_seg(dec.args[0]) in ("jit", "vmap", "pmap")
    return False


def walk_skip_nested(fn) -> list:
    """All descendant nodes of a function def, not descending into nested
    function/class defs (their bodies are separate analysis units)."""
    out: list = []
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        out.append(node)
        if isinstance(node, FunctionNode + (ast.ClassDef,)):
            continue
        stack.extend(ast.iter_child_nodes(node))
    return out


class _Scope:
    """One lexical scope (module or function): its immediate function
    defs and its simple function aliases (``x = partial(f, ...)``)."""

    def __init__(self, node, parent: Optional["_Scope"]):
        self.node = node
        self.parent = parent
        self.defs: dict[str, ast.AST] = {}
        self.aliases: dict[str, str] = {}

    def resolve(self, name: str):
        scope: Optional[_Scope] = self
        seen = set()
        while scope is not None:
            if name in scope.defs:
                return scope.defs[name]
            if name in scope.aliases and name not in seen:
                seen.add(name)
                name = scope.aliases[name]
                continue
            scope = scope.parent
        return None


class ModuleInfo:
    """One parsed source file plus lazily-built analysis indexes."""

    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.parents: dict[int, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parents[id(child)] = node
        # import aliases: {"np": "numpy", "jnp": "jax.numpy", ...}
        self.imports: dict[str, str] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.imports[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.imports[a.asname or a.name] = \
                        f"{node.module}.{a.name}"
        self._scopes: Optional[dict[int, _Scope]] = None
        self._reachable: Optional[list] = None

    # -- plumbing ---------------------------------------------------------

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def finding(self, rule: str, node, message: str) -> Finding:
        return Finding(rule=rule, path=self.rel, line=node.lineno,
                       col=node.col_offset, message=message,
                       line_text=self.line_text(node.lineno))

    def enclosing_function(self, node):
        cur = self.parents.get(id(node))
        while cur is not None and not isinstance(cur, FunctionNode):
            cur = self.parents.get(id(cur))
        return cur

    def numpy_aliases(self) -> set[str]:
        return {alias for alias, mod in self.imports.items()
                if mod == "numpy" or mod.startswith("numpy.")}

    # -- scopes -----------------------------------------------------------

    def scopes(self) -> dict[int, _Scope]:
        if self._scopes is not None:
            return self._scopes
        scopes: dict[int, _Scope] = {}

        def build(node, parent_scope):
            scope = _Scope(node, parent_scope)
            scopes[id(node)] = scope
            for sub in walk_skip_nested(node) if isinstance(
                    node, FunctionNode) else ast.walk(node):
                if sub is node:
                    continue
                if isinstance(sub, FunctionNode):
                    owner = self.enclosing_function(sub)
                    owner_scope = scopes.get(id(owner)) if owner else \
                        scopes[id(self.tree)]
                    if owner_scope is scope or (owner is None
                                                and node is self.tree):
                        scope.defs[sub.name] = sub
                elif isinstance(sub, ast.Assign) and len(sub.targets) == 1 \
                        and isinstance(sub.targets[0], ast.Name):
                    op = func_operand(sub.value)
                    if op is not None and op.id != sub.targets[0].id:
                        scope.aliases[sub.targets[0].id] = op.id

        # module scope first (walks everything for module-level defs is
        # wrong — restrict to statement-level recursion)
        def build_exact(node, parent_scope):
            scope = _Scope(node, parent_scope)
            scopes[id(node)] = scope
            for sub in walk_skip_nested(node) if isinstance(
                    node, FunctionNode) else self._walk_module_level(node):
                if isinstance(sub, FunctionNode):
                    scope.defs[sub.name] = sub
                elif isinstance(sub, ast.Assign) and len(sub.targets) == 1 \
                        and isinstance(sub.targets[0], ast.Name):
                    op = func_operand(sub.value)
                    if op is not None and op.id != sub.targets[0].id:
                        scope.aliases[sub.targets[0].id] = op.id
            for name, fn in scope.defs.items():
                build_exact(fn, scope)

        build_exact(self.tree, None)
        self._scopes = scopes
        return scopes

    def _walk_module_level(self, node) -> list:
        """Module/class statements, not descending into function defs
        (class bodies are transparent: methods resolve like module-level
        defs for reachability purposes)."""
        out: list = []
        stack = list(ast.iter_child_nodes(node))
        while stack:
            sub = stack.pop()
            out.append(sub)
            if isinstance(sub, FunctionNode):
                continue
            stack.extend(ast.iter_child_nodes(sub))
        return out

    def scope_of(self, node) -> _Scope:
        scopes = self.scopes()
        fn = node if isinstance(node, FunctionNode) else \
            self.enclosing_function(node)
        while fn is not None:
            s = scopes.get(id(fn))
            if s is not None:
                return s
            fn = self.enclosing_function(fn)
        return scopes[id(self.tree)]

    # -- jit reachability -------------------------------------------------

    def jit_reachable(self) -> list:
        """Function defs traced under jit/vmap/shard_map/scan (see module
        docstring for the exact contract)."""
        if self._reachable is not None:
            return self._reachable
        entries: list = []
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call):
                for operand in jit_entry_operands(node):
                    op = func_operand(operand)
                    if op is None:
                        continue
                    target = self.scope_of(node).resolve(op.id)
                    if isinstance(target, FunctionNode):
                        entries.append(target)
            elif isinstance(node, FunctionNode):
                if any(is_jit_decorator(d) for d in node.decorator_list):
                    entries.append(node)
        reachable: dict[int, ast.AST] = {}
        stack = entries
        while stack:
            fn = stack.pop()
            if id(fn) in reachable:
                continue
            reachable[id(fn)] = fn
            scope = self.scopes().get(id(fn))
            for node in walk_skip_nested(fn):
                if isinstance(node, ast.Name) and isinstance(
                        node.ctx, ast.Load) and scope is not None:
                    target = scope.resolve(node.id)
                    if isinstance(target, FunctionNode):
                        stack.append(target)
        self._reachable = sorted(reachable.values(), key=lambda f: f.lineno)
        return self._reachable
