"""Lint framework: module contexts, traced-scope detection, suppressions.

A *traced scope* is a function whose Python body executes under a JAX trace
— anything passed (by name or as a lambda) to ``jax.jit`` / ``lax.scan`` /
``jax.vmap`` / ``shard_map`` / ``pallas_call`` / control-flow combinators,
anything decorated with ``jit``, anything that bumps a ``TRACE_WHITELIST``
counter (``TRACE_COUNTS``, the repo's trace-time marker), and anything
lexically nested inside one of those. The detection over-approximates (a
name collision marks an unrelated same-named def) — acceptable for a lint
whose false positives are one ``# repro: allow[Rn]`` away.

Suppressions: ``# repro: allow[R1]`` (or ``allow[R1,R4]``) on the violating
line or on the line directly above it. Every suppression is inventoried in
the report, used or not, so dead suppressions are visible.
"""
from __future__ import annotations

import ast
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Set

SUPPRESS_RE = re.compile(r"#\s*repro:\s*allow\[([^\]]+)\]")

# call names whose function-valued arguments are traced
TRACE_ENTRY_NAMES = {
    "jit", "scan", "vmap", "pmap", "shard_map", "pallas_call", "make_jaxpr",
    "switch", "cond", "while_loop", "fori_loop", "checkpoint", "remat",
    "grad", "value_and_grad", "custom_vjp", "custom_jvp", "eval_shape",
}

# array-materializing constructors (terminal attribute names)
ARRAY_CTORS = {
    "array", "asarray", "zeros", "ones", "arange", "linspace", "eye",
    "full", "stack", "concatenate", "tile",
}

NUMPY_ROOTS = {"np", "numpy"}
JNP_ROOTS = {"jnp", "np", "numpy"} | {"jax"}


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    path: str
    line: int
    message: str
    suppressed: bool = False

    def format(self) -> str:
        mark = " (suppressed)" if self.suppressed else ""
        return f"{self.path}:{self.line}: {self.rule}{mark}: {self.message}"


def terminal_name(node) -> Optional[str]:
    """The last attribute segment of a call target: ``jax.lax.scan`` →
    ``scan``, ``fold_in`` → ``fold_in``."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def root_name(node) -> Optional[str]:
    """The leftmost name of an attribute chain: ``jnp.zeros`` → ``jnp``."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _set_parents(tree: ast.AST) -> None:
    for parent in ast.walk(tree):
        for child in ast.iter_child_nodes(parent):
            child._repro_parent = parent  # type: ignore[attr-defined]


def parent_of(node: ast.AST) -> Optional[ast.AST]:
    return getattr(node, "_repro_parent", None)


def enclosing_functions(node: ast.AST) -> List[ast.AST]:
    """Innermost-first chain of enclosing function/lambda nodes."""
    out = []
    cur = parent_of(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            out.append(cur)
        cur = parent_of(cur)
    return out


def local_bindings(fn_node) -> Set[str]:
    """Names bound inside a function (params + assignment/for/with/
    comprehension targets), EXCLUDING bindings of nested defs."""
    out: Set[str] = set()
    if isinstance(fn_node, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
        a = fn_node.args
        for arg in (a.posonlyargs + a.args + a.kwonlyargs
                    + ([a.vararg] if a.vararg else [])
                    + ([a.kwarg] if a.kwarg else [])):
            out.add(arg.arg)
    body = fn_node.body if isinstance(fn_node.body, list) else [fn_node.body]
    for stmt in body:
        for sub in ast.walk(stmt):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.add(sub.name)
            elif isinstance(sub, ast.Name) and isinstance(
                    sub.ctx, (ast.Store, ast.Del)):
                out.add(sub.id)
    return out


# the ONLY module-level object a traced body may mutate: the trace-time
# bookkeeping counter TRACE_COUNTS, the retrace-discipline marker
# (repro.core.runner) — it records "this body traced", never per-call state.
TRACE_WHITELIST = {"TRACE_COUNTS"}


def _is_trace_counts_target(node) -> bool:
    """True when an expression's attribute/subscript chain ends at one of
    the ``TRACE_WHITELIST`` counters (the whitelisted trace-time side
    effects)."""
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        if isinstance(node, ast.Attribute) and node.attr in TRACE_WHITELIST:
            return True
        node = node.value
    return isinstance(node, ast.Name) and node.id in TRACE_WHITELIST


def module_array_bindings(tree: ast.Module) -> Dict[str, int]:
    """Module-level ``NAME = jnp/np.<ctor>(...)`` bindings: name → line.
    These are exactly the arrays a traced body must NOT capture by closure
    (they bake into the jaxpr as consts instead of riding as operands)."""
    out: Dict[str, int] = {}
    for stmt in tree.body:
        targets = []
        if isinstance(stmt, ast.Assign):
            targets = stmt.targets
            value = stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets = [stmt.target]
            value = stmt.value
        else:
            continue
        if not (isinstance(value, ast.Call)
                and terminal_name(value.func) in ARRAY_CTORS
                and root_name(value.func) in JNP_ROOTS):
            continue
        for t in targets:
            if isinstance(t, ast.Name):
                out[t.id] = stmt.lineno
    return out


def module_level_names(tree: ast.Module) -> Set[str]:
    out: Set[str] = set()
    for stmt in tree.body:
        if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = (stmt.targets if isinstance(stmt, ast.Assign)
                       else [stmt.target])
            for t in targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
    return out


def find_traced_scopes(tree: ast.Module) -> Set[ast.AST]:
    """All function/lambda nodes whose bodies run under a JAX trace (see
    module docstring for the heuristic)."""
    traced: Set[ast.AST] = set()
    defs_by_name: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, []).append(node)

    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and (
                terminal_name(node.func) in TRACE_ENTRY_NAMES):
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name):
                    traced.update(defs_by_name.get(arg.id, ()))
                elif isinstance(arg, ast.Lambda):
                    traced.add(arg)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                names = {terminal_name(dec)}
                if isinstance(dec, ast.Call):
                    names.add(terminal_name(dec.func))
                    names.update(terminal_name(a) for a in dec.args)
                if names & TRACE_ENTRY_NAMES:
                    traced.add(node)
            for sub in ast.walk(node):
                if isinstance(sub, (ast.AugAssign, ast.Assign)):
                    tgt = (sub.target if isinstance(sub, ast.AugAssign)
                           else sub.targets[0])
                    if _is_trace_counts_target(tgt):
                        traced.add(node)

    # nesting closure: a def inside a traced def is traced too
    changed = True
    while changed:
        changed = False
        for node in ast.walk(tree):
            if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda))
                    and node not in traced
                    and any(fn in traced for fn in enclosing_functions(node))):
                traced.add(node)
                changed = True
    return traced


def parse_suppressions(lines: Iterable[str]) -> Dict[int, Set[str]]:
    """``{line: rules}`` from REAL ``# repro: allow[...]`` comments only —
    tokenized, so rule syntax quoted in docstrings never counts."""
    import io
    import tokenize

    source = "\n".join(lines) if not isinstance(lines, str) else lines
    out: Dict[int, Set[str]] = {}
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = SUPPRESS_RE.search(tok.string)
            if m:
                out[tok.start[0]] = {
                    r.strip() for r in m.group(1).split(",") if r.strip()}
    except (tokenize.TokenError, IndentationError, SyntaxError):
        # fall back to the line regex on untokenizable sources
        for i, line in enumerate(source.splitlines(), start=1):
            m = SUPPRESS_RE.search(line)
            if m:
                out[i] = {r.strip() for r in m.group(1).split(",")
                          if r.strip()}
    return out


class ModuleContext:
    """Everything a checker needs about one source file, parsed once."""

    def __init__(self, path: str, source: str):
        self.path = path
        self.source = source
        self.tree = ast.parse(source, filename=path)
        _set_parents(self.tree)
        self.lines = source.splitlines()
        self.suppressions = parse_suppressions(self.lines)
        self.used_suppressions: Dict[int, Set[str]] = {}
        self.module_arrays = module_array_bindings(self.tree)
        self.module_names = module_level_names(self.tree)
        self.traced_scopes = find_traced_scopes(self.tree)

    def in_traced_scope(self, node: ast.AST) -> bool:
        return any(fn in self.traced_scopes
                   for fn in enclosing_functions(node))

    def violation(self, rule: str, node: ast.AST, message: str) -> Violation:
        line = getattr(node, "lineno", 1)
        suppressed = False
        for at in (line, line - 1):
            if rule in self.suppressions.get(at, set()):
                suppressed = True
                self.used_suppressions.setdefault(at, set()).add(rule)
                break
        return Violation(rule=rule, path=self.path, line=line,
                         message=message, suppressed=suppressed)


class Checker:
    """A single lint rule. ``check`` returns ALL findings, suppressed ones
    included — the reporter splits them so the suppression inventory stays
    honest."""

    rule = "R?"
    title = ""

    def check(self, ctx: ModuleContext) -> List[Violation]:
        raise NotImplementedError
