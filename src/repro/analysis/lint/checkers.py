"""AST checkers R1–R4: the per-module trace-discipline rules.

Each checker emits every finding (suppressed or not); ``repro.analysis.cli``
separates them so suppressions stay visible in the report. See the package
docstring (``repro.analysis``) for the full rule statements and rationale.
"""
from __future__ import annotations

import ast
from typing import List, Set

from repro.analysis.lint import base
from repro.analysis.lint.base import (
    ARRAY_CTORS, Checker, ModuleContext, NUMPY_ROOTS, Violation,
    enclosing_functions, local_bindings, root_name, terminal_name,
)

# jax.random samplers that CONSUME a key (split/fold_in DERIVE streams and
# may take the same parent key any number of times)
RANDOM_SAMPLERS = {
    "normal", "uniform", "bernoulli", "permutation", "choice", "categorical",
    "randint", "truncated_normal", "gumbel", "laplace", "rademacher",
    "exponential", "bits", "poisson", "gamma", "beta", "dirichlet",
    "orthogonal", "ball", "maxwell",
}

# mutating method names on module-level objects (Python side effects a
# traced body must not perform — they run once per TRACE, not per call)
MUTATOR_METHODS = {
    "append", "add", "update", "extend", "insert", "remove", "pop",
    "popitem", "clear", "setdefault", "write", "move_to_end",
}


class ClosureArrayChecker(Checker):
    """R1: traced bodies must not capture module-level arrays by closure or
    materialize host (numpy) arrays — both bake into the jaxpr as consts
    instead of riding as operands, pinning memory and defeating the
    structural executor cache."""

    rule = "R1"
    title = "no closure-captured or host-materialized arrays in traced code"

    def check(self, ctx: ModuleContext) -> List[Violation]:
        out, seen = [], set()
        for fn in ctx.traced_scopes:
            locals_chain: Set[str] = set()
            for scope in [fn] + enclosing_functions(fn):
                locals_chain |= local_bindings(scope)
            for node in ast.walk(fn):
                if (isinstance(node, ast.Name)
                        and isinstance(node.ctx, ast.Load)
                        and node.id in ctx.module_arrays
                        and node.id not in locals_chain):
                    key = ("name", node.lineno, node.id)
                    if key not in seen:
                        seen.add(key)
                        out.append(ctx.violation(
                            self.rule, node,
                            f"module-level array {node.id!r} (defined at "
                            f"line {ctx.module_arrays[node.id]}) captured by "
                            f"closure in a traced body — pass it as an "
                            f"operand argument instead"))
                elif (isinstance(node, ast.Call)
                      and terminal_name(node.func) in ARRAY_CTORS
                      and root_name(node.func) in NUMPY_ROOTS):
                    key = ("ctor", node.lineno, terminal_name(node.func))
                    if key not in seen:
                        seen.add(key)
                        out.append(ctx.violation(
                            self.rule, node,
                            f"host numpy array "
                            f"({root_name(node.func)}."
                            f"{terminal_name(node.func)}) materialized "
                            f"inside a traced body becomes a baked jaxpr "
                            f"const — build it outside the trace and pass "
                            f"it as an operand (or use jnp)"))
        return out


class SideEffectChecker(Checker):
    """R2: no Python side effects in traced bodies — they run once per
    trace, not once per call, so anything but a ``base.TRACE_WHITELIST``
    counter bump (``TRACE_COUNTS``) is a silent correctness bug."""

    rule = "R2"
    title = "no Python side effects in traced bodies except TRACE_WHITELIST"

    def check(self, ctx: ModuleContext) -> List[Violation]:
        out, seen = [], set()

        def emit(node, msg):
            key = (node.lineno, msg)
            if key not in seen:
                seen.add(key)
                out.append(ctx.violation(self.rule, node, msg))

        for fn in ctx.traced_scopes:
            locals_chain: Set[str] = set()
            for scope in [fn] + enclosing_functions(fn):
                locals_chain |= local_bindings(scope)
            for node in ast.walk(fn):
                if isinstance(node, ast.Global):
                    emit(node, "`global` rebinding inside a traced body "
                               "runs at trace time, not per call")
                elif isinstance(node, ast.Call):
                    name = terminal_name(node.func)
                    if name in ("print", "open") and isinstance(
                            node.func, ast.Name):
                        emit(node, f"{name}() inside a traced body executes "
                                   f"once per TRACE, not per call (use "
                                   f"jax.debug.print for runtime output)")
                    elif (isinstance(node.func, ast.Attribute)
                          and node.func.attr in MUTATOR_METHODS):
                        tgt = node.func.value
                        if base._is_trace_counts_target(node.func):
                            continue
                        root = root_name(tgt)
                        if (root is not None and root in ctx.module_names
                                and root not in locals_chain):
                            emit(node,
                                 f"mutation of module-level {root!r} "
                                 f"(.{node.func.attr}) inside a traced body "
                                 f"is a trace-time side effect")
                elif isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = (node.targets if isinstance(node, ast.Assign)
                               else [node.target])
                    for t in targets:
                        if base._is_trace_counts_target(t):
                            continue
                        if not isinstance(t, (ast.Subscript, ast.Attribute)):
                            continue
                        root = root_name(t)
                        if (root is not None and root in ctx.module_names
                                and root not in locals_chain):
                            emit(node,
                                 f"assignment into module-level {root!r} "
                                 f"inside a traced body is a trace-time "
                                 f"side effect (only the TRACE_WHITELIST "
                                 f"counter bumps are allowed)")
        return out


class KeyStreamChecker(Checker):
    """R3: ``fold_in`` streams must be tagged with registered constants
    (never bare integer literals), and a PRNG key must not feed two
    samplers without an intervening ``split``/``fold_in``."""

    rule = "R3"
    title = "tagged fold_in streams; no PRNG key consumed twice"

    def check(self, ctx: ModuleContext) -> List[Violation]:
        from repro.analysis import REGISTERED_KEY_TAGS

        out = []
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and terminal_name(node.func) == "fold_in"):
                continue
            if len(node.args) < 2:
                continue
            tag = node.args[1]
            if isinstance(tag, ast.Constant) and isinstance(tag.value, int):
                out.append(ctx.violation(
                    self.rule, node,
                    f"fold_in stream tagged with the bare literal "
                    f"{tag.value!r} — register a named tag constant in "
                    f"repro.analysis.REGISTERED_KEY_TAGS (both engines "
                    f"must derive identical streams from one registry)"))
            elif (isinstance(tag, ast.Name) and tag.id.endswith("_TAG")
                  and tag.id not in REGISTERED_KEY_TAGS):
                out.append(ctx.violation(
                    self.rule, node,
                    f"fold_in tag {tag.id!r} is not registered in "
                    f"repro.analysis.REGISTERED_KEY_TAGS"))

        seen: Set[tuple] = set()
        self._scan_block(ctx, ctx.tree.body, set(), out, seen)
        return out

    def _scan_block(self, ctx, stmts, consumed: Set[str], out, seen) -> None:
        """Linear key-consumption scan; branch bodies inherit a COPY of the
        consumed set (an if/else legitimately consumes the same key once on
        each path) and never merge back."""
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                self._scan_block(ctx, stmt.body, set(), out, seen)
            elif isinstance(stmt, (ast.If, ast.While)):
                self._consume_in(ctx, stmt.test, consumed, out, seen)
                self._scan_block(ctx, stmt.body, set(consumed), out, seen)
                self._scan_block(ctx, stmt.orelse, set(consumed), out, seen)
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                self._consume_in(ctx, stmt.iter, consumed, out, seen)
                inner = set(consumed)
                for n in ast.walk(stmt.target):
                    if isinstance(n, ast.Name):
                        inner.discard(n.id)
                self._scan_block(ctx, stmt.body, inner, out, seen)
                self._scan_block(ctx, stmt.orelse, set(consumed), out, seen)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    self._consume_in(ctx, item.context_expr, consumed, out,
                                     seen)
                self._scan_block(ctx, stmt.body, consumed, out, seen)
            elif isinstance(stmt, ast.Try):
                for block in (stmt.body, stmt.orelse, stmt.finalbody,
                              *[h.body for h in stmt.handlers]):
                    self._scan_block(ctx, block, set(consumed), out, seen)
            else:
                self._consume_in(ctx, stmt, consumed, out, seen)
                if isinstance(stmt, ast.Assign):
                    for t in stmt.targets:
                        for n in ast.walk(t):
                            if isinstance(n, ast.Name):
                                consumed.discard(n.id)

    def _consume_in(self, ctx, node, consumed: Set[str], out, seen) -> None:
        for sub in ast.walk(node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # scanned with their own fresh consumed set
            if not isinstance(sub, ast.Call):
                continue
            name = terminal_name(sub.func)
            if (name in RANDOM_SAMPLERS and sub.args
                    and isinstance(sub.args[0], ast.Name)
                    and self._is_random_call(sub.func)):
                kid = sub.args[0].id
                if kid in consumed and (sub.lineno, kid) not in seen:
                    seen.add((sub.lineno, kid))
                    out.append(ctx.violation(
                        self.rule, sub,
                        f"PRNG key {kid!r} consumed twice without "
                        f"split/fold_in — the second sample REPLAYS the "
                        f"first one's randomness"))
                consumed.add(kid)

    @staticmethod
    def _is_random_call(func) -> bool:
        """Only flag samplers reached through a ``random`` module path
        (``jax.random.normal``, ``jr.normal``) — ``normal`` alone is too
        generic a method name to claim."""
        if isinstance(func, ast.Attribute):
            parent = func.value
            if isinstance(parent, ast.Attribute):
                return parent.attr == "random"
            if isinstance(parent, ast.Name):
                return parent.id in ("random", "jr", "jrandom")
        return False


class DonationChecker(Checker):
    """R4: every ``donate_argnums=`` must be a named tuple threaded through
    the executor cache key — a literal donation (or a name used nowhere
    else) means two structurally-equal executors with different donation
    can be served interchangeably, silently invalidating caller buffers."""

    rule = "R4"
    title = "donate_argnums threaded through the executor cache key"

    def check(self, ctx: ModuleContext) -> List[Violation]:
        out = []
        for node in ast.walk(ctx.tree):
            # only DIRECT jit calls: a literal donate tuple passed to a
            # builder that threads it into the cache key itself (e.g.
            # dist.grid._sharded_grid_fn) is the callee's responsibility
            if not (isinstance(node, ast.Call)
                    and terminal_name(node.func) == "jit"):
                continue
            for kw in node.keywords:
                if kw.arg != "donate_argnums":
                    continue
                names = {n.id for n in ast.walk(kw.value)
                         if isinstance(n, ast.Name)}
                if not names:
                    out.append(ctx.violation(
                        self.rule, node,
                        "literal donate_argnums= — bind the donate tuple to "
                        "a name and thread it through the executor cache "
                        "key (runner._cache_put) so donation is part of "
                        "the executor's identity"))
                    continue
                scopes = enclosing_functions(node)
                search_root = scopes[-1] if scopes else ctx.tree
                loads = sum(
                    1 for n in ast.walk(search_root)
                    if isinstance(n, ast.Name) and n.id in names
                    and isinstance(n.ctx, ast.Load)
                    and n not in set(ast.walk(kw.value)))
                if loads == 0:
                    out.append(ctx.violation(
                        self.rule, node,
                        f"donate tuple {sorted(names)} is used ONLY in "
                        f"donate_argnums= — it must also appear in the "
                        f"executor cache key"))
        return out


AST_CHECKERS = (ClosureArrayChecker, SideEffectChecker, KeyStreamChecker,
                DonationChecker)
