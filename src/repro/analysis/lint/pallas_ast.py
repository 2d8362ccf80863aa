"""Python-AST lint for Pallas kernel sources (DESIGN.md §13.3).

Two bug classes this repo has actually hit:

  * ``pl.program_id`` (or ``pl.num_programs``) staged *inside* a
    ``pl.when`` body.  The PR-6 class of bug: ``when`` stages its body
    under a predicate, and grid-position queries inside it miscompile
    on Mosaic (see the "hoisted: program_id can't be staged into
    when()" comment in ``kernels/fused_ce/kernel.py``).  Calls must be
    hoisted above the ``when``.
  * Non-pure ``BlockSpec`` index-map lambdas: an index map must be a
    pure function of the grid indices.  Flagged are (a) ``program_id``
    calls inside the lambda (the grid position is the lambda's
    *argument*, querying it inside is wrong under another grid) and
    (b) late binding — a lambda built inside a ``for`` loop that closes
    over the loop variable, so every spec ends up using the *last*
    iteration's value.

Both checks are pure-Python AST walks over kernel source files; no JAX
import needed, so they run even where jax is absent."""

from __future__ import annotations

import ast
from typing import List, Set

from repro.analysis.lint.rules import Finding, Rule, RuleContext, register

_GRID_QUERIES = ("program_id", "num_programs")


def _call_name(node: ast.AST) -> str:
    """'program_id' for both ``pl.program_id(0)`` and ``program_id(0)``."""
    if isinstance(node, ast.Call):
        f = node.func
        if isinstance(f, ast.Attribute):
            return f.attr
        if isinstance(f, ast.Name):
            return f.id
    return ""


def _is_when(node: ast.AST) -> bool:
    """True for a ``pl.when(...)`` call (decorator or direct form)."""
    return isinstance(node, ast.Call) and _call_name(node) == "when"


def _grid_queries_in(tree: ast.AST) -> List[ast.Call]:
    return [n for n in ast.walk(tree)
            if isinstance(n, ast.Call) and _call_name(n) in _GRID_QUERIES]


def _lambda_free_names(lam: ast.Lambda) -> Set[str]:
    bound = {a.arg for a in (lam.args.args + lam.args.posonlyargs
                             + lam.args.kwonlyargs)}
    if lam.args.vararg:
        bound.add(lam.args.vararg.arg)
    if lam.args.kwarg:
        bound.add(lam.args.kwarg.arg)
    return {n.id for n in ast.walk(lam.body)
            if isinstance(n, ast.Name)
            and isinstance(n.ctx, ast.Load)} - bound


def lint_source(src: str, path: str = "<source>") -> List[Finding]:
    """Run both AST checks over one Python source string."""
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Finding("pallas-kernel-ast",
                        f"unparsable kernel source: {e.msg}",
                        f"{path}:{e.lineno or 0}")]
    out: List[Finding] = []

    # -- program_id staged inside pl.when bodies ---------------------------
    for node in ast.walk(tree):
        when_bodies: List[ast.AST] = []
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_is_when(d) for d in node.decorator_list):
                when_bodies.extend(node.body)
        elif isinstance(node, ast.Call) and _is_when(node.func):
            # pl.when(cond)(lambda: ...) / pl.when(cond)(fn) — only the
            # inline-lambda form carries a body we can see here
            when_bodies.extend(a for a in node.args
                               if isinstance(a, ast.Lambda))
        for body in when_bodies:
            for call in _grid_queries_in(body):
                out.append(Finding(
                    "pallas-kernel-ast",
                    f"'{_call_name(call)}' staged inside a pl.when body "
                    "— hoist the grid query above the when() "
                    "(miscompiles under predication)",
                    f"{path}:{call.lineno}"))

    # -- BlockSpec index-map lambdas ---------------------------------------
    # map lambda -> enclosing for-loop target names for late-binding check
    loop_targets_at: dict = {}

    class _LoopWalker(ast.NodeVisitor):
        def __init__(self):
            self.stack: List[Set[str]] = []

        def visit_For(self, node: ast.For):
            names = {n.id for n in ast.walk(node.target)
                     if isinstance(n, ast.Name)}
            self.stack.append(names)
            self.generic_visit(node)
            self.stack.pop()

        def visit_Lambda(self, node: ast.Lambda):
            if self.stack:
                loop_targets_at[node] = set().union(*self.stack)
            self.generic_visit(node)

    _LoopWalker().visit(tree)

    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and _call_name(node) == "BlockSpec"):
            continue
        lambdas = [a for a in node.args if isinstance(a, ast.Lambda)]
        lambdas += [k.value for k in node.keywords
                    if isinstance(k.value, ast.Lambda)]
        for lam in lambdas:
            for call in _grid_queries_in(lam.body):
                out.append(Finding(
                    "pallas-kernel-ast",
                    f"'{_call_name(call)}' inside a BlockSpec index map "
                    "— the grid position is the lambda's argument; "
                    "index maps must be pure functions of it",
                    f"{path}:{call.lineno}"))
            leaked = _lambda_free_names(lam) & loop_targets_at.get(
                lam, set())
            defaults = {a.arg for a in lam.args.args[
                len(lam.args.args) - len(lam.args.defaults):]}
            leaked -= defaults
            if leaked:
                out.append(Finding(
                    "pallas-kernel-ast",
                    "BlockSpec index-map lambda closes over loop "
                    f"variable(s) {sorted(leaked)} — late binding means "
                    "every spec sees the final iteration; bind via a "
                    "default argument instead",
                    f"{path}:{lam.lineno}"))
    return out


def lint_file(path: str) -> List[Finding]:
    with open(path, "r") as f:
        return lint_source(f.read(), path)


@register
class PallasKernelAstRule(Rule):
    """AST-lint every kernel source file handed to the context."""

    name = "pallas-kernel-ast"
    requires = "source"

    def run(self, ctx: RuleContext) -> List[Finding]:
        out: List[Finding] = []
        for path in ctx.sources:
            out.extend(lint_file(path))
        return out
