"""donation: arguments donated to a jitted program must not be read after
the call.

``donate_argnums`` hands the argument's buffer to XLA — after the call the
Python reference is a deleted array, and touching it raises (or, worse,
on some paths silently aliases freed memory). The repo's donated programs
are the serving KV-slab updaters (``_lm_prefill_paged_jit`` etc.,
models/transformer.py); the safe idiom is ``pool.pages =
_lm_decode_paged_jit(params, pool.pages, ...)`` — the donated reference is
overwritten by the very statement that consumes it.

Two passes, repo-wide:

1. Collect donated callables: module-scope ``@functools.partial(jax.jit,
   donate_argnums=...)`` / ``@jax.jit(...)`` decorations and ``name =
   jax.jit(fn, donate_argnums=...)`` assignments, keyed by *name* so
   imported call sites in other modules resolve.
2. At every call of a donated name, each donated positional argument that
   is a plain name/attribute chain is traced forward through the enclosing
   function: a load of the same chain after the call line — before the
   chain is reassigned — is a read-after-donation finding.

The forward trace is line-ordered (control flow is not modeled), which is
exactly the PR-8 idiom's shape; genuinely-safe reads on disjoint branches
can carry ``# analyze: ignore[donation]`` with the reason.
"""

from __future__ import annotations

import ast

from ..core import Finding, Repo, dotted

NAME = "donation"
SCOPE = "files"


def _donated_positions(call: ast.Call) -> tuple[int, ...] | None:
    """donate_argnums of a jax.jit(...) call expression, else None."""
    d = dotted(call.func) or ""
    if d.split(".")[-1] == "partial":
        # functools.partial(jax.jit, donate_argnums=...)
        if not (call.args and (dotted(call.args[0]) or "").endswith("jit")):
            return None
    elif not d.endswith("jit"):
        return None
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            if isinstance(kw.value, (ast.Tuple, ast.List)):
                out = []
                for el in kw.value.elts:
                    if isinstance(el, ast.Constant) and isinstance(el.value,
                                                                   int):
                        out.append(el.value)
                return tuple(out)
            if isinstance(kw.value, ast.Constant) and isinstance(
                    kw.value.value, int):
                return (kw.value.value,)
    return None


def collect_donated(repo: Repo) -> dict[str, tuple[int, ...]]:
    donated: dict[str, tuple[int, ...]] = {}
    for sf in repo.py_files():
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call):
                        pos = _donated_positions(dec)
                        if pos:
                            donated[node.name] = pos
            elif isinstance(node, ast.Assign) and isinstance(node.value,
                                                             ast.Call):
                pos = _donated_positions(node.value)
                if pos:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            donated[tgt.id] = pos
    return donated


class _FnIndex(ast.NodeVisitor):
    """Loads and stores of dotted chains within one function, by line."""

    def __init__(self):
        self.loads: list[tuple[str, int]] = []
        self.stores: list[tuple[str, int]] = []
        self.calls: list[ast.Call] = []

    def _visit_chain(self, node, ctx):
        d = dotted(node)
        if d is not None:
            (self.stores if isinstance(ctx, (ast.Store, ast.Del))
             else self.loads).append((d, node.lineno))
            return True
        return False

    def visit_Call(self, node):
        self.calls.append(node)
        self.generic_visit(node)

    def visit_Name(self, node):
        self._visit_chain(node, node.ctx)

    def visit_Attribute(self, node):
        if not self._visit_chain(node, node.ctx):
            self.generic_visit(node)

    def visit_FunctionDef(self, node):
        pass  # nested scopes analyzed separately

    visit_AsyncFunctionDef = visit_FunctionDef


def _check_function(sf, fn, donated, findings):
    idx = _FnIndex()
    for stmt in fn.body:
        idx.visit(stmt)
    for node in idx.calls:
        d = dotted(node.func)
        callee = (d or "").split(".")[-1]
        if callee not in donated:
            continue
        end = getattr(node, "end_lineno", node.lineno)
        for pos in donated[callee]:
            if pos >= len(node.args):
                continue
            chain = dotted(node.args[pos])
            if chain is None or chain == "self":
                continue
            # first reassignment at/after the call (the consuming statement
            # itself counts: `x = f(x)` re-binds x)
            re_lines = [ln for c, ln in idx.stores
                        if c == chain and ln >= node.lineno]
            rebound = min(re_lines) if re_lines else None
            for c, ln in idx.loads:
                if c != chain or ln <= end:
                    continue
                if rebound is not None and ln >= rebound:
                    continue
                if sf.ignored(ln, NAME):
                    continue
                findings.append(Finding(
                    check=NAME, path=sf.rel, line=ln,
                    message=(f"`{chain}` is read after being donated to "
                             f"{callee}() (arg {pos}, donated via "
                             f"donate_argnums) at line {node.lineno}; the "
                             f"buffer is deleted by the call"),
                    hint=("rebind the result over the donated reference "
                          f"(`{chain} = {callee}(...)`) before any further "
                          "read, or drop donation for this argument"),
                    key=(f"{NAME}:{sf.rel}:{fn.name}.{chain}"
                         f"@{callee}")))


def run(repo: Repo) -> list[Finding]:
    donated = collect_donated(repo)
    if not donated:
        return []
    findings: list[Finding] = []
    for sf in repo.py_files():
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                _check_function(sf, node, donated, findings)
    return findings
