"""AST helpers + the Rule base class for repro-lint rules.

Rules are small ``ast`` visitors over one parsed module; everything they
share — dotted-name resolution, "which local functions does jit/shard_map
/pallas_call trace" discovery, transitive local-call closure — lives
here so each rule stays a page of intent.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from repro.analysis.findings import Finding


def dotted(node: ast.AST) -> Optional[str]:
    """'jax.random.fold_in' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_target(node: ast.Call) -> Optional[str]:
    return dotted(node.func)


def int_const(node: ast.AST) -> Optional[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    return None


def int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    """Literal int, or tuple/list of literal ints, else None."""
    one = int_const(node)
    if one is not None:
        return (one,)
    if isinstance(node, (ast.Tuple, ast.List)):
        vals = [int_const(e) for e in node.elts]
        if all(v is not None for v in vals):
            return tuple(vals)  # type: ignore[arg-type]
    return None


def names_loaded(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}


def assign_target_names(stmt: ast.stmt) -> Set[str]:
    """Simple Name targets bound by an assignment statement (tuple
    unpacking included); Attribute/Subscript targets are skipped."""
    targets: List[ast.expr] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)) and stmt.value:
        targets = [stmt.target]
    out: Set[str] = set()
    for t in targets:
        for n in ast.walk(t):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
                out.add(n.id)
    return out


def param_names(fn: ast.FunctionDef) -> List[str]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


def index_functions(tree: ast.Module) -> Dict[str, ast.FunctionDef]:
    """name -> FunctionDef for every def anywhere in the module (later
    defs win on name collision — good enough for lint granularity)."""
    out: Dict[str, ast.FunctionDef] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out[node.name] = node  # type: ignore[assignment]
    return out


def _resolve_fn_arg(arg: ast.AST) -> Optional[str]:
    """Function-valued argument -> local name: bare ``f`` or
    ``functools.partial(f, ...)``."""
    if isinstance(arg, ast.Name):
        return arg.id
    if isinstance(arg, ast.Call):
        tgt = call_target(arg)
        if tgt in ("functools.partial", "partial") and arg.args:
            return _resolve_fn_arg(arg.args[0])
    return None


#: call targets whose first function-valued argument is traced
TRACE_ENTRY_CALLS = (
    "jax.jit", "jit", "pjit", "jax.pmap",
    "shard_map", "jax.shard_map", "jax.experimental.shard_map.shard_map",
    "pl.pallas_call", "pallas_call", "jax.experimental.pallas.pallas_call",
)


def is_entry_call(tgt: Optional[str], entries: Iterable[str]) -> bool:
    """Dotted call target names a tracing entry point? Matches on the
    final component so ``jax.jit`` / ``pl.pallas_call`` aliases all hit."""
    if tgt is None:
        return False
    leaves = {e.split(".")[-1] for e in entries}
    return tgt.split(".")[-1] in leaves


def traced_function_names(tree: ast.Module, entries: Iterable[str]
                          ) -> Dict[str, ast.Call]:
    """Local function names passed (possibly via functools.partial) as the
    first argument of one of ``entries`` -> the entry Call node."""
    out: Dict[str, ast.Call] = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and node.args):
            continue
        if not is_entry_call(call_target(node), entries):
            continue
        name = _resolve_fn_arg(node.args[0])
        if name:
            out.setdefault(name, node)
    return out


def decorator_traces(fn: ast.FunctionDef) -> bool:
    """True when the def carries a tracing decorator: @jax.jit / @jit /
    @functools.partial(jax.jit, ...)."""
    for dec in fn.decorator_list:
        tgt = dotted(dec)
        if tgt in ("jax.jit", "jit"):
            return True
        if isinstance(dec, ast.Call):
            tgt = call_target(dec)
            if tgt in ("jax.jit", "jit"):
                return True
            if tgt in ("functools.partial", "partial") and dec.args:
                inner = dotted(dec.args[0])
                if inner in ("jax.jit", "jit"):
                    return True
    return False


def local_call_closure(roots: Iterable[str],
                       fns: Dict[str, ast.FunctionDef]) -> Set[str]:
    """Roots plus every same-module function reachable from them through
    bare-name calls (one module is the lint unit — cross-module dataflow
    is the sanitizer lane's job)."""
    seen: Set[str] = set()
    todo = [r for r in roots if r in fns]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for node in ast.walk(fns[name]):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                callee = node.func.id
                if callee in fns and callee not in seen:
                    todo.append(callee)
    return seen


def static_param_names(fn: ast.FunctionDef) -> Set[str]:
    """Params marked static via jit(static_argnames=/static_argnums=) in
    the def's decorators — Python values at trace time, not tracers."""
    params = param_names(fn)
    static: Set[str] = set()
    for dec in fn.decorator_list:
        if not isinstance(dec, ast.Call):
            continue
        kws = list(dec.keywords)
        if call_target(dec) in ("functools.partial", "partial") and \
                dec.args and isinstance(dec.args[0], ast.Call):
            kws += list(dec.args[0].keywords)
        for kw in kws:
            if kw.arg == "static_argnames":
                if isinstance(kw.value, ast.Constant) and \
                        isinstance(kw.value.value, str):
                    static.add(kw.value.value)
                elif isinstance(kw.value, (ast.Tuple, ast.List)):
                    static |= {e.value for e in kw.value.elts
                               if isinstance(e, ast.Constant)
                               and isinstance(e.value, str)}
            elif kw.arg == "static_argnums":
                for n in int_tuple(kw.value) or ():
                    if 0 <= n < len(params):
                        static.add(params[n])
    return static


class Rule:
    """One lint rule: ``check`` yields findings for a parsed module."""

    id: str = "R0"
    name: str = "base"
    doc: str = ""

    def check(self, tree: ast.Module, src_lines: List[str], path: str
              ) -> Iterable[Finding]:
        raise NotImplementedError

    # -- helpers -------------------------------------------------------------
    def finding(self, path: str, src_lines: List[str], node: ast.AST,
                message: str) -> Finding:
        line = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        snippet = src_lines[line - 1].strip() if 0 < line <= len(src_lines) \
            else ""
        return Finding(rule=self.id, name=self.name, path=path, line=line,
                       col=col, message=message, snippet=snippet)
