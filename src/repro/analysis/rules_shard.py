"""R4 — shard_map hygiene, R5 — import-time compute.

R4: inside a shard_map body the client axis is physically sharded;
``gather`` / ``dynamic_slice`` / ``take`` along it silently re-gathers
the full cohort onto one shard (defeating the memory plan), and a bare
``lax.psum`` bypasses the strategy layer's step-boundary accounting —
cross-shard reduction must route through ``strategy.psum_reduce`` (or
the module's own ``psum_reduce`` wrapper) so DESIGN.md §5's "psum only
at step boundaries" stays auditable in one place.

R5: module scope runs at import; ``jnp.*`` / device-array creation
there triggers backend init + compilation before any config is read,
breaks `import repro` on accelerator-free machines, and bakes arrays
into module state that escapes donation. Constants belong in functions
or plain Python/numpy-at-call-time.
"""
from __future__ import annotations

import ast
from typing import Iterable, List, Set

from repro.analysis import astutil
from repro.analysis.astutil import Rule
from repro.analysis.findings import Finding

_SHARD_ENTRIES = ("shard_map", "jax.shard_map",
                  "jax.experimental.shard_map.shard_map")

_GATHERS = {"gather", "dynamic_slice", "take", "take_along_axis",
            "all_gather"}
_SANCTIONED_PSUM = {"psum_reduce", "global_sum"}


class ShardMapHygieneRule(Rule):
    id = "R4"
    name = "shard-hygiene"
    doc = ("no gather/dynamic_slice/take and no bare lax.psum inside "
           "shard_map bodies — reductions go through strategy.psum_reduce")

    def check(self, tree: ast.Module, src_lines: List[str], path: str
              ) -> Iterable[Finding]:
        fns = astutil.index_functions(tree)
        roots = set(astutil.traced_function_names(tree, _SHARD_ENTRIES))
        if not roots:
            return
        for name in sorted(astutil.local_call_closure(roots, fns)):
            yield from self._check_fn(fns[name], src_lines, path)

    def _check_fn(self, fn: ast.FunctionDef, src_lines: List[str],
                  path: str) -> Iterable[Finding]:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            tgt = astutil.call_target(node)
            if tgt is None:
                continue
            leaf = tgt.split(".")[-1]
            if leaf in _GATHERS:
                yield self.finding(
                    path, src_lines, node,
                    f"`{tgt}` inside shard_map body `{fn.name}` — "
                    "gathering along the sharded client axis re-"
                    "materializes the cohort on one shard; restructure "
                    "with masked per-shard compute")
            elif leaf == "psum" and \
                    not any(tgt.endswith(s) for s in _SANCTIONED_PSUM):
                yield self.finding(
                    path, src_lines, node,
                    f"bare `{tgt}` inside shard_map body `{fn.name}` — "
                    "route cross-shard reductions through "
                    "`strategy.psum_reduce` so step-boundary accounting "
                    "stays in one place")


def _walk_eager(node: ast.AST) -> Iterable[ast.AST]:
    """ast.walk, but without descending into def/lambda bodies — those
    defer execution past import time."""
    stack = [node]
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        yield n
        stack.extend(ast.iter_child_nodes(n))


class ImportTimeComputeRule(Rule):
    id = "R5"
    name = "import-compute"
    doc = ("no jnp.* / device-array creation at module scope — import "
           "must not touch the backend")

    #: module-scope call prefixes that allocate on device / trigger tracing
    _BANNED_PREFIXES = ("jnp.", "jax.numpy.")
    # NOTE: jax.jit is absent on purpose — wrapping is lazy (no trace, no
    # backend) and `step = jax.jit(f)` at module scope is a fine idiom.
    _BANNED_CALLS = {
        "jax.device_put", "jax.random.PRNGKey", "jax.random.key",
        "jax.random.normal", "jax.random.uniform", "jax.devices",
        "jax.local_devices", "jax.device_count",
    }

    def check(self, tree: ast.Module, src_lines: List[str], path: str
              ) -> Iterable[Finding]:
        yield from self._scan(tree.body, src_lines, path)

    def _scan(self, stmts: List[ast.stmt], src_lines: List[str],
              path: str) -> Iterable[Finding]:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                # class bodies also execute at import time
                if isinstance(stmt, ast.ClassDef):
                    yield from self._scan(stmt.body, src_lines, path)
                continue
            if isinstance(stmt, (ast.Import, ast.ImportFrom)):
                continue
            for node in _walk_eager(stmt):
                if not isinstance(node, ast.Call):
                    continue
                tgt = astutil.call_target(node)
                if tgt is None:
                    continue
                if tgt.startswith(self._BANNED_PREFIXES) or \
                        tgt in self._BANNED_CALLS:
                    yield self.finding(
                        path, src_lines, node,
                        f"`{tgt}` at module scope — runs at import, "
                        "initializes the backend before config is read; "
                        "move into a function or make it lazy")
