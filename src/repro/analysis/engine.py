"""repro-lint engine: walk .py files, parse once, run the rule catalog.

Pure stdlib (ast + tomllib) — importing this module must never touch jax,
so the lint stage runs first in CI and on accelerator-free machines.
"""
from __future__ import annotations

import ast
import dataclasses
import json
import os
import posixpath
from typing import Iterable, List, Optional, Sequence

from repro.analysis.findings import (
    AllowEntry,
    Finding,
    apply_allowlist,
    load_allowlist,
)
from repro.analysis.rules_jax import DonationMisuseRule, PRNGDisciplineRule
from repro.analysis.rules_pallas import PallasKernelRule
from repro.analysis.rules_shard import ImportTimeComputeRule, ShardMapHygieneRule
from repro.analysis.rules_tracer import TracerBranchRule

#: the catalog, in rule-id order (DESIGN.md §14)
ALL_RULES = (
    TracerBranchRule(),
    DonationMisuseRule(),
    PRNGDisciplineRule(),
    ShardMapHygieneRule(),
    ImportTimeComputeRule(),
    PallasKernelRule(),
)


def rule_ids() -> List[str]:
    return [r.id for r in ALL_RULES]


@dataclasses.dataclass
class LintResult:
    """Outcome of one lint run over a set of paths."""

    findings: List[Finding]  # kept (not suppressed)
    suppressed: List[Finding]
    files: int
    parse_errors: List[str]
    allowlist: List[AllowEntry]

    @property
    def ok(self) -> bool:
        return not self.findings and not self.parse_errors

    def unused_allowlist(self) -> List[AllowEntry]:
        return [e for e in self.allowlist if e.hits == 0]

    # -- rendering -----------------------------------------------------------
    def to_text(self) -> str:
        lines = [f.format() for f in self.findings]
        lines += [f"parse error: {e}" for e in self.parse_errors]
        n = len(self.findings)
        lines.append(
            f"repro-lint: {n} finding{'s' if n != 1 else ''} in "
            f"{self.files} file{'s' if self.files != 1 else ''}"
            + (f" ({len(self.suppressed)} allowlisted)"
               if self.suppressed else ""))
        return "\n".join(lines)

    def to_json(self) -> str:
        return json.dumps({
            "findings": [f.to_json() for f in self.findings],
            "suppressed": [f.to_json() for f in self.suppressed],
            "files": self.files,
            "parse_errors": self.parse_errors,
        }, indent=2, sort_keys=True)


def _iter_py_files(targets: Sequence[str]) -> Iterable[str]:
    for target in targets:
        if os.path.isfile(target):
            yield target
        elif os.path.isdir(target):
            for root, dirs, files in os.walk(target):
                dirs[:] = sorted(d for d in dirs
                                 if d not in ("__pycache__", ".git"))
                for fn in sorted(files):
                    if fn.endswith(".py"):
                        yield os.path.join(root, fn)
        else:
            raise FileNotFoundError(f"lint target does not exist: {target}")


def lint_file(path: str, rules: Sequence = ALL_RULES
              ) -> List[Finding]:
    """Lint one file with the given rules (no allowlist applied)."""
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    tree = ast.parse(src, filename=path)
    src_lines = src.splitlines()
    norm = posixpath.join(*path.split(os.sep)) if os.sep != "/" else path
    findings: List[Finding] = []
    for rule in rules:
        findings.extend(rule.check(tree, src_lines, norm))
    return findings


def lint_paths(targets: Sequence[str], *,
               select: Optional[Sequence[str]] = None,
               ignore: Optional[Sequence[str]] = None,
               allowlist: Optional[str] = None) -> LintResult:
    """Run the catalog over files/directories.

    select/ignore take rule ids ("R1"); allowlist is a path to an
    allowlist.toml (entries must justify themselves — see findings.py).
    """
    known = set(rule_ids())
    for rid in list(select or []) + list(ignore or []):
        if rid not in known:
            raise ValueError(
                f"unknown rule id {rid!r}; known: {sorted(known)}")
    rules = [r for r in ALL_RULES
             if (not select or r.id in select)
             and (not ignore or r.id not in ignore)]
    entries = load_allowlist(allowlist) if allowlist else []

    findings: List[Finding] = []
    parse_errors: List[str] = []
    files = 0
    for path in _iter_py_files(targets):
        files += 1
        try:
            findings.extend(lint_file(path, rules))
        except SyntaxError as e:
            parse_errors.append(f"{path}:{e.lineno}: {e.msg}")
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    kept, suppressed = apply_allowlist(findings, entries)
    return LintResult(findings=kept, suppressed=suppressed, files=files,
                      parse_errors=parse_errors, allowlist=entries)
