"""Finding + allowlist plumbing shared by every repro-lint rule.

A finding is one (rule, file, line) violation with the offending source
line attached; the allowlist (``allowlist.toml``) suppresses findings by
(rule, path glob, source-line substring) and EVERY entry must carry a
one-line justification — an unexplained suppression is itself a lint
error (DESIGN.md §14).
"""
from __future__ import annotations

import dataclasses
import fnmatch
from typing import List, Optional


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""

    rule: str  # "R1".."R6"
    name: str  # rule slug, e.g. "tracer-branch"
    path: str  # path as given to the engine (posix separators)
    line: int  # 1-based
    col: int  # 0-based
    message: str
    snippet: str = ""  # stripped source line (allowlist `contains` target)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    def format(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: "
                f"{self.rule}[{self.name}] {self.message}")


@dataclasses.dataclass
class AllowEntry:
    """One justified suppression.

    ``path`` is an fnmatch glob over the finding's path; ``contains``
    must be a substring of the flagged source line (so entries survive
    line-number drift); ``reason`` is mandatory and non-empty.
    """

    rule: str  # "R4" or "*"
    path: str
    contains: str
    reason: str
    hits: int = 0  # findings suppressed by this entry (stale detection)

    def matches(self, f: Finding) -> bool:
        if self.rule not in ("*", f.rule):
            return False
        if not fnmatch.fnmatch(f.path, self.path) and \
                not fnmatch.fnmatch(f.path, "*/" + self.path):
            return False
        return self.contains in f.snippet


class AllowlistError(ValueError):
    """Malformed allowlist: missing fields or an empty justification."""


def load_allowlist(path: str) -> List[AllowEntry]:
    """Parse ``allowlist.toml``: a list of ``[[allow]]`` tables."""
    import tomllib

    with open(path, "rb") as f:
        data = tomllib.load(f)
    entries = []
    for i, raw in enumerate(data.get("allow", [])):
        missing = [k for k in ("rule", "path", "reason") if k not in raw]
        if missing:
            raise AllowlistError(
                f"{path}: allow entry #{i + 1} is missing {missing}")
        if not str(raw["reason"]).strip():
            raise AllowlistError(
                f"{path}: allow entry #{i + 1} ({raw['rule']} {raw['path']}) "
                "has an empty reason — every suppression must be justified")
        entries.append(AllowEntry(
            rule=str(raw["rule"]), path=str(raw["path"]),
            contains=str(raw.get("contains", "")), reason=str(raw["reason"]),
        ))
    return entries


def apply_allowlist(findings: List[Finding], entries: List[AllowEntry]):
    """Split findings into (kept, suppressed); bumps entry hit counts."""
    kept, suppressed = [], []
    for f in findings:
        entry: Optional[AllowEntry] = next(
            (e for e in entries if e.matches(f)), None)
        if entry is None:
            kept.append(f)
        else:
            entry.hits += 1
            suppressed.append(f)
    return kept, suppressed
