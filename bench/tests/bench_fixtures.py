"""Shared pieces of the benchmark's CPU tests."""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
FIXTURES = Path(__file__).resolve().parent / "fixtures"


def fixture_cell(name: str):
    from bench import harness

    manifest = json.loads((FIXTURES / "BENCHMARK.json").read_text())
    return harness.load_cell(name, manifest, base=FIXTURES)
