"""What every cell shares: the manifest, finding a cell's files by name,
the device label, and the result line.

A cell ``<config>.<traffic>`` is found through ``BENCHMARK.json`` and its
files: ``configs/<config>.json`` (sizes, and the ``kind`` of system that
serves it), ``configs/<config>.ref.py`` (its plain reference),
``traffic/<traffic>.json``, ``limits/<cell>.json`` (the limits of the
correctness check) and ``kinds/<kind>.py`` (build, warm up, measure,
check). Each per-layer metric is ``metrics/<metric>.py``. Adding a cell,
a configuration, a mix or a metric adds files and manifest entries; no
file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"
CACHE = ROOT / ".jax_cache"


def use_compile_cache() -> None:
    """JAX's persistent compile cache in this checkout, at a fixed path
    (the path is part of every entry's key), every program kept, no
    eviction (its access-time files fail to write on some hosts). Call
    before the first compile; the program reads the same directory from
    ``JAX_COMPILATION_CACHE_DIR``."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def process_start_time() -> float:
    """Wall-clock time at which this process started (Linux /proc); the
    harness import time where /proc cannot say."""
    try:
        fields = Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()
        start_ticks = int(fields[19])
        btime = next(int(line.split()[1]) for line in
                     Path("/proc/stat").read_text().splitlines()
                     if line.startswith("btime"))
        return btime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return _IMPORTED


_IMPORTED = time.time()


def load_module(path: Path, name: str):
    """Import a benchmark file by path (names may hold '-' and '.')."""
    if not path.exists():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    if not path.exists():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]  # the manifest's metrics this cell reports
    per_layer: List[dict]
    config_name: str
    traffic_name: str
    base: Path = BENCH

    def reference(self):
        """The configuration's plain reference: ``<config>.ref.py``, or
        the file its ``reference`` key names, beside the configuration."""
        fname = self.config.get("reference", f"{self.config_name}.ref.py")
        path = self.base / "configs" / fname
        if not path.exists():
            path = BENCH / "configs" / fname
        return load_module(path, f"bench_ref_{self.config_name}")

    def kind(self):
        k = self.config["kind"]
        return load_module(BENCH / "kinds" / f"{k}.py", f"bench_kind_{k}")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, manifest: Optional[dict] = None,
              base: Path = BENCH) -> Cell:
    """The cell ``name`` of ``manifest`` (default: BENCHMARK.json), its
    files read from ``base``."""
    m = manifest if manifest is not None else read_json(MANIFEST)
    cells = {w["name"]: w for w in m["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    e2e = [e for e in m["end_to_end"] if _reports(e, name)]
    names = {e["name"] for e in e2e}
    per_layer = [p for p in m["per_layer"]
                 if _reports(p, name) and p["moves"] in names]
    limits_file = base / "limits" / f"{name}.json"
    return Cell(
        name=name, chips=w["chips"],
        config=read_json(base / "configs" / f"{w['config']}.json"),
        traffic=read_json(base / "traffic" / f"{w['traffic']}.json"),
        limits=read_json(limits_file) if limits_file.exists() else {},
        end_to_end=e2e, per_layer=per_layer,
        config_name=w["config"], traffic_name=w["traffic"], base=base)


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py", f"bench_metric_{name}")


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


class NoAccelerator(RuntimeError):
    pass


def require_chips(n: int) -> None:
    """Refuse the CPU and too few chips: no result is ever measured there."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoAccelerator("JAX found no accelerator (platform cpu); the "
                            "benchmark measures only on a chip")
    if len(devs) < n:
        raise NoAccelerator(f"the cell needs {n} chips, JAX found {len(devs)}")


def device_info(n: int) -> dict:
    import jax

    devs = jax.devices()[:n]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


# ---------------------------------------------------------------------------
# checks and the result line
# ---------------------------------------------------------------------------


def judge(readings: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """{name: {"value", "limit"}} for every limit of the cell; a limit
    whose reading is missing fails the check."""
    out = {}
    for name, limit in limits.items():
        v = readings.get(name)
        out[name] = {"value": v, "limit": limit}
    return out


def passed(checks: Dict) -> bool:
    return bool(checks) and all(
        c["value"] is not None and c["value"] == c["value"]
        and c["value"] <= c["limit"] for c in checks.values())


def emit(result: dict) -> None:
    """Numbers compared, each beside its limit, as the last lines on
    standard error; then the result as the last line of standard output
    with ``checks`` as its last key."""
    checks = result.get("checks", {})
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    ordered = {k: result[k] for k in ("correct", "attempted", "failed",
                                      "metrics", "device") if k in result}
    if result.get("breakdown") is not None:
        ordered["breakdown"] = result["breakdown"]
    ordered["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(ordered), flush=True)
