"""From a profiler trace to the numbers the per-layer metrics read.

A trace is reduced once, right after the traced window, to a compact
event table (``load_xplane``), which is also the format of the recorded
trace under ``testdata/``. Everything else here works on that table:

* device busy time: the union of the intervals in which an operation ran
  on a device (``XLA Ops`` line), clipped to the traced window;
* time per program or kernel: the summed durations of the events whose
  name matches one of a metric file's patterns;
* collective time, and the part of it with no other operation beside it;
* the operations that took most time, and the longest idle gaps with the
  host span that was open in each (the ``breakdown`` of a traced run).
"""
from __future__ import annotations

import gzip
import json
import re
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench:window"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|psum", re.I)

Event = Tuple[str, float, float]  # name, start_ns, duration_ns


def start(trace_dir) -> None:
    """Start the profiler without its Python call tracer (a span per
    Python call would swamp the trace); host spans come from
    ``TraceAnnotation``."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def op_name(event_name: str) -> str:
    """An XLA op event is named by its whole HLO instruction
    (``%name.3 = f32[...] fusion(%operand, ...)``): keep ``name.3`` alone,
    so that a pattern never matches an operand."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str) -> dict:
    """Read an ``.xplane.pb`` into {"devices": {plane: {line: [ev]}},
    "host": [ev]}; op events keep their op's name alone, host events are
    the ``bench:`` spans of any host thread."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out: dict = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [(op_name(e.name), float(e.start_ns),
                                         float(e.duration_ns))
                                        for e in line.events]
            out["devices"][plane.name] = lines
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out["host"] += [(e.name, float(e.start_ns),
                                 float(e.duration_ns))
                                for e in line.events
                                if e.name.startswith("bench:")]
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return str(found[-1])


def save(table: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(table, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        t = json.load(f)
    t["host"] = [tuple(e) for e in t["host"]]
    for lines in t["devices"].values():
        for k in lines:
            lines[k] = [tuple(e) for e in lines[k]]
    return t


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to [lo, hi); those wholly outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def length(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a_iv, b_iv) -> List[Tuple[float, float]]:
    """Parts of merged intervals ``a_iv`` not covered by merged ``b_iv``."""
    out, j = [], 0
    for a, b in a_iv:
        cur = a
        while j < len(b_iv) and b_iv[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_iv) and b_iv[k][0] < b:
            if b_iv[k][0] > cur:
                out.append((cur, b_iv[k][0]))
            cur = max(cur, b_iv[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


def window_bounds(table: dict) -> Tuple[float, float]:
    """The traced window: the harness's ``bench:window`` host span."""
    spans = [(s, d) for n, s, d in table["host"] if n == WINDOW_SPAN]
    if not spans:
        raise ValueError("trace holds no bench:window span")
    s, d = spans[0]
    return s, s + d


class Reduced:
    """A trace cut to its window, with the sums metrics ask for."""

    def __init__(self, table: dict, lo: float = None, hi: float = None):
        if lo is None:
            lo, hi = window_bounds(table)
        self.lo, self.hi = lo, hi
        self.window_s = (hi - lo) * 1e-9
        self.ops: Dict[str, List[Event]] = {}
        self.modules: Dict[str, List[Event]] = {}
        for plane, lines in table["devices"].items():
            ops = clip(lines.get(OPS_LINE, []), lo, hi)
            if ops:
                self.ops[plane] = ops
                self.modules[plane] = clip(lines.get(MODULES_LINE, []), lo, hi)
        self.host = clip(table["host"], lo, hi)

    @property
    def n_devices(self) -> int:
        return len(self.ops)

    def busy_intervals(self, plane: str) -> List[Tuple[float, float]]:
        return union((s, s + d) for _, s, d in self.ops[plane])

    def busy_s(self) -> float:
        """Device busy seconds, averaged over the devices that ran ops."""
        if not self.ops:
            return 0.0
        return sum(length(self.busy_intervals(p)) for p in self.ops) \
            * 1e-9 / len(self.ops)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def _match(self, events, patterns):
        rx = re.compile("|".join(patterns))
        return [e for e in events if rx.search(e[0])]

    def op_time_s(self, patterns: Sequence[str]) -> float:
        """Summed device seconds of ops matching any pattern, averaged
        over devices."""
        if not self.ops:
            return 0.0
        return sum(d for p in self.ops for _, _, d in
                   self._match(self.ops[p], patterns)) * 1e-9 / len(self.ops)

    def op_count(self, patterns: Sequence[str]) -> int:
        """Matching op events on the first device."""
        if not self.ops:
            return 0
        return len(self._match(next(iter(self.ops.values())), patterns))

    def module_time_s(self, patterns: Sequence[str]) -> float:
        """Summed device seconds of programs (XLA modules) matching any
        pattern, averaged over devices."""
        if not self.modules:
            return 0.0
        return sum(d for p in self.modules for _, _, d in
                   self._match(self.modules[p], patterns)) * 1e-9 \
            / len(self.modules)

    def module_count(self, patterns: Sequence[str]) -> int:
        if not self.modules:
            return 0
        return len(self._match(next(iter(self.modules.values())), patterns))

    def collective_s(self) -> Tuple[float, float]:
        """(collective seconds, seconds of it with no other op running on
        that device), averaged over devices."""
        tot = exposed = 0.0
        for p, ops in self.ops.items():
            coll = union((s, s + d) for n, s, d in ops if COLLECTIVE.search(n))
            other = union((s, s + d) for n, s, d in ops
                          if not COLLECTIVE.search(n))
            tot += length(coll)
            exposed += length(subtract(coll, other))
        n = max(len(self.ops), 1)
        return tot * 1e-9 / n, exposed * 1e-9 / n

    def top_ops(self, n: int = 10) -> List[List]:
        """[name, seconds] of the ops that took most device time (first
        device), op names with their numeric suffix dropped."""
        if not self.ops:
            return []
        acc: Dict[str, float] = {}
        for name, _, d in next(iter(self.ops.values())):
            key = re.sub(r"[.:]\d+$", "", name)
            acc[key] = acc.get(key, 0.0) + d * 1e-9
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """[host span open in the gap, seconds] of the longest idle gaps
        on the first device; a gap with no bench span open is 'host'."""
        if not self.ops:
            return []
        plane = next(iter(self.ops))
        busy = self.busy_intervals(plane)
        gaps = subtract([(self.lo, self.hi)], busy)
        spans = sorted((s, s + d, name) for name, s, d in self.host
                       if name != WINDOW_SPAN)
        out = []
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = 0.5 * (a + b)
            open_ = [(e - s, name) for s, e, name in spans if s <= mid < e]
            label = min(open_)[1] if open_ else "host"
            out.append([label, (b - a) * 1e-9])
        return out
