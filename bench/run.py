"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell from the seed, warms up every shape it will use, measures
for ``--seconds`` and checks what the measured path produced against the
cell's plain reference. With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` a profiler trace of the window
gives its per-layer metrics instead. The last line of standard output is
the result as one JSON object. With no accelerator, or fewer chips than
the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import argparse
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
TRACE_DIR = ROOT / ".bench_trace"


def per_layer_metrics(cell, out: dict, table) -> dict:
    """Each per-layer metric of the cell from its own reader; a reader
    that finds nothing to read is left out."""
    from bench import harness, reduce, work

    red = reduce.Reduced(table)
    ctx = dict(counters=out["counters"], trace=red, cell=cell,
               peaks=work.peaks(out["device"]["kind"]), work=work)
    metrics = {}
    for m in cell.per_layer:
        value = harness.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out["device"].update(busy_s=red.busy_s(), window_s=red.window_s)
    out["breakdown"] = {"device_ops": red.top_ops(10),
                        "idle_gaps": red.idle_gaps(10)}
    return metrics


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    t_process = harness.process_start_time()
    harness.use_compile_cache()
    cell = harness.load_cell(args.workload)
    try:
        harness.require_chips(cell.chips)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    trace_dir = TRACE_DIR / cell.name
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = cell.kind().run(cell, args.seed, args.seconds, bool(args.trace),
                          t_process, trace_dir=trace_dir)
    print(f"bench: {cell.name}: {out['counters'].get('compiles_in_window')} "
          "compiles inside the window", file=sys.stderr)
    if args.trace:
        from bench import reduce

        table = reduce.load_xplane(reduce.find_xplane(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = per_layer_metrics(cell, out, table)
    else:
        names = [m["name"] for m in cell.end_to_end]
        metrics = {m["name"]: {"value": out["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in out["e2e"]}
        missing = set(names) - set(metrics)
        if missing:
            raise RuntimeError(f"{cell.name}: no value for {sorted(missing)}")
    print(f"bench: readings {out['readings']} (reference {out['check_s']:.1f} s)",
          file=sys.stderr)
    harness.emit(dict(correct=out["correct"], attempted=out["attempted"],
                      failed=out["failed"], metrics=metrics,
                      device=out["device"], breakdown=out.get("breakdown"),
                      checks=out["checks"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
