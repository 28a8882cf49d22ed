"""Find a serving mix's knee once, by a sweep on the chip: one process,
one build, one window at each fixed rate.

    python3 bench/knee.py --workload <serve cell> --seconds 30 --rates 2,3,4,5

For each rate it prints tokens per second, the TTFT p90 and how many
requests due in the window were still waiting at its close. The knee is
the highest rate whose queue does not grow through the window; a cell
below it runs at about four fifths of it (written into its traffic file).
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=4242)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    from bench.kinds import paged_serve

    harness.use_compile_cache()
    cell = harness.load_cell(args.workload)
    harness.require_chips(cell.chips)
    _, _, loop, _ = paged_serve._build(cell, args.seed)
    base = cell.traffic
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell.traffic = dict(base, arrivals=dict(base["arrivals"], rate=rate))
        t = time.time()
        reqs, c = paged_serve.serve_window(
            loop, cell, args.seed + i + 1, args.seconds,
            cell.config["vocab_size"], False, None)
        e2e, _ = paged_serve.measure(reqs, c, cell)
        due = set(c["due_in"])
        waiting = sum(1 for r in reqs if r.rid in due
                      and c["popped"].get(r.rid, float("inf")) >= c["t_end"])
        print(json.dumps(dict(rate=rate, due=len(due),
                              waiting_at_close=waiting, **e2e,
                              wall_s=time.time() - t)), flush=True)
        while loop.table.any_active():  # the next rate starts empty
            loop.tick(paged_serve.WallClockQueue([], []))
    return 0


if __name__ == "__main__":
    sys.exit(main())
