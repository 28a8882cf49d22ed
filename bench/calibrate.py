"""Readings the correctness limits are set from, many seeds in ONE
process (set-up and compiles are paid once).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 40 \
        [--control] [--fault half_batch]

Per seed it prints one JSON line: the program's readings from a window of
``--seconds``, each judged against the cell's limits as a run judges
them (``correct``). With ``--control`` the line adds the control's
readings on the same work (the reference one precision below the
configuration's, in the program's place) and ``control_correct``, the
same judgement of them, which has to come out false. With ``--fault`` the
program runs with that fault planted (``bench/faults``). The benchmark's
own runs never run any of these.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import faults, harness

    harness.use_compile_cache()
    cell = harness.load_cell(args.workload)
    harness.require_chips(cell.chips)
    kind = cell.kind()
    plant = faults.FAULTS[args.fault] if args.fault else contextlib.nullcontext
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        rec = {"seed": seed}
        if args.control:
            out = kind.control(cell, seed, args.seconds)
            ctl = harness.judge(out["control"], cell.limits)
            rec.update(control=out["control"],
                       control_correct=harness.passed(ctl))
        else:
            with plant():
                out = kind.run(cell, seed, args.seconds, False, time.time())
        rec.update({args.fault or "program": out["readings"],
                    "correct": out["correct"], "e2e": out["e2e"],
                    "check_s": out["check_s"], "wall_s": time.time() - t0})
        print(json.dumps(rec, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
