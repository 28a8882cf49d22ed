"""Run one cell several times, each run a fresh ``bench/run.py`` process.

    python3 bench/repeat.py --workload <cell> --seeds 11,12,13 --seconds 40 \
        [--trace 0|1] [--out results.jsonl]

This parent never imports JAX, so each child has the chips to itself.
Every run's result line (with its seed, exit code and wall time) goes to
``--out``; the summary prints, per end-to-end metric, the median and the
spread (interquartile range over the median) the bounds are set from.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    sys.path.insert(0, str(HERE.parent))
    from bench.stats import spread

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    runs = []
    for seed in args.seeds.split(","):
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, str(HERE / "run.py"), "--workload",
             args.workload, "--seed", seed, "--seconds", str(args.seconds),
             "--trace", str(args.trace)],
            capture_output=True, text=True)
        lines = proc.stdout.strip().splitlines()
        try:
            res = json.loads(lines[-1]) if lines and proc.returncode == 0 \
                else None
        except json.JSONDecodeError:
            res = None
        rec = dict(seed=int(seed), rc=proc.returncode,
                   wall_s=time.time() - t0, result=res,
                   stderr_tail=proc.stderr[-3000:])
        runs.append(rec)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
        metrics = res["metrics"] if res else {}
        print(f"seed {seed}: rc {proc.returncode} correct "
              f"{res and res['correct']} wall {rec['wall_s']:.1f}s "
              + " ".join(f"{k}={v['value']:.6g}" for k, v in metrics.items()),
              flush=True)
        if res is None:
            print(proc.stderr[-3000:], flush=True)
        else:
            print("  checks " + json.dumps(res["checks"]), flush=True)
    ok = [r["result"] for r in runs if r["result"]]
    for name in (ok[0]["metrics"] if ok else {}):
        vals = [r["metrics"][name]["value"] for r in ok]
        med = sorted(vals)[len(vals) // 2]
        sp = spread(vals) if len(vals) >= 2 else float("nan")
        print(f"{name}: median {med:.6g} spread {sp:.4%} over {len(vals)}")
    return 0 if all(r["rc"] == 0 for r in runs) else 1


if __name__ == "__main__":
    sys.exit(main())
