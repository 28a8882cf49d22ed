"""Benchmark entrypoint: one function per paper figure/table + the roofline
harness + kernel micros. Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run            # quick profile
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale (slow)
  PYTHONPATH=src python -m benchmarks.run --only fig3,roofline
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import (  # noqa: E402
    beyond_paper,
    buffered_round,
    controller_driver,
    fig3_loss_accuracy,
    fig4_premise,
    fig5_cases,
    fig6_instantaneous,
    fig7_alpha_sensitivity,
    fig8_clients,
    kernels_micro,
    roofline,
    round_engine,
    serve_loop,
    serve_paged,
    serve_slo,
    sharded_round,
    wire_compression,
)
from benchmarks.common import FULL, QUICK, emit  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

BENCHES = {
    "fig3": fig3_loss_accuracy.run,
    "fig4": fig4_premise.run,
    "fig5": fig5_cases.run,
    "fig6": fig6_instantaneous.run,
    "fig7": fig7_alpha_sensitivity.run,
    "fig8": fig8_clients.run,
    "kernels": kernels_micro.run,
    "paged_kernel": kernels_micro.run_paged,
    "beyond": beyond_paper.run,
    "roofline": roofline.run,
    "round_engine": round_engine.run,
    "controller_driver": controller_driver.run,
    "sharded_round": sharded_round.run,
    "buffered_round": buffered_round.run,
    "serve_loop": serve_loop.run,
    "serve_paged": serve_paged.run,
    "serve_slo": serve_slo.run,
    "wire_compression": wire_compression.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale rounds")
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    ap.add_argument("--list", action="store_true",
                    help="print available bench names and exit")
    ap.add_argument("--csv-dir", default="experiments/bench_csv")
    ap.add_argument("--force", action="store_true",
                    help="re-measure cached artifacts (roofline: redo the "
                    "vecavg/paged-attention timing rows instead of reusing "
                    "experiments/dryrun/*.json)")
    args = ap.parse_args()
    use_compile_cache()

    if args.list:
        for name in BENCHES:
            print(name)
        return

    scale = FULL if args.full else QUICK
    names = args.only.split(",") if args.only else list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        ap.error(f"unknown bench name(s): {', '.join(unknown)} — "
                 f"available: {', '.join(BENCHES)}")
    os.makedirs(args.csv_dir, exist_ok=True)

    rows: list = []
    print("name,us_per_call,derived")
    for name in names:
        fn = BENCHES[name]
        t0 = time.time()
        before = len(rows)
        kw = {"csv_dir": args.csv_dir}
        if name == "roofline":
            kw["force"] = args.force
        try:
            fn(scale, rows, **kw)
        except Exception as e:  # noqa: BLE001
            rows.append(dict(name=f"{name}/ERROR", us_per_call=0.0,
                             derived=f"{type(e).__name__}:{e}"))
        emit(rows[before:])
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)

    # persist for benchmarks.gen_experiments (§Repro table)
    import csv

    os.makedirs("experiments", exist_ok=True)
    mode = "a" if args.only else "w"
    seen = set()
    if mode == "a" and os.path.exists("experiments/bench_rows.csv"):
        seen = {r["name"] for r in csv.DictReader(open("experiments/bench_rows.csv"))}
    with open("experiments/bench_rows.csv", mode, newline="") as f:
        w = csv.DictWriter(f, fieldnames=["name", "us_per_call", "derived"])
        if mode == "w" or not seen:
            w.writeheader()
        for r in rows:
            if r["name"] not in seen:
                w.writerow({k: r[k] for k in ("name", "us_per_call", "derived")})


if __name__ == "__main__":
    main()
