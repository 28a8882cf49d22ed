"""Quickstart: FedVeca vs FedAvg/FedNova on Non-IID data in ~2 minutes.

Reproduces the paper's headline experiment (SVM, Case-3 Non-IID split,
5 clients) at laptop scale:

    PYTHONPATH=src python examples/quickstart.py [--rounds 30] [--case 3]
"""
import argparse

import numpy as np

from repro.data.partition import client_weights, partition_by_label, partition_case3, partition_iid
from repro.data.synthetic import Dataset, binarize_even_odd, make_classification
from repro.fed.simulator import FederatedSimulator, FedSimConfig, centralized_sgd, fair_fixed_tau
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import build_model_by_name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--clients", type=int, default=5)
    ap.add_argument("--case", type=int, default=3, choices=(1, 2, 3))
    ap.add_argument("--tau-max", type=int, default=20)
    ap.add_argument("--eta", type=float, default=0.05)
    ap.add_argument("--cohort", type=int, default=None,
                    help="participating clients per round (default: all)")
    ap.add_argument("--aggregator", default="auto",
                    choices=("auto", "pallas", "fallback"),
                    help="server reduce: Pallas vecavg kernel or XLA fallback")
    ap.add_argument("--data-path", default="device", choices=("device", "host"),
                    help="device-resident shards vs legacy host-built batches")
    ap.add_argument("--overlap", type=int, default=1,
                    help="rounds in flight before host sync (0 = sync mode)")
    args = ap.parse_args()
    use_compile_cache()

    print(f"== FedVeca quickstart: SVM / Case {args.case} / {args.clients} clients ==")
    orig = make_classification(4000, (784,), 10, seed=0)
    train = binarize_even_odd(orig)
    test = binarize_even_odd(make_classification(1000, (784,), 10, seed=1))
    part_fn = {1: lambda: partition_iid(len(train.y), args.clients),
               2: lambda: partition_by_label(orig.y, args.clients),
               3: lambda: partition_case3(orig.y, args.clients)}[args.case]
    parts = part_fn()
    clients = [Dataset(train.x[s], train.y[s]) for s in parts]
    print("client sizes:", [len(c) for c in clients])

    model = build_model_by_name("svm-mnist")

    cfg = FedSimConfig(mode="fedveca", rounds=args.rounds, tau_max=args.tau_max,
                       batch_size=16, eta=args.eta, cohort_size=args.cohort,
                       aggregator=args.aggregator, data_path=args.data_path,
                       overlap=args.overlap)
    veca = FederatedSimulator(model, clients, cfg, test).run()
    print("\nround  loss    acc    tau (adaptive)            eta*tau_k*L")
    for r in veca.rows[:: max(1, args.rounds // 10)]:
        prem = r.get("premise")
        print(f"{r['round']:5d}  {r['test_loss']:.4f}  {r.get('test_acc', 0):.3f}  "
              f"{str(r['tau']):24s}  {prem if prem is None else f'{prem:.2f}'}")

    sizes = np.array([len(c) for c in clients], float)
    ft = np.minimum(fair_fixed_tau(veca.tau_all, args.rounds, 16, sizes), args.tau_max)
    results = {"fedveca": veca.rows[-1]}
    for mode in ("fedavg", "fednova"):
        bcfg = FedSimConfig(mode=mode, rounds=args.rounds, tau_max=args.tau_max,
                            batch_size=16, eta=args.eta, fixed_tau=ft,
                            cohort_size=args.cohort, aggregator=args.aggregator,
                            data_path=args.data_path, overlap=args.overlap)
        results[mode] = FederatedSimulator(model, clients, bcfg, test).run().rows[-1]
    pooled = Dataset(np.concatenate([c.x for c in clients]),
                     np.concatenate([c.y for c in clients]))
    _, cent = centralized_sgd(model, pooled, veca.tau_all, 16, args.eta, test)

    print(f"\n== final (rounds={args.rounds}, total local iters={veca.tau_all}) ==")
    for name, row in results.items():
        print(f"{name:12s} loss={row['test_loss']:.4f} acc={row.get('test_acc', 0):.3f}")
    print(f"{'centralized':12s} loss={cent['test_loss']:.4f} acc={cent.get('test_acc', 0):.3f}")


if __name__ == "__main__":
    main()
