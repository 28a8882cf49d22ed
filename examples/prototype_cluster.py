"""The paper's prototype deployment, literally: a parameter server
(Algorithm 1) and N client processes (Algorithm 2) exchanging messages —
the software twin of the 5-Raspberry-Pi + laptop testbed (§IV-A), with
wire-bytes accounting.

By default the cluster's replies are computed through the RoundEngine's
continuous batcher (``engine.client_update_many``): one masked device
program per round serves every client message whatever its tau, instead
of a per-client Python loop of separate dispatches (ROADMAP serving-path
item). ``--serial`` restores the literal one-dispatch-per-client testbed
loop; both produce bit-identical replies (fed/prototype.py).

    PYTHONPATH=src python examples/prototype_cluster.py --rounds 10
    PYTHONPATH=src python examples/prototype_cluster.py --serial
"""
import argparse
import time

import numpy as np

from repro.data.partition import partition_case3
from repro.data.synthetic import Dataset, binarize_even_odd, make_classification
from repro.fed.prototype import FedVecaClient, FedVecaServer
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import build_model_by_name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=5)
    ap.add_argument("--eta", type=float, default=0.05)
    ap.add_argument("--serial", action="store_true",
                    help="literal per-client dispatch loop (testbed mode)")
    args = ap.parse_args()
    use_compile_cache()

    orig = make_classification(2000, (784,), 10, seed=0)
    train = binarize_even_odd(orig)
    parts = partition_case3(orig.y, args.clients, seed=0)
    model = build_model_by_name("svm-mnist")
    clients = [
        FedVecaClient(i, model, Dataset(train.x[s], train.y[s]), batch_size=16,
                      eta=args.eta)
        for i, s in enumerate(parts)
    ]
    p = np.array([len(s) for s in parts], float)
    p /= p.sum()
    server = FedVecaServer(model, clients, p, eta=args.eta, tau_max=20,
                           batched=not args.serial)

    fabric = "serial per-client dispatches" if args.serial else \
        "continuous-batched (one dispatch/round)"
    print(f"server + {args.clients} clients, weights={np.round(p, 3)}, "
          f"fabric={fabric}")
    t0 = time.time()
    for k in range(args.rounds):
        row = server.round()
        print(f"round {k:3d}: tau={row['tau']} L={row['L']:.3f} "
              f"premise={row['premise'] if row['premise'] is None else round(row['premise'], 2)}")
    print(f"\n{args.rounds} rounds in {time.time()-t0:.1f}s ({fabric})")
    print(f"wire traffic: server->clients {server.bytes_sent/1e6:.2f} MB, "
          f"clients->server {server.bytes_recv/1e6:.2f} MB over {args.rounds} rounds")
    print("STOP flag semantics exercised by server.run(); see fed/prototype.py")


if __name__ == "__main__":
    main()
