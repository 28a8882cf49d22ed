"""Production training launcher.

On a real TPU pod this builds the production mesh and runs FedVeca rounds
of the selected architecture; on this CPU container it runs the same code
path on a host mesh with reduced configs (--reduced), which is how the
examples and CI exercise it.

    PYTHONPATH=src python -m repro.launch.train \
        --arch starcoder2-3b --reduced --rounds 3 --seq 64 --batch-per-client 2

Flags mirror the dry-run: --arch selects the assigned architecture,
--mode fedveca|fednova|fedavg the aggregation rule, --tau-max the local
step budget. Data: synthetic Non-IID topic streams (per-client topics),
held device-resident and sampled inside the jitted round (RoundEngine;
--host-data re-enables the legacy per-round upload for comparison).
--cohort m sub-samples m participating clients per round.

Rounds run through ``core/driver.TrainDriver``: the controller is fused
into the jitted round (device-resident Alg. 1 state) and round k+1 is
dispatched while round k's diagnostics are still in flight (--overlap;
0 = sync debugging mode).

--mesh "data=K" (optionally "pod=J,data=K") builds a federated client
mesh and shards the whole round over it (DESIGN.md §11): data buffers,
shard_map round with psum aggregation, controller per-client state. Run
under XLA_FLAGS=--xla_force_host_platform_device_count=8 to exercise it
on a CPU box.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.configs.base import ShapeConfig
from repro.core.controller import ControllerConfig, ControllerCore
from repro.core.driver import TrainDriver
from repro.core.engine import EngineConfig, RoundEngine
from repro.data.device import DeviceShards, host_stacked_batches
from repro.data.synthetic import make_lm_tokens
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import (
    make_federated_mesh,
    make_host_mesh,
    make_production_mesh,
    num_clients,
)
from repro.metrics.logger import format_bytes
from repro.models.model import build_model
from repro.sharding.api import logical_axis_rules


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", default="fedveca")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--tau-max", type=int, default=2)
    ap.add_argument("--eta", type=float, default=0.01)
    ap.add_argument("--alpha", type=float, default=0.95)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch-per-client", type=int, default=2)
    ap.add_argument("--cohort", type=int, default=None,
                    help="participating clients per round (default: all)")
    ap.add_argument("--aggregator", default="auto",
                    choices=("auto", "pallas", "fallback"))
    ap.add_argument("--wire", default="none", metavar="none|int8|topk:K",
                    help="client->server update codec with error feedback "
                         "(core/wire.py); none is bit-identical to the "
                         "pre-wire engine")
    ap.add_argument("--host-data", action="store_true",
                    help="legacy path: build batches on host, upload per round")
    ap.add_argument("--overlap", type=int, default=1,
                    help="rounds in flight before host sync (0 = sync mode)")
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 pod mesh (requires 256 devices)")
    ap.add_argument("--mesh", default=None, metavar="data=K[,pod=J]",
                    help="client-axis sharding: shard the round over a "
                         "('pod','data') federated mesh (DESIGN.md §11)")
    ap.add_argument("--clients-per-shard", type=int, default=2,
                    help="clients per client-axis shard under --mesh")
    ap.add_argument("--data-axis", type=int, default=2)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--buffered", action="store_true",
                    help="buffered asynchronous rounds (core/buffered.py): "
                         "continuous admission, step every m arrivals")
    ap.add_argument("--buffer-waves", type=int, default=2,
                    help="cohort waves in flight under --buffered")
    ap.add_argument("--grad-decay", type=float, default=0.9,
                    help="staleness weight decay^age on buffered arrivals")
    ap.add_argument("--latency", default="exp",
                    choices=("instant", "uniform", "exp", "hetero"),
                    help="simulated client latency model under --buffered")
    ap.add_argument("--latency-scale", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="root seed: init, per-client data topics, round "
                         "subkeys all derive from it")
    ap.add_argument("--sanitize", action="store_true",
                    help="run under the analysis sanitizer lane "
                         "(DESIGN.md §14): NaN checks armed and the run "
                         "must prove zero steady-state recompiles")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    fed_mesh = None
    if args.mesh:
        try:
            spec = dict(kv.split("=") for kv in args.mesh.split(","))
            pod, data = int(spec.get("pod", 1)), int(spec["data"])
        except (KeyError, ValueError):
            ap.error(f"--mesh {args.mesh!r}: expected data=K or pod=J,data=K")
        mesh = make_federated_mesh(pod * data, pod=pod)
        fed_mesh = mesh
        C = num_clients(mesh) * args.clients_per_shard
    else:
        mesh = (
            make_production_mesh()
            if args.production_mesh
            else make_host_mesh(args.data_axis, args.model_axis)
        )
        C = num_clients(mesh)
    shape = ShapeConfig("cli", args.seq, C * args.batch_per_client, "train")
    print(f"arch={cfg.name} mesh={dict(mesh.shape)} clients={C} "
          f"global_batch={shape.global_batch} seq={shape.seq_len} "
          f"sharded={fed_mesh is not None} "
          f"data={'host' if args.host_data else 'device'} "
          f"cohort={args.cohort or C} overlap={args.overlap} "
          f"wire={args.wire}")

    datasets = [
        make_lm_tokens(64, args.seq, cfg.vocab_size, topic=i, seed=args.seed)
        for i in range(C)
    ]
    # Inside the federated round the mesh data axes are consumed by the
    # CLIENT dimension; per-client activation batches should NOT claim them.
    engine = RoundEngine(
        model.loss,
        EngineConfig(
            mode=args.mode, eta=args.eta, tau_max=args.tau_max,
            batch_size=args.batch_per_client, cohort_size=args.cohort,
            aggregator=args.aggregator, wire=args.wire,
        ),
        shards=(
            None if args.host_data
            else DeviceShards.from_datasets(datasets, mesh=fed_mesh)
        ),
        num_clients=C,
        controller=ControllerCore(
            ControllerConfig(eta=args.eta, alpha=args.alpha, tau_max=args.tau_max),
            C, adapt=(args.mode == "fedveca"), mesh=fed_mesh,
        ),
        context=lambda: logical_axis_rules(mesh, {"batch": None}),
        mesh=fed_mesh,
    )

    params = model.init(jax.random.PRNGKey(args.seed))
    taus = np.full(C, 2, np.int32)
    p = np.full((C,), 1.0 / C, np.float32)
    t_last = [time.time()]

    def on_row(row):
        now = time.time()
        wire = ""
        if row.get("wire", "identity") != "identity":
            wire = (f" wire[{row['wire']}]="
                    f"{format_bytes(row['wire_bytes'])}/round")
        print(f"round {row['round']}: loss={row['train_loss']:.4f} "
              f"tau_k={row['tau_k']:.2f} tau_next={np.asarray(row['tau']).tolist()} "
              f"({now - t_last[0]:.1f}s){wire}")
        t_last[0] = now

    if args.buffered:
        if args.host_data:
            ap.error("--buffered needs the device data path (drop --host-data)")
        from repro.core.buffered import (
            BufferedConfig,
            BufferedRoundEngine,
            LatencyModel,
        )

        buffered = BufferedRoundEngine(
            engine, p,
            BufferedConfig(
                waves=args.buffer_waves, grad_decay=args.grad_decay,
                latency=LatencyModel(args.latency, scale=args.latency_scale),
                seed=args.seed, overlap=max(args.overlap, 1),
            ),
            mode=args.mode, on_row=on_row, sanitize=args.sanitize,
        )
        with mesh:
            buffered.run(params, args.rounds, taus)
        print(f"done. host-blocked {buffered.host_blocked_s:.2f}s, "
              f"sim_time {buffered.sim_time:.1f} ticks over "
              f"{args.rounds} buffered steps ({buffered.wave_dispatches} "
              f"waves, {buffered.fold_dispatches} folds)")
        return

    driver = TrainDriver(
        engine, p, overlap=args.overlap, seed=args.seed, mode=args.mode,
        sanitize=args.sanitize,
        batches_fn=(
            (lambda rng: host_stacked_batches(datasets, rng, args.tau_max,
                                              args.batch_per_client))
            if args.host_data
            else None
        ),
        on_row=on_row,
    )
    with mesh:
        driver.run(params, args.rounds, taus)
    print(f"done. host-blocked {driver.host_blocked_s:.2f}s over "
          f"{args.rounds} rounds")


if __name__ == "__main__":
    main()
