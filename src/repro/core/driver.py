"""TrainDriver: the overlapped federated training loop (DESIGN.md §10).

With the controller fused into the round (``RoundEngine.run_fused``), a
round's dispatch needs NOTHING from the previous round on the host — taus
and ||grad F(w_{k-1})||^2 live in the device-resident ``CoreState``. The
driver exploits jax async dispatch to overlap work:

  * round k+1's cohort sampling and dispatch (host) run while round k is
    still executing on device;
  * the only device->host traffic per round is the small ``diag`` bundle
    (scalars + [C] vectors) and it is fetched ``overlap`` rounds late, so
    the host blocks on a result the device has usually already finished;
  * eval is dispatched asynchronously on the fresh params and its scalars
    are fetched at the same deferred point.

``overlap=0`` is the sync debugging mode: every round is finalized (and
therefore host-synced) before the next is dispatched. Any ``overlap``
produces bit-identical parameters — the host RNG (cohort sampling, legacy
host batches) is consumed in dispatch order, and the device program
sequence does not depend on when results are read back.

``host_blocked_s`` accumulates the time the loop spends blocked on
device->host transfers; ``benchmarks/controller_driver.py`` compares it
sync vs. overlapped against the legacy numpy-controller loop.

With a client-axis-sharded engine (DESIGN.md §11) nothing here changes
shape: ``engine.sample_cohort`` already draws per-shard index sets (a
stratified cohort whose flat, sorted form the driver logs as usual), the
fused dispatch is one shard_map program, and the deferred ``diag`` fetch
gathers only [C]-sized arrays. ``benchmarks/sharded_round.py`` records
host-blocked ms/round against the data-shard count.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.analysis import sanitize as _sanitize
from repro.core.engine import RoundEngine
from repro.data.device import format_batch
from repro.metrics.logger import RunLogger


def make_dataset_evaluator(loss_fn, data, max_batch: int = 2048) -> Callable:
    """Whole-dataset eval as one async dispatch: params -> device scalars.

    The test set is chunked into equal [k, b, ...] stacks (plus one
    remainder batch) ONCE, host-side; the returned callable only
    dispatches jitted work and never blocks, so the driver can fetch the
    scalars rounds later. Sample-weighted exactly like the simulator's
    blocking ``evaluate`` (sum of per-batch loss * batch_size / n).
    """
    n = len(data)
    b = min(n, max_batch)
    k, rem = divmod(n, b)

    def fmt(x, y):
        return format_batch(x, None if y is None else y)

    def stack(sl):
        x = data.x[sl]
        y = None if np.issubdtype(data.x.dtype, np.integer) else data.y[sl]
        return x, y

    x_main, y_main = stack(slice(0, k * b))
    main = fmt(x_main.reshape((k, b) + x_main.shape[1:]),
               None if y_main is None else y_main.reshape(k, b))
    tail = fmt(*stack(slice(k * b, n))) if rem else None

    def _eval(params, main, tail):
        def one(batch):
            loss, mets = loss_fn(params, batch)
            return loss, mets.get("acc")

        losses, accs = jax.lax.map(one, main)
        tot = jnp.sum(losses) * b
        acc_tot = None if accs is None else jnp.sum(accs) * b
        if tail is not None:
            loss_r, mets_r = loss_fn(params, tail)
            tot = tot + loss_r * rem
            if acc_tot is not None:
                acc_tot = acc_tot + mets_r["acc"] * rem
        out = {"test_loss": tot / n}
        if acc_tot is not None:
            out["test_acc"] = acc_tot / n
        return out

    jitted = jax.jit(_eval)
    return lambda params: jitted(params, main, tail)


class TrainDriver:
    """K rounds of the fused round+controller step, pipelined against host.

    The engine must be built with ``controller=ControllerCore``. ``p`` is
    the full-C client weight vector; ``batches_fn(rng)`` (optional)
    supplies legacy host-built batches per round; ``eval_fn(params)``
    (optional, see ``make_dataset_evaluator``) must be non-blocking;
    ``on_row`` is called with each finalized row (printing, early stop).
    """

    def __init__(
        self,
        engine: RoundEngine,
        p: np.ndarray,
        *,
        overlap: int = 1,
        seed: int = 0,
        mode: str = "fedveca",
        eval_fn: Optional[Callable] = None,
        eval_every: int = 1,
        batches_fn: Optional[Callable] = None,
        on_row: Optional[Callable[[Dict[str, Any]], None]] = None,
        sanitize=None,
    ):
        if engine.controller is None:
            raise ValueError("TrainDriver needs an engine built with "
                             "controller=ControllerCore")
        if overlap < 0:
            raise ValueError(f"overlap must be >= 0, got {overlap}")
        self.engine = engine
        self.p = jnp.asarray(p, jnp.float32)  # device-resident once
        self.overlap = overlap
        self.seed = seed
        self.mode = mode
        self.eval_fn = eval_fn
        self.eval_every = eval_every
        self.batches_fn = batches_fn
        self.on_row = on_row
        # sanitize=True / Sanitizer instance: run under the analysis
        # lane — NaN checks armed, and the run must prove zero
        # steady-state recompiles (round 0 is the warmup; every later
        # round must hit the jit cache). DESIGN.md §14.
        self.sanitizer = _sanitize.coerce(sanitize, label="train-driver")
        self.host_blocked_s = 0.0  # device->host readback waits
        self.dispatch_s = 0.0  # time inside the dispatch calls themselves:
        #   ~0 under true async dispatch (TPU); on the CPU backend the call
        #   blocks on the round's compute, so dispatch_s + host_blocked_s
        #   is the honest "host loop blocked" total there
        self.tau_all = 0

    # -- main loop ----------------------------------------------------------
    def run(self, params, rounds: int, taus: np.ndarray,
            logger: Optional[RunLogger] = None) -> RunLogger:
        """Run ``rounds`` fused rounds from ``params``/``taus``; returns the
        logger with ``.params`` (final, donated-through) and ``.tau_all``."""
        engine = self.engine
        log = logger or RunLogger(None, name=self.mode)
        engine.reset_wire()  # fresh error-feedback residuals per run
        # static per-client wire cost (core/wire.py): what one client's
        # update upload costs under the engine's codec, dense for identity
        self._wire_bpc = engine.wire_bytes_per_client(params)
        rng = np.random.default_rng(self.seed)
        key = jax.random.PRNGKey(self.seed)
        cstate = engine.init_controller_state(params, taus)
        if engine.mesh is not None:
            # place round 0's params where every later round's come from
            # (replicated on the client mesh): host-placed params would be
            # a second input sharding and compile the round twice
            params = jax.device_put(
                params, NamedSharding(engine.mesh, PartitionSpec()))
        scaffold = None
        pending: deque = deque()
        self.host_blocked_s = 0.0
        self.dispatch_s = 0.0
        self.tau_all = 0

        # Warmup must happen INSIDE the sanitize context: the sanitize
        # flags are part of jit's cache key, so entering it later would
        # itself force the recompiles it is meant to rule out.
        with _sanitize.maybe(self.sanitizer):
            for k in range(rounds):
                cohort = engine.sample_cohort(rng)
                key, sub = jax.random.split(key)
                batches = self.batches_fn(rng) if self.batches_fn else None
                t0 = time.perf_counter()
                params, cstate, scaffold, diag = engine.run_fused(
                    params, cstate, self.p, key=sub, batches=batches,
                    scaffold=scaffold, cohort=cohort,
                )
                self.dispatch_s += time.perf_counter() - t0
                ev = None
                if self.eval_fn and ((k % self.eval_every) == 0
                                     or k == rounds - 1):
                    ev = self.eval_fn(params)
                pending.append((k, cohort, diag, ev))
                while len(pending) > self.overlap:
                    self._finalize(pending.popleft(), log)
                if self.sanitizer is not None and k == 0:
                    # round 0 dispatched everything once (round + eval):
                    # from here on every round must hit the jit cache
                    jax.block_until_ready(params)
                    self.sanitizer.mark_steady()
            while pending:
                self._finalize(pending.popleft(), log)

            t0 = time.perf_counter()
            jax.block_until_ready(params)
            self.host_blocked_s += time.perf_counter() - t0
            if self.sanitizer is not None and rounds > 1:
                self.sanitizer.assert_steady_state()
        log.params = params  # type: ignore[attr-defined]
        log.tau_all = self.tau_all  # type: ignore[attr-defined]
        log.close()
        return log

    # -- deferred device->host sync + logging -------------------------------
    def _finalize(self, entry, log: RunLogger) -> None:
        k, cohort, diag, ev = entry
        t0 = time.perf_counter()
        host = {name: np.asarray(v) for name, v in diag.items()}  # blocks
        ev_host = None if ev is None else {name: float(v) for name, v in ev.items()}
        self.host_blocked_s += time.perf_counter() - t0

        self.tau_all += int(host["tau_round_sum"])
        row: Dict[str, Any] = dict(
            round=k,
            mode=self.mode,
            train_loss=float(host["train_loss"]),
            tau=host["tau_next"].copy(),
            tau_k=float(host["tau_k"]),
            tau_all=self.tau_all,
            beta=host["beta"],
            delta=host["delta"],
            cohort=None if cohort is None else np.asarray(cohort).copy(),
            A=host["A"],
            L=float(host["L"]),
            premise=float(host["premise"]),
            alpha_k=float(host["alpha_k"]),
            wire=self.engine.wire_codec.name,
            wire_bytes=self._wire_bpc * (
                len(cohort) if cohort is not None else self.engine.controller.C
            ),
        )
        if ev_host:
            row.update(ev_host)
        log.log(**row)
        if self.on_row:
            self.on_row(row)
