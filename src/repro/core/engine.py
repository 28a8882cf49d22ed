"""RoundEngine: the single owner of the jitted federated round.

Every consumer of the FedVeca round — the simulator, the message-passing
prototype, the production launcher, and the examples — goes through this
engine; ``core/aggregation.py`` stays as the only independent
implementation, deliberately, as the test oracle (DESIGN.md §3).

The engine composes the pieces that used to be re-implemented per caller:

  * the fused round step (``core/fedveca.make_round_step``) specialized by
    a per-mode ``Strategy`` with a pluggable server reduce — the Pallas
    vecavg kernel on TPU, ``tree_weighted_sum`` elsewhere;
  * parameter/scaffold buffer donation (``donate_argnums``), so the global
    model is updated in place instead of double-buffered — the controller
    was already designed to consume only RoundStats for exactly this;
  * the on-device data path (``data/device.DeviceShards``): minibatch
    indices are drawn *inside* the jitted round, eliminating the per-round
    host->device upload of a [C, tau_max, batch, ...] tensor (the legacy
    host-batched path is still accepted via ``batches=``);
  * cohort sub-sampling: ``m <= C`` participating clients per round with
    weight renormalization (p restricted to the cohort and rescaled to
    sum to 1), the standard partial-participation knob for Non-IID FL;
  * client-axis sharding (``mesh=``, DESIGN.md §11): with a federated
    mesh the round body runs under ``shard_map`` over the client axes
    ('pod','data') — each shard's local updates touch only its own
    clients' data, the server reduce is a shard-local (Pallas or
    fallback) partial reduce completed by ``jax.lax.psum``, and cohorts
    are drawn as per-shard index sets so dispatch never gathers client
    data cross-shard.

The message-passing prototype uses the engine's two half-round entry
points (``client_update`` / ``server_aggregate``) so its wire protocol
stays explicit while the math is shared; ``client_update_many`` is the
continuously-batched form (one masked tau_max-trip program serving every
client message, whatever its tau).
"""
from __future__ import annotations

import contextlib
import dataclasses
import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.controller import ControllerCore
from repro.core.fedveca import ScaffoldState, make_local_update, make_round_step
from repro.core.strategy import get_strategy, global_sum, make_reduce
from repro.core.tree import tree_axpy, tree_zeros_like
from repro.data.device import DeviceShards


@contextlib.contextmanager
def _quiet_donation():
    """CPU backends that predate donation support just ignore the hint; the
    warning would otherwise fire once per trace in every example run. Scoped
    to the engine's own dispatches — module import must NOT mutate global
    warning state for every importer."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        yield


@dataclasses.dataclass
class EngineConfig:
    mode: str = "fedveca"  # fedveca | fednova | fedavg | fedprox | scaffold
    eta: float = 0.01
    tau_max: int = 2
    mu: float = 0.0  # fedprox proximal coefficient
    batch_size: int = 32  # per-client per-step minibatch (device data path)
    cohort_size: Optional[int] = None  # m <= C participating clients; None = all
    aggregator: str = "auto"  # server reduce: 'pallas' | 'fallback' | 'auto'
    donate: bool = True  # donate params (+ scaffold) buffers to the round
    unroll_tau: bool = False
    stat_dtype: Any = jnp.float32
    wire: Any = "none"  # client->server update codec (core/wire.py):
    #   'none'/'identity' | 'int8' | 'topk:K' | a WireCodec. Non-identity
    #   codecs carry per-client error-feedback residuals as engine state
    #   ([C, ...] rows, client-sharded under a mesh, donated per round).


class RoundEngine:
    """Owns the jitted round for one (loss_fn, config) pair.

    loss_fn(params, batch) -> (scalar, metrics dict).

    ``run_round`` executes one full round; pass ``key=`` to sample from the
    engine's device-resident shards, or ``batches=`` (leaves
    [C, tau_max, b, ...]) to use host-built data. ``cohort=`` (int32 [m])
    restricts the round to a sub-sampled cohort.

    ``mesh=`` (a federated mesh, ``launch/mesh.make_federated_mesh``)
    shards the client axis: C must divide evenly over the client-axis
    shards, cohorts must be per-shard balanced (``sample_cohort`` draws
    them that way), and the round executes as one shard_map program with
    psum aggregation — numerically matching the single-device round
    within f32 reduce-ordering tolerance (tests/test_sharded_round.py).
    """

    def __init__(
        self,
        loss_fn: Callable,
        cfg: EngineConfig,
        *,
        shards: Optional[DeviceShards] = None,
        num_clients: Optional[int] = None,
        controller: Optional[ControllerCore] = None,  # fuse Alg. 1 into the
        #   round: run_fused dispatches round + controller as ONE program
        context: Optional[Callable] = None,  # trace-time ambient (e.g. mesh
        #   logical axis rules); entered around the round body
        mesh=None,  # federated mesh: shard the client axis over ('pod','data')
    ):
        if cfg.cohort_size is not None and cfg.cohort_size < 1:
            raise ValueError(f"cohort_size must be >= 1, got {cfg.cohort_size}")
        self.cfg = cfg
        self.shards = shards
        self.controller = controller
        self.num_clients = num_clients if num_clients is not None else (
            shards.num_clients if shards is not None else None
        )
        self._context = context or contextlib.nullcontext

        # -- client-axis sharding setup (DESIGN.md §11) ---------------------
        self.mesh = mesh
        if mesh is not None:
            from repro.sharding.api import client_axes, client_shard_count

            self._client_axes = client_axes(mesh)
            self._n_shards = client_shard_count(mesh)
        else:
            self._client_axes = ()
            self._n_shards = 1
        self.sharded = self._n_shards > 1
        if self.sharded:
            from repro.sharding.api import validate_client_count

            C = self.num_clients
            if C is None:
                raise ValueError("sharded engine needs num_clients or shards=")
            validate_client_count(mesh, C)
            self._local_C = C // self._n_shards
            # cohort_size need NOT divide the shard count: sample_cohort
            # degrades to an imbalanced-but-valid per-shard split (warned)
            # and _prep_cohort sentinel-pads the short rows
            if shards is not None and shards.mesh is not mesh:
                # place the data ONCE at build time, not per dispatch
                from repro.sharding.api import client_sharding

                def put(a):
                    return jax.device_put(a, client_sharding(mesh, a.ndim))

                self.shards = shards = DeviceShards(
                    put(shards.x),
                    None if shards.y is None else put(shards.y),
                    put(shards.sizes), mesh=mesh,
                )

        self._strategy = get_strategy(cfg.mode, mu=cfg.mu)
        self._reduce = make_reduce(cfg.aggregator)

        # -- wire stage (core/wire.py, DESIGN.md §15) -----------------------
        from repro.core.wire import make_codec

        self.wire_codec = make_codec(cfg.wire)
        # identity bypasses entirely: no residual state, no extra ops in
        # the trace — the bit-identity contract vs the pre-wire engine
        self._wire_active = not self.wire_codec.is_identity
        if self._wire_active and self._strategy.uses_scaffold:
            raise ValueError(
                f"mode {cfg.mode!r} aggregates parameter deltas, not cum_g; "
                "wire compression is not supported (use wire='none')"
            )
        self._wire_res = None  # [C, ...] error-feedback rows, lazily built

        axis_name = self._client_axes if self.sharded else None
        self._round = make_round_step(
            loss_fn, eta=cfg.eta, tau_max=cfg.tau_max, mode=cfg.mode,
            mu=cfg.mu, unroll_tau=cfg.unroll_tau, stat_dtype=cfg.stat_dtype,
            aggregator=cfg.aggregator, axis_name=axis_name,
            wire=self.wire_codec if self._wire_active else None,
        )
        self._local = make_local_update(
            loss_fn, eta=cfg.eta, tau_max=cfg.tau_max, strategy=self._strategy,
            stat_dtype=cfg.stat_dtype,
        )

        def round_body(params, data, key, batches, tau, p, gprev_sqnorm,
                       scaffold, cohort, residual, offset=None):
            """Shared cohort/data/scaffold plumbing around the fused round.

            ``residual`` (wire stage, [C, ...] error-feedback rows or
            None) is gathered/scattered per cohort exactly like SCAFFOLD's
            ``c_i``: rows are keyed by client id, pads clamp on gather and
            drop on scatter, and under shard_map the tree is shard-local.

            One body serves both execution modes. ``offset=None`` is the
            single-device path. Inside shard_map, ``offset`` is this
            shard's first global client id, every client-axis argument
            holds only the shard's clients, cohort rows carry GLOBAL ids
            (localized here — never a cross-shard gather; balance is
            enforced host-side), and the cohort weight normalizer is
            psum-completed.
            """
            sub_scaffold = scaffold
            local = None  # row ids into the (local) client-axis arrays
            gids = None  # matching GLOBAL client ids (key folding)
            if cohort is not None:
                gids = cohort.reshape(-1)
                if offset is None:
                    local = gids
                    pw_l = p[local]
                else:
                    # imbalanced stratified cohorts sentinel-pad short rows
                    # with id C: pads localize to C_loc (out of range, so
                    # scatters drop them / gathers clamp) and weigh 0
                    valid = gids < jnp.int32(self.num_clients)
                    local = jnp.where(valid, gids - offset,
                                      jnp.int32(self._local_C))
                    pw_l = jnp.where(valid, p[jnp.minimum(local,
                                                          self._local_C - 1)],
                                     jnp.float32(0.0))
                tau = tau[local]
                # partial participation: renormalize cohort weights (psum
                # routes through the strategy layer when sharded)
                norm = global_sum(
                    pw_l, self._client_axes if offset is not None else None)
                pw = pw_l / norm
                if scaffold is not None:
                    # c_i rows are per CLIENT ID, not cohort position
                    sub_scaffold = ScaffoldState(
                        c=scaffold.c,
                        c_i=jax.tree.map(lambda x: x[local], scaffold.c_i),
                    )
            else:
                pw = p  # full-C weights already sum to 1 across shards
                if offset is not None:
                    gids = offset + jnp.arange(self._local_C, dtype=jnp.int32)
            if batches is None:
                batches = self.shards.sample(
                    data, key, cfg.tau_max, cfg.batch_size, local,
                    ids_global=gids,
                )
            elif cohort is not None:
                batches = jax.tree.map(lambda x: x[local], batches)
            res_rows = residual
            if residual is not None and cohort is not None:
                # pad rows (local == C_loc) clamp-gather a neighbor's
                # residual, but their decoded output weighs 0 in the
                # reduce and their scatter below is dropped (OOB)
                res_rows = jax.tree.map(lambda x: x[local], residual)
            with self._context():
                if residual is not None:
                    new_params, stats, new_scaffold, new_res_rows = (
                        self._round(params, batches, tau, pw, gprev_sqnorm,
                                    sub_scaffold, res_rows)
                    )
                else:
                    new_params, stats, new_scaffold = self._round(
                        params, batches, tau, pw, gprev_sqnorm, sub_scaffold
                    )
                    new_res_rows = None
            if cohort is not None and scaffold is not None and new_scaffold is not None:
                new_scaffold = ScaffoldState(
                    c=new_scaffold.c,
                    c_i=jax.tree.map(
                        lambda full, rows: full.at[local].set(rows),
                        scaffold.c_i, new_scaffold.c_i,
                    ),
                )
            new_residual = residual
            if residual is not None:
                new_residual = (
                    new_res_rows if cohort is None
                    else jax.tree.map(
                        lambda full, rows: full.at[local].set(rows),
                        residual, new_res_rows,
                    )
                )
            return new_params, stats, new_scaffold, pw, new_residual

        def sharded_body(params, data, key, batches, tau, p, gprev_sqnorm,
                         scaffold, cohort, residual):
            sidx = jnp.int32(0)
            for a in self._client_axes:
                sidx = sidx * mesh.shape[a] + jax.lax.axis_index(a)
            return round_body(params, data, key, batches, tau, p,
                              gprev_sqnorm, scaffold, cohort, residual,
                              offset=sidx * self._local_C)

        def dispatch_round(params, data, key, batches, tau, p, gprev_sqnorm,
                           scaffold, cohort, residual):
            if not self.sharded:
                return round_body(params, data, key, batches, tau, p,
                                  gprev_sqnorm, scaffold, cohort, residual)
            # build the shard_map at trace time: in/out specs depend on
            # which optional args (batches/scaffold/cohort) are present
            from repro.core.fedveca import RoundStats

            cspec = P(self._client_axes if len(self._client_axes) > 1
                      else self._client_axes[0])
            rep = P()

            def cs(t):  # leading-client-axis tree
                return jax.tree.map(lambda _: cspec, t)

            def rs(t):  # replicated tree
                return jax.tree.map(lambda _: rep, t)

            scaf_spec = (
                None if scaffold is None
                else ScaffoldState(c=rs(scaffold.c), c_i=cs(scaffold.c_i))
            )
            res_spec = None if residual is None else cs(residual)
            in_specs = (rs(params), cs(data), None if key is None else rep,
                        cs(batches), cspec, cspec, rep, scaf_spec,
                        None if cohort is None else cspec, res_spec)
            stats_spec = RoundStats(
                loss0=cspec, beta=cspec, delta=cspec, g0_sqnorm=cspec,
                tau=cspec, tau_k=rep, global_grad=rs(params),
                update_sqnorm=rep, params_sqnorm=rep, global_grad_sqnorm=rep,
            )
            out_specs = (rs(params), stats_spec, scaf_spec, cspec, res_spec)
            return shard_map(
                sharded_body, mesh=mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False,
            )(params, data, key, batches, tau, p, gprev_sqnorm, scaffold,
              cohort, residual)

        def step(params, data, key, batches, tau, p, gprev_sqnorm, scaffold,
                 cohort, residual):
            new_params, stats, new_scaffold, _, new_residual = dispatch_round(
                params, data, key, batches, tau, p, gprev_sqnorm, scaffold,
                cohort, residual,
            )
            return new_params, stats, new_scaffold, new_residual

        donate = (0, 7) if cfg.donate else ()  # params, scaffold
        if cfg.donate and self._wire_active:
            donate = donate + (9,)  # error-feedback residual rows
        self._step = jax.jit(step, donate_argnums=donate)

        def fused(params, cstate, data, key, batches, p, scaffold, cohort,
                  residual):
            """Round k + controller update as ONE dispatch (DESIGN.md §10).

            taus and ||grad F(w_{k-1})||^2 come from the device-resident
            controller state, so the host never syncs between rounds; only
            the small ``diag`` arrays need a device->host copy, and the
            caller decides when to block on them.
            """
            taus_full = jnp.clip(cstate.taus, 1, cfg.tau_max)
            new_params, stats, new_scaffold, pw, new_residual = dispatch_round(
                params, data, key, batches, taus_full, p,
                cstate.prev_grad_sqnorm, scaffold, cohort, residual,
            )
            C = taus_full.shape[0]
            cohort_flat = None if cohort is None else cohort.reshape(-1)
            members = (
                jnp.arange(C, dtype=jnp.int32) if cohort is None else cohort_flat
            )
            new_cstate, diag = self.controller.step(
                cstate, stats, members, taus_full
            )
            if cohort is None:
                tau_round_sum = jnp.sum(taus_full)
            else:
                # sentinel-padded entries (id == C) must not clamp-gather
                # the last client's tau into the sum
                valid = cohort_flat < C
                tau_round_sum = jnp.sum(jnp.where(
                    valid, taus_full[jnp.minimum(cohort_flat, C - 1)], 0
                ))
            diag = dict(
                diag,
                train_loss=jnp.sum(pw * stats.loss0),
                tau_k=stats.tau_k,
                tau_round_sum=tau_round_sum,
                update_sqnorm=stats.update_sqnorm,
            )
            return new_params, new_cstate, new_scaffold, new_residual, diag

        if controller is not None:
            fused_donate = (0, 1, 6) if cfg.donate else ()  # params, cstate,
            if cfg.donate and self._wire_active:                   # scaffold
                fused_donate = fused_donate + (8,)  # wire residual rows
            self._fused = jax.jit(fused, donate_argnums=fused_donate)

        def client_update(params, batches_c, tau_c, gprev_sqnorm):
            with self._context():
                zeros = tree_zeros_like(params)
                out = self._local(params, batches_c, tau_c, gprev_sqnorm,
                                  zeros, zeros)
            tau_f = tau_c.astype(jnp.float32)
            G = jax.tree.map(lambda x: x / tau_f, out["cum_g"])
            return dict(G=G, g0=out["g0"], beta=out["beta"], delta=out["delta"],
                        loss0=out["loss0"])

        self._client_update = jax.jit(client_update)

        def client_update_many(params, batches_stacked, taus, gprev_sqnorm):
            """M clients' Alg. 2 in one dispatch: leaves [M, tau_max, b, ...]
            with per-client tau masking — the continuously-batched serving
            form of ``client_update`` (one static-shape program handles any
            mix of taus; steps past tau_i are masked no-ops)."""
            with self._context():
                zeros = tree_zeros_like(params)
                outs = jax.vmap(
                    self._local, in_axes=(None, 0, 0, None, None, None)
                )(params, batches_stacked, taus, gprev_sqnorm, zeros, zeros)
            tau_f = taus.astype(jnp.float32)
            G = jax.tree.map(
                lambda x: x / tau_f.reshape((-1,) + (1,) * (x.ndim - 1)),
                outs["cum_g"],
            )
            return dict(G=G, g0=outs["g0"], beta=outs["beta"],
                        delta=outs["delta"], loss0=outs["loss0"])

        self._client_update_many = jax.jit(client_update_many)

        def wave_update(params, data, key, taus, gprev_sqnorm, cohort,
                        residual, offset=None):
            """One dispatch wave of the buffered engine (core/buffered.py):
            the cohort's Alg. 2 local updates against ONE params version,
            returning per-slot gradient accumulators + stats. This is exactly
            the client half of the fused round — same clip, same per-client
            fold_in sampling, same masked-tau vmap — with the server
            fold/step deferred to the buffered scheduler, so instant
            arrivals reproduce the synchronous round exactly."""
            taus_full = jnp.clip(taus, 1, cfg.tau_max)
            gids = cohort.reshape(-1)
            local = gids if offset is None else gids - offset
            tau = taus_full[local]
            batches = self.shards.sample(
                data, key, cfg.tau_max, cfg.batch_size, local, ids_global=gids
            )
            with self._context():
                M = gids.shape[0]
                zeros = tree_zeros_like(params)
                zrows = jax.tree.map(
                    lambda x: jnp.zeros((M,) + x.shape, x.dtype), params
                )
                outs = jax.vmap(
                    self._local, in_axes=(None, 0, 0, None, None, 0)
                )(params, batches, tau, gprev_sqnorm, zeros, zrows)
            cum_g = outs["cum_g"]
            new_residual = residual
            if residual is not None:
                # wire stage on the streaming path: residual rows are keyed
                # by GLOBAL client id (shard-local gather by `local`), so
                # arrivals folded rounds later still telescope correctly
                from repro.core.wire import wire_fold

                rows = jax.tree.map(lambda x: x[local], residual)
                cum_g, new_rows = wire_fold(self.wire_codec, cum_g, rows)
                new_residual = jax.tree.map(
                    lambda full, r: full.at[local].set(r), residual, new_rows
                )
            # raw accumulators, NOT normalized: the buffered commit routes
            # through strategy.server_delta exactly like the sync round, so
            # every mode's op sequence (and bitwise result) is preserved
            return dict(cum_g=cum_g, g0=outs["g0"],
                        loss0=outs["loss0"], beta=outs["beta"],
                        delta=outs["delta"], tau=tau), new_residual

        def dispatch_wave(params, data, key, taus, gprev_sqnorm, cohort,
                          residual):
            if not self.sharded:
                return wave_update(params, data, key, taus, gprev_sqnorm,
                                   cohort, residual)
            cspec = P(self._client_axes if len(self._client_axes) > 1
                      else self._client_axes[0])
            rep = P()

            def sharded_wave(params, data, key, taus, gprev_sqnorm, cohort,
                             residual):
                sidx = jnp.int32(0)
                for a in self._client_axes:
                    sidx = sidx * mesh.shape[a] + jax.lax.axis_index(a)
                return wave_update(params, data, key, taus, gprev_sqnorm,
                                   cohort, residual,
                                   offset=sidx * self._local_C)

            res_spec = (None if residual is None
                        else jax.tree.map(lambda _: cspec, residual))
            in_specs = (
                jax.tree.map(lambda _: rep, params),
                jax.tree.map(lambda _: cspec, data),
                rep, cspec, rep, cspec, res_spec,
            )
            out_specs = (dict(
                cum_g=jax.tree.map(lambda _: cspec, params),
                g0=jax.tree.map(lambda _: cspec, params),
                loss0=cspec, beta=cspec, delta=cspec, tau=cspec,
            ), res_spec)
            return shard_map(
                sharded_wave, mesh=mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False,
            )(params, data, key, taus, gprev_sqnorm, cohort, residual)

        # buffered wave dispatch needs the device data path (shards)
        wave_donate = (6,) if (cfg.donate and self._wire_active) else ()
        self._wave = (
            jax.jit(dispatch_wave, donate_argnums=wave_donate)
            if shards is not None else None
        )

        def server_aggregate(params, G_stacked, tau, p):
            tau_f = tau.astype(jnp.float32)
            with self._context():
                delta_w = self._strategy.delta_from_normalized(
                    G_stacked, tau_f, p, cfg.eta, self._reduce
                )
            return tree_axpy(1.0, delta_w, params), jnp.sum(p * tau_f)

        self._server_aggregate = jax.jit(server_aggregate)
        self._weighted_average = jax.jit(
            lambda stacked, w: self._reduce(stacked, w, 1.0)[0]
        )

    # -- full round ---------------------------------------------------------
    def run_round(self, params, tau, p, gprev_sqnorm, *, key=None, batches=None,
                  scaffold: Optional[ScaffoldState] = None, cohort=None):
        """One round: (new_params, RoundStats over the cohort, scaffold).

        The params (and scaffold) buffers are DONATED when cfg.donate —
        callers must use the returned arrays, never the arguments.
        """
        data = self._resolve_data(batches, key)
        tau = jnp.asarray(tau, jnp.int32)
        p = jnp.asarray(p, jnp.float32)
        cohort = self._prep_cohort(cohort)
        scaffold = self._materialize_scaffold(scaffold, params, int(tau.shape[0]))
        residual = self._wire_state(params, int(tau.shape[0]))
        with _quiet_donation():
            new_params, stats, new_scaffold, new_res = self._step(
                params, data, key, batches, tau, p,
                jnp.asarray(gprev_sqnorm, jnp.float32), scaffold, cohort,
                residual,
            )
        if self._wire_active:
            self._wire_res = new_res
        return new_params, stats, new_scaffold

    # -- fused round + controller (core/driver.TrainDriver) -----------------
    def init_controller_state(self, params, taus):
        """Device-resident Alg. 1 state for ``run_fused`` (round 0)."""
        if self.controller is None:
            raise ValueError("engine built without controller=ControllerCore")
        return self.controller.init_state(params, taus)

    def run_fused(self, params, cstate, p, *, key=None, batches=None,
                  scaffold: Optional[ScaffoldState] = None, cohort=None):
        """One round + controller update in a single dispatch.

        Returns ``(new_params, new_cstate, new_scaffold, diag)`` where
        ``diag`` holds only small arrays (scalars + [C] vectors) — the one
        device->host surface of the fused step. params, cstate, and
        scaffold buffers are DONATED when cfg.donate.
        """
        args = self._fused_args(params, cstate, p, key, batches, scaffold,
                                cohort)
        with _quiet_donation():
            new_params, new_cstate, new_scaffold, new_res, diag = \
                self._fused(*args)
        if self._wire_active:
            self._wire_res = new_res
        return new_params, new_cstate, new_scaffold, diag

    def lower_fused(self, params, cstate, p, *, key=None, batches=None,
                    scaffold: Optional[ScaffoldState] = None, cohort=None):
        """``run_fused``'s program lowered for the current backend, without
        running it (``jax.stages.Lowered``: ``.as_text()`` shows which
        kernels the round calls, e.g. a ``tpu_custom_call`` for the Pallas
        reduce)."""
        return self._fused.lower(*self._fused_args(
            params, cstate, p, key, batches, scaffold, cohort))

    def _fused_args(self, params, cstate, p, key, batches, scaffold, cohort):
        if self.controller is None:
            raise ValueError("engine built without controller=ControllerCore")
        data = self._resolve_data(batches, key)
        p = jnp.asarray(p, jnp.float32)
        cohort = self._prep_cohort(cohort)
        scaffold = self._materialize_scaffold(scaffold, params, self.controller.C)
        residual = self._wire_state(params, self.controller.C)
        return (params, cstate, data, key, batches, p, scaffold, cohort,
                residual)

    def _prep_cohort(self, cohort):
        """Host-side cohort normalization. Single-device: int32 [m].
        Sharded: [n_shards, per_max] with row s holding ONLY shard s's
        client ids, grouped here so the device program never needs a
        cross-shard gather. Rows shorter than the longest shard's count
        (imbalanced cohorts) are padded with the sentinel id C: the round
        body gives pad entries weight 0 and a local row index of C_loc
        (out of range — scatters drop it, gathers clamp harmlessly), and
        the controller scatter at global id C is dropped by jax's
        out-of-bounds-update semantics."""
        if cohort is None:
            return None
        if not self.sharded:
            return jnp.asarray(cohort, jnp.int32)
        c = np.asarray(cohort, np.int32).reshape(-1)
        K, C_loc = self._n_shards, self._local_C
        C = K * C_loc
        if c.size == 0:
            raise ValueError("cohort must not be empty")
        if (c < 0).any() or (c >= C).any():
            raise ValueError(
                f"cohort ids must be in [0, {C}); got range "
                f"[{int(c.min())}, {int(c.max())}]"
            )
        owners = c // C_loc
        counts = np.bincount(owners, minlength=K)
        per = int(counts.max())
        out = np.full((K, per), C, np.int32)  # C = masked-pad sentinel
        for s in range(K):
            row = np.sort(c[owners == s])
            out[s, : row.size] = row
        return jnp.asarray(out)

    def _resolve_data(self, batches, key):
        """Shared data-path contract for run_round/run_fused: host batches
        XOR (device shards + round key)."""
        if batches is not None:
            return None
        if self.shards is None:
            raise ValueError("no device shards: pass batches= or build the "
                             "engine with shards=DeviceShards.from_datasets(...)")
        if key is None:
            raise ValueError("device data path needs key=")
        return self.shards.tree()

    def _materialize_scaffold(self, scaffold, params, C: int):
        if not self._strategy.uses_scaffold or scaffold is not None:
            return scaffold
        # materialize the full-C zero state up front: keeps c_i rows
        # aligned to client ids under cohorts, and keeps the jit trace
        # unique (None -> ScaffoldState would retrace round 1)
        return ScaffoldState(
            c=jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params),
            c_i=jax.tree.map(
                lambda x: jnp.zeros((C,) + x.shape, jnp.float32), params
            ),
        )

    # -- wire stage state (core/wire.py, DESIGN.md §15) ----------------------
    @property
    def wire_active(self) -> bool:
        """True when a non-identity codec compresses the update wire."""
        return self._wire_active

    def reset_wire(self) -> None:
        """Drop the error-feedback residuals (start of a fresh run)."""
        self._wire_res = None

    def _wire_state(self, params, C: int):
        """Materialize-or-return the full-C residual rows ([C, ...] zeros
        in stat_dtype, client-sharded under a mesh). None when inactive.
        Like the scaffold, the full state exists from round 0 so the jit
        trace is unique and cohort rows stay keyed by client id."""
        if not self._wire_active:
            return None
        if self._wire_res is None:
            rows = jax.tree.map(
                lambda x: jnp.zeros((C,) + x.shape, self.cfg.stat_dtype),
                params,
            )
            if self.sharded:
                from repro.sharding.api import client_sharding

                rows = jax.tree.map(
                    lambda x: jax.device_put(
                        x, client_sharding(self.mesh, x.ndim)
                    ),
                    rows,
                )
            self._wire_res = rows
        return self._wire_res

    def wire_bytes_per_client(self, params) -> int:
        """Static wire bytes ONE client's update costs under the codec
        (the dense stat_dtype bytes for the identity/none codec)."""
        like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape,
                                           np.dtype(self.cfg.stat_dtype)),
            params,
        )
        return self.wire_codec.payload_nbytes(like)

    # -- message-passing halves (fed/prototype.py) --------------------------
    def client_update(self, params, batches_c, tau: int, gprev_sqnorm):
        """Alg. 2 for ONE client: batches_c leaves [T, b, ...], T = tau.

        Returns dict(G, g0, beta, delta, loss0) — the client's reply
        message. Retraces per distinct T (the wire carries exactly tau
        minibatches, matching the paper's deployment).
        """
        return self._client_update(
            params, batches_c, jnp.asarray(tau, jnp.int32),
            jnp.asarray(gprev_sqnorm, jnp.float32),
        )

    def client_update_many(self, params, batches_stacked, taus, gprev_sqnorm):
        """Alg. 2 for M clients as ONE batched dispatch (the serving path's
        continuous batcher): leaves [M, tau_max, b, ...], ``taus`` [M]
        int32. Per client this is ``client_update`` up to last-ulp f32
        rounding (vmap lowers the per-batch gradient reductions
        differently) — padding batches to tau_max changes nothing because
        steps past tau_i are masked no-ops. One trace serves every tau
        mix (no per-T retraces).
        """
        return self._client_update_many(
            params, batches_stacked, jnp.asarray(taus, jnp.int32),
            jnp.asarray(gprev_sqnorm, jnp.float32),
        )

    def server_aggregate(self, params, G_stacked, tau, p):
        """Alg. 1 line 7 over stacked normalized vectors (leaves [C, ...])."""
        return self._server_aggregate(
            params, G_stacked, jnp.asarray(tau, jnp.int32),
            jnp.asarray(p, jnp.float32),
        )

    def weighted_average(self, stacked, w):
        """sum_c w_c * stacked_c through the engine's reduce (Eq. 8)."""
        return self._weighted_average(stacked, jnp.asarray(w, jnp.float32))

    # -- cohort sub-sampling ------------------------------------------------
    def sample_cohort(self, rng: np.random.Generator) -> Optional[np.ndarray]:
        """Draw this round's participating clients, or None for all of them.

        ``rng`` is a ``np.random.Generator`` (``np.random.default_rng``);
        the legacy ``RandomState`` also works (same ``choice`` API) but new
        call sites should pass a Generator.

        Sharded engines draw STRATIFIED cohorts — about m/n_shards clients
        from each shard's own id range — so the cohort is a per-shard index
        set and dispatch never gathers client data across shards. The flat
        array is still sorted (shard id ranges are contiguous). When m does
        not divide the shard count (or m < n_shards), the draw degrades to
        an imbalanced-but-valid split — ``extra = m % n_shards`` randomly
        chosen shards contribute one extra client — with a host-side
        warning; ``_prep_cohort`` sentinel-pads the short rows so the
        device program stays rectangular (pad entries are exact no-ops).
        """
        m, C = self.cfg.cohort_size, self.num_clients
        if m is None or C is None or m >= C:
            return None
        if not self.sharded:
            return np.sort(rng.choice(C, size=m, replace=False)).astype(np.int32)
        K, C_loc = self._n_shards, self._local_C
        base, extra = divmod(m, K)
        counts = np.full(K, base, np.int64)
        if extra:
            warnings.warn(
                f"cohort_size={m} does not divide the {K} client-axis "
                f"shards: degrading to an imbalanced per-shard split "
                f"({extra} shards draw {base + 1} clients, the rest "
                f"{base}); pad rows are masked no-ops",
                RuntimeWarning,
                stacklevel=2,
            )
            counts[rng.choice(K, size=extra, replace=False)] += 1
        rows = [
            s * C_loc + np.sort(rng.choice(C_loc, size=int(counts[s]),
                                           replace=False))
            for s in range(K)
        ]
        return np.concatenate(rows).astype(np.int32)
