"""Cells of FedVeca rounds: FederatedSimulator -> TrainDriver ->
RoundEngine.run_fused, the paper's deployment as a user runs it.

One object serves the whole run. Set-up builds the simulator from the
seed's data and the benchmark's own initial parameters, and starts ONE
``TrainDriver.run``: its first rounds compile, warm up and are the rounds
the reference checks; the window opens when the ``warm_rounds``-th row is
final and closes at the first row final after ``--seconds``. The early
stop is the driver's own ``on_row`` hook raising.
"""
from __future__ import annotations

import gc
import time

CHECK_ROUNDS = 3


class _WindowClosed(Exception):
    pass


def _program(cell, clients, test, seed):
    """The simulator as a user builds it; on more than one chip, its
    round shards the clients over all of them (``make_federated_mesh``)."""
    from repro.configs.base import ArchConfig
    from repro.data.synthetic import Dataset
    from repro.fed.simulator import FederatedSimulator, FedSimConfig
    from repro.launch.mesh import make_federated_mesh
    from repro.models.model import build_model

    from bench.traffic import derive

    cfg, alg = cell.config, cell.config["algorithm"]
    mesh = make_federated_mesh(cell.chips) if cell.chips > 1 else None
    model = build_model(ArchConfig(
        name=cfg["name"], family="toy", source=cfg["source"],
        input_shape=tuple(cfg["input_shape"]),
        num_classes=cfg["num_classes"], param_dtype=cfg["param_dtype"],
        compute_dtype=cfg["param_dtype"]))
    sim = FederatedSimulator(
        model, [Dataset(x, y) for x, y in clients],
        FedSimConfig(mode=alg["mode"], eta=alg["eta"], alpha=alg["alpha"],
                     tau_max=alg["tau_max"], tau_init=alg["tau_init"],
                     batch_size=alg["batch"], rounds=1 << 30,
                     seed=derive(seed, "driver"),
                     data_path=alg["data_path"],
                     aggregator=alg["aggregator"], mesh=mesh),
        Dataset(*test))
    return model, sim


def run(cell, seed: int, seconds: float, trace: bool, t_process: float,
        trace_dir=None) -> dict:
    return _run(cell, seed, seconds, trace, t_process, trace_dir)[0]


def _run(cell, seed, seconds, trace, t_process, trace_dir):
    """-> (the run's result, what the reference was given)"""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis.sanitize import Sanitizer

    from bench import harness, reduce, work
    from bench.traffic import derive, fl_clients

    cfg, tr, alg = cell.config, cell.traffic, cell.config["algorithm"]
    ref = cell.reference()
    clients, test = fl_clients(cfg, tr, seed)
    model, sim = _program(cell, clients, test, seed)
    params = jax.block_until_ready(jax.jit(
        lambda k: ref.init_params(cfg, k))(
            jax.random.PRNGKey(derive(seed, "params"))))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            a.shape != b.shape for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"{cell.config_name}: the configuration's sizes do "
                         "not give the program's parameter shapes")
    w0 = jax.device_get(params)

    driver = sim.driver
    warm = tr["warm_rounds"]
    window = min(seconds, tr["trace_seconds"]) if trace else seconds
    rows, snaps, st = [], [], {}
    eval_fn = driver.eval_fn

    def snapshot(p):
        # the params after each of the checked rounds, copied before the
        # next round's dispatch donates them
        snaps.append(jax.tree.map(jnp.copy, p))
        if len(snaps) == CHECK_ROUNDS:
            driver.eval_fn = eval_fn
        return eval_fn(p)

    def on_row(row):
        now = time.perf_counter()
        rows.append(row)
        if len(rows) == warm:
            st.update(t0=now, wall0=time.time(), d0=driver.dispatch_s)
            san.mark_steady()
            if trace:
                st["span"] = jax.profiler.TraceAnnotation(reduce.WINDOW_SPAN)
                st["span"].__enter__()
        elif "t0" in st and now - st["t0"] >= window:
            st.update(t1=now, d1=driver.dispatch_s,
                      compiles=san.steady_compiles)
            if trace:
                st["span"].__exit__(None, None, None)
            raise _WindowClosed

    driver.eval_fn = snapshot
    driver.on_row = on_row
    san = Sanitizer(nan_checks=False, label=cell.name)
    if trace:
        reduce.start(trace_dir)
    try:
        with san:
            sim.run(params=params, rounds=1 << 30)
    except _WindowClosed:
        pass
    finally:
        if trace:
            jax.profiler.stop_trace()
    device = harness.device_info(cell.chips)

    run_out = dict(
        loss=[r["train_loss"] for r in rows[:CHECK_ROUNDS]],
        params=[jax.device_get(snaps[0]), jax.device_get(snaps[-1])],
        taus_next=[np.asarray(r["tau"]) for r in rows[:CHECK_ROUNDS]],
        A=[np.asarray(r["A"]) for r in rows[:CHECK_ROUNDS]],
        L=[r["L"] for r in rows[:CHECK_ROUNDS]],
        tau_k0=rows[0]["tau_k"])
    p_w = np.asarray(sim.p)
    C = len(clients)
    taus_used = [np.asarray(rows[k - 1]["tau"]) for k in range(warm,
                                                               len(rows))]
    win_rows = rows[warm:]
    del sim, model, params, snaps, driver
    gc.collect()

    t_check = time.perf_counter()
    given = (cfg, clients, p_w, w0, derive(seed, "driver"))
    readings = ref.compare(*given, run_out, CHECK_ROUNDS)
    check_s = time.perf_counter() - t_check
    checks = harness.judge(readings, cell.limits)
    finite = [bool(np.isfinite(r["train_loss"])) for r in win_rows]

    R = len(win_rows)
    window_s = st["t1"] - st["t0"]
    n_params = work.cnn_param_count(cfg)
    counters = dict(
        rounds=R, window_s=window_s, clients=C, tau_max=alg["tau_max"],
        batch=alg["batch"], dispatch_s=st["d1"] - st["d0"],
        tau_sum=float(sum(np.sum(t) for t in taus_used)),
        flops_per_sample_step=work.cnn_train_flops_per_sample(cfg),
        n_params=n_params, compiles_in_window=st["compiles"])
    return dict(
        e2e={"round_ms": 1e3 * window_s / R,
             "setup_s": st["wall0"] - t_process},
        attempted=R, failed=R - sum(finite),
        correct=harness.passed(checks) and all(finite),
        checks=checks, readings=readings, device=device, counters=counters,
        check_s=check_s), given


def control(cell, seed: int, seconds: float) -> dict:
    """A run of the program, and beside its readings (``control``) those
    of the reference in bfloat16 put in the program's place, on the same
    data, parameters and minibatches."""
    out, given = _run(cell, seed, seconds, False, time.time(), None)
    ref = cell.reference()
    ctl = ref.control_run(*given, CHECK_ROUNDS)
    return dict(out, control=ref.compare(*given, ctl, CHECK_ROUNDS))
