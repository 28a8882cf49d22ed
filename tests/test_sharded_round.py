"""Client-axis mesh sharding (DESIGN.md §11): the shard_map round with
psum aggregation against the single-device RoundEngine, the sharded data
placement, per-shard cohorts, and the sharded fused controller.

Multi-device stage: run as
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_sharded_round.py
(scripts/ci.sh does this in a separate process — the main tier-1 pytest
process keeps the default single device on purpose, so the sharded tests
here skip there and only the device-count-agnostic mesh-builder tests
run).

Numerics contract: per-client work is element-wise across the client
axis, so shard-local vmap matches the single-device vmap exactly; the
server reduce becomes shard-local partial sums + psum, whose f32
summation order differs from the single-device tensordot — tolerances
below (1e-6 one round, 2e-5 over 6 driver rounds) document that reduce-
ordering gap. tau trajectories (integer) must match EXACTLY. The device
data path matches bit-for-bit by construction: minibatch indices are
drawn from per-(global-)client folded keys (data/device.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.controller import ControllerConfig, ControllerCore
from repro.core.driver import TrainDriver
from repro.core.engine import EngineConfig, RoundEngine
from repro.data.device import DeviceShards
from repro.data.synthetic import Dataset, binarize_even_odd, make_classification
from repro.launch.mesh import (
    build_mesh,
    make_federated_mesh,
    make_production_mesh,
    num_clients,
)
from repro.models.model import build_model_by_name

C, TAU_MAX, BATCH = 16, 4, 16

needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
    "(scripts/ci.sh multi-device stage)",
)


# ---------------------------------------------------------------------------
# mesh builders (device-count-agnostic: run in tier-1 too)
# ---------------------------------------------------------------------------


def test_build_mesh_strict_raises_with_hint():
    n = len(jax.devices())
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        build_mesh(("data", "model"), (n + 1, 16))


def test_build_mesh_shrink_fits_any_box():
    m = build_mesh(("data", "model"), (16, 16), shrink=True)
    assert set(m.shape) == {"data", "model"}
    assert m.shape["data"] * m.shape["model"] <= len(jax.devices())
    # production smoke path goes through the same builder
    sm = make_production_mesh(smoke=True)
    assert set(sm.shape) == {"data", "model"}


def test_build_mesh_validates_shape():
    with pytest.raises(ValueError, match="mismatch"):
        build_mesh(("data",), (1, 1))
    with pytest.raises(ValueError, match="positive"):
        build_mesh(("data",), (0,))


def test_federated_mesh_pod_divisibility():
    with pytest.raises(ValueError, match="pod"):
        make_federated_mesh(3, pod=2)


# ---------------------------------------------------------------------------
# sharded fixtures
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    orig = make_classification(C * 40, (784,), 10, seed=0)
    train = binarize_even_odd(orig)
    ds = [Dataset(train.x[i::C], train.y[i::C]) for i in range(C)]
    model = build_model_by_name("svm-mnist")
    p = np.full(C, 1.0 / C, np.float32)
    tau = np.array([4, 2, 3, 1] * (C // 4), np.int32)
    r = np.random.RandomState(0)
    batches = dict(
        x=jnp.asarray(r.randn(C, TAU_MAX, BATCH, 784), jnp.float32),
        y=jnp.asarray(r.randint(0, 2, (C, TAU_MAX, BATCH)), jnp.int32),
    )
    return model, ds, p, tau, batches


def _engine(model, ds, mesh=None, mode="fedveca", cohort=None, agg="fallback",
            controller=None, donate=False, wire="none"):
    return RoundEngine(
        model.loss,
        EngineConfig(mode=mode, eta=0.05, tau_max=TAU_MAX, batch_size=BATCH,
                     cohort_size=cohort, aggregator=agg, donate=donate,
                     wire=wire),
        shards=DeviceShards.from_datasets(ds, mesh=mesh),
        num_clients=C,
        controller=controller,
        mesh=mesh,
    )


@needs_devices
def test_federated_mesh_shapes():
    m = make_federated_mesh(8)
    assert dict(m.shape) == {"pod": 1, "data": 8}
    m2 = make_federated_mesh(8, pod=2)
    assert dict(m2.shape) == {"pod": 2, "data": 4}
    assert num_clients(m2) == 8


@needs_devices
def test_device_shards_place_clients_on_their_shard(setup):
    """Each data shard must hold only its own C/K clients' rows."""
    model, ds, *_ = setup
    mesh = make_federated_mesh(8)
    shards = DeviceShards.from_datasets(ds, mesh=mesh)
    assert shards.mesh is mesh
    for arr in (shards.x, shards.sizes):
        owners = sorted(
            (s.index[0].start or 0, s.index[0].stop) for s in arr.addressable_shards
        )
        # 8 contiguous, disjoint 2-client blocks covering [0, 16)
        assert owners == [(i * 2, (i + 1) * 2) for i in range(8)]


@needs_devices
def test_device_shards_reject_indivisible_C(setup):
    model, ds, *_ = setup
    mesh = make_federated_mesh(8)
    with pytest.raises(ValueError, match="divide evenly"):
        DeviceShards.from_datasets(ds[:10], mesh=mesh)
    with pytest.raises(ValueError, match="divide evenly"):
        RoundEngine(model.loss, EngineConfig(), num_clients=10, mesh=mesh)
    # cohort_size not dividing the shard count is no longer a construction
    # error: sample_cohort degrades to an imbalanced-but-valid split with a
    # host-side warning, and _prep_cohort sentinel-pads the short rows
    eng = RoundEngine(model.loss, EngineConfig(cohort_size=6), num_clients=C,
                      mesh=mesh)
    with pytest.warns(RuntimeWarning, match="imbalanced"):
        c = eng.sample_cohort(np.random.default_rng(0))
    assert c.shape == (6,)
    assert np.array_equal(c, np.sort(c))
    assert len(np.unique(c)) == 6 and c.min() >= 0 and c.max() < C
    # m < n_shards degrades too (some shards draw zero clients)
    eng1 = RoundEngine(model.loss, EngineConfig(cohort_size=3), num_clients=C,
                       mesh=mesh)
    with pytest.warns(RuntimeWarning, match="imbalanced"):
        c1 = eng1.sample_cohort(np.random.default_rng(0))
    assert c1.shape == (3,) and len(np.unique(c1)) == 3


# ---------------------------------------------------------------------------
# sharded round == single-device oracle
# ---------------------------------------------------------------------------


@needs_devices
@pytest.mark.parametrize("mode", ["fedveca", "fednova", "fedavg"])
@pytest.mark.parametrize("agg", ["fallback", "pallas"])
def test_sharded_round_matches_single_device(setup, mode, agg):
    """shard_map round (host batches) == single-device round within the
    documented f32 reduce-ordering tolerance, on both reduce paths."""
    model, ds, p, tau, batches = setup
    mesh = make_federated_mesh(8)
    params = model.init(jax.random.PRNGKey(0))
    p1, st1, _ = _engine(model, ds, None, mode, agg=agg).run_round(
        params, tau, p, 0.05, batches=batches)
    p2, st2, _ = _engine(model, ds, mesh, mode, agg=agg).run_round(
        params, tau, p, 0.05, batches=batches)
    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   atol=1e-6)
    for name in ("loss0", "beta", "delta", "g0_sqnorm"):
        np.testing.assert_allclose(np.asarray(getattr(st1, name)),
                                   np.asarray(getattr(st2, name)),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(st1.tau_k), float(st2.tau_k), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(st1.global_grad),
                    jax.tree.leaves(st2.global_grad)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@needs_devices
@pytest.mark.parametrize("pod", [1, 2])
def test_sharded_device_data_path_draws_identical_minibatches(setup, pod):
    """The per-(global-)client folded keys make the shard-local sampler
    draw the SAME minibatches as the single-device sampler, so the device
    data path matches across shardings too (not just host batches)."""
    model, ds, p, tau, _ = setup
    mesh = make_federated_mesh(8, pod=pod)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(7)
    p1, st1, _ = _engine(model, ds, None).run_round(params, tau, p, 0.05, key=key)
    p2, st2, _ = _engine(model, ds, mesh).run_round(params, tau, p, 0.05, key=key)
    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   atol=1e-6)
    np.testing.assert_allclose(np.asarray(st1.loss0), np.asarray(st2.loss0),
                               rtol=1e-5)


@needs_devices
def test_sharded_cohort_round_matches_single_device(setup):
    """Same (per-shard balanced) cohort through both engines: renormalized
    weights, cohort-sized stats, and params all match."""
    model, ds, p, tau, batches = setup
    mesh = make_federated_mesh(8)
    params = model.init(jax.random.PRNGKey(0))
    cohort = np.array([1, 2, 5, 7, 8, 10, 13, 14], np.int32)  # 1 per shard
    p1, st1, _ = _engine(model, ds, None).run_round(
        params, tau, p, 0.05, batches=batches, cohort=cohort)
    p2, st2, _ = _engine(model, ds, mesh).run_round(
        params, tau, p, 0.05, batches=batches, cohort=cohort)
    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   atol=1e-6)
    assert st2.beta.shape == (8,)
    np.testing.assert_allclose(np.asarray(st1.beta), np.asarray(st2.beta),
                               rtol=1e-5, atol=1e-6)


@needs_devices
def test_stratified_cohorts_and_rejection(setup):
    """sample_cohort draws per-shard index sets; out-of-range cohort ids
    are still refused, but imbalanced cohorts now run (sentinel-padded)."""
    model, ds, *_ = setup
    mesh = make_federated_mesh(8)
    eng = _engine(model, ds, mesh, cohort=8)
    rng = np.random.default_rng(0)
    for _ in range(5):
        c = eng.sample_cohort(rng)
        assert c.shape == (8,)
        assert np.array_equal(c // 2, np.arange(8))  # one client per shard
        assert np.array_equal(c, np.sort(c))
    with pytest.raises(ValueError, match=r"cohort ids must be in"):
        eng.run_round(model.init(jax.random.PRNGKey(0)),
                      np.full(C, 2, np.int32), np.full(C, 1 / C, np.float32),
                      0.0, key=jax.random.PRNGKey(0),
                      cohort=np.array([0, 1, 2, 3, 4, 5, 6, C], np.int32))


@needs_devices
def test_imbalanced_cohort_matches_single_device(setup):
    """Regression for the sample_cohort degrade path: an UNBALANCED cohort
    (ids 0..7 all live on the first 4 of 8 shards — two clients each, zero
    on the rest) must run sharded via sentinel padding and reproduce the
    single-device round on the same cohort within the documented reduce-
    ordering tolerance."""
    model, ds, p, tau, _ = setup
    mesh = make_federated_mesh(8)
    cohort = np.arange(8, dtype=np.int32)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(7)
    p1, st1, _ = _engine(model, ds, None).run_round(
        params, tau, p, 0.05, key=key, cohort=cohort)
    p2, st2, _ = _engine(model, ds, mesh).run_round(
        params, tau, p, 0.05, key=key, cohort=cohort)
    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   atol=1e-6, rtol=1e-6)
    # per-cohort stats come back sentinel-padded as (shard, slot) row-major:
    # ids 0..7 fill shards 0-3 two slots each, so the 8 valid rows are
    # exactly the first 8 of the flattened [16] vector, in cohort order
    np.testing.assert_allclose(np.asarray(st2.loss0).reshape(-1)[:8],
                               np.asarray(st1.loss0), atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(float(st1.tau_k), float(st2.tau_k),
                               atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# sharded fused controller + driver
# ---------------------------------------------------------------------------


@needs_devices
@pytest.mark.parametrize("cohort", [None, 8])
def test_sharded_fused_trajectory_matches_single_device(setup, cohort):
    """6 fused rounds (device data path, donation ON): the sharded engine
    must emit EXACTLY the single-device tau trajectory and matching params;
    the controller's per-client state stays sharded round over round."""
    model, ds, p, _, _ = setup
    mesh = make_federated_mesh(8, pod=2)
    ctl_cfg = ControllerConfig(eta=0.05, tau_max=TAU_MAX)

    def build(mesh_):
        return _engine(model, ds, mesh_, cohort=cohort, donate=True,
                       controller=ControllerCore(ctl_cfg, C, mesh=mesh_))

    # identical per-shard cohorts fed to both engines
    rng = np.random.default_rng(0)
    sharded_eng = build(mesh)
    cohorts = [sharded_eng.sample_cohort(rng) for _ in range(6)]
    outs = {}
    for name, eng in (("single", build(None)), ("sharded", sharded_eng)):
        key = jax.random.PRNGKey(0)
        params = model.init(jax.random.PRNGKey(0))
        cstate = eng.init_controller_state(params, np.full(C, 2, np.int32))
        taus = []
        for k in range(6):
            key, sub = jax.random.split(key)
            params, cstate, _, diag = eng.run_fused(
                params, cstate, p, key=sub, cohort=cohorts[k])
            taus.append(np.asarray(diag["tau_next"]).copy())
        outs[name] = (jax.tree.map(np.asarray, params), taus, cstate)
    for a, b in zip(outs["single"][1], outs["sharded"][1]):
        np.testing.assert_array_equal(a, b)  # tau trace EXACT
    for k in outs["single"][0]:
        np.testing.assert_allclose(outs["single"][0][k], outs["sharded"][0][k],
                                   atol=2e-5, rtol=1e-4)
    # per-client controller state is still sharded after 6 donated rounds
    cstate = outs["sharded"][2]
    spec = cstate.taus.sharding.spec
    assert any(s is not None for s in spec), spec
    assert np.ndim(cstate.L) == 0  # scalar state replicated scalars


@needs_devices
def test_sharded_driver_compiles_the_round_once(setup):
    """Round 0's params are placed where later rounds' come from
    (replicated on the mesh), so the sharded round compiles once: the
    sanitizer sees zero compiles after round 0."""
    model, ds, p, _, _ = setup
    mesh = make_federated_mesh(8)
    eng = _engine(model, ds, mesh, donate=True,
                  controller=ControllerCore(
                      ControllerConfig(eta=0.05, tau_max=TAU_MAX), C,
                      mesh=mesh))
    drv = TrainDriver(eng, p, overlap=0, seed=0, sanitize=True)
    log = drv.run(model.init(jax.random.PRNGKey(0)), 3,
                  np.full(C, 2, np.int32))  # raises on a steady compile
    assert len(log.rows) == 3


@needs_devices
def test_sharded_driver_end_to_end(setup):
    """TrainDriver over a sharded engine: overlap semantics hold (sync ==
    overlapped bit-for-bit) and losses stay finite."""
    model, ds, p, _, _ = setup
    mesh = make_federated_mesh(8)
    ctl_cfg = ControllerConfig(eta=0.05, tau_max=TAU_MAX)
    outs = {}
    for ov in (0, 2):
        eng = _engine(model, ds, mesh, cohort=8, donate=True,
                      controller=ControllerCore(ctl_cfg, C, mesh=mesh))
        drv = TrainDriver(eng, p, overlap=ov, seed=0)
        log = drv.run(model.init(jax.random.PRNGKey(0)), 5,
                      np.full(C, 2, np.int32))
        assert all(np.isfinite(r["train_loss"]) for r in log.rows)
        assert all(len(r["cohort"]) == 8 for r in log.rows)
        outs[ov] = (jax.tree.map(np.asarray, log.params),
                    [r["tau"] for r in log.rows])
    for k in outs[0][0]:
        np.testing.assert_array_equal(outs[0][0][k], outs[2][0][k])
    for a, b in zip(outs[0][1], outs[2][1]):
        np.testing.assert_array_equal(a, b)


@needs_devices
def test_sharded_buffered_matches_sync_sharded(setup):
    """Buffered engine on the federated mesh in parity mode (waves=1,
    instant arrivals, grad_decay=1.0): the tau trace must EXACTLY match
    the sharded sync TrainDriver; params stay within the documented
    reduce-order tolerance (the buffered commit reduces under GSPMD
    rather than inside shard_map). An async (waves=2, exp-latency) run
    then smoke-checks liveness on the same mesh."""
    from repro.core.buffered import (
        BufferedConfig,
        BufferedRoundEngine,
        LatencyModel,
    )

    model, ds, p, _, _ = setup
    mesh = make_federated_mesh(8)
    ctl_cfg = ControllerConfig(eta=0.05, tau_max=TAU_MAX)

    def build(mesh_):
        return _engine(model, ds, mesh_, cohort=8, donate=True,
                       controller=ControllerCore(ctl_cfg, C, mesh=mesh_))

    drv = TrainDriver(build(mesh), p, overlap=1, seed=0)
    log_s = drv.run(model.init(jax.random.PRNGKey(0)), 5,
                    np.full(C, 2, np.int32))

    buf = BufferedRoundEngine(
        build(mesh), p,
        BufferedConfig(waves=1, grad_decay=1.0,
                       latency=LatencyModel("instant"), seed=0))
    log_b = buf.run(model.init(jax.random.PRNGKey(0)), 5,
                    np.full(C, 2, np.int32))

    for rs, rb in zip(log_s.rows, log_b.rows):
        np.testing.assert_array_equal(rs["tau"], rb["tau"])  # EXACT
        np.testing.assert_array_equal(np.sort(np.asarray(rs["cohort"])),
                                      rb["cohort"])
        assert rb["mean_age"] == 0.0
    ps = jax.tree.map(np.asarray, log_s.params)
    pb = jax.tree.map(np.asarray, log_b.params)
    for k in ps:
        np.testing.assert_allclose(ps[k], pb[k], atol=2e-5, rtol=1e-4)
    # buffer and controller per-client state stay client-sharded
    spec = buf._buf["loss0"].sharding.spec
    assert any(s is not None for s in spec), spec

    buf2 = BufferedRoundEngine(
        build(mesh), p,
        BufferedConfig(waves=2, grad_decay=0.5,
                       latency=LatencyModel("exp", scale=1.0, seed=1), seed=0))
    log2 = buf2.run(model.init(jax.random.PRNGKey(0)), 5,
                    np.full(C, 2, np.int32))
    assert all(np.isfinite(r["train_loss"]) for r in log2.rows)
    assert max(r["max_age"] for r in log2.rows) > 0


@needs_devices
def test_sharded_buffered_rejects_indivisible_buffer(setup):
    """Slot j is owned by the shard owning wave row j, so the buffer size
    must divide the client-axis shard count."""
    from repro.core.buffered import BufferedRoundEngine

    model, ds, p, _, _ = setup
    mesh = make_federated_mesh(8)
    eng = _engine(model, ds, mesh, cohort=6, donate=True,
                  controller=ControllerCore(
                      ControllerConfig(eta=0.05, tau_max=TAU_MAX), C,
                      mesh=mesh))
    with pytest.raises(ValueError, match="must divide"):
        BufferedRoundEngine(eng, p)


@needs_devices
def test_sharded_simulator_smoke(setup):
    """FedSimConfig(mesh=...) end to end through the simulator."""
    from repro.fed.simulator import FederatedSimulator, FedSimConfig

    model, ds, *_ = setup
    mesh = make_federated_mesh(8)
    cfg = FedSimConfig(mode="fedveca", rounds=4, tau_max=TAU_MAX,
                       batch_size=BATCH, eta=0.05, cohort_size=8, mesh=mesh)
    log = FederatedSimulator(model, ds, cfg).run()
    assert len(log.rows) == 4
    for r in log.rows:
        assert np.isfinite(r["train_loss"])
        tau = np.asarray(r["tau"])
        assert tau.min() >= 2 and tau.max() <= TAU_MAX


# ---------------------------------------------------------------------------
# wire stage (core/wire.py, DESIGN.md §15) on the sharded round
# ---------------------------------------------------------------------------


@needs_devices
@pytest.mark.parametrize("wire", ["identity", "int8"])
def test_sharded_wire_tau_trace_matches_single_device(setup, wire):
    """Contract 2: with the wire stage active (and with the identity
    bypass) the sharded fused trajectory still emits EXACTLY the
    single-device tau trace — the shard-local error-feedback fold plus
    psum reduce preserves the controller's integer decisions."""
    model, ds, p, _, _ = setup
    mesh = make_federated_mesh(8)
    ctl_cfg = ControllerConfig(eta=0.05, tau_max=TAU_MAX)

    def build(mesh_):
        return _engine(model, ds, mesh_, cohort=8, donate=True, wire=wire,
                       controller=ControllerCore(ctl_cfg, C, mesh=mesh_))

    rng = np.random.default_rng(0)
    sharded_eng = build(mesh)
    cohorts = [sharded_eng.sample_cohort(rng) for _ in range(5)]
    outs = {}
    for name, eng in (("single", build(None)), ("sharded", sharded_eng)):
        key = jax.random.PRNGKey(0)
        params = model.init(jax.random.PRNGKey(0))
        cstate = eng.init_controller_state(params, np.full(C, 2, np.int32))
        taus = []
        for k in range(5):
            key, sub = jax.random.split(key)
            params, cstate, _, diag = eng.run_fused(
                params, cstate, p, key=sub, cohort=cohorts[k])
            taus.append(np.asarray(diag["tau_next"]).copy())
        outs[name] = (jax.tree.map(np.asarray, params), taus, eng)
    for a, b in zip(outs["single"][1], outs["sharded"][1]):
        np.testing.assert_array_equal(a, b)  # tau trace EXACT
    for k in outs["single"][0]:
        np.testing.assert_allclose(outs["single"][0][k], outs["sharded"][0][k],
                                   atol=2e-5, rtol=1e-4)


@needs_devices
def test_wire_residuals_stay_client_sharded_through_donation(setup):
    """The error-feedback rows are [C, ...] client-axis sharded state:
    after 4 donated fused rounds they must still carry the client
    NamedSharding (no silent gather/replication), hold real quantization
    error, and zero out on reset_wire()."""
    from repro.sharding.api import client_spec

    model, ds, p, _, _ = setup
    mesh = make_federated_mesh(8, pod=2)
    ctl_cfg = ControllerConfig(eta=0.05, tau_max=TAU_MAX)
    eng = _engine(model, ds, mesh, cohort=8, donate=True, wire="int8",
                  controller=ControllerCore(ctl_cfg, C, mesh=mesh))
    assert eng.wire_active
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    params = model.init(jax.random.PRNGKey(0))
    cstate = eng.init_controller_state(params, np.full(C, 2, np.int32))
    for _ in range(4):
        key, sub = jax.random.split(key)
        params, cstate, _, _ = eng.run_fused(
            params, cstate, p, key=sub, cohort=eng.sample_cohort(rng))
    res = eng._wire_res
    assert res is not None
    want = client_spec(mesh, 1)[0]  # the client-axis partition entry
    for leaf, plike in zip(jax.tree.leaves(res), jax.tree.leaves(params)):
        assert leaf.shape == (C,) + plike.shape
        # leading axis still split over the client axes of the mesh
        # (trailing dims unsharded; specs may omit trailing Nones)
        spec = leaf.sharding.spec
        assert spec[0] == want, spec
        assert all(s is None for s in spec[1:]), spec
    # lossy codec left genuine error feedback behind
    assert any(float(jnp.abs(x).max()) > 0 for x in jax.tree.leaves(res))
    eng.reset_wire()
    assert eng._wire_res is None
