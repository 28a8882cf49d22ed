"""Hypothesis property-based tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.controller import ControllerConfig, FedVecaController
from repro.data.partition import (
    client_weights,
    partition_by_label,
    partition_case3,
    partition_dirichlet,
    partition_iid,
)
from repro.kernels.vecavg import ref as va_ref


# ---------------------------------------------------------------------------
# partitioners: disjoint + complete + weights sum to 1
# ---------------------------------------------------------------------------

part_args = st.tuples(
    st.integers(min_value=50, max_value=400),  # n samples
    st.integers(min_value=2, max_value=10),  # clients
    st.integers(min_value=2, max_value=10),  # classes
    st.integers(min_value=0, max_value=5),  # seed
)


@settings(max_examples=25, deadline=None)
@given(part_args)
def test_partitions_are_exact_covers(args):
    n, C, K, seed = args
    labels = np.random.RandomState(seed).randint(0, K, n)
    for parts in (
        partition_iid(n, C, seed),
        partition_by_label(labels, C, seed),
        partition_case3(labels, C, seed),
        partition_dirichlet(labels, C, 0.5, seed),
    ):
        allidx = np.concatenate([p for p in parts]) if parts else np.array([])
        assert len(allidx) == n  # complete
        assert len(np.unique(allidx)) == n  # disjoint
        w = client_weights(parts)
        assert abs(float(w.sum()) - 1.0) < 1e-5


@settings(max_examples=20, deadline=None)
@given(part_args)
def test_case2_label_exclusivity(args):
    n, C, K, seed = args
    labels = np.random.RandomState(seed).randint(0, K, n)
    parts = partition_by_label(labels, C, seed)
    # each client sees at most ceil(K/C) labels (Case 2 semantics)
    import math

    for part in parts:
        if len(part):
            assert len(np.unique(labels[part])) <= math.ceil(K / C)


# ---------------------------------------------------------------------------
# vecavg algebra: linearity + convexity of weights
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(
    st.integers(min_value=1, max_value=8),
    st.integers(min_value=1, max_value=64),
    st.floats(min_value=0.01, max_value=10.0),
    st.integers(min_value=0, max_value=100),
)
def test_vecavg_scale_linearity(C, D, scale, seed):
    r = np.random.RandomState(seed)
    u = jnp.asarray(r.randn(C, D), jnp.float32)
    p = jnp.asarray(np.abs(r.rand(C)) + 0.01, jnp.float32)
    p = p / p.sum()
    d1, _ = va_ref.vecavg(u, p, scale)
    d2, _ = va_ref.vecavg(u, p, 1.0)
    np.testing.assert_allclose(np.asarray(d1), scale * np.asarray(d2), rtol=1e-4, atol=1e-5)
    # convex weights: |delta| <= scale * max_c |u_c| (row-wise bound)
    assert float(jnp.max(jnp.abs(d1))) <= scale * float(jnp.max(jnp.abs(u))) + 1e-4


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=2, max_value=6), st.integers(min_value=0, max_value=50))
def test_vecavg_identical_clients_collapse(C, seed):
    """If every client sends the same vector, weighting must not matter."""
    r = np.random.RandomState(seed)
    row = r.randn(1, 32)
    u = jnp.asarray(np.repeat(row, C, 0), jnp.float32)
    p1 = jnp.full((C,), 1.0 / C, jnp.float32)
    p2 = jnp.asarray(np.abs(r.rand(C)) + 0.01, jnp.float32)
    p2 = p2 / p2.sum()
    d1, _ = va_ref.vecavg(u, p1, 2.0)
    d2, _ = va_ref.vecavg(u, p2, 2.0)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# controller: predicted taus always within [tau_min, tau_max]; Theorem-2
# denominator sign drives the bi-directional direction
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(
    st.lists(st.floats(min_value=1e-4, max_value=100.0), min_size=2, max_size=8),
    st.lists(st.floats(min_value=1e-4, max_value=100.0), min_size=2, max_size=8),
    st.floats(min_value=0.05, max_value=0.999),
)
def test_controller_tau_always_bounded(betas, deltas, alpha):
    C = min(len(betas), len(deltas))
    betas, deltas = betas[:C], deltas[:C]
    cfg = ControllerConfig(eta=0.01, alpha=alpha, tau_max=50)
    ctl = FedVecaController(cfg, C)
    state = ctl.init_state()
    from repro.core.fedveca import RoundStats

    gg = {"w": jnp.ones((3,))}

    def stats(b, d):
        return RoundStats(
            loss0=jnp.zeros((C,)), beta=jnp.asarray(b, jnp.float32),
            delta=jnp.asarray(d, jnp.float32), g0_sqnorm=jnp.ones((C,)),
            tau=jnp.full((C,), 2, jnp.int32), tau_k=jnp.float32(2.0),
            global_grad=gg, update_sqnorm=jnp.float32(0.01),
            params_sqnorm=jnp.float32(4.0),
        )

    state, tau, _ = ctl.update(state, stats(betas, deltas))  # round 0
    state, tau, diag = ctl.update(state, stats(betas, deltas))
    assert tau.dtype == np.int32
    assert np.all(tau >= cfg.tau_min)
    assert np.all(tau <= cfg.tau_max)
    if "alpha_k" in diag:
        # the controller clamps in f32 (the device core's op order), so its
        # ceiling is alpha rounded to f32 — up to half an f32 ulp above the
        # float64 alpha drawn here (2.4e-10 at alpha=0.3228)
        assert 0 < diag["alpha_k"] <= np.float32(alpha)
    # the arg-min-A client always gets the largest allowed tau
    A = diag["A"]
    if np.all(np.isfinite(A)) and A.max() > A.min() * (1 + 1e-6):
        assert tau[int(np.argmin(A))] == tau.max()
