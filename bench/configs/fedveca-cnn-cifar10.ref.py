"""Plain reference of FedVeca rounds on the paper's CNN (arXiv:2209.13803).

Written from the paper (Alg. 1, Alg. 2, Eq. 5, Eq. 15, Theorem 2) in
straightforward ``jax.numpy``: one client and one local SGD step at a
time, float32 at ``highest`` matmul precision, no vmap, no masked scan,
no kernels. It imports nothing of the program. Two conventions are the
deployment's and are followed as stated, not derived:

* the data feed: round k's key is the k-th split of ``PRNGKey(seed)``;
  client i draws its step-l minibatch indices as row l of
  ``randint(fold_in(key_k, i), (tau_max, batch), 0, D_i)``;
* the statistics of Alg. 2 lines 15-18 (beta from gradient and parameter
  drift, delta from the running gradient sum against the broadcast
  ||grad F(w_{k-1})||^2), the one-round-delayed L estimate of Alg. 1
  lines 11-16, and the reset of tau <= 1 to tau_min (lines 19-21).

``dtype=bfloat16`` computes the same rounds with parameters, data and
activations in bfloat16: the control that a correct program must beat.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-12


def init_params(cfg: dict, key):
    """The CNN's parameters from a key (He-style normal, zero biases)."""
    h, w, c = cfg["input_shape"]
    ks, ch, fc, n = cfg["kernel"], cfg["channels"], cfg["fc_width"], \
        cfg["num_classes"]
    flat = (h // 4) * (w // 4) * ch
    k = jax.random.split(key, 4)
    f32 = jnp.float32

    def normal(key, shape, fan_in):
        return jax.random.normal(key, shape, f32) / math.sqrt(fan_in)

    return {
        "conv1": normal(k[0], (ks, ks, c, ch), ks * ks * c),
        "b1": jnp.zeros((ch,), f32),
        "conv2": normal(k[1], (ks, ks, ch, ch), ks * ks * ch),
        "b2": jnp.zeros((ch,), f32),
        "fc1": normal(k[2], (flat, fc), flat),
        "bf1": jnp.zeros((fc,), f32),
        "fc2": normal(k[3], (fc, n), fc),
        "bf2": jnp.zeros((n,), f32),
    }


def forward(p, x):
    """NHWC input -> logits: conv5x5+relu, maxpool2, conv5x5+relu,
    maxpool2, fc+relu, fc."""
    def conv(x, w, b):
        y = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jax.nn.relu(y + b)

    def pool(x):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                     (1, 2, 2, 1), "VALID")

    x = pool(conv(x, p["conv1"], p["b1"]))
    x = pool(conv(x, p["conv2"], p["b2"]))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["fc1"] + p["bf1"])
    return x @ p["fc2"] + p["bf2"]


def loss(p, x, y):
    """Mean softmax cross-entropy, summed in float32."""
    logits = forward(p, x).astype(jnp.float32)
    ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - ll)


def _sqnorm(t):
    return sum(jnp.sum(jnp.square(v.astype(jnp.float32)))
               for v in jax.tree.leaves(t))


def _precision(dtype):
    return "highest" if dtype == jnp.float32 else "default"


@functools.partial(jax.jit, static_argnames=("eta", "dtype", "prec"))
def _local_step(w, w_start, g0, cum, X, Y, client, idx, lam, gprev, *,
                eta, dtype, prec):
    """One SGD step of one client and its Alg. 2 statistics."""
    with jax.default_matmul_precision(prec):
        l_val, g = jax.value_and_grad(loss)(w, X[client][idx], Y[client][idx])
    g = jax.tree.map(lambda v: v.astype(jnp.float32), g)
    g0 = jax.tree.map(lambda a, b: jnp.where(lam == 0, b, a), g0, g)
    drift = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                         - b.astype(jnp.float32), w, w_start)
    gd = jax.tree.map(jnp.subtract, g, g0)
    beta = jnp.sqrt(_sqnorm(gd) / jnp.maximum(_sqnorm(drift), 1e-20))
    cum = jax.tree.map(jnp.add, cum, g)
    delta = _sqnorm(cum) / ((lam + 1.0) * jnp.maximum(gprev, 1e-20))
    w = jax.tree.map(lambda a, d: (a.astype(jnp.float32) - eta * d)
                     .astype(dtype), w, g)
    return l_val, g0, cum, beta, delta, w


def tau_law(A, L, taus_used, k, cfg: dict):
    """Alg. 1 / Eq. 15 with Theorem 2's bound on alpha, in float32: the
    step counts of round k+1 from round k's A_i and the L estimate."""
    alg = cfg["algorithm"]
    f32 = np.float32
    eps = f32(EPS)
    A = np.asarray(A, f32)
    if k < 1 or not np.all(np.isfinite(A)) or not np.any(A > eps):
        return np.asarray(taus_used, np.int64), np.full(len(A), np.nan)
    A_s = np.maximum(A, eps)
    A_min = A_s.min()
    bound = f32(2.0) * f32(L) / np.maximum(A_min, eps)
    alpha = f32(alg["alpha"])
    a_k = np.minimum(alpha, f32(0.999) * bound) if bound < 1.0 else alpha
    denom = A_s - a_k * A_min
    ratios = np.where(denom > eps, A_s / np.maximum(denom, eps),
                      f32(alg["tau_max"]))
    t = np.floor(ratios)
    t = np.where(t <= 1.0, f32(alg["tau_min"]), t)
    return np.clip(t, alg["tau_min"], alg["tau_max"]).astype(np.int64), ratios


def rounds(cfg: dict, clients, p_w, w0, seed: int, n_rounds: int, *,
           dtype=jnp.float32, taus_used=None):
    """Run ``n_rounds`` FedVeca rounds from ``w0`` on host ``clients``
    [(x, y)] with weights ``p_w``.

    ``taus_used[k]`` (optional) fixes the local step counts of round k;
    otherwise the rounds follow the controller's own predictions.

    -> dict(loss [n_rounds], params [w_1 .. w_n] (host float32),
            taus [used per round], taus_next, A [per round], L, tau_k0,
            grad0 (the first aggregated gradient))
    """
    alg = cfg["algorithm"]
    eta, tau_max, batch = alg["eta"], alg["tau_max"], alg["batch"]
    C = len(clients)
    p_w = np.asarray(p_w, np.float32)
    # every client's data in one [C, N_max, ...] buffer: one compiled step
    n_max = max(len(y) for _, y in clients)
    X = np.zeros((C, n_max) + clients[0][0].shape[1:], np.float32)
    Y = np.zeros((C, n_max), np.int32)
    for i, (x, y) in enumerate(clients):
        X[i, :len(y)], Y[i, :len(y)] = x, y
    X, Y = jnp.asarray(X, dtype), jnp.asarray(Y)
    step_kw = dict(eta=float(eta), dtype=jnp.dtype(dtype).name,
                   prec=_precision(dtype))

    params = jax.tree.map(lambda v: jnp.asarray(v, dtype), w0)
    zeros = jax.tree.map(lambda v: jnp.zeros(v.shape, jnp.float32), params)
    key = jax.random.PRNGKey(seed)
    taus = np.full(C, alg["tau_init"], np.int64)
    L = 0.0
    prev_gg = prev2_gg = None
    prev_gsq = params0_sq = prev_upd = prev2_upd = 0.0
    out = dict(loss=[], params=[], taus=[], taus_next=[], A=[], L=[])
    for k in range(n_rounds):
        if taus_used is not None:
            taus = np.asarray(taus_used[k], np.int64)
        key, sub = jax.random.split(key)
        G, g0s, losses, betas, deltas = [], [], [], [], []
        for i in range(C):
            idx = jax.random.randint(jax.random.fold_in(sub, i),
                                     (tau_max, batch), 0, len(clients[i][1]))
            w, g0, cum, beta, delta = params, zeros, zeros, 0.0, 0.0
            for lam in range(int(taus[i])):
                l_val, g0, cum, b_l, d_l, w = _local_step(
                    w, params, g0, cum, X, Y, np.int32(i), idx[lam],
                    np.float32(lam), np.float32(prev_gsq), **step_kw)
                if lam == 0:
                    loss0 = float(l_val)
                else:
                    beta = max(beta, float(b_l))
                    delta = max(delta, float(d_l))
            G.append(jax.tree.map(lambda v: v / float(taus[i]), cum))
            g0s.append(g0)
            losses.append(loss0)
            betas.append(beta)
            deltas.append(delta)
        tau_k = float(np.sum(p_w * taus))
        step = jax.tree.map(lambda *gs: sum(float(p_w[i]) * gs[i]
                                            for i in range(C)), *G)
        gg = jax.tree.map(lambda *gs: sum(float(p_w[i]) * gs[i]
                                          for i in range(C)), *g0s)
        if k == 0:
            out["tau_k0"] = tau_k
            out["grad0"] = jax.device_get(step)
        upd = jax.tree.map(lambda v: -eta * tau_k * v, step)
        params_sq = float(_sqnorm(params))
        params = jax.tree.map(
            lambda w, u: (w.astype(jnp.float32) + u).astype(dtype), params, upd)
        out["loss"].append(float(np.sum(p_w * np.asarray(losses))))
        out["params"].append(jax.device_get(jax.tree.map(
            lambda v: v.astype(jnp.float32), params)))
        out["taus"].append(taus.copy())

        # Alg. 1: the L estimate one round late, then A_i and Eq. 15
        if k == 1:
            L = max(L, math.sqrt(prev_gsq) / max(math.sqrt(params0_sq), EPS))
        elif k >= 2:
            diff = jax.tree.map(jnp.subtract, prev_gg, prev2_gg)
            L = max(L, math.sqrt(float(_sqnorm(diff)))
                    / max(math.sqrt(prev2_upd), EPS))
        A = eta * np.square(np.asarray(betas)) * np.asarray(deltas)
        nxt, _ = tau_law(A, L, taus, k, cfg)
        out["taus_next"].append(nxt)
        out["A"].append(A)
        out["L"].append(L)
        taus = nxt
        prev2_gg, prev_gg = prev_gg, gg
        prev_gsq = float(_sqnorm(gg))
        if k == 0:
            params0_sq = params_sq
        prev2_upd, prev_upd = prev_upd, float(_sqnorm(upd))
    return out


def _leaf_gap(prog, ref, keep):
    """Worst leaf of |‖prog‖ - ‖ref‖| / max(‖ref‖, median leaf ‖ref‖)."""
    norms = {n: float(np.linalg.norm(ref[n])) for n in keep}
    med = float(np.median(list(norms.values())))
    return max(abs(float(np.linalg.norm(prog[n])) - norms[n])
               / max(norms[n], med) for n in keep)


def compare(cfg: dict, clients, p_w, w0, seed: int, run: dict,
            n_rounds: int = 3) -> dict:
    """The readings of a run against the float32 reference.

    ``run`` holds what the run under test produced in its first rounds:
    ``loss`` per round, ``params`` [after round 1, after ``n_rounds``],
    ``taus_next``, ``A`` and ``L`` per round, and ``tau_k0``. The
    reference takes the run's own step counts (the controller's integer
    choices): a choice is checked against the law applied to the run's
    own A and L (``tau_law``, exact), and A against the reference's A.
    """
    eta = cfg["algorithm"]["eta"]
    taus_used = [np.full(len(clients), cfg["algorithm"]["tau_init"])] + \
        [np.asarray(t) for t in run["taus_next"][:n_rounds - 1]]
    ref = rounds(cfg, clients, p_w, w0, seed, n_rounds, taus_used=taus_used)

    # leaves whose reference gradient is nought to rounding move by
    # round-off alone: a rule on the gradient, never on a leaf's name
    g_ref = ref["grad0"]
    gn = {n: float(np.linalg.norm(v)) for n, v in g_ref.items()}
    med = float(np.median(list(gn.values())))
    keep = [n for n in g_ref if gn[n] >= 1e-3 * med]

    w1 = run["params"][0]
    g_run = {n: (np.asarray(w0[n]) - np.asarray(w1[n])) / (eta * run["tau_k0"])
             for n in keep}
    d_run = {n: np.asarray(run["params"][-1][n]) - np.asarray(w0[n])
             for n in keep}
    d_ref = {n: np.asarray(ref["params"][n_rounds - 1][n]) - np.asarray(w0[n])
             for n in keep}
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(run["loss"][:n_rounds], ref["loss"]))
    # A_i from round 1 on (round 0 has no broadcast gradient norm yet)
    a_gap = 0.0
    for k in range(1, n_rounds):
        a_ref = np.asarray(ref["A"][k], np.float64)
        scale = np.maximum(a_ref, np.median(a_ref))
        a_gap = max(a_gap, float(np.max(
            np.abs(np.asarray(run["A"][k], np.float64) - a_ref) / scale)))
    # the law on the run's own A and L must give the run's choices; a
    # ratio within 1e-5 of an integer may floor either way (the chip's
    # float32 division is not correctly rounded)
    law = 0
    for k in range(n_rounds):
        want, ratio = tau_law(run["A"][k], run["L"][k], taus_used[k], k, cfg)
        for i in np.nonzero(np.asarray(run["taus_next"][k]) != want)[0]:
            r = float(ratio[i])
            law += 0 if abs(r - round(r)) <= 1e-5 * abs(r) else 1
    return {
        "loss_gap": float(loss_gap),
        "grad_gap": float(_leaf_gap(g_run, g_ref, keep)),
        "change_gap": float(_leaf_gap(d_run, d_ref, keep)),
        "A_gap": a_gap,
        "tau_law": float(law),
    }


def control_run(cfg: dict, clients, p_w, w0, seed: int, n_rounds: int = 3):
    """The reference in bfloat16, shaped like a run for ``compare``."""
    out = rounds(cfg, clients, p_w, w0, seed, n_rounds, dtype=jnp.bfloat16)
    return dict(loss=out["loss"], params=[out["params"][0], out["params"][-1]],
                taus_next=out["taus_next"], A=out["A"], L=out["L"],
                tau_k0=out["tau_k0"])
