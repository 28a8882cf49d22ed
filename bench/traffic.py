"""The benchmark's two general generators, driven by a traffic file.

* ``fl_clients``: a federated deployment's client data — CIFAR-shaped
  Gaussian-mixture samples split over clients by one of the paper's
  Non-IID cases (the program's ``data/synthetic`` and ``data/partition``
  recipes, copied so the yardstick cannot move with the program).
* ``serve_requests``: a serving mix — prompt and output lengths from a
  distribution snapped to a grid, and arrival offsets in wall-clock
  seconds (a backlog, or Poisson arrivals whose rate alternates in
  bursts).

Steadiness across seeds: the multiset of sizes and the arrival times come
from the traffic file's own ``sizes_seed``; the run's seed only permutes
the sizes within fixed blocks and draws the token ids. Every seed thus
offers the same work in another order.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np


def derive(seed: int, stream: str) -> int:
    """A 31-bit seed for one named stream of a run's randomness (valid
    for numpy and for ``jax.random.PRNGKey``), from any whole number."""
    words = [int(b) for b in stream.encode()]
    ss = np.random.SeedSequence([int(seed) % (1 << 64), *words])
    return int(ss.generate_state(1, np.uint32)[0]) & 0x7FFFFFFF


# ---------------------------------------------------------------------------
# federated client data
# ---------------------------------------------------------------------------


def _mixture(n, shape, k, noise, rng, mus):
    """n samples, n/k of each class in a random order: every seed splits
    into clients of the same sizes, so the round program keeps one shape
    (and one compile) across seeds. Drawn in float32, in place, so the
    published 50,000 + 10,000 CIFAR-10 samples take a second or two."""
    dim = int(np.prod(shape))
    y = rng.permutation(np.arange(n) % k)
    x = rng.standard_normal((n, dim), dtype=np.float32)
    x *= np.float32(noise / np.sqrt(dim))
    for i in range(0, n, 4096):
        x[i:i + 4096] += mus[y[i:i + 4096]]
    return x.reshape((n,) + tuple(shape)), y.astype(np.int32)


def _by_label(labels, n_clients, rng) -> List[np.ndarray]:
    """Case 2: every client holds (nearly) one label."""
    classes = np.unique(labels)
    shards: List[List[np.ndarray]] = [[] for _ in range(n_clients)]
    if n_clients <= len(classes):
        for j, c in enumerate(classes):
            idx = np.where(labels == c)[0]
            rng.shuffle(idx)
            shards[j % n_clients].append(idx)
    else:
        owners: List[List[int]] = [[] for _ in classes]
        for cl in range(n_clients):
            owners[cl % len(classes)].append(cl)
        for j, c in enumerate(classes):
            idx = np.where(labels == c)[0]
            rng.shuffle(idx)
            for cl, part in zip(owners[j], np.array_split(idx, len(owners[j]))):
                shards[cl].append(part)
    return [np.sort(np.concatenate(s)) for s in shards]


def _case3(labels, n_clients, rng) -> List[np.ndarray]:
    """Case 3: the first half of the labels IID over the first half of
    the clients, the second half label-exclusive over the rest."""
    classes = np.unique(labels)
    first = np.where(np.isin(labels, classes[: len(classes) // 2]))[0]
    second = np.where(~np.isin(labels, classes[: len(classes) // 2]))[0]
    c1 = n_clients // 2 + n_clients % 2
    parts = [np.sort(s) for s in np.array_split(rng.permutation(first), c1)]
    parts += [np.sort(second[s])
              for s in _by_label(labels[second], n_clients - c1, rng)]
    return parts


def fl_clients(model_cfg: dict, traffic: dict, seed: int):
    """-> (clients [(x, y)], test (x, y)) for one run: ``train_samples``
    split over ``clients`` by the Non-IID ``case``, and ``test_samples``."""
    shape, k = tuple(model_cfg["input_shape"]), model_cfg["num_classes"]
    C, n = traffic["clients"], traffic["train_samples"]
    dim = int(np.prod(shape))
    mus = (np.random.default_rng(derive(seed, "task")).standard_normal(
        (k, dim)) * traffic["sep"] / np.sqrt(dim)).astype(np.float32)
    x, y = _mixture(n, shape, k, traffic["noise"],
                    np.random.default_rng(derive(seed, "train")), mus)
    test = _mixture(traffic["test_samples"], shape, k, traffic["noise"],
                    np.random.default_rng(derive(seed, "test")), mus)
    rng = np.random.RandomState(derive(seed, "split"))
    case = traffic["case"]
    if case == 1:
        parts = [np.sort(s) for s in np.array_split(rng.permutation(n), C)]
    elif case == 2:
        parts = _by_label(y, C, rng)
    elif case == 3:
        parts = _case3(y, C, rng)
    else:
        raise ValueError(f"unknown Non-IID case {case!r}")
    return [(x[s], y[s]) for s in parts], test


# ---------------------------------------------------------------------------
# serving requests
# ---------------------------------------------------------------------------


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] == "lognormal":
        v = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        v = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    v = np.clip(np.floor(v), spec["min"], spec["max"]).astype(np.int64)
    snap = spec.get("snap", 1)
    return (-(-v // snap) * snap).astype(np.int64)


def length_grid(spec: dict) -> List[int]:
    """Every length a spec can produce once snapped (the prefill shapes a
    run must warm up)."""
    snap = spec.get("snap", 1)
    lo = -(-spec["min"] // snap) * snap
    hi = -(-spec["max"] // snap) * snap
    return list(range(lo, hi + 1, snap))


def arrival_offsets(spec: dict, n: int, rng) -> np.ndarray:
    """Seconds after the window opens at which each request is due."""
    if spec["kind"] == "backlog":
        return np.zeros(n)
    if spec["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {spec['kind']!r}")
    rate = spec["rate"]
    burst = spec.get("burst")
    out, t = [], 0.0
    while len(out) < n:
        r = rate
        if burst:
            hi = int(t // burst["period_s"]) % 2 == 0
            r = rate * (burst["high"] if hi else burst["low"])
        t += rng.exponential(1.0 / r)
        out.append(t)
    return np.asarray(out)


def serve_requests(traffic: dict, vocab: int, seed: int
                   ) -> List[Tuple[int, np.ndarray, int, float]]:
    """-> [(rid, prompt ids, max_new, due offset s)] in due order."""
    n, block = traffic["requests"], traffic["block"]
    fixed = np.random.RandomState(traffic["sizes_seed"])
    plens = _lengths(traffic["prompt"], block, fixed)
    outs = _lengths(traffic["output"], block, fixed)
    due = arrival_offsets(traffic["arrivals"], n, fixed)
    rng = np.random.RandomState(derive(seed, "requests"))
    order = np.concatenate([rng.permutation(block)
                            for _ in range(-(-n // block))])[:n]
    reqs = []
    for i, j in enumerate(order):
        toks = rng.randint(0, vocab, int(plens[j])).astype(np.int32)
        reqs.append((i, toks, int(outs[j]), float(due[i])))
    return reqs
