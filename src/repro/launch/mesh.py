"""Mesh construction (DESIGN.md §6, §11).

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init).

Every mesh — the 256-chip production mesh, the laptop/test host mesh, and
the federated client mesh — goes through one divisibility-aware builder,
``build_mesh``: it validates the device count with an actionable error
(strict mode) or shrinks each axis to the largest divisor that fits the
available devices (``shrink=True``, the smoke/laptop path), so dry-run and
laptop runs share code instead of each caller re-implementing the clamp.
"""
from __future__ import annotations

import math
import sys
from typing import Sequence, Tuple

import jax
from jax.sharding import Mesh

# The mesh axes the federated CLIENT dimension shards over (DESIGN.md §6):
# inside the round these axes are consumed by the client axis, so
# per-client activation batches must not also claim them.
CLIENT_AXES: Tuple[str, ...] = ("pod", "data")


def build_mesh(axes: Sequence[str], shape: Sequence[int], *,
               shrink: bool = False) -> Mesh:
    """The one mesh builder: validate (or shrink) ``shape`` against the
    available devices and build ``Mesh``.

    strict (default): raise with the XLA_FLAGS hint when fewer than
    prod(shape) devices exist — the production path must never silently
    downsize. ``shrink=True``: reduce each axis, left to right, to the
    largest divisor of the remaining device count that does not exceed the
    requested extent — the smoke/laptop path (a 1-device box yields an
    all-ones mesh with the same axis names, so downstream code that looks
    up axis extents keeps working). A shrink that changes the shape says
    so on stderr: on one chip the launcher's default data=2 becomes a
    single client.
    """
    import numpy as np

    axes = tuple(axes)
    shape = tuple(int(s) for s in shape)
    if len(axes) != len(shape):
        raise ValueError(f"axes {axes} and shape {shape} length mismatch")
    if any(s < 1 for s in shape):
        raise ValueError(f"mesh shape must be positive, got {shape}")
    devs = jax.devices()
    if shrink:
        left = len(devs)
        fitted = []
        for s in shape:
            s = min(s, left)
            while left % s:
                s -= 1  # largest divisor of `left` that is <= requested
            fitted.append(s)
            left //= s
        if tuple(fitted) != shape:
            print(f"build_mesh: shrank {dict(zip(axes, shape))} to "
                  f"{dict(zip(axes, fitted))} on {len(devs)} device(s)",
                  file=sys.stderr)
        shape = tuple(fitted)
    n = math.prod(shape)
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {dict(zip(axes, shape))}; have "
            f"{len(devs)} (set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} before any jax import, or pass shrink=True for a smoke run)"
        )
    return Mesh(np.array(devs[:n]).reshape(shape), axes)


def make_production_mesh(*, multi_pod: bool = False, smoke: bool = False) -> Mesh:
    """v5e pod mesh: (data=16, model=16) = 256 chips; multi_pod prepends
    pod=2 for the 512-chip two-pod configuration.

    ``smoke=True`` shrinks the same axis layout onto whatever devices
    exist (laptop/CI) instead of raising — the shapes change, the code
    path does not."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return build_mesh(axes, shape, shrink=smoke)


def make_federated_mesh(n_devices: int = None, *, pod: int = 1) -> Mesh:
    """Client-axis mesh for the sharded federated round (DESIGN.md §11):
    axes ('pod', 'data') with pod * data = n_devices (default: all
    devices). The [C, ...] client buffers shard over both axes."""
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    if pod < 1 or n % pod:
        raise ValueError(f"pod={pod} must divide n_devices={n}")
    return build_mesh(CLIENT_AXES, (pod, n // pod))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over however many (host) devices exist — tests/smoke."""
    return build_mesh(("data", "model"), (data, model), shrink=True)


def num_clients(mesh: Mesh) -> int:
    """Federated client cohorts = pod * data axis extent (DESIGN.md §6)."""
    return mesh.shape.get("pod", 1) * mesh.shape.get("data", 1)
