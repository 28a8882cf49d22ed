"""Shared kernel-package helpers."""
from __future__ import annotations

import jax


def auto_interpret() -> bool:
    """Single source of truth for the Pallas interpret-mode default:
    interpret exactly when the backend is the CPU, the native compile
    everywhere else. There is no override, so nothing can put a kernel
    into the emulator on a TPU. Every kernel ops.py routes through here so
    the policy can never drift between kernels."""
    return jax.default_backend() == "cpu"
