"""Faults planted in the program under test, to show that the check
catches them (the fault tests, and readings on the chip).

Each is a context manager that patches one function of the program for
its duration; the harness itself is untouched.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def unchanged_state():
    """Every round returns the parameters it was given."""
    import jax
    import jax.numpy as jnp

    from repro.core import engine

    orig = engine.RoundEngine.run_fused

    def run_fused(self, params, cstate, p, **kw):
        keep = jax.tree.map(jnp.copy, params)
        _, cstate, scaffold, diag = orig(self, params, cstate, p, **kw)
        return keep, cstate, scaffold, diag

    engine.RoundEngine.run_fused = run_fused
    try:
        yield
    finally:
        engine.RoundEngine.run_fused = orig


@contextlib.contextmanager
def half_batch():
    """The CNN's loss leaves out half of every minibatch and takes the
    mean over the rest."""
    from repro.models import simple

    orig = simple.cnn_loss

    def cnn_loss(cfg, p, batch):
        half = batch["y"].shape[0] // 2
        return orig(cfg, p, {k: v[:half] for k, v in batch.items()})

    simple.cnn_loss = cnn_loss
    try:
        yield
    finally:
        simple.cnn_loss = orig


@contextlib.contextmanager
def token_altered():
    """Every sampled token is replaced by its successor id."""
    from repro.serve import loop

    orig = loop.make_sample_fn

    def make_sample_fn(sampler):
        sample = orig(sampler)

        def altered(logits, rid, nstep):
            return (sample(logits, rid, nstep) + 1) % logits.shape[-1]

        return altered

    loop.make_sample_fn = make_sample_fn
    try:
        yield
    finally:
        loop.make_sample_fn = orig


@contextlib.contextmanager
def no_exchange():
    """The client-sharded round leaves out the psum between chips: each
    chip's partial weighted sum stands for the whole."""
    from repro.core import fedveca

    orig = fedveca.psum_reduce
    fedveca.psum_reduce = lambda base, axis_name: base
    try:
        yield
    finally:
        fedveca.psum_reduce = orig


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "token_altered": token_altered, "no_exchange": no_exchange}
