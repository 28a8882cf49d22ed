"""R4 fixtures: shard_map bodies gathering along the client axis or
psum-ing outside the strategy layer."""
import functools

import jax
import jax.numpy as jnp
from jax import shard_map


def _round_body(stacked, w):
    picked = jnp.take(stacked, jnp.array([0]), axis=0)  # BAD: gather along
    #   the sharded client axis re-materializes the cohort on one shard
    total = jax.lax.psum(picked * w, "data")  # BAD: bare psum — must route
    #   through strategy.psum_reduce
    return total


def build(mesh, specs):
    return shard_map(_round_body, mesh=mesh, in_specs=specs,
                     out_specs=specs[0])


def _helper(x):
    return jax.lax.dynamic_slice(x, (0,), (2,))  # BAD: reached from the
    #   shard_map body below through the local call closure


def _outer_body(x):
    return _helper(x) + 1.0


def build2(mesh, spec):
    return shard_map(_outer_body, mesh=mesh, in_specs=(spec,),
                     out_specs=spec)
