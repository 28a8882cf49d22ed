"""Pallas TPU kernel: fused FedVeca vectorized averaging + client norms.

One HBM pass over the stacked client-gradient matrix U[C, D]:
  * weighted reduction over the client axis  ->  delta_w = -scale * p @ U
  * per-client squared norms (for the Alg. 2 beta/delta estimators)

The grid tiles D; each step keeps a (C, BLOCK_D) tile resident in VMEM, so
the stats ride along for free instead of costing a second HBM sweep (the
point of fusing them — see DESIGN.md §7). C (clients per pod, 16-32) is
small; BLOCK_D is VMEM/MXU-aligned (multiple of 128 lanes).

TPU layout: every VMEM block is 2-D with trailing dims that are either
(8, 128)-aligned or the array's full dims — p rides as a [C, 1] column,
the output as a [1, D] row and the norms as a [C, 1] column — and the
scale scalar sits in SMEM. D is never padded: the last block's columns
past D are masked to zero before they reach the norms (Pallas drops the
out-of-range part of the output block's write).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _vecavg_kernel(scale_ref, p_ref, u_ref, out_ref, sqn_ref, *,
                   d: int, block_d: int):
    j = pl.program_id(0)
    u = u_ref[...].astype(jnp.float32)  # [C, BD]
    col = j * block_d + jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    u = jnp.where(col < d, u, 0.0)
    p = p_ref[...].astype(jnp.float32)  # [C, 1]
    out_ref[...] = (-scale_ref[0] * jnp.sum(p * u, axis=0, keepdims=True)
                    ).astype(out_ref.dtype)

    @pl.when(j == 0)
    def _init():
        sqn_ref[...] = jnp.zeros_like(sqn_ref)

    sqn_ref[...] += jnp.sum(jnp.square(u), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def vecavg_pallas(u, p, scale, *, block_d: int = 512, interpret: bool = True):
    """u [C, D], p [C], scale scalar -> (delta_w [D], sqnorms [C])."""
    C, D = u.shape
    scale_arr = jnp.reshape(jnp.asarray(scale, jnp.float32), (1,))
    out, sqn = pl.pallas_call(
        functools.partial(_vecavg_kernel, d=D, block_d=block_d),
        grid=(pl.cdiv(D, block_d),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # scale
            pl.BlockSpec((C, 1), lambda j: (0, 0)),  # p: resident
            pl.BlockSpec((C, block_d), lambda j: (0, j)),  # U tile
        ],
        out_specs=[
            pl.BlockSpec((1, block_d), lambda j: (0, j)),
            pl.BlockSpec((C, 1), lambda j: (0, 0)),  # accumulated across grid
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, D), u.dtype),
            jax.ShapeDtypeStruct((C, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(scale_arr, p.reshape(C, 1), u)
    return out[0], sqn[:, 0]
