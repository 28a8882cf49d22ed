"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test lowers a kernel for one chip of a described (not
attached) v5e:2x2 topology and compiles it with the TPU compiler, which
refuses what interpret mode on the CPU never sees (block shapes off the
(8, 128) tiling, too much VMEM). Shapes are the ones the chip smoke
(chip_smoke.py) runs:

* vecavg: the FedVeca server reduce over cnn-cifar10's [5, D_total] f32;
* paged decode: starcoder2-3b's widths (Hq=24, Hkv=2, head_dim 128, bf16
  pools of 8 slots x 256 pages x 16 rows), full attention and window 4096;
* paged insert: the layer-stacked prefill write at L=30.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one running this file
loads the TPU library. The persistent compile cache is off around each
compile: a compile for a described chip is written to it but cannot be
read back without one.
"""
import functools
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, HQ, HKV, HD, PS = 8, 24, 2, 128, 16  # starcoder2-3b serving widths
P = 4096 // PS  # pages per slot: the 4096-token window
N = B * P
L = 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_vecavg_compiles_for_v5e(one_chip, no_persistent_cache):
    from repro.kernels.vecavg.kernel import vecavg_pallas
    from repro.models.model import build_model_by_name

    model = build_model_by_name("cnn-cifar10")
    d_total = sum(math.prod(x.shape) for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = _compiled_text(
        functools.partial(vecavg_pallas, interpret=False),
        S((5, d_total), jnp.float32), S((5,), jnp.float32),
        S((), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("window", [0, 4096])
def test_paged_decode_compiles_for_v5e(one_chip, no_persistent_cache, window):
    from repro.kernels.paged_attention.kernel import (
        paged_decode_attention_pallas,
    )

    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = _compiled_text(
        functools.partial(paged_decode_attention_pallas, window=window,
                          interpret=False),
        S((B, HQ, HD), jnp.bfloat16),
        S((N, PS, HKV, HD), jnp.bfloat16), S((N, PS, HKV, HD), jnp.bfloat16),
        S((B, HKV, HD), jnp.bfloat16), S((B, HKV, HD), jnp.bfloat16),
        S((B, P), jnp.int32), S((B,), jnp.int32), S((B,), jnp.bool_))
    assert "tpu_custom_call" in text


def test_paged_insert_compiles_for_v5e(one_chip, no_persistent_cache):
    from repro.kernels.paged_attention.kernel import paged_insert_pallas

    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = _compiled_text(
        functools.partial(paged_insert_pallas, interpret=False),
        S((L, N, PS, HKV, HD), jnp.bfloat16),
        S((L, N, PS, HKV, HD), jnp.bfloat16),
        S((L, P, PS, HKV, HD), jnp.bfloat16),
        S((L, P, PS, HKV, HD), jnp.bfloat16), S((P,), jnp.int32))
    assert "tpu_custom_call" in text
