"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test lowers a kernel for one chip of a described (not
attached) v5e:2x2 topology and compiles it with the TPU compiler, which
refuses what interpret mode on the CPU never sees (block shapes off the
(8, 128) tiling, too much VMEM). Shapes are the ones the chip smoke
(chip_smoke.py) runs:

* vecavg: the FedVeca server reduce over cnn-cifar10's [5, D_total] f32;
* paged decode: starcoder2-3b's widths (Hq=24, Hkv=2, head_dim 128, bf16
  pools of 8 slots x 256 pages x 16 rows), full attention and window 4096,
  with no copy of the donated pools and VMEM within the scoped limit;
* paged insert: the layer-stacked prefill write at L=30.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one running this file
loads the TPU library. The persistent compile cache is off around each
compile: a compile for a described chip is written to it but cannot be
read back without one.
"""
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, HQ, HKV, HD, PS = 8, 24, 2, 128, 16  # starcoder2-3b serving widths
P = 4096 // PS  # pages per slot: the 4096-token window
N = B * P
L = 30
V5E_SCOPED_VMEM = 16 * 2**20  # the compiler's default scoped VMEM limit


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


def _scoped_vmem(custom_call_line: str) -> int:
    """Bytes of VMEM (memory space 1) a compiled Mosaic call reserves."""
    sizes = re.findall(
        r'"used_scoped_memory_configs":\[\{"memory_space":"1",'
        r'"offset":"\d+","size":"(\d+)"', custom_call_line)
    return max(map(int, sizes), default=0)


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
    """The blocked DMA walk compiles at starcoder2-3b's widths (P = 256
    pages of 16 rows, 16 pages a block), the pools are not copied around
    the kernel when the caller donates them, and its VMEM fits the
    chip's default scoped limit."""
    from repro.kernels.paged_attention.kernel import (
        paged_decode_attention_pallas,
    )

    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    pool = S((N, PS, HKV, HD), jnp.bfloat16)
    text = jax.jit(
        functools.partial(paged_decode_attention_pallas, window=window,
                          interpret=False),
        donate_argnums=(1, 2)).lower(
        S((B, HQ, HD), jnp.bfloat16), pool, pool,
        S((B, HKV, HD), jnp.bfloat16), S((B, HKV, HD), jnp.bfloat16),
        S((B, P), jnp.int32), S((B,), jnp.int32),
        S((B,), jnp.bool_)).compile().as_text()
    call = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(call) == 1
    pool_ty = f"bf16[{N},{PS},{HKV},{HD}]"
    assert not [ln for ln in text.splitlines()
                if re.search(r"= " + re.escape(pool_ty) + r"\S* copy", ln)]
    assert 0 < _scoped_vmem(call[0]) <= V5E_SCOPED_VMEM


@pytest.mark.parametrize("kv_heads, head_dim, kernel", [
    (40, 128, True),  # qwen1.5-32b: one 160 KiB page a block
    (5, 64, False),  # hymba-1.5b: the DMA cannot slice padded pages
    (8, 64, False),  # granite-moe-1b
])
def test_paged_decode_routes_by_head_shape_for_v5e(
        one_chip, no_persistent_cache, kv_heads, head_dim, kernel):
    """ops.paged_decode_attention compiles for the chip at other served
    widths: the DMA walk where its pages tile, within the scoped VMEM,
    and the XLA oracle path where they do not."""
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.kernels.paged_attention.kernel import dma_walk_fits

    assert dma_walk_fits(kv_heads, head_dim) is kernel
    b, p = 4, 64
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    pool = S((b * p, PS, kv_heads, head_dim), jnp.bfloat16)
    text = jax.jit(
        functools.partial(pa_ops.paged_decode_attention, window=0,
                          interpret=False),
        donate_argnums=(1, 2)).lower(
        S((b, 2 * kv_heads, head_dim), jnp.bfloat16), pool, pool,
        S((b, kv_heads, head_dim), jnp.bfloat16),
        S((b, kv_heads, head_dim), jnp.bfloat16), S((b, p), jnp.int32),
        S((b,), jnp.int32), active=S((b,), jnp.bool_)).compile().as_text()
    call = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(call) == int(kernel)
    if kernel:
        assert _scoped_vmem(call[0]) <= V5E_SCOPED_VMEM


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
