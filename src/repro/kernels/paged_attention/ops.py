"""Public paged-attention decode ops: Pallas on TPU, interpret-mode on CPU
(`kernels.auto_interpret`).

models/attention.py dispatches here behind ``cache_update="kernel"``; the
XLA "mask"/"scatter" paths stay as oracles (tests/test_paged_kernel.py).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import auto_interpret
from repro.kernels.paged_attention import ref
from repro.kernels.paged_attention.kernel import (
    dma_walk_fits,
    paged_decode_attention_pallas,
    paged_insert_pallas,
)


def paged_decode_attention(q, k_pool, v_pool, k_new, v_new, page_table, pos,
                           *, window: int = 0, active=None,
                           use_pallas: bool = True,
                           interpret: Optional[bool] = None):
    """One decode tick against the shared page pool, page-table walk +
    fused new-token row write in one kernel launch.

    q [B,Hq,hd], pools [N,ps,Hkv,hd], k_new/v_new [B,Hkv,hd],
    page_table [B,P] int32, pos [B]; active bool [B] (None = all live)
    -> (o [B,Hq,hd], k_pool', v_pool').

    On the TPU, pools whose pages its DMA cannot move alone (head_dim off
    the 128 lanes, or KV heads that leave a sublane tile padded:
    ``dma_walk_fits``) take the XLA oracle path instead of the kernel.
    """
    B = q.shape[0]
    act = jnp.ones((B,), bool) if active is None else active
    interpret = auto_interpret() if interpret is None else interpret
    if not use_pallas or not (interpret or dma_walk_fits(*k_pool.shape[2:])):
        return ref.paged_decode_attention(
            q, k_pool, v_pool, k_new, v_new, page_table, pos, act,
            window=window)
    return paged_decode_attention_pallas(
        q, k_pool, v_pool, k_new, v_new, page_table, pos, act,
        window=int(window), interpret=interpret)


def paged_insert(k_pool, v_pool, k_src, v_src, page_ids, *,
                 use_pallas: bool = True, interpret: Optional[bool] = None):
    """Prefill-into-pages write, layer-stacked: pools [L,N,ps,Hkv,hd],
    src [L,P,ps,Hkv,hd], page_ids [P] (-1 = unallocated, skipped).
    Replaces the full-pool jnp.where of attention.insert_kv_pages with
    routed per-page block writes (only the slot's own pages are touched).
    """
    if not use_pallas:
        return ref.paged_insert(k_pool, v_pool, k_src, v_src, page_ids)
    return paged_insert_pallas(
        k_pool, v_pool, k_src, v_src, page_ids,
        interpret=auto_interpret() if interpret is None else interpret)
