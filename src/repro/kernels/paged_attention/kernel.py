"""Pallas TPU paged-attention decode: walk the page table in-kernel and
fuse the new token's pool write into the same launch.

The XLA paths in models/attention.py pay two pool-sized costs per layer
per tick: the read side gathers every slot's pages into a dense
[B, P*page_size, Hkv, hd] buffer before the masked softmax, and the
"mask" write builds a B x n_pages x page_size one-hot selector over the
WHOLE pool. This kernel does neither:

  * grid (B, P) with the page axis innermost. The page table rides
    in as a SCALAR-PREFETCH operand (pltpu.PrefetchScalarGridSpec), so
    the K/V pool BlockSpec index maps read ``page_table[b, p]`` directly
    and stream exactly one physical [page_size, Hkv, hd] page per grid
    step — the gather never exists. Unallocated entries (-1) clamp to page 0;
    their rows are masked invalid so the values never matter.
  * online softmax across the page walk: the [Hkv, G, hd] output tile (G
    = grouped query heads per KV head), running max and running
    denominator persist in VMEM across the P sweep (the output's index map
    is independent of the page axis; the stats are scratch) — the
    flash-attention recurrence, per slot.
  * validity is recomputed ARITHMETICALLY per tile, reproducing
    attention.paged_slot_valid bit-for-bit: entry i of a slot is valid iff
    its page is allocated and ``i <= pos`` (full) or ``i < W and
    pos - ((pos - i) mod W) >= 0`` (SWA ring, which reduces to ``i < W
    and i <= pos``).
  * the new token's K/V row is written through a routed one-row output
    block aliased onto the pool (input_output_aliases): slot b's write
    block sits at physical page ``page_table[b, idx // ps]`` row ``idx %
    ps`` (idx = pos, or pos mod W). Pages are slot-exclusive, so live
    writes never collide; slots with nothing to write (inactive, or an
    unallocated target) are ROUTED ONTO the first live slot's target with
    that slot's bytes — idempotent duplicate writes, safe under any
    write-back order. When NO slot writes, every block routes to pool row
    (0, 0) carrying that row's current bytes (an exact no-op).

Write/read ordering never matters for the attention result: the kernel
INJECTS the new token's row into the loaded K tile in-register (page
``idx // ps``, row ``idx % ps``, active slots only), so the output is the
same whether the aliased pool write has landed or not.

The prefill sibling (`paged_insert_pallas`) replaces the full-pool
jnp.where of attention.insert_kv_pages: grid (L, P) over layers x slot
pages, each allocated logical page DMAs one [page_size, Hkv, hd] source
tile onto its physical page; unallocated entries duplicate-route onto the
first allocated page (same idempotent trick). Only the slot's own pages
are ever touched.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# decode: fused page-walk attention + one-row pool write
# ---------------------------------------------------------------------------


def _decode_kernel(pt_ref, pos_ref, act_ref, wpage_ref, wrow_ref,  # prefetch
                   q_ref, kpool_ref, vpool_ref, knew_ref, vnew_ref,
                   kwrite_ref, vwrite_ref,
                   o_ref, kout_ref, vout_ref, m_ref, l_ref, *,
                   scale: float, window: int, ps: int, n_pages_slot: int,
                   hkv: int):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[b]
    active = act_ref[b] != 0
    entry = pt_ref[b, p]  # logical page p's physical id (-1 = unallocated)
    alloc = entry >= 0
    idx = (pos % window) if window else pos  # the new token's slot index

    # inject the new token's row in-register: correctness is then
    # independent of whether the aliased pool write has landed yet
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0)
    inject = active & alloc & (p == idx // ps)
    rowhit = inject & (row_iota == idx % ps)  # [ps, 1]

    # arithmetic validity == attention.paged_slot_valid for this tile. For
    # the SWA ring, entry i < W holds position pos - ((pos - i) mod W),
    # which is >= 0 exactly when i <= pos.
    i = p * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)  # [1, ps]
    valid = alloc & (i <= pos)
    if window:
        valid = valid & (i < window)

    for h in range(hkv):  # kv heads; each serves G grouped query heads
        q = q_ref[0, h].astype(jnp.float32)  # [G, hd]
        k = kpool_ref[0, :, h, :].astype(jnp.float32)  # [ps, hd]
        v = vpool_ref[0, :, h, :].astype(jnp.float32)
        k = jnp.where(rowhit, knew_ref[0, h:h + 1, :].astype(jnp.float32), k)
        v = jnp.where(rowhit, vnew_ref[0, h:h + 1, :].astype(jnp.float32), v)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        s = jnp.where(valid, s, NEG_INF)  # [G, ps]

        m_prev = m_ref[h]  # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        pexp = jnp.where(valid, pexp, 0.0)
        corr = jnp.exp(m_prev - m_new)
        o_ref[0, h] = o_ref[0, h] * corr + pexp @ v
        m_ref[h] = m_new
        l_ref[h] = l_ref[h] * corr + jnp.sum(pexp, axis=-1, keepdims=True)

    @pl.when(p == n_pages_slot - 1)
    def _final():
        for h in range(hkv):
            o_ref[0, h] = o_ref[0, h] / jnp.maximum(l_ref[h], 1e-30)

    # fused pool write: this p-invariant block lands at the routed
    # (page, row); duplicates carry identical bytes
    kout_ref[0, 0] = kwrite_ref[0]
    vout_ref[0, 0] = vwrite_ref[0]


@functools.partial(
    jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention_pallas(q, k_pool, v_pool, k_new, v_new,
                                  page_table, pos, active, *,
                                  window: int = 0, interpret: bool = True):
    """q [B,Hq,hd], pools [N,ps,Hkv,hd], k_new/v_new [B,Hkv,hd],
    page_table [B,P] int32 (-1 = unallocated), pos [B], active bool [B]
    -> (o [B,Hq,hd], k_pool', v_pool') with the new token's row written
    into the pools for every active slot (others bit-identical).

    TPU layout: grid (B, P), one physical page of every KV head per step.
    Every block's two trailing dims are whole array dims — q/o ride as
    [B, Hkv, G, hd], pool pages as [ps, Hkv, hd], the new rows as
    [Hkv, hd] — so any G, Hkv and page size tile legally; the running
    max/denominator live in VMEM scratch."""
    B, Hq, hd = q.shape
    N, ps, Hkv, _ = k_pool.shape
    P = page_table.shape[1]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)

    pt = page_table.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    act = active.astype(jnp.int32)

    # write routing (host-side arithmetic, all [B]): slots with nothing to
    # write duplicate the first live slot's write; if NO slot writes,
    # everything routes to pool row (0, 0) carrying its current bytes
    idx = ((pos % window) if window else pos).astype(jnp.int32)
    phys = jnp.take_along_axis(pt, (idx // ps)[:, None], axis=1)[:, 0]
    ok = (phys >= 0) & (act != 0)
    any_ok = ok.any()
    first = jnp.argmax(ok).astype(jnp.int32)
    src = jnp.where(ok, jnp.arange(B, dtype=jnp.int32), first)
    wpage = jnp.where(any_ok, jnp.maximum(phys[src], 0), 0)
    wrow = jnp.where(any_ok, idx[src] % ps, 0)
    kwrite = jnp.where(any_ok, k_new[src], jnp.broadcast_to(k_pool[0, 0], k_new.shape))
    vwrite = jnp.where(any_ok, v_new[src], jnp.broadcast_to(v_pool[0, 0], v_new.shape))

    def _pool_route(b, p, pt_ref, *_):
        return (jnp.maximum(pt_ref[b, p], 0), 0, 0, 0)

    def _write_route(b, p, pt_ref, pos_ref, act_ref, wpage_ref, wrow_ref):
        return (wpage_ref[b], wrow_ref[b], 0, 0)

    def _slot(b, p, *_):
        return (b, 0, 0)

    def _slot4(b, p, *_):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, hd), _slot4),  # q
            pl.BlockSpec((1, ps, Hkv, hd), _pool_route),  # k_pool page
            pl.BlockSpec((1, ps, Hkv, hd), _pool_route),  # v_pool page
            pl.BlockSpec((1, Hkv, hd), _slot),  # k_new
            pl.BlockSpec((1, Hkv, hd), _slot),  # v_new
            pl.BlockSpec((1, Hkv, hd), _slot),  # kwrite
            pl.BlockSpec((1, Hkv, hd), _slot),  # vwrite
        ],
        out_specs=[
            pl.BlockSpec((1, Hkv, G, hd), _slot4),  # o
            pl.BlockSpec((1, 1, Hkv, hd), _write_route),  # k_pool row
            pl.BlockSpec((1, 1, Hkv, hd), _write_route),  # v_pool row
        ],
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),  # running max
            pltpu.VMEM((Hkv, G, 1), jnp.float32),  # running denominator
        ],
    )
    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, ps=ps, n_pages_slot=P,
        hkv=Hkv)
    o, k_out, v_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, hd), jnp.float32),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # operands: 5 prefetch, then q=5 kpool=6 vpool=7 knew=8 vnew=9 ...
        input_output_aliases={6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(pt, pos, act, wpage, wrow, q.reshape(B, Hkv, G, hd), k_pool, v_pool,
      k_new, v_new, kwrite, vwrite)
    return o.reshape(B, Hq, hd).astype(q.dtype), k_out, v_out


# ---------------------------------------------------------------------------
# prefill: write a slot's pages into the pool (insert_kv_pages sibling)
# ---------------------------------------------------------------------------


def _insert_kernel(dst_ref, src_ref, ksrc_ref, vsrc_ref, pin_k, pin_v,
                   kout_ref, vout_ref):
    kout_ref[...] = ksrc_ref[...]
    vout_ref[...] = vsrc_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_insert_pallas(k_pool, v_pool, k_src, v_src, page_ids, *,
                        interpret: bool = True):
    """Layer-stacked prefill-into-pages write: pools [L,N,ps,Hkv,hd],
    src [L,P,ps,Hkv,hd], page_ids [P] int32 (-1 = unallocated, skipped).
    Each allocated logical page j lands IN FULL on physical page
    page_ids[j]; unallocated entries duplicate-route the first allocated
    page's write (identical bytes, so order never matters). Untouched
    pool pages keep their bytes via input/output aliasing."""
    L, N, ps, Hkv, hd = k_pool.shape
    P = page_ids.shape[0]
    ids = page_ids.astype(jnp.int32)
    ok = ids >= 0
    any_ok = ok.any()
    first = jnp.argmax(ok).astype(jnp.int32)
    src_idx = jnp.where(ok, jnp.arange(P, dtype=jnp.int32), first)
    dst = jnp.where(any_ok, jnp.maximum(ids[src_idx], 0), 0)
    k_w = jnp.where(any_ok, jnp.take(k_src, src_idx, axis=1),
                    jnp.broadcast_to(k_pool[:, :1], k_src.shape))
    v_w = jnp.where(any_ok, jnp.take(v_src, src_idx, axis=1),
                    jnp.broadcast_to(v_pool[:, :1], v_src.shape))

    def _dst_route(l, p, dst_ref, src_ref):
        return (l, dst_ref[p], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(L, P),
        in_specs=[
            pl.BlockSpec((1, 1, ps, Hkv, hd), lambda l, p, *_: (l, p, 0, 0, 0)),
            pl.BlockSpec((1, 1, ps, Hkv, hd), lambda l, p, *_: (l, p, 0, 0, 0)),
            pl.BlockSpec((1, 1, ps, Hkv, hd), _dst_route),  # aliased k pool
            pl.BlockSpec((1, 1, ps, Hkv, hd), _dst_route),  # aliased v pool
        ],
        out_specs=[
            pl.BlockSpec((1, 1, ps, Hkv, hd), _dst_route),
            pl.BlockSpec((1, 1, ps, Hkv, hd), _dst_route),
        ],
    )
    k_out, v_out = pl.pallas_call(
        _insert_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # operands: 2 prefetch, then ksrc=2 vsrc=3 kpool=4 vpool=5
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(dst, src_idx, k_w.astype(k_pool.dtype), v_w.astype(v_pool.dtype),
      k_pool, v_pool)
    return k_out, v_out
