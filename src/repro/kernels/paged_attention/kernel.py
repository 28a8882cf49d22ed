"""Pallas TPU paged-attention decode: walk each slot's live pages in
blocks by manual DMA, and write the new token's row into the pool in the
same launch.

The XLA paths in models/attention.py pay two pool-sized costs per layer
per tick: the read side gathers every slot's pages into a dense
[B, P*page_size, Hkv, hd] buffer before the masked softmax, and the
"mask" write builds a B x n_pages x page_size one-hot selector over the
WHOLE pool. This kernel does neither (DESIGN.md §7):

  * walk bound: slot b reads ``live_pages`` = ceil((min(pos, W-1) + 1) /
    ps) pages under a window W, ceil((pos + 1) / ps) without, none when
    inactive: the pages up to the last entry attention.paged_slot_valid
    admits.
  * grid (B, ceil(P / K)) with K = ``pages_per_block`` pages, about
    BLOCK_BYTES of K a block, from the shapes alone. The pools stay in HBM
    (memory_space=pl.ANY); a live block issues one async copy per
    allocated live page ([ps, Hkv, hd], one contiguous run) for K and V
    into a double-buffered VMEM block, and starts the next live block's
    copies, across the slot boundary too, before it computes. Blocks past
    the bound copy and compute nothing. The table, pos and the walk's
    scalars ride in by scalar prefetch.
  * online softmax across the slot's blocks: the [Hkv, G, hd] output tile
    (G = grouped query heads per KV head), running max and running
    denominator persist in VMEM across the walk — the flash-attention
    recurrence, per slot, in float32.
  * validity is recomputed ARITHMETICALLY per block, reproducing
    attention.paged_slot_valid bit-for-bit: entry i of a slot is valid iff
    its page is allocated and ``i <= pos`` (full) or ``i < W and
    pos - ((pos - i) mod W) >= 0`` (SWA ring, which reduces to ``i < W
    and i <= pos``). -1 entries are neither copied nor attended.
  * the new token's K/V row (entry idx = pos, or pos mod W) is stored
    into the VMEM block that holds it once that block has landed, so the
    result never depends on the pool write; the same step copies the row
    into the aliased pool, HBM to HBM, behind the block's compute. The
    page was read before the write starts, so nothing races. Inactive
    slots and unallocated targets write nothing.
  * bf16 blocks with paired KV heads are read as packed 32-bit words,
    each head split out with a shift (its exact float32 upcast).

The prefill sibling (`paged_insert_pallas`) replaces the full-pool
jnp.where of attention.insert_kv_pages: grid (L, P) over layers x slot
pages, each allocated logical page DMAs one [page_size, Hkv, hd] source
tile onto its physical page; unallocated entries duplicate-route onto the
first allocated page (identical bytes, so order never matters). Only the
slot's own pages are ever touched.
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
# decode: blocked page walk by manual DMA + one-row pool write
# ---------------------------------------------------------------------------

BLOCK_BYTES = 128 * 1024  # of K (and as much of V) one grid step reads


def pages_per_block(pages_per_slot: int, page_bytes: int) -> int:
    """K, the pages one grid step walks: as many as fill BLOCK_BYTES, at
    least one, at most the slot's table (16 pages of 16 rows at
    starcoder2-3b's two 128-wide bf16 KV heads). A slot's last block may be
    ragged: K need not divide the table."""
    return max(1, min(pages_per_slot, BLOCK_BYTES // page_bytes))


def dma_walk_fits(kv_heads: int, head_dim: int) -> bool:
    """Whether the TPU's DMA can move a pool page [ps, Hkv, hd] on its own:
    whole 128-lane rows, and KV heads that fill the sublane tiles of the
    pool's layout (a padded tile cannot be sliced out of HBM)."""
    return head_dim % 128 == 0 and (kv_heads in (2, 4) or kv_heads % 8 == 0)


def live_pages(pos, active, page_size: int, window: int,
               pages_per_slot: int, xp=jnp):
    """The walk bound: pages of each slot that decode attention reads,
    ``ceil((min(pos, W - 1) + 1) / ps)`` under a window W and ``ceil((pos +
    1) / ps)`` without, at most the table; 0 for inactive slots. ``xp`` is
    the array module (numpy on the host, jax.numpy in the kernel wrapper)."""
    last = xp.minimum(pos, window - 1) if window else pos
    n = xp.minimum(last // page_size + 1, pages_per_slot)
    return xp.where(active, n, 0).astype(xp.int32)


def _decode_kernel(pt_ref, pos_ref, npg_ref, nxt_ref, first_ref, wpage_ref,
                   q_ref, knew_ref, vnew_ref, knew_hbm, vnew_hbm,
                   kpool_ref, vpool_ref, o_ref, kout_ref, vout_ref,
                   kbuf, vbuf, m_ref, l_ref, sem, wsem, *,
                   scale: float, window: int, ps: int, n_pages_slot: int,
                   pages_blk: int, n_slots: int, dot_dtype,
                   interpreted: bool):
    b, i = pl.program_id(0), pl.program_id(1)
    P, K = n_pages_slot, pages_blk
    hkv, hd = kbuf.shape[-2:]
    rows = K * ps
    pos = pos_ref[b]
    idx = (pos % window) if window else pos  # the new token's slot entry
    wpage = wpage_ref[b]  # its physical page; -1 = nothing to write
    nblk = (npg_ref[b] + K - 1) // K  # live blocks of this slot

    def page_ok(bb, blk, j):
        """Page j of slot bb's block blk: (live and allocated, its id)."""
        g = blk * K + j
        page = pt_ref[bb * P + jnp.minimum(g, P - 1)]
        return (g < npg_ref[bb]) & (page >= 0), page

    def walk(bb, blk, slot, op):
        """Start or wait one DMA per live page of the block, K and V."""
        for j in range(K):
            ok, page = page_ok(bb, blk, j)

            @pl.when(ok)
            def _():
                for pool, buf in ((kpool_ref, kbuf), (vpool_ref, vbuf)):
                    cp = pltpu.make_async_copy(
                        pool.at[page], buf.at[slot, pl.ds(j * ps, ps)],
                        sem.at[slot])
                    getattr(cp, op)()

    def write_row(op):
        """The new token's K/V row into the aliased pool, HBM to HBM."""
        for j, (new, out) in enumerate(((knew_hbm, kout_ref),
                                        (vnew_hbm, vout_ref))):
            cp = pltpu.make_async_copy(new.at[b], out.at[wpage, idx % ps],
                                       wsem.at[j])
            getattr(cp, op)()

    # bf16 heads in pairs share 32-bit words (the pool's packed layout):
    # read the blocks as dense words and split each head out with a shift.
    # Picking one head out of the packed rows instead costs thousands of
    # vector relayout ops a block. The interpreters have no ref bitcast,
    # so interpreted the kernel reads the same words by a value bitcast.
    packed = kbuf.dtype == jnp.bfloat16 and hkv % 2 == 0
    by_ref = packed and not interpreted

    def words(buf):
        """The block buffer as [2, rows, Hkv/2 * hd] uint32 words."""
        return buf.bitcast(jnp.uint32).reshape(2, rows, hkv // 2 * hd)

    def head_rows(buf, slot, h):
        """Head h's [rows, hd] of a buffered block, as float32 (exact)."""
        if not packed:
            return buf[slot, :, h, :].astype(jnp.float32)
        if by_ref:
            w = words(buf)[slot, :, pl.ds(h // 2 * hd, hd)]
        else:
            w = pltpu.bitcast(buf[slot], jnp.uint32)[:, h // 2, :]
        w = (w << 16) if h % 2 == 0 else (w & jnp.uint32(0xFFFF0000))
        return jax.lax.bitcast_convert_type(w, jnp.float32)

    @pl.when((b == 0) & (i == 0))
    def _zero_buffers():
        # rows a block leaves unloaded keep finite bytes: p = 0 there, and
        # 0 * (uninitialised memory) could be NaN
        for buf in (kbuf, vbuf):
            view = words(buf) if by_ref else buf
            view[...] = jnp.zeros_like(view)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i < nblk)
    def _block():
        n = first_ref[b] + i  # ordinal among all live blocks: buffer parity
        slot = n % 2

        @pl.when(n == 0)
        def _():
            walk(b, i, slot, "start")

        # prefetch the next live block, into the next slot if need be
        more = i + 1 < nblk
        nb_ = jnp.where(more, b, nxt_ref[b])
        ni = jnp.where(more, i + 1, 0)

        @pl.when(nb_ < n_slots)
        def _():
            walk(nb_, ni, 1 - slot, "start")

        walk(b, i, slot, "wait")

        # the block holding the new token's entry: its page has been
        # read, so the pool write can start; the row goes into VMEM too
        r = idx - i * rows
        holds_new = (wpage >= 0) & (r >= 0) & (r < rows)

        @pl.when(holds_new)
        def _():
            write_row("start")
            kbuf[slot, r] = knew_ref[0]
            vbuf[slot, r] = vnew_ref[0]

        # validity == attention.paged_slot_valid over the block's entries:
        # a live, allocated page, and entry <= pos (for the SWA ring,
        # entry i < W holds position pos - ((pos - i) mod W), which is >= 0
        # exactly when i <= pos)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        alloc = jnp.zeros((1, rows), jnp.int32)
        for j in range(K):
            ok, _ = page_ok(b, i, j)
            alloc = jnp.where(col // ps == j, ok.astype(jnp.int32), alloc)
        e = i * rows + col
        valid = (alloc != 0) & (e <= pos)
        if window:
            valid = valid & (e < window)

        for h in range(hkv):  # kv heads; each serves G grouped query heads
            q = q_ref[0, h].astype(dot_dtype)  # [G, hd]
            k = head_rows(kbuf, slot, h).astype(dot_dtype)  # [rows, hd]
            v = head_rows(vbuf, slot, h)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)  # [G, rows]

            m_prev = m_ref[h]  # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            o_ref[0, h] = o_ref[0, h] * corr + jnp.dot(
                pexp, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new
            l_ref[h] = l_ref[h] * corr + jnp.sum(pexp, axis=-1, keepdims=True)

        @pl.when(holds_new)
        def _():
            write_row("wait")

    @pl.when(i == pl.num_programs(1) - 1)
    def _final():
        for h in range(hkv):
            o_ref[0, h] = o_ref[0, h] / jnp.maximum(l_ref[h], 1e-30)


@functools.partial(
    jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention_pallas(q, k_pool, v_pool, k_new, v_new,
                                  page_table, pos, active, *,
                                  window: int = 0, interpret=True):
    """q [B,Hq,hd], pools [N,ps,Hkv,hd], k_new/v_new [B,Hkv,hd],
    page_table [B,P] int32 (-1 = unallocated), pos [B], active bool [B]
    -> (o [B,Hq,hd], k_pool', v_pool') with the new token's row written
    into the pools for every active slot whose target page is allocated
    (all other bytes bit-identical).

    Grid (B, ceil(P / K)), K = pages_per_block(P, page bytes). The pools
    stay in HBM; each grid step of a live block DMAs its K physical pages (one
    contiguous [ps, Hkv, hd] copy each, K and V) into a double-buffered
    VMEM block, and starts the next live block's copies, across the slot
    boundary too, before it computes. Blocks past the slot's live pages
    (live_pages) copy and compute nothing. q/o ride as [B, Hkv, G, hd];
    the running max/denominator live in VMEM scratch. ``interpret`` is
    False (compile for the TPU), True, or a pltpu.InterpretParams."""
    B, Hq, hd = q.shape
    N, ps, Hkv, _ = k_pool.shape
    P = page_table.shape[1]
    G = Hq // Hkv
    K = pages_per_block(P, ps * Hkv * hd * k_pool.dtype.itemsize)
    k_new, v_new = k_new.astype(k_pool.dtype), v_new.astype(v_pool.dtype)
    scale = 1.0 / math.sqrt(hd)

    pt = page_table.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    npg = live_pages(pos, active, ps, window, P)
    nblk = (npg + K - 1) // K
    # next slot with a live block (B = none), and each slot's first
    # block's ordinal among all live blocks (its parity picks the buffer)
    ids = jnp.where(nblk > 0, jnp.arange(B, dtype=jnp.int32), B)
    nxt = jnp.append(jax.lax.cummin(ids, reverse=True)[1:], B)
    first = jnp.cumsum(nblk) - nblk
    idx = (pos % window) if window else pos
    phys = jnp.take_along_axis(pt, (idx // ps)[:, None], axis=1)[:, 0]
    wpage = jnp.where(active & (phys >= 0), phys, -1)
    # bf16 operands with a float32 product where that upcast is exact
    dot_dtype = jnp.promote_types(q.dtype, k_pool.dtype)

    def _slot(b, i, *_):
        return (b, 0, 0)

    def _slot4(b, i, *_):
        return (b, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(B, pl.cdiv(P, K)),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, hd), _slot4),  # q
            pl.BlockSpec((1, Hkv, hd), _slot),  # k_new
            pl.BlockSpec((1, Hkv, hd), _slot),  # v_new
            hbm,  # k_new, the pool write's source
            hbm,  # v_new
            hbm,  # k_pool
            hbm,  # v_pool
        ],
        out_specs=[
            pl.BlockSpec((1, Hkv, G, hd), _slot4),  # o
            hbm,  # k_pool, aliased
            hbm,  # v_pool, aliased
        ],
        scratch_shapes=[
            pltpu.VMEM((2, K * ps, Hkv, hd), k_pool.dtype),  # K block x2
            pltpu.VMEM((2, K * ps, Hkv, hd), v_pool.dtype),  # V block x2
            pltpu.VMEM((Hkv, G, 1), jnp.float32),  # running max
            pltpu.VMEM((Hkv, G, 1), jnp.float32),  # running denominator
            pltpu.SemaphoreType.DMA((2,)),  # page reads, per buffer
            pltpu.SemaphoreType.DMA((2,)),  # row writes, K and V
        ],
    )
    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, ps=ps, n_pages_slot=P,
        pages_blk=K, n_slots=B, dot_dtype=dot_dtype,
        interpreted=interpret is not False)
    o, k_out, v_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, hd), jnp.float32),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # operands: 6 prefetch, then q=6 knew=7 vnew=8 knew=9 vnew=10
        # kpool=11 vpool=12
        input_output_aliases={11: 1, 12: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(pt.reshape(-1), pos, npg, nxt, first, wpage,
      q.reshape(B, Hkv, G, hd), k_new, v_new, k_new, v_new, k_pool, v_pool)
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
