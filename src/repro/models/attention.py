"""GQA attention: chunked online-softmax prefill + KV-cache decode.

Three execution paths, all numerically equivalent (tests assert so):
  * direct einsum (short sequences / smoke tests),
  * chunked online-softmax over KV blocks (bounded activation memory for
    32k prefill; pure-jnp sibling of the Pallas flash kernel),
  * kernels/flash_attention Pallas kernel (TPU target; interpret-validated).

Sliding-window attention uses a ring-buffer KV cache of `window` slots for
decode — the TPU-native adaptation that makes long_500k decode O(window)
instead of O(seq) for dense archs (DESIGN.md §5).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init
from repro.sharding.api import constrain

NEG_INF = -1e30


def attn_init(rng, cfg, d: int):
    dt = jnp.dtype(cfg.param_dtype)
    r = jax.random.split(rng, 4)
    p = {
        "w_q": dense_init(r[0], d, cfg.q_dim, dt),
        "w_k": dense_init(r[1], d, cfg.kv_dim, dt),
        "w_v": dense_init(r[2], d, cfg.kv_dim, dt),
        "w_o": dense_init(r[3], cfg.q_dim, d, dt),
    }
    if cfg.qkv_bias:
        p["b_q"] = jnp.zeros((cfg.q_dim,), dt)
        p["b_k"] = jnp.zeros((cfg.kv_dim,), dt)
        p["b_v"] = jnp.zeros((cfg.kv_dim,), dt)
    return p


def _project_qkv(cfg, p, x, positions, rope: bool):
    B, S, _ = x.shape
    q = x @ p["w_q"]
    k = x @ p["w_k"]
    v = x @ p["w_v"]
    if "b_q" in p:
        q, k, v = q + p["b_q"], k + p["b_k"], v + p["b_v"]
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mask(q_pos, k_pos, causal: bool, window: int):
    """q_pos [Sq], k_pos [Sk] -> bool [Sq, Sk] (True = attend)."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window:
        m &= k_pos[None, :] > q_pos[:, None] - window
    return m


def _direct_attention(q, k, v, q_pos, k_pos, causal, window, k_valid=None):
    """q [B,Sq,Hq,hd], k/v [B,Sk,Hkv,hd] -> [B,Sq,Hq,hd]; fp32 softmax."""
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    logits *= 1.0 / math.sqrt(hd)
    m = _mask(q_pos, k_pos, causal, window)  # [Sq, Sk]
    if k_valid is not None:  # [B, Sk] cache-slot validity
        m = m[None, None, None] & k_valid[:, None, None, None, :]
    else:
        m = m[None, None, None]
    logits = jnp.where(m, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v)
    return o.reshape(B, Sq, Hq, hd)


def _chunked_attention(q, k, v, q_pos, k_pos, causal, window, q_block=512, k_block=1024):
    """Online-softmax attention, scanning KV blocks; O(Sq*k_block) memory."""
    B, Sq, Hq, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    nq = -(-Sq // q_block)
    nk = -(-Sk // k_block)
    pad_q = nq * q_block - Sq
    pad_k = nk * k_block - Sk
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    qpos = jnp.pad(q_pos, (0, pad_q), constant_values=-1)
    kpos = jnp.pad(k_pos, (0, pad_k), constant_values=2**30)
    qb = qp.reshape(B, nq, q_block, Hkv, G, hd)
    kb = kp.reshape(B, nk, k_block, Hkv, hd)
    vb = vp.reshape(B, nk, k_block, Hkv, hd)
    qposb = qpos.reshape(nq, q_block)
    kposb = kpos.reshape(nk, k_block)

    def per_qblock(qi, qpos_i):
        # qi [B, qb, Hkv, G, hd]
        acc0 = jnp.zeros(qi.shape, jnp.float32)
        m0 = jnp.full((B, q_block, Hkv, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, q_block, Hkv, G), jnp.float32)

        def kv_step(carry, blk):
            acc, m, l = carry
            kj, vj, kpos_j = blk
            logit = jnp.einsum("bqhgd,bkhd->bqhgk", qi, kj).astype(jnp.float32) * scale
            msk = _mask(qpos_i, kpos_j, causal, window) & (kpos_j < 2**30)[None, :]
            msk = msk[None, :, None, None, :]
            logit = jnp.where(msk, logit, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(logit, axis=-1))
            p = jnp.exp(logit - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p.astype(vj.dtype), vj
            ).astype(jnp.float32)
            return (acc, m_new, l), None

        # unroll=True: the KV sweep is static in the HLO, so compiled
        # cost_analysis counts every block (roofline accuracy) and the TPU
        # scheduler can software-pipeline the tiles.
        (acc, m, l), _ = jax.lax.scan(
            kv_step,
            (acc0, m0, l0),
            (kb.swapaxes(0, 1), vb.swapaxes(0, 1), kposb),
            unroll=True,
        )
        return acc / jnp.maximum(l[..., None], 1e-30)

    # vmap (not lax.map): q blocks are independent; vectorizing keeps them
    # in the cost model and lets XLA fuse across blocks.
    out = jax.vmap(per_qblock)(qb.swapaxes(0, 1), qposb)  # [nq, B, qb, Hkv, G, hd]
    out = out.swapaxes(0, 1).reshape(B, nq * q_block, Hq, hd)[:, :Sq]
    return out.astype(q.dtype)


def attention_block(cfg, p, x, positions, *, window: Optional[int] = None,
                    causal: bool = True, impl: str = "auto"):
    """Full (training / prefill) attention sub-block. x [B,S,d]."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, positions, cfg.rope)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    win = cfg.sliding_window if window is None else window
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops

        o = fa_ops.flash_attention(q, k, v, causal=causal, window=win)
    elif impl == "direct" or (impl == "auto" and S <= 2048):
        o = _direct_attention(q, k, v, positions, positions, causal, win)
    else:
        o = _chunked_attention(q, k, v, positions, positions, causal, win)
    o = constrain(o, "batch", None, "heads", None)
    return o.reshape(B, S, cfg.q_dim) @ p["w_o"]


# ---------------------------------------------------------------------------
# KV cache (decode)
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: jax.Array  # [B, W, Hkv, hd]
    v: jax.Array  # [B, W, Hkv, hd]
    pos: jax.Array  # [B, W] absolute position of each slot, -1 = empty


def init_kv_cache(cfg, batch: int, seq_len: int, window: int = 0):
    """Full cache of `seq_len` slots, or ring buffer of `window` slots."""
    W = window if window else seq_len
    dt = jnp.dtype(cfg.param_dtype)
    shape = (batch, W, cfg.num_kv_heads, cfg.head_dim)
    return KVCache(
        k=jnp.zeros(shape, dt),
        v=jnp.zeros(shape, dt),
        pos=jnp.full((batch, W), -1, jnp.int32),
    )


def decode_attention_block(cfg, p, x, cache: KVCache, pos, *, window: int = 0,
                           cache_update: str = "mask", active=None):
    """One-token decode. x [B,1,d], pos [B] absolute position of the token.

    Ring-buffer semantics: the new token's K/V lands in slot pos % W; the
    mask combines slot validity (pos >= 0), causality and the window.

    cache_update: "mask" (one-hot jnp.where — shardable in-place update;
    a batch-sharded cache scatter with global row indices makes GSPMD
    all-gather the cache, see EXPERIMENTS.md §Perf / qwen1.5-32b
    decode_32k) or "scatter" (baseline .at[].set).

    active: optional bool [B] slot mask (serve/ continuous batching) —
    rows with active=False keep their cache entries bit-identical (exact
    no-op write); their attention output is garbage and must be ignored.
    """
    B = x.shape[0]
    q, k_new, v_new = _project_qkv(cfg, p, x, pos[:, None], cfg.rope)
    W = cache.k.shape[1]
    slot = (pos % W).astype(jnp.int32)
    if cache_update == "mask":
        sel = (jnp.arange(W, dtype=jnp.int32)[None, :] == slot[:, None])  # [B,W]
        if active is not None:
            sel &= active[:, None]
        k = jnp.where(sel[..., None, None], k_new, cache.k)
        v = jnp.where(sel[..., None, None], v_new, cache.v)
        kpos = jnp.where(sel, pos[:, None].astype(jnp.int32), cache.pos)
    else:
        bidx = jnp.arange(B)
        k_w, v_w = k_new[:, 0], v_new[:, 0]
        p_w = pos.astype(jnp.int32)
        if active is not None:
            k_w = jnp.where(active[:, None, None], k_w, cache.k[bidx, slot])
            v_w = jnp.where(active[:, None, None], v_w, cache.v[bidx, slot])
            p_w = jnp.where(active, p_w, cache.pos[bidx, slot])
        k = cache.k.at[bidx, slot].set(k_w)
        v = cache.v.at[bidx, slot].set(v_w)
        kpos = cache.pos.at[bidx, slot].set(p_w)
    new_cache = KVCache(k, v, kpos)

    G = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(B, cfg.num_kv_heads, G, cfg.head_dim)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qg, k).astype(jnp.float32)
    logits *= 1.0 / math.sqrt(cfg.head_dim)
    valid = kpos >= 0
    valid &= kpos <= pos[:, None]
    if window:
        valid &= kpos > (pos[:, None] - window)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", w.astype(v.dtype), v)
    o = o.reshape(B, 1, cfg.q_dim)
    return o @ p["w_o"], new_cache


def insert_kv_slot(cache: KVCache, one: KVCache, slot) -> KVCache:
    """Write a single request's cache (batch 1) into row `slot` of a B-row
    cache via the masked update path (one-hot jnp.where, no scatter — the
    same shardable in-place form as cache_update="mask", so a request can
    join a mid-flight decode batch without recompiling or re-sharding).

    cache leaves [B, W, ...]; one leaves [1, W, ...] with matching W.
    """
    B = cache.k.shape[0]
    sel = jnp.arange(B, dtype=jnp.int32) == slot  # [B]
    return KVCache(
        k=jnp.where(sel[:, None, None, None], one.k, cache.k),
        v=jnp.where(sel[:, None, None, None], one.v, cache.v),
        pos=jnp.where(sel[:, None], one.pos, cache.pos),
    )


# ---------------------------------------------------------------------------
# paged KV cache (decode; DESIGN.md §12)
# ---------------------------------------------------------------------------


class PagedKVPool(NamedTuple):
    """Shared KV page pool: ``n_pages`` pages of ``page_size`` rows each.

    Unlike :class:`KVCache` there is NO stored per-entry position: slot
    validity is purely arithmetic (entry ``i`` of a slot holds absolute
    position ``i`` for full attention, or the ring position
    ``pos - ((pos - i) mod W)`` under SWA), computed in
    :func:`paged_decode_attention_block` from the page table and the
    slot's current ``pos``.  A freed-and-reallocated page therefore can
    never leak a previous request's validity metadata — stale K/V rows
    are masked (exact-zero attention weight) until the new owner
    overwrites them.
    """

    k: jax.Array  # [N, page_size, Hkv, hd]
    v: jax.Array  # [N, page_size, Hkv, hd]


def init_paged_kv_pool(cfg, n_pages: int, page_size: int) -> PagedKVPool:
    dt = jnp.dtype(cfg.param_dtype)
    shape = (n_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
    return PagedKVPool(k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt))


def paged_slot_valid(page_table, pos, page_size: int, window: int):
    """Arithmetic KV validity: page_table [B, P] (-1 = unallocated),
    pos [B] -> bool [B, P*page_size] (True = attend).

    Full attention (window=0): entry ``i`` holds position ``i``; valid iff
    ``i <= pos`` and its page is allocated. SWA ring (modulus ``window``):
    entry ``i < W`` holds ``p_i = pos - ((pos - i) mod W)``; valid iff
    ``p_i >= 0`` (the ring construction already bounds ``p_i`` to
    ``(pos - W, pos]``). Identical to the stored-kpos mask of
    :func:`decode_attention_block` for every entry a live slot has
    actually written; never-written / stale entries come out invalid.
    """
    B, P = page_table.shape
    cap = P * page_size
    i = jnp.arange(cap, dtype=jnp.int32)[None, :]  # [1, cap]
    alloc = jnp.repeat(page_table >= 0, page_size, axis=1)  # [B, cap]
    posb = pos[:, None].astype(jnp.int32)
    if window:
        p_i = posb - ((posb - i) % window)
        return alloc & (i < window) & (p_i >= 0)
    return alloc & (i <= posb)


def paged_decode_attention_block(cfg, p, x, pool: PagedKVPool, page_table,
                                 pos, *, window: int = 0,
                                 cache_update: str = "mask", active=None):
    """One-token decode against the shared page pool. x [B,1,d], pos [B].

    Write: the token's K/V lands in physical page ``page_table[b, idx //
    page_size]`` row ``idx % page_size`` (idx = pos, or pos % W for SWA
    rings). ``cache_update="mask"`` uses a one-hot masked update over the
    pool — the shardable in-place form, but its selector spans the WHOLE
    pool per batch row (B x n_pages x page_size), which is the paged
    loop's extra per-tick cost at generous pool sizes; "scatter" writes
    ``pool.at[phys, row]`` directly (masked rows route to an out-of-
    bounds index and are dropped; pages are write-exclusive — prefix
    caching aliases pages across slots for READS only — so live
    writes never collide) —
    cheaper unsharded, same bits. A slot whose target page is unallocated
    (-1) drops the write either way (the host allocator guarantees live
    slots always have their page).

    Read: gather the slot's pages into [B, P*page_size, ...] and run the
    identical masked-softmax as :func:`decode_attention_block`, with
    validity from :func:`paged_slot_valid`. Masked entries contribute
    EXACT zeros (NEG_INF logit -> 0 weight -> 0 * finite), so greedy
    streams are bit-identical to the contiguous cache whenever the
    logical capacities match.

    active: optional bool [B] slot mask — inactive rows never write and
    their outputs are garbage the caller must ignore.

    cache_update="kernel" routes to kernels/paged_attention: the Pallas
    decode kernel walks each slot's live pages only, a block of pages per
    grid step by manual DMA, with online-softmax accumulation — no
    [B, P*page_size, ...] gather — and writes the new row into the pool
    in the same launch. Pool bits are
    identical to "mask"/"scatter"; the attention output reassociates the
    fp32 softmax reduction (ULP-level differences; greedy streams still
    match bit-for-bit, asserted in tests/test_paged_kernel.py).
    """
    B = x.shape[0]
    N, ps, Hkv, hd = pool.k.shape
    P = page_table.shape[1]
    q, k_new, v_new = _project_qkv(cfg, p, x, pos[:, None], cfg.rope)

    if cache_update == "kernel":
        from repro.kernels.paged_attention import ops as pa_ops

        o, k_pool, v_pool = pa_ops.paged_decode_attention(
            q[:, 0], pool.k, pool.v, k_new[:, 0], v_new[:, 0],
            page_table, pos, window=window, active=active)
        o = o.reshape(B, 1, cfg.q_dim)
        return o @ p["w_o"], PagedKVPool(k_pool, v_pool)

    idx = ((pos % window) if window else pos).astype(jnp.int32)
    phys = jnp.take_along_axis(page_table, (idx // ps)[:, None], axis=1)[:, 0]
    if cache_update == "mask":
        sel = (jnp.arange(N, dtype=jnp.int32)[None, :] == phys[:, None])[:, :, None] \
            & (jnp.arange(ps, dtype=jnp.int32)[None, None, :] == (idx % ps)[:, None, None])
        if active is not None:
            sel &= active[:, None, None]
        # pages are WRITE-exclusive: prefix caching may alias a page into
        # several slots' tables, but decode writes land at idx >= plen —
        # pages past every shared prefix — so the sum over B still has at
        # most one non-zero term per (page, row) and the write is exact
        # (1.0 * k_new + zeros)
        selv = sel.astype(k_new.dtype)
        k_pool = jnp.where(sel.any(0)[..., None, None],
                           jnp.einsum("bnr,bhd->nrhd", selv, k_new[:, 0]), pool.k)
        v_pool = jnp.where(sel.any(0)[..., None, None],
                           jnp.einsum("bnr,bhd->nrhd", selv, v_new[:, 0]), pool.v)
    else:
        ok = phys >= 0
        if active is not None:
            ok &= active
        phys_w = jnp.where(ok, phys, N)  # N is out of bounds -> dropped
        k_pool = pool.k.at[phys_w, idx % ps].set(k_new[:, 0], mode="drop")
        v_pool = pool.v.at[phys_w, idx % ps].set(v_new[:, 0], mode="drop")
    new_pool = PagedKVPool(k_pool, v_pool)

    safe_pt = jnp.maximum(page_table, 0)
    k = k_pool[safe_pt].reshape(B, P * ps, Hkv, hd)
    v = v_pool[safe_pt].reshape(B, P * ps, Hkv, hd)
    valid = paged_slot_valid(page_table, pos, ps, window)

    G = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(B, cfg.num_kv_heads, G, cfg.head_dim)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qg, k).astype(jnp.float32)
    logits *= 1.0 / math.sqrt(cfg.head_dim)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", w.astype(v.dtype), v)
    o = o.reshape(B, 1, cfg.q_dim)
    return o @ p["w_o"], new_pool


def paged_prefill_attention_block(cfg, p, x, pool: PagedKVPool, page_row,
                                  start, length, cache_update: str = "mask"):
    """Chunked/suffix prefill straight into the page pool: one batch-1
    chunk of ``C`` tokens covering absolute positions ``[start, start +
    length)`` of a single slot. x [1, C, d]; page_row [P] int32 (-1 =
    unallocated); start/length traced int32 scalars (one compile per
    chunk WIDTH, any start/length). Rows >= ``length`` are padding:
    never written, outputs garbage the caller ignores.

    Write-then-read: the chunk's K/V land in their pages first, then the
    slot's pages are gathered and attended with the same arithmetic
    validity as :func:`paged_decode_attention_block` (entry ``j`` valid
    iff its page is allocated and ``j <= start + i`` for query row
    ``i``) — so within-chunk causal attention, earlier chunks, AND
    prefix-cached shared pages all come out of the pool. Masked entries
    contribute exact zeros, and every valid row was written by a prior
    chunk / the shared prefix (page aliasing is read-only: decode and
    chunk writes only ever target the slot's PRIVATE suffix pages), so
    the outputs are bit-identical to a monolithic prefill of the same
    prompt — full attention only (the SWA ring wraps decode writes into
    early pages; callers gate on ``sliding_window``).
    """
    B, C, _ = x.shape
    N, ps, Hkv, hd = pool.k.shape
    P = page_row.shape[0]
    positions = start + jnp.arange(C, dtype=jnp.int32)  # [C]
    q, k_new, v_new = _project_qkv(cfg, p, x, positions, cfg.rope)

    row_ok = jnp.arange(C, dtype=jnp.int32) < length  # [C] real chunk rows
    idx = positions  # full attention: entry i holds absolute position i
    phys = page_row[jnp.clip(idx // ps, 0, P - 1)]  # [C] physical pages
    if cache_update == "scatter":
        ok = row_ok & (phys >= 0)
        phys_w = jnp.where(ok, phys, N)  # N is out of bounds -> dropped
        k_pool = pool.k.at[phys_w, idx % ps].set(k_new[0], mode="drop")
        v_pool = pool.v.at[phys_w, idx % ps].set(v_new[0], mode="drop")
    else:  # "mask" (the kernel decode loop reuses it for chunk writes)
        sel = (jnp.arange(N, dtype=jnp.int32)[None, :] == phys[:, None])[:, :, None] \
            & (jnp.arange(ps, dtype=jnp.int32)[None, None, :] == (idx % ps)[:, None, None])
        sel &= row_ok[:, None, None]  # [C, N, ps]
        # chunk rows target distinct (page, row) cells and suffix pages are
        # slot-private, so the sum has at most one non-zero term per cell
        selv = sel.astype(k_new.dtype)
        k_pool = jnp.where(sel.any(0)[..., None, None],
                           jnp.einsum("cnr,chd->nrhd", selv, k_new[0]), pool.k)
        v_pool = jnp.where(sel.any(0)[..., None, None],
                           jnp.einsum("cnr,chd->nrhd", selv, v_new[0]), pool.v)
    new_pool = PagedKVPool(k_pool, v_pool)

    safe_pt = jnp.maximum(page_row, 0)
    cap = P * ps
    k = k_pool[safe_pt].reshape(1, cap, Hkv, hd)
    v = v_pool[safe_pt].reshape(1, cap, Hkv, hd)
    j = jnp.arange(cap, dtype=jnp.int32)
    alloc = jnp.repeat(page_row >= 0, ps)
    valid = alloc[None, :] & (j[None, :] <= positions[:, None])  # [C, cap]

    G = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(1, C, Hkv, G, hd)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    logits *= 1.0 / math.sqrt(hd)
    logits = jnp.where(valid[None, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v)
    o = o.reshape(1, C, cfg.q_dim)
    return o @ p["w_o"], new_pool


def insert_kv_pages(pool: PagedKVPool, one: KVCache, page_ids,
                    use_kernel: bool = False) -> PagedKVPool:
    """Write a batch-1 prefill cache into pool pages ``page_ids`` [P]
    (int32, -1 = unallocated -> skipped); slot page ``j`` gets rows
    ``[j*page_size, (j+1)*page_size)`` of ``one``. ``one.k`` [1, cap, ...]
    with cap == P * page_size (pad the prefill cache up to a page multiple
    first). Every ALLOCATED page is written IN FULL, so page reuse can
    never leak a previous request's K/V into the new owner's valid range
    (poisoning guard #1; the arithmetic validity mask of
    :func:`paged_decode_attention_block` is guard #2).

    use_kernel=True swaps the full-pool jnp.where (selector over all N
    pages) for the kernels/paged_attention routed block-write kernel that
    only touches the slot's own pages — same bits either way.
    """
    N, ps, Hkv, hd = pool.k.shape
    P = page_ids.shape[0]
    src_k = one.k[0].reshape(P, ps, Hkv, hd)
    src_v = one.v[0].reshape(P, ps, Hkv, hd)
    if use_kernel:
        from repro.kernels.paged_attention import ops as pa_ops

        k, v = pa_ops.paged_insert(
            pool.k[None], pool.v[None], src_k[None], src_v[None], page_ids)
        return PagedKVPool(k=k[0], v=v[0])
    sel = (page_ids[:, None] == jnp.arange(N, dtype=jnp.int32)[None, :]) \
        & (page_ids >= 0)[:, None]  # [P, N]; page ids are distinct
    selv = sel.astype(src_k.dtype)
    hit = sel.any(0)[:, None, None, None]  # [N,1,1,1]
    return PagedKVPool(
        k=jnp.where(hit, jnp.einsum("pn,prhd->nrhd", selv, src_k), pool.k),
        v=jnp.where(hit, jnp.einsum("pn,prhd->nrhd", selv, src_v), pool.v),
    )


def prefill_kv_cache(cfg, p, x, positions, *, window: int = 0, pad_to: int = 0):
    """Compute K/V for a full prompt and lay them into a (ring) cache.

    Full attention: cache capacity is max(pad_to, S) — pass pad_to > S to
    leave room for subsequently decoded tokens. SWA: ring buffer of `window`.
    """
    _, k, v = _project_qkv(cfg, p, x, positions, cfg.rope)
    B, S = x.shape[0], x.shape[1]
    W = window if window else max(pad_to, S)
    if W >= S:
        pad = W - S
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pos = jnp.pad(
            jnp.broadcast_to(positions, (B, S)).astype(jnp.int32),
            ((0, 0), (0, pad)), constant_values=-1,
        )
        return KVCache(k, v, pos)
    # ring buffer keeps the last W tokens (slot = pos % W)
    k = k[:, -W:]
    v = v[:, -W:]
    pos = jnp.broadcast_to(positions[-W:], (B, W)).astype(jnp.int32)
    shift = (S % W)
    k = jnp.roll(k, shift, axis=1)
    v = jnp.roll(v, shift, axis=1)
    pos = jnp.roll(pos, shift, axis=1)
    return KVCache(k, v, pos)
